"""The benchmark's own library: the cell spec, the device gate and peaks, the
trace reduction, the yardstick arithmetic and the result line."""
