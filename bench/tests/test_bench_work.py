"""The yardstick arithmetic against counts made by hand."""
import tinyroot  # noqa: F401  (puts bench/ on the path)

import pytest

from benchlib import work


@pytest.mark.parametrize("m,b,n,flops,bytes_", [
    # 2*4096*512*1024; 4*(4096*1024 + 512*1024 + 4096 + 512 + 4096*512)
    (4096, 512, 1024, 4294967296.0, 27281408.0),
    # 2*8*16*32; 4*(8*32 + 16*32 + 8 + 16 + 8*16)
    (8, 16, 32, 8192.0, 3680.0),
])
def test_similarity_counts(m, b, n, flops, bytes_):
    assert work.similarity(m, b, n) == (flops, bytes_)


@pytest.mark.parametrize("m,b,n,want", [
    # similarity 2mbn + Ginv K 2m^2 b + W^T D 2bmn
    (4096, 512, 1024, 4294967296.0 + 17179869184.0 + 4294967296.0),
    (8, 16, 32, 8192.0 + 2048.0 + 8192.0),
])
def test_estimate_counts(m, b, n, want):
    assert work.estimate(m, b, n) == want


def test_roofline_names_its_bound():
    # 1e12 flops at 1e15 flop/s: 1 ms; 1e6 bytes at 1e9 B/s: 1 ms... x2 -> memory
    assert work.roofline_share(1e12, 2e6, 4e-3, 1e15, 1e9) == (50.0, "memory")
    assert work.roofline_share(4e12, 2e6, 4e-3, 1e15, 1e9) == (100.0, "compute")
