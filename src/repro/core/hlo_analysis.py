"""Extract roofline inputs from a compiled XLA executable.

``cost_analysis()`` gives per-device FLOPs / bytes-accessed; collective traffic is
NOT in cost_analysis, so we parse the post-SPMD HLO text and sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
# collective op line: "%name = <shapes> <kind>(" or "ROOT %name = ..."
_COLL_LINE_RE = re.compile(
    r"=\s*(?P<shapes>\([^)]*\)|[\w\[\]{},\s]*?)\s*"
    r"(?P<kind>all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)\(")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: dict = field(default_factory=dict)
    count_by_kind: dict = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum per-device operand/result bytes of collective ops in post-SPMD HLO."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_LINE_RE.search(line)
        if not m:
            continue
        kind = m.group("kind").replace("-start", "")
        nbytes = _shape_bytes(m.group("shapes"))
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) + nbytes
        stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) + 1
    return stats


@dataclass
class CompiledCost:
    """Everything the roofline needs, in GLOBAL units (per-device x n_devices)."""
    n_devices: int
    flops: float                 # global FLOPs per step
    bytes_accessed: float        # global HBM traffic per step
    collective_bytes: float      # global collective traffic per step
    collectives: CollectiveStats
    peak_memory_per_device: float
    argument_bytes_per_device: float
    temp_bytes_per_device: float
    output_bytes_per_device: float

    def as_dict(self) -> dict:
        return {
            "n_devices": self.n_devices,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "collective_bytes": self.collective_bytes,
            "collective_bytes_by_kind": dict(self.collectives.bytes_by_kind),
            "collective_count_by_kind": dict(self.collectives.count_by_kind),
            "peak_memory_per_device": self.peak_memory_per_device,
            "argument_bytes_per_device": self.argument_bytes_per_device,
            "temp_bytes_per_device": self.temp_bytes_per_device,
            "output_bytes_per_device": self.output_bytes_per_device,
        }


def analyze_compiled(compiled, n_devices: int = 1,
                     hlo_text: Optional[str] = None) -> CompiledCost:
    """cost_analysis()/memory_analysis() report PER-DEVICE numbers for SPMD
    executables; pass n_devices to globalize."""
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    text = hlo_text if hlo_text is not None else compiled.as_text()
    colls = parse_collectives(text)

    flops_dev = float(ca.get("flops", 0.0))
    bytes_dev = float(ca.get("bytes accessed", 0.0))
    arg_b = float(getattr(ma, "argument_size_in_bytes", 0.0))
    tmp_b = float(getattr(ma, "temp_size_in_bytes", 0.0))
    out_b = float(getattr(ma, "output_size_in_bytes", 0.0))
    return CompiledCost(
        n_devices=n_devices,
        flops=flops_dev * n_devices,
        bytes_accessed=bytes_dev * n_devices,
        collective_bytes=float(colls.total_bytes) * n_devices,
        collectives=colls,
        peak_memory_per_device=arg_b + tmp_b + out_b,
        argument_bytes_per_device=arg_b,
        temp_bytes_per_device=tmp_b,
        output_bytes_per_device=out_b,
    )
