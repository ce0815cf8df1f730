"""Roofline terms from a compiled dry-run artifact.

  compute term    = HLO_FLOPs / peak_FLOPs            (per device)
  memory term     = HLO_bytes / HBM_bw                (per device)
  collective term = wire_bytes / link_bw              (per device)

Peaks come from one table keyed by ``device_kind`` (as JAX reports it);
a kind that is not in the table is an error, never a default.

cost_analysis() on the SPMD-partitioned module reports per-device FLOPs and
bytes. Collective wire bytes are parsed from the partitioned HLO text:
per-op local shapes x a ring-algorithm wire factor per collective kind
(all-reduce moves ~2x its local payload; gather/scatter/all-to-all ~1x; a
collective-permute exactly 1x). Replica-group size D refines (D-1)/D.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional


@dataclass(frozen=True)
class ChipPeaks:
    flops: float          # bf16 FLOP/s per chip
    hbm_bw: float         # HBM bytes/s per chip
    link_bw: float        # bytes/s over one ICI link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of ICI per chip over 4 links (50 GB/s per link, the
# conservative one-active-link figure the collective term uses).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _op_kind(line: str) -> Optional[str]:
    m = re.search(r"=\s+(?:\([^)]*\)|\S+)\s+([\w-]+)\(", line)
    if not m:
        return None
    op = m.group(1).rstrip(".0123456789")
    for kind in COLLECTIVE_KINDS:
        if op.startswith(kind):
            return kind
    return None


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    return 2


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int] = field(default_factory=dict)
    wire_bytes: float = 0.0
    count: int = 0

    def add(self, kind: str, nbytes: int, wire: float) -> None:
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0) + nbytes
        self.wire_bytes += wire
        self.count += 1


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum per-device collective payloads from partitioned HLO text.

    The *result* region (everything between '=' and the op name) is summed —
    collectives may return tuples (shard_map all_to_all lowers to a 16-ary
    tuple op), so every shape there counts. Operand shapes are generally
    printed as operand *names*, so per-kind wire factors are derived from
    the result: a reduce-scatter's input is result x D, an all-gather's
    result is already the gathered full, etc.
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        s = line.strip()
        kind = _op_kind(s)
        if kind is None or s.startswith("//") or "-done" in s:
            continue
        cut = s.find(f" {kind}")
        result_region = s[:cut] if cut > 0 else s
        shapes = _SHAPE_RE.findall(result_region)
        if not shapes:
            continue
        result_b = sum(_shape_bytes(*sh) for sh in shapes
                       if sh[0] in _DTYPE_BYTES)
        d = _group_size(s)
        frac = (d - 1) / d if d > 1 else 1.0
        if kind == "all-reduce":
            wire = 2.0 * result_b * frac
            nbytes = result_b
        elif kind == "all-gather":
            wire = result_b * frac          # result is the gathered full
            nbytes = result_b
        elif kind == "reduce-scatter":
            operand_b = result_b * d        # input is D x the scattered out
            wire = operand_b * frac
            nbytes = operand_b
        elif kind == "all-to-all":
            wire = result_b * frac          # tuple in == tuple out
            nbytes = result_b
        else:  # collective-permute
            wire = float(result_b)
            nbytes = result_b
        stats.add(kind, nbytes, wire)
    return stats


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops_global: float
    collectives: Dict[str, int]
    peak_memory_bytes: Optional[float] = None

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_lower_bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs / (chips x peak x bound step time)."""
        t = self.step_time_lower_bound_s
        if t <= 0:
            return 0.0
        return self.model_flops_global / (
            self.chips * peaks(self.device_kind).flops * t)

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops_global": self.model_flops_global,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu,
            "collectives": self.collectives,
            "peak_memory_bytes": self.peak_memory_bytes,
        }

