"""Compiled-HLO analysis: collective bytes + roofline terms.

``cost_analysis()`` exposes FLOPs and HBM bytes of the *partitioned*
(per-device) module, but not collective traffic.  :func:`collective_bytes`
parses the compiled HLO text and sums the result-shape bytes of every
``all-gather`` / ``all-reduce`` / ``reduce-scatter`` / ``all-to-all`` /
``collective-permute`` op (per device, matching cost_analysis semantics).

:func:`roofline` combines the three terms against TPU v5e constants:
197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

# -- hardware constants (TPU v5e) ------------------------------------------
PEAK_FLOPS = 197e12          # bf16 MXU, per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# result can be a plain shape `f32[8,128]{1,0}` or a tuple of shapes
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\s]+?)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-collective-kind byte totals + counts from compiled HLO text."""
    stats = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        if "-done(" in line:
            continue  # avoid double-counting async start/done pairs
        stats[kind]["bytes"] += _shape_bytes(shape_str)
        stats[kind]["count"] += 1
    return stats


def collective_bytes(hlo_text: str) -> int:
    return sum(v["bytes"] for v in collective_stats(hlo_text).values())


@dataclasses.dataclass
class Roofline:
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    chips: int
    model_flops: Optional[float] = None   # 6·N·D (global, per step)

    @property
    def compute_s(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_dev / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step-time estimate: max of the three overlapped terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> Optional[float]:
        """MODEL_FLOPS / total compiled FLOPs (remat/redundancy waste)."""
        if not self.model_flops:
            return None
        return self.model_flops / max(self.flops_per_dev * self.chips, 1.0)

    @property
    def mfu(self) -> Optional[float]:
        """Roofline-implied model-FLOPs utilization at the step estimate."""
        if not self.model_flops:
            return None
        return (self.model_flops / (self.chips * PEAK_FLOPS)) / self.step_s

    def as_dict(self) -> dict:
        return {
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_s": self.step_s,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "mfu": self.mfu,
        }


def model_flops_per_step(n_params: int, tokens: int, kind: str = "train",
                         active_params: Optional[int] = None) -> float:
    """6·N·D for training (fwd+bwd), 2·N·D for inference forward."""
    n = active_params if active_params is not None else n_params
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens


def roofline_from_compiled(compiled, chips: int,
                           model_flops: Optional[float] = None) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    return Roofline(flops, byts, float(coll), chips, model_flops)
