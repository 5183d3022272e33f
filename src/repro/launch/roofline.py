"""Roofline-term derivation from compiled dry-run artifacts.

Three terms (seconds, per device — the SPMD-partitioned module cost
analysis is per device):

  compute    = HLO_FLOPs / peak_FLOP/s
  memory     = HLO_bytes_accessed / HBM_bandwidth
  collective = sum(collective result bytes x op multiplier) / ICI_bandwidth

collective bytes are NOT in cost_analysis: we parse the partitioned HLO
(``compiled.as_text()``) and sum the result-buffer sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
with ring-algorithm byte multipliers (all-reduce moves ~2x its buffer).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

from repro.launch.mesh import chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# bytes moved over ICI per byte of result buffer (ring algorithms)
_OP_MULTIPLIER = {
    "all-reduce": 2.0,        # reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\w+\[[^\]]*\][^ ]*|\()[^=]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes appearing in a result type string."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_op: Dict[str, int] = field(default_factory=dict)
    count_by_op: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    @property
    def weighted_bytes(self) -> float:
        return sum(b * _OP_MULTIPLIER[o] for o, b in self.bytes_by_op.items())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        if f" {op}-done" in line:
            continue  # async completion carries the same buffer
        b = _shape_bytes(shape_str)
        stats.bytes_by_op[op] = stats.bytes_by_op.get(op, 0) + b
        stats.count_by_op[op] = stats.count_by_op.get(op, 0) + 1
    return stats


@dataclass
class Roofline:
    flops: float                  # per-device HLO flops
    hbm_bytes: float              # per-device bytes accessed
    collective_bytes: float       # per-device weighted ICI bytes
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float            # 6*N*D (analytic, global)
    useful_ratio: float           # model_flops / (flops * chips)
    collectives: Dict[str, int]

    def to_dict(self):
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops_global": self.model_flops,
            "useful_flops_ratio": self.useful_ratio,
            "collective_breakdown": self.collectives,
        }


def analyze(cost: dict, hlo_text: str, *, n_chips: int,
            model_flops: float, device_kind: str) -> Roofline:
    """cost: raw compiled.cost_analysis() (recorded for reference only — it
    counts while bodies once); the roofline terms come from the while-aware
    HLO analyzer (repro.launch.hlo_cost), over the peaks of one
    ``device_kind`` chip (:func:`repro.launch.mesh.chip_peaks`)."""
    from repro.launch.hlo_cost import analyze_hlo

    peaks = chip_peaks(device_kind)
    hc = analyze_hlo(hlo_text)
    flops = hc.flops
    hbm = hc.bytes

    compute_s = flops / peaks.flops_bf16
    memory_s = hbm / peaks.hbm_bandwidth
    collective_s = hc.coll_bytes / peaks.ici_bandwidth
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    total_flops = flops * n_chips
    return Roofline(
        flops=flops, hbm_bytes=hbm, collective_bytes=hc.coll_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=model_flops,
        useful_ratio=(model_flops / total_flops) if total_flops else 0.0,
        collectives={k: int(v) for k, v in hc.coll_by_op.items()},
    )


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D prefill/decode
    (N = active params, D = tokens processed this step)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n_active * tokens
