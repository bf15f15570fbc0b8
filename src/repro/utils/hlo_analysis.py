"""HLO-text analysis: collective bytes, op census, roofline terms.

``cost_analysis()`` gives FLOPs and bytes but NOT collective traffic, so we
parse the (stable)HLO text: every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op contributes its operand bytes. Hardware
peaks come from the one table, ``repro.obs.mfu.PEAKS``, by the target
chip's ``device_kind`` (TPU v5e by default).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.obs.mfu import ChipPeak, chip_peak

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "e4m3": 1, "e5m2": 1,
}

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'f32[128,256]' -> byte count; tuples handled by caller."""
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


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op, by op kind.

    We count the op's *result* shape (post-HLO convention puts the full
    result shape on the lhs of '='), which upper-bounds moved bytes for
    all-gather and matches operand bytes for the others.
    """
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # HLO: '%x = f32[...] all-reduce(...)' ; stableHLO: '"mhlo.all_reduce"'
        for kind in COLLECTIVE_OPS:
            token = f" {kind}(" if "(" in s else kind
            if f" {kind}(" in s or f'"{kind}"' in s or f"{kind}-start(" in s:
                lhs = s.split("=")[0] if "=" in s else s
                rhs_shape = s.split("=", 1)[1] if "=" in s else s
                out[kind] += _shape_bytes(rhs_shape.split(kind)[0])
                out["count"] += 1
                break
    out["total"] = sum(out[k] for k in COLLECTIVE_OPS)
    return out


def op_census(hlo_text: str, ops=("exponential", "divide", "multiply", "maximum", "log")) -> Dict[str, int]:
    """Count elementwise op *instances* (the non-matmul FLOP census used by
    the FA1-vs-FA2 benchmark)."""
    out = {}
    for op in ops:
        out[op] = len(re.findall(rf"\b{op}\(", hlo_text)) + len(
            re.findall(rf'"stablehlo\.{op}"', hlo_text)
        )
    return out


@dataclasses.dataclass
class Roofline:
    """All quantities are PER-CHIP (cost_analysis/memory_analysis of an SPMD
    module report the per-partition program -- calibrated against known
    sharded matmuls). ``model_flops`` must likewise be global/chips. The
    brief's formulas ``X / (chips * BW)`` with global X reduce to exactly
    these per-chip ratios."""

    flops: float
    hbm_bytes: float
    coll_bytes: float
    chips: int  # metadata (mesh size); terms below are already per-chip
    model_flops: Optional[float] = None
    device_kind: str = "TPU v5 lite"  # the chip the program targets

    @property
    def peak(self) -> ChipPeak:
        return chip_peak(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peak.flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.peak.hbm_bytes_per_s

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.peak.ici_bytes_per_s

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> Optional[float]:
        if self.model_flops is None or self.flops == 0:
            return None
        return self.model_flops / self.flops

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak the dominant-term-bound step achieves on
        *useful* model FLOPs. All fields here are already per-chip (see
        class docstring), so the brief's MODEL_FLOPS/(chips*peak)/step_time
        reduces to mf/peak/step_time -- no further /chips."""
        mf = self.model_flops if self.model_flops is not None else self.flops
        ideal = mf / self.peak.flops
        return ideal / max(self.step_time, 1e-30)

    def to_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "chips": self.chips,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }
