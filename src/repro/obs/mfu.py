"""Live efficiency accounting: achieved-vs-model FLOPs, tokens/s, MFU.

The paper's headline metric (Table 1: 72% model-FLOPs utilization
end-to-end) folded into gauges a training run updates every step instead of a
one-off benchmark:

  * **model FLOPs** come from the analytic formulas in
    ``utils/flops.py`` (6*N_active*D + the 12*L*H*S^2 Megatron attention
    term, causal halving deliberately NOT applied -- the literature's
    convention, and the MFU numerator the paper reports);
  * **hardware FLOPs** apply the visible-tile census
    (``utils/flops._visible_fraction``, the same oracle
    ``kernels/schedule.py`` builds its compact grids from) to the
    attention term -- causal/windowed masks shrink the work the kernels
    actually launch, so HFU > MFU on masked workloads;
  * **MFU / HFU** divide by the chip's peak FLOPs/s
    (:func:`peak_flops`: ``REPRO_PEAK_FLOPS`` env override, else the
    :data:`PEAKS` row of the running device's ``device_kind`` -- a kind
    missing from the table raises, never falls back).

All accounting is host-side arithmetic on numbers the loop already has
(config, batch shape, wall time) -- nothing here touches a traced
value, so attaching a meter cannot add compiles (tests/test_obs.py).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence, Tuple

from repro.configs.base import ModelConfig, ShapeConfig
from repro.obs.metrics import MetricsRegistry
from repro.utils import flops as F

__all__ = ["PEAKS", "ChipPeak", "chip_peak", "peak_flops", "mfu",
           "TrainEfficiency"]


@dataclasses.dataclass(frozen=True)
class ChipPeak:
    """Published per-chip peaks of one device kind."""

    flops: float            # dense bf16 FLOP/s
    hbm_bytes_per_s: float  # HBM bandwidth
    ici_bytes_per_s: float  # chip-to-chip bandwidth per link
    source: str


# The one peak table, keyed by ``jax.Device.device_kind``. MFU here and the
# dry-run roofline (utils/hlo_analysis) both read it.
PEAKS: Dict[str, ChipPeak] = {
    "TPU v5 lite": ChipPeak(
        flops=197e12, hbm_bytes_per_s=819e9, ici_bytes_per_s=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "819 GB/s HBM, 1,600 Gbit/s ICI over 4 links",
    ),
    # Not a hardware figure: an order of magnitude for a few AVX cores, so
    # MFU on a CPU test host is finite and > 0 -- a sanity value only.
    "cpu": ChipPeak(
        flops=1e11, hbm_bytes_per_s=2e10, ici_bytes_per_s=0.0,
        source="sanity value only, not a measurement",
    ),
}


def chip_peak(device_kind: Optional[str] = None) -> ChipPeak:
    """The :data:`PEAKS` row of ``device_kind`` (default: the first JAX
    device's). A kind missing from the table raises ``KeyError``."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it, "
            "with its source, to repro.obs.mfu.PEAKS"
        ) from None


def peak_flops(device_kind: Optional[str] = None) -> float:
    """Peak FLOP/s of one chip: ``REPRO_PEAK_FLOPS`` if set, else the
    :func:`chip_peak` of ``device_kind``."""
    env = os.environ.get("REPRO_PEAK_FLOPS")
    if env:
        return float(env)
    return chip_peak(device_kind).flops


def mfu(model_flops: float, seconds: float, peak: Optional[float] = None) -> float:
    """Model-FLOPs utilization of ``model_flops`` of work done in
    ``seconds`` on one chip; 0.0 when no time has elapsed."""
    if seconds <= 0:
        return 0.0
    return model_flops / seconds / (peak or peak_flops())


def _attn_layer_dims(cfg: ModelConfig) -> Sequence[Tuple[Optional[int], int]]:
    """(window, sink) per attention-bearing layer, precomputed once."""
    dims = []
    for kind in cfg.layer_kinds():
        if kind.startswith("attn") or kind.startswith("hybrid"):
            w = cfg.kind_window(kind)
            sink = cfg.meta_tokens if (w is not None and cfg.meta_tokens) else 0
            dims.append((w, sink))
    return dims


class TrainEfficiency:
    """Per-step train gauges: ``<prefix>/mfu``, ``/hfu``, ``/tokens_per_s``.

    Model FLOPs per step are fixed by (config, batch, seq) and computed
    once; hardware FLOPs scale the attention term by the visible-tile
    fraction of each layer's mask (causal ~ 1/2, window ~ W/S) at the
    128-token tile granularity the census uses elsewhere. ``step(dt)``
    feeds one measured step; gauges report *cumulative* utilization (the
    Table 1 convention -- noise-robust), counters carry the raw totals.
    """

    def __init__(self, cfg: ModelConfig, batch_size: int, seq_len: int,
                 registry: MetricsRegistry, prefix: str = "train",
                 peak: Optional[float] = None):
        self.registry = registry
        self.prefix = prefix
        self.peak = peak or peak_flops()
        self.tokens_per_step = batch_size * seq_len
        shape = ShapeConfig("live_train", "train", seq_len, batch_size)
        self.model_flops_per_step = F.train_model_flops(cfg, shape)
        # hardware = model with each layer's attention term rescaled by
        # its visible fraction (the schedule census, bq = bk = 128 tiles)
        bq = bk = min(128, max(8, seq_len))
        t = -(-seq_len // bq)
        hw = self.model_flops_per_step
        for window, sink in _attn_layer_dims(cfg):
            kind = "window" if window is not None else "causal"
            vf = F._visible_fraction(kind, window, sink, t, t, bq, bk)
            s_eff = min(window, seq_len) if window else seq_len
            term = 12.0 * cfg.q_dim * s_eff * seq_len * batch_size
            hw -= (1.0 - vf) * term
        self.hardware_flops_per_step = hw
        self._steps = registry.counter(f"{prefix}/steps")
        self._tok = registry.counter(f"{prefix}/tokens")
        self._flops = registry.counter(f"{prefix}/model_flops")
        self._secs = registry.counter(f"{prefix}/compute_seconds")
        self._g_mfu = registry.gauge(f"{prefix}/mfu")
        self._g_hfu = registry.gauge(f"{prefix}/hfu")
        self._g_tps = registry.gauge(f"{prefix}/tokens_per_s")
        self._g_tflops = registry.gauge(f"{prefix}/model_tflops_per_s")

    def step(self, seconds: float) -> None:
        self._steps.inc()
        self._tok.inc(self.tokens_per_step)
        self._flops.inc(self.model_flops_per_step)
        self._secs.inc(seconds)
        secs = self._secs.value
        if secs > 0:
            achieved = self._flops.value / secs
            self._g_mfu.set(achieved / self.peak)
            self._g_hfu.set(
                achieved / self.peak
                * self.hardware_flops_per_step / self.model_flops_per_step
            )
            self._g_tps.set(self._tok.value / secs)
            self._g_tflops.set(achieved / 1e12)
