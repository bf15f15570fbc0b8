"""Jit'd public wrappers for the Pallas kernels.

Handles: layout normalization ((B,S,H,D) -> per-head rows), padding to block
multiples, q pre-scaling, the fwd<->bwd pairing via ``jax.custom_vjp``
(Algorithm 1 + Algorithm 2), and the decode split merge. The pure-jnp oracle
lives in ref.py; parity is enforced by tests/test_flash_kernels.py.

Memory contract (DESIGN.md Section 2):

  * The ``custom_vjp`` boundary sits INSIDE the layout prep: the core
    differentiable function takes *prepped* tensors (head-major, padded,
    q pre-scaled) and its residuals are exactly those tensors plus the
    kernel outputs -- the backward never re-runs ``_prep`` (no re-transpose
    / re-pad / re-scale of q, k, v). The cheap layout ops around the core
    are differentiated by XLA itself.
  * The logsumexp is lane-major ``(BH, 1, Sqp)`` f32 end to end (kernels
    emit it, the backward consumes it, decode's split merge reuses it) --
    128x fewer softmax-stat bytes than the old ``(BH, Sqp, LANES)``
    broadcast, for both lse and delta. The unit axis is the sublane axis
    Mosaic's block-shape rule needs (flash_fwd.py's module docstring).
  * The backward is ``bwd="fused"`` by default: ONE kv-major pallas_call
    (``flash_bwd.flash_bwd_fused``) producing dK, dV, dQ *and* delta --
    (s, p) recomputed once per visible tile, delta fused into the q-row
    prologue, dQ read-modify-written by DMA in an f32 HBM output.
    ``bwd="split"`` keeps the 3-launch baseline (``flash_bwd_delta`` +
    ``flash_bwd_dkv`` + ``flash_bwd_dq``) for parity and comparison.
  * Tile scheduling is ``schedule="compact"`` by default (see
    kernels/schedule.py); ``"dense"`` keeps the legacy visit-every-tile
    grid for comparison.
  * Knob resolution is measurement-driven (ISSUE 6): whenever a
    ``PallasFlashConfig`` knob is ``None``, :func:`resolve_pallas_knobs`
    consults the committed tuned cache (``kernels/autotune.py`` /
    ``tuned.json``) before falling back to the hand heuristics. Precedence,
    per knob: explicit arg > tuned cache > heuristic
    (``default_block_sizes`` / ``default_forward_partitions`` /
    ``_resolve_bwd``). ``use_tuned=False`` (or env ``REPRO_TUNED_CACHE=0``)
    disables the cache and restores pure-heuristic resolution.
  * Block sizes default to a shape-aware table (``default_block_sizes``):
    clamped to the padded sequence length, ``block_kv`` shrinking as the
    head dim grows so the fused backward's f32 dK/dV scratch plus streamed
    tiles stay inside the VMEM budget. Pass explicit ``block_q``/
    ``block_kv`` to override, exactly as before -- explicit values are
    *legalized* (rounded up to the alignment the kernels need, clamped to
    the padded sequence length) with a warning, instead of silently
    mis-padding the sequence. Compiled by Mosaic, a block is 128-aligned:
    ``block_q`` is the lane axis of the lse/delta rows and ``block_kv`` that
    of the kv segment ids. Interpret mode keeps the 8-row alignment, so
    small test shapes still get several tiles.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.masks import MaskSpec, pad_segments
from repro.core.online_softmax import combine_lse_outputs
from repro.kernels import autotune as _autotune
from repro.kernels import flash_bwd as _bwd
from repro.kernels import flash_decode as _dec
from repro.kernels import flash_fwd as _fwd
from repro.kernels.compat import resolve_interpret
from repro.kernels.schedule import (  # re-export
    PartitionedSchedule,
    TileSchedule,
    build_partitioned_schedule,
    build_tile_schedule,
)

LANES = _fwd.LANES

__all__ = [
    "PallasFlashConfig",
    "PartitionedSchedule",
    "TileSchedule",
    "build_partitioned_schedule",
    "build_tile_schedule",
    "default_block_sizes",
    "default_forward_partitions",
    "resolve_pallas_knobs",
    "flash_attention_pallas",
    "flash_attention_pallas_shard_bwd",
    "flash_attention_pallas_varlen",
    "flash_attention_pallas_varlen_with_lse",
    "flash_attention_pallas_with_lse",
    "flash_decode_pallas",
]


@dataclasses.dataclass(frozen=True)
class PallasFlashConfig:
    """The five-knob kernel config. ``None`` = resolve per shape at call
    time with precedence explicit arg > tuned cache > heuristic (see
    :func:`resolve_pallas_knobs`)."""

    spec: MaskSpec
    block_q: Optional[int] = None   # None -> tuned / default_block_sizes
    block_kv: Optional[int] = None
    scale: Optional[float] = None
    interpret: Optional[bool] = None  # None -> auto (off on TPU); compat.py
    schedule: Optional[str] = None  # 'compact' | 'dense'; None -> tuned/'compact'
    bwd: Optional[str] = None  # 'fused' (one-pass) | 'split'; None -> tuned/'fused'
    # Forward partitioning (compact schedule; paper Section 3.2). None ->
    # tuned cache, then the shape-aware default_forward_partitions policy;
    # explicit ints override (1 disables). Bands are bitwise-free; kv
    # splits change the fp summation order (exact up to merge rounding).
    num_q_bands: Optional[int] = None
    kv_splits: Optional[int] = None
    # Tri-state tuned-cache switch: None -> env REPRO_TUNED_CACHE (on by
    # default); False forces pure-heuristic resolution for every knob.
    use_tuned: Optional[bool] = None

    def __post_init__(self):
        if self.schedule not in (None, "compact", "dense"):
            raise ValueError(f"unknown tile schedule: {self.schedule!r}")
        if self.bwd not in (None, "fused", "split"):
            raise ValueError(f"unknown backward mode: {self.bwd!r}")
        for name in ("num_q_bands", "kv_splits"):
            val = getattr(self, name)
            if val is not None and val < 1:
                raise ValueError(f"{name} must be >= 1 (or None for auto)")


@dataclasses.dataclass(frozen=True)
class _KernelMeta:
    """Static call contract of the custom_vjp core (hashable, nondiff)."""

    spec: MaskSpec
    block_q: int
    block_kv: int
    group: int
    kv_valid: int
    schedule: str
    bwd: str
    interpret: Optional[bool]
    num_q_bands: int = 1  # resolved (never None) forward partition counts
    kv_splits: int = 1


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# The fused backward keeps every q tile's delta = rowsum(dO o O) row in a
# (G, t_q, block_q) f32 VMEM scratch for the whole kv-major sweep -- an
# O(G * padded_seq) term no block size can shrink. Past this budget the
# fused kernel would blow the ~16 MB/core VMEM on real TPUs (interpret
# mode never notices), so bwd="fused" silently degrades to the split
# 3-launch baseline, which keeps delta in HBM. The TPU compiler accepts
# the fused kernel at the budget's edge on a v5e
# (tests/test_tpu_compile.py).
_FUSED_DELTA_VMEM_BUDGET = 2 * 1024 * 1024  # bytes; G * Sqp * 4 must fit


def _resolve_bwd(bwd: str, group: int, seq_q_padded: int) -> str:
    """Shape-aware backward-mode resolution (see _FUSED_DELTA_VMEM_BUDGET)."""
    if bwd == "fused" and group * seq_q_padded * 4 > _FUSED_DELTA_VMEM_BUDGET:
        return "split"
    return bwd


# Target number of *parallel* grid cells for the compact forward. The
# flattened compact schedule exposes only B*Hq parallel cells; below this
# target the auto policy adds q bands (paper Section 3.2 forward
# partitioning) until BH * bands reaches it (or runs out of q tiles). A
# modest multiple of real TPU core counts so the scheduler can also
# pipeline across cells; large-BH shapes stay at 1 band (no padding cost).
_TARGET_PARALLEL_CELLS = 64


def default_forward_partitions(bh: int, t_q: int, t_kv: int):
    """Shape-aware (num_q_bands, kv_splits) for the compact forward.

    Bands: enough that ``bh * bands >= _TARGET_PARALLEL_CELLS``, capped at
    the q-tile count; degrade to 1 when ``bh`` alone fills the target
    (large-batch training) or the sequence is a single q tile. Banding is
    bitwise-free, so it is safe to apply by default.

    KV splits: only for the prefill-like corner where q-parallelism cannot
    exist at all -- a single q tile against many kv tiles (short-q/long-kv
    cross-attention, chunked prefill) with bh under the target. Splits
    change the fp merge order (exact up to rounding), so wider shapes that
    merely *also* want splits opt in explicitly via ``kv_splits=``.
    """
    bands = 1
    if bh < _TARGET_PARALLEL_CELLS and t_q > 1:
        bands = min(t_q, -(-_TARGET_PARALLEL_CELLS // bh))
    splits = 1
    if t_q == 1 and t_kv >= 4 and bh < _TARGET_PARALLEL_CELLS:
        splits = min(t_kv, -(-_TARGET_PARALLEL_CELLS // bh))
    return bands, splits


def _resolve_partitions(cfg: PallasFlashConfig, tuned: dict, schedule: str,
                        bh: int, t_q: int, t_kv: int):
    """Knobs (explicit > tuned > auto) -> concrete (num_q_bands, kv_splits)."""
    if schedule != "compact":
        if (cfg.num_q_bands or 1) > 1 or (cfg.kv_splits or 1) > 1:
            raise ValueError(
                "num_q_bands/kv_splits require schedule='compact'"
            )
        return 1, 1
    auto_nb, auto_ks = default_forward_partitions(bh, t_q, t_kv)
    nb = cfg.num_q_bands if cfg.num_q_bands is not None else \
        tuned.get("num_q_bands", auto_nb)
    ks = cfg.kv_splits if cfg.kv_splits is not None else \
        tuned.get("kv_splits", auto_ks)
    return max(1, min(nb, t_q)), max(1, min(ks, t_kv))


def default_block_sizes(seq_q: int, seq_kv: int, head_dim: int):
    """Shape-aware default (block_q, block_kv) for the Pallas kernels.

    The table keys off the head dim: the fused backward holds two f32
    ``(block_kv, D)`` scratch tiles (dK, dV) plus the streamed q/do/o tiles
    and the f32 dq block in flight in VMEM at once, so ``block_kv`` shrinks
    as D grows to keep that working set inside the ~16 MB/core budget.
    Both blocks clamp to the (8-aligned) padded sequence length so short
    sequences never over-pad. Explicit ``block_q``/``block_kv`` arguments
    override the table everywhere, exactly as before.
    """
    if head_dim <= 128:
        bq, bk = 512, 512
    elif head_dim <= 256:
        bq, bk = 512, 256
    else:
        bq, bk = 256, 128
    return min(bq, _round_up(seq_q, 8)), min(bk, _round_up(seq_kv, 8))


def _legalize_block(name: str, val, seq: int, *, explicit: bool,
                    align: int = 8) -> int:
    """Legalize one block-size knob against the kernels' layout contract.

    The kernels pad the sequence to a block multiple; a misaligned explicit
    value used to flow straight into ``_round_up(S, block)`` and silently
    corrupt the padding geometry. Non-positive / non-integer values raise;
    otherwise the value is rounded up to a multiple of ``align`` (8 rows in
    interpret mode; 128 lanes under Mosaic, whose block shapes must tile
    the lane-major side arrays) and clamped to the 8-aligned padded
    sequence length -- a block that covers the whole padded axis is legal
    at any alignment. A warning fires when an *explicit* request had to
    change (the heuristic and the tuned cache legalize silently -- clamping
    to a short sequence is their normal operating mode, not a user error).
    """
    if isinstance(val, bool) or not isinstance(val, int):
        raise ValueError(f"{name} must be an int >= 1, got {val!r}")
    if val < 1:
        raise ValueError(f"{name} must be >= 1, got {val}")
    legal = min(_round_up(val, align), _round_up(seq, 8))
    if explicit and legal != val:
        warnings.warn(
            f"{name}={val} is not legal for seq={seq} (blocks must be "
            f"{align}-aligned and <= the padded sequence); using {legal}",
            stacklevel=3,
        )
    return legal


def resolve_pallas_knobs(cfg: PallasFlashConfig, q_shape, k_shape,
                         dtype=jnp.float32) -> dict:
    """Concrete knob resolution for one call -- explicit > tuned > heuristic.

    ``q_shape``/``k_shape`` are the public-layout shapes (B, S, H, D). Every
    ``None`` knob on ``cfg`` is filled from the tuned cache entry for
    (impl='flash_pallas', causal, seq, heads, head dim, dtype) when the
    cache is enabled and has a (near-enough) entry -- see
    ``kernels/autotune.lookup`` -- and from the hand heuristics otherwise.
    Returns the full dict the kernel call contract is built from:
    ``block_q``, ``block_kv``, ``schedule``, ``bwd`` (VMEM-guard resolved),
    ``num_q_bands``, ``kv_splits``, plus ``tuned`` (the raw cache knobs
    consulted; empty when disabled or missed) for introspection.
    """
    B, Sq, Hq, D = q_shape
    _, Sk, Hk, _ = k_shape
    spec = cfg.spec
    tuned = {}
    # Windowed / sink mask families were never swept; their knob landscape
    # differs from plain causal/full, so they stay on the heuristics.
    if (_autotune.cache_enabled(cfg.use_tuned) and spec.window is None
            and spec.sink == 0):
        tuned = _autotune.lookup(
            "flash_pallas", spec.causal, Sq, Hq, D, dtype
        )
    bq_def, bk_def = default_block_sizes(Sq, Sk, D)
    bq = cfg.block_q if cfg.block_q is not None else tuned.get("block_q", bq_def)
    bk = cfg.block_kv if cfg.block_kv is not None else tuned.get("block_kv", bk_def)
    mosaic = not resolve_interpret(cfg.interpret)
    align = LANES if mosaic else 8
    bq = _legalize_block("block_q", bq, Sq, explicit=cfg.block_q is not None,
                         align=align)
    bk = _legalize_block("block_kv", bk, Sk, explicit=cfg.block_kv is not None,
                         align=align)
    Sqp, Skp = _round_up(Sq, bq), _round_up(Sk, bk)
    schedule = cfg.schedule or tuned.get("schedule") or "compact"
    bwd = cfg.bwd or tuned.get("bwd") or "fused"
    nb, ks = _resolve_partitions(
        cfg, tuned, schedule, B * Hq, Sqp // bq, Skp // bk
    )
    _count_knob_sources(cfg, tuned, schedule)
    return dict(
        block_q=bq, block_kv=bk, schedule=schedule,
        bwd=_resolve_bwd(bwd, Hq // Hk, Sqp),
        num_q_bands=nb, kv_splits=ks, tuned=dict(tuned),
    )


def _count_knob_sources(cfg: PallasFlashConfig, tuned: dict, schedule: str):
    """Telemetry: which precedence tier supplied each knob of this call.

    Increments ``knobs/flash_pallas/{explicit,tuned,heuristic}`` on the
    process-wide default registry (repro.obs.metrics) -- one hit per knob,
    so a call resolving block_q explicitly but everything else from the
    cache counts 1 explicit + N tuned. Runs at *trace* time (resolution
    happens once per jit trace); cached executions do not re-count, the
    same way they do not re-compile.
    """
    from repro.obs.metrics import count_knob

    per_source = {"explicit": 0, "tuned": 0, "heuristic": 0}

    def classify(explicit: bool, tuned_key: str):
        if explicit:
            per_source["explicit"] += 1
        elif tuned_key in tuned:
            per_source["tuned"] += 1
        else:
            per_source["heuristic"] += 1

    classify(cfg.block_q is not None, "block_q")
    classify(cfg.block_kv is not None, "block_kv")
    classify(cfg.schedule is not None, "schedule")
    classify(cfg.bwd is not None, "bwd")
    if schedule != "dense":  # dense forces 1/1: no partition knobs in play
        classify(cfg.num_q_bands is not None, "num_q_bands")
        classify(cfg.kv_splits is not None, "kv_splits")
    for source, n in per_source.items():
        if n:
            count_knob("flash_pallas", source, n)


def _heads_layout(x: jnp.ndarray) -> jnp.ndarray:
    """(B, S, H, D) -> (B*H, S, D)."""
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _unheads_layout(x: jnp.ndarray, B: int, H: int) -> jnp.ndarray:
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _prep(q, k, v, cfg: PallasFlashConfig, resolved: dict):
    B, Sq, Hq, D = q.shape
    _, Sk, Hk, _ = k.shape
    assert Hq % Hk == 0
    G = Hq // Hk
    scale = cfg.scale if cfg.scale is not None else 1.0 / math.sqrt(D)
    bq, bk = resolved["block_q"], resolved["block_kv"]
    qh = _heads_layout(q)
    kh = _heads_layout(k)
    vh = _heads_layout(v)
    pad_q = _round_up(Sq, bq) - Sq
    pad_k = _round_up(Sk, bk) - Sk
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kh = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0)))
    qh = (qh.astype(jnp.float32) * scale).astype(q.dtype)
    return qh, kh, vh, dict(
        B=B, Sq=Sq, Sk=Sk, Sqp=qh.shape[1], Skp=kh.shape[1],
        Hq=Hq, Hk=Hk, G=G, D=D, bq=bq, bk=bk, scale=scale,
    )


def _prep_call(q, k, v, cfg: PallasFlashConfig, q_seg=None, kv_seg=None):
    """Layout prep + the static kernel-call contract.

    Segment ids stay UNREPLICATED (B, Sqp)/(B, Skp) -- the kernels' index
    maps divide the head-row id down, so the ids are never materialized per
    head. Padding uses the repo-wide sentinels (masks.pad_segments): padded
    tiles become cross-segment, so padded q rows attend nothing (l = 0 ->
    o = 0, lse = -inf; trimmed by the caller).
    """
    r = resolve_pallas_knobs(cfg, q.shape, k.shape, q.dtype)
    qh, kh, vh, m = _prep(q, k, v, cfg, r)
    meta = _KernelMeta(
        spec=cfg.spec, block_q=m["bq"], block_kv=m["bk"], group=m["G"],
        kv_valid=m["Sk"], schedule=r["schedule"],
        bwd=r["bwd"], interpret=cfg.interpret,
        num_q_bands=r["num_q_bands"], kv_splits=r["kv_splits"],
    )
    qs = ks = None
    if q_seg is not None:
        qs, ks = pad_segments(
            q_seg.astype(jnp.int32), kv_seg.astype(jnp.int32), m["Sqp"], m["Skp"]
        )
    return qh, kh, vh, qs, ks, m, meta


# ---------------------------------------------------------------------------
# The differentiable core: prepped tensors in, prepped tensors out.
# ---------------------------------------------------------------------------


def _core_fwd(qh, kh, vh, qs, ks, meta: _KernelMeta):
    """flash_fwd on prepped tensors -> (o (BH, Sqp, D), lse (BH, 1, Sqp)).

    With ``meta.kv_splits > 1`` the kernel emits per-split partials which
    are folded here by the associative ``merge_partials`` tree
    (``combine_lse_outputs``) -- the same primitive split-KV decode and the
    ring merge use. A split that saw no visible tile for a row emitted the
    merge identity (o = 0, lse = -inf), so fully-masked rows still come out
    as (0, -inf) exactly like the single-pass kernel.
    """
    out = _fwd.flash_fwd(
        qh, kh, vh, meta.spec, group=meta.group, block_q=meta.block_q,
        block_kv=meta.block_kv, kv_valid=meta.kv_valid, q_seg=qs, kv_seg=ks,
        interpret=meta.interpret, schedule=meta.schedule,
        num_q_bands=meta.num_q_bands, kv_splits=meta.kv_splits,
    )
    if meta.kv_splits > 1:
        o_parts, lse_parts = out  # (BH, ks, Sqp, D) f32, (BH, ks, 1, Sqp) f32
        o, lse = combine_lse_outputs(
            jnp.moveaxis(o_parts, 1, 0), jnp.moveaxis(lse_parts[:, :, 0], 1, 0)
        )
        return o.astype(qh.dtype), lse[:, None, :]
    return out


def _core_bwd(qh, kh, vh, o, lse, do, meta: _KernelMeta, qs=None, ks=None):
    """Algorithm 2 on prepped residuals; returns (dqh, dkh, dvh).

    ``bwd="fused"``: one kv-major launch computes delta, dK, dV and dQ with
    a single (s, p) recompute per visible tile. ``bwd="split"``: the
    3-launch baseline (delta preprocess, then dkv and dq each recomputing
    (s, p) for every tile they visit).
    """
    doh = do.astype(qh.dtype)
    kw = dict(
        group=meta.group, block_q=meta.block_q, block_kv=meta.block_kv,
        kv_valid=meta.kv_valid, q_seg=qs, kv_seg=ks,
        interpret=meta.interpret, schedule=meta.schedule,
    )
    if meta.bwd == "fused":
        # Raw lse: the -inf cleanup for fully-masked rows happens in-kernel.
        dk, dv, dq = _bwd.flash_bwd_fused(
            qh, kh, vh, o, doh, lse, meta.spec, **kw
        )
        return dq.astype(qh.dtype), dk.astype(kh.dtype), dv.astype(vh.dtype)
    delta = _bwd.flash_bwd_delta(
        o, do, block_q=meta.block_q, interpret=meta.interpret
    )  # (BH, 1, Sqp) f32: Algorithm 2 line 4
    # Fully-masked rows carry lse = -inf; zero it so exp(S - lse) stays 0
    # (S is DEFAULT_MASK_VALUE there) instead of producing inf.
    lse_s = jnp.where(jnp.isneginf(lse), 0.0, lse)
    dk, dv = _bwd.flash_bwd_dkv(qh, kh, vh, doh, lse_s, delta, meta.spec, **kw)
    dq = _bwd.flash_bwd_dq(qh, kh, vh, doh, lse_s, delta, meta.spec, **kw)
    # dq is w.r.t. the *scaled* q; the wrapper's prep transpose applies the
    # scale (and the unpad/unhead) when XLA differentiates through it.
    return dq.astype(qh.dtype), dk.astype(kh.dtype), dv.astype(vh.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core(qh, kh, vh, meta: _KernelMeta):
    return _core_fwd(qh, kh, vh, None, None, meta)[0]


def _flash_core_fwd(qh, kh, vh, meta):
    o, lse = _core_fwd(qh, kh, vh, None, None, meta)
    return o, (qh, kh, vh, o, lse)  # prepped residuals: no _prep in the bwd


def _flash_core_bwd(meta, res, do):
    qh, kh, vh, o, lse = res
    return _core_bwd(qh, kh, vh, o, lse, do, meta)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _flash_core_varlen(qh, kh, vh, qs, ks, meta: _KernelMeta):
    return _core_fwd(qh, kh, vh, qs, ks, meta)[0]


def _flash_core_varlen_fwd(qh, kh, vh, qs, ks, meta):
    o, lse = _core_fwd(qh, kh, vh, qs, ks, meta)
    return o, (qh, kh, vh, qs, ks, o, lse)


def _flash_core_varlen_bwd(meta, res, do):
    qh, kh, vh, qs, ks, o, lse = res
    dq, dk, dv = _core_bwd(qh, kh, vh, o, lse, do, meta, qs, ks)
    return dq, dk, dv, None, None  # integer segment ids carry no gradient


_flash_core_varlen.defvjp(_flash_core_varlen_fwd, _flash_core_varlen_bwd)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def flash_attention_pallas(
    q, k, v, spec: MaskSpec = MaskSpec(causal=True), *,
    scale: Optional[float] = None,
    block_q: Optional[int] = None, block_kv: Optional[int] = None,
    interpret: Optional[bool] = None, schedule: Optional[str] = None,
    bwd: Optional[str] = None,
    num_q_bands: Optional[int] = None, kv_splits: Optional[int] = None,
    use_tuned: Optional[bool] = None,
):
    """Differentiable FA2 via the Pallas TPU kernels. q (B,Sq,Hq,D).

    ``bwd`` picks the backward: ``"fused"`` (one-pass kernel, the resolved
    default) or ``"split"`` (delta + dkv + dq baseline). Every ``None``
    knob resolves per shape -- tuned cache first (``kernels/autotune``,
    disable with ``use_tuned=False``), then the shape-aware heuristics
    (:func:`default_block_sizes` / :func:`default_forward_partitions`).
    """
    cfg = PallasFlashConfig(
        spec=spec, block_q=block_q, block_kv=block_kv, scale=scale,
        interpret=interpret, schedule=schedule, bwd=bwd,
        num_q_bands=num_q_bands, kv_splits=kv_splits, use_tuned=use_tuned,
    )
    qh, kh, vh, _, _, m, meta = _prep_call(q, k, v, cfg)
    o = _flash_core(qh, kh, vh, meta)
    return _unheads_layout(o[:, : m["Sq"]], m["B"], m["Hq"]).astype(q.dtype)


def flash_attention_pallas_varlen(
    q, k, v, segment_ids, spec: MaskSpec = MaskSpec(causal=True), *,
    kv_segment_ids=None, scale: Optional[float] = None,
    block_q: Optional[int] = None, block_kv: Optional[int] = None,
    interpret: Optional[bool] = None, schedule: Optional[str] = None,
    bwd: Optional[str] = None,
    num_q_bands: Optional[int] = None, kv_splits: Optional[int] = None,
    use_tuned: Optional[bool] = None,
):
    """Differentiable segment-packed (varlen) FA2 via the Pallas kernels.

    Each batch row packs several back-to-back sequences; ``segment_ids``
    (B, Sq) int32 marks which tokens belong together (id 0 = padding by the
    data-pipeline convention -- any non-negative ids work). Query i attends
    key j iff their ids match AND the MaskSpec admits the *global* positions
    (with contiguous packing, global causality == within-segment causality).
    Cross-segment tiles are skipped in all three kernels (fwd, dkv, dq):
    under the compact schedule via a prefetched per-(batch, step) range-
    disjointness table, under the dense schedule via in-kernel per-tile
    id-range probing -- the paper's Section 3.1 block skipping generalized
    from a static causal schedule to data-dependent segments.

    kv_segment_ids defaults to segment_ids (self-attention over one packed
    layout); a ``masks.SegmentInfo`` is accepted in place of the raw array.
    Returns o (B, Sq, Hq, D).
    """
    from repro.core.masks import SegmentInfo

    if isinstance(segment_ids, SegmentInfo):
        segment_ids, kv_segment_ids = segment_ids.q, segment_ids.kv
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids
    assert segment_ids.shape == q.shape[:2], (segment_ids.shape, q.shape)
    assert kv_segment_ids.shape == k.shape[:2], (kv_segment_ids.shape, k.shape)
    cfg = PallasFlashConfig(
        spec=spec, block_q=block_q, block_kv=block_kv, scale=scale,
        interpret=interpret, schedule=schedule, bwd=bwd,
        num_q_bands=num_q_bands, kv_splits=kv_splits, use_tuned=use_tuned,
    )
    qh, kh, vh, qs, ks, m, meta = _prep_call(q, k, v, cfg, segment_ids, kv_segment_ids)
    o = _flash_core_varlen(qh, kh, vh, qs, ks, meta)
    return _unheads_layout(o[:, : m["Sq"]], m["B"], m["Hq"]).astype(q.dtype)


def _fwd_with_lse(q, k, v, cfg, q_seg=None, kv_seg=None):
    qh, kh, vh, qs, ks, m, meta = _prep_call(q, k, v, cfg, q_seg, kv_seg)
    o, lse = _core_fwd(qh, kh, vh, qs, ks, meta)
    o = _unheads_layout(o[:, : m["Sq"]], m["B"], m["Hq"]).astype(q.dtype)
    lse_rows = lse[:, 0, : m["Sq"]].reshape(m["B"], m["Hq"], m["Sq"])
    return o, lse_rows


def flash_attention_pallas_varlen_with_lse(
    q, k, v, segment_ids, spec: MaskSpec = MaskSpec(causal=True), *,
    kv_segment_ids=None, scale: Optional[float] = None,
    block_q: Optional[int] = None, block_kv: Optional[int] = None,
    interpret: Optional[bool] = None, schedule: Optional[str] = None,
    num_q_bands: Optional[int] = None, kv_splits: Optional[int] = None,
    use_tuned: Optional[bool] = None,
):
    """Forward-only varlen (serving): returns (o, lse (B, Hq, Sq))."""
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids
    cfg = PallasFlashConfig(
        spec=spec, block_q=block_q, block_kv=block_kv, scale=scale,
        interpret=interpret, schedule=schedule,
        num_q_bands=num_q_bands, kv_splits=kv_splits, use_tuned=use_tuned,
    )
    return _fwd_with_lse(
        q, k, v, cfg, segment_ids.astype(jnp.int32), kv_segment_ids.astype(jnp.int32)
    )


def flash_attention_pallas_with_lse(
    q, k, v, spec: MaskSpec = MaskSpec(causal=True), *,
    scale: Optional[float] = None,
    block_q: Optional[int] = None, block_kv: Optional[int] = None,
    interpret: Optional[bool] = None, schedule: Optional[str] = None,
    num_q_bands: Optional[int] = None, kv_splits: Optional[int] = None,
    use_tuned: Optional[bool] = None,
):
    cfg = PallasFlashConfig(
        spec=spec, block_q=block_q, block_kv=block_kv, scale=scale,
        interpret=interpret, schedule=schedule,
        num_q_bands=num_q_bands, kv_splits=kv_splits, use_tuned=use_tuned,
    )
    return _fwd_with_lse(q, k, v, cfg)


def flash_attention_pallas_shard_bwd(
    q, k, v, o, lse, do, spec: MaskSpec = MaskSpec(causal=True), *,
    scale: Optional[float] = None,
    block_q: Optional[int] = None, block_kv: Optional[int] = None,
    interpret: Optional[bool] = None, schedule: Optional[str] = None,
    bwd: Optional[str] = None, use_tuned: Optional[bool] = None,
    out_dtype=None,
):
    """Shard-local Algorithm 2 against an externally merged (o, lse).

    The ring-attention backward (distributed/ring_attention.py) replays each
    (q_shard, kv_shard) rectangle it visited in the forward and needs that
    rectangle's (dq, dk, dv) contribution computed with the *globally*
    merged softmax statistics: ``lse`` (B, Hq, Sq) f32 is the final merged
    logsumexp over ALL keys, and ``o`` (B, Sq, Hq, D) the final merged
    output (so ``delta = rowsum(dO o O)``, Algorithm 2 line 4, is the global
    row term). With those, ``P = exp(S_rect - lse)`` is exactly this
    rectangle's slice of the global probability matrix, and the three bwd
    kernels run their ordinary compact schedule restricted to the
    rectangle's spec. Summing the returned (dq, dk, dv) over rectangles (as
    the ring does) reproduces the single-device backward.

    There is no ``custom_vjp`` here on purpose — the caller IS a vjp; this
    is a direct kernel entry on one shard pair. Returns (dq, dk, dv) in the
    input dtypes, or in ``out_dtype`` when given — the ring passes f32 so
    its traveling (dK, dV) accumulators fold in each rectangle's
    contribution without a lossy round-trip through the bf16 input dtype.
    ``bwd="fused"`` runs the rectangle as ONE kernel launch (ring training
    inherits the fused win); ``"split"`` keeps the 3-launch baseline.
    """
    cfg = PallasFlashConfig(
        spec=spec, block_q=block_q, block_kv=block_kv, scale=scale,
        interpret=interpret, schedule=schedule, bwd=bwd, use_tuned=use_tuned,
    )
    qh, kh, vh, _, _, m, meta = _prep_call(q, k, v, cfg)
    oh = _heads_layout(o.astype(jnp.float32))
    doh = _heads_layout(do.astype(jnp.float32))
    lse_h = lse.astype(jnp.float32).reshape(m["B"] * m["Hq"], 1, m["Sq"])
    pad_q = m["Sqp"] - m["Sq"]
    if pad_q:
        # Padded rows carry do = 0 and lse = -inf -> every bwd term is 0.
        oh = jnp.pad(oh, ((0, 0), (0, pad_q), (0, 0)))
        doh = jnp.pad(doh, ((0, 0), (0, pad_q), (0, 0)))
        lse_h = jnp.pad(lse_h, ((0, 0), (0, 0), (0, pad_q)),
                        constant_values=-jnp.inf)
    dqh, dkh, dvh = _core_bwd(qh, kh, vh, oh, lse_h, doh, meta)
    # _core_bwd differentiates w.r.t. the pre-scaled q; fold the scale back.
    dq = _unheads_layout(dqh[:, : m["Sq"]].astype(jnp.float32) * m["scale"],
                         m["B"], m["Hq"])
    dk = _unheads_layout(dkh[:, : m["Sk"]], m["B"], m["Hk"])
    dv = _unheads_layout(dvh[:, : m["Sk"]], m["B"], m["Hk"])
    return (
        dq.astype(out_dtype or q.dtype),
        dk.astype(out_dtype or k.dtype),
        dv.astype(out_dtype or v.dtype),
    )


def flash_decode_pallas(
    q, k_cache, v_cache, cache_length, *,
    window: Optional[int] = None, sink: int = 0, scale: Optional[float] = None,
    num_splits: int = 8, kv_segment_ids=None, q_segment=None,
    interpret: Optional[bool] = None,
):
    """Split-KV decode via the Pallas kernel. q (B,1,Hq,D); returns (o, lse).

    kv_segment_ids (B, S) + q_segment (B,) restrict each query to its own
    segment of a *packed* KV cache (no reads across segment boundaries).
    """
    B, one, Hq, D = q.shape
    assert one == 1
    _, S, Hk, _ = k_cache.shape
    G = Hq // Hk
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qh = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qh = qh.reshape(B, Hk, G, D).reshape(B * Hk, G, D)
    kh = _heads_layout(k_cache)
    vh = _heads_layout(v_cache)
    lens = jnp.repeat(cache_length.astype(jnp.int32), Hk)
    kv_seg = q_seg = None
    if kv_segment_ids is not None:
        assert q_segment is not None, "packed decode needs q_segment (B,)"
        kv_seg = jnp.repeat(kv_segment_ids.astype(jnp.int32), Hk, axis=0)
        q_seg = jnp.repeat(q_segment.astype(jnp.int32), Hk)
    o_parts, lse_parts = _dec.flash_decode_kernel(
        qh, kh, vh, lens, num_splits=num_splits, window=window, sink=sink,
        kv_seg=kv_seg, q_seg=q_seg, interpret=interpret,
    )
    # Merge the splits (associative combine) -- (ns, BHk, G, D) / (ns, BHk, G).
    o, lse = combine_lse_outputs(
        jnp.moveaxis(o_parts, 1, 0), jnp.moveaxis(lse_parts[:, :, 0], 1, 0)
    )
    return (
        o.reshape(B, 1, Hq, D).astype(q.dtype),
        lse.reshape(B, Hq, 1),
    )


def flash_decode_paged_pallas(
    q, k_pages, v_pages, cache_length, block_table, layer=0, *,
    window: Optional[int] = None, sink: int = 0, scale: Optional[float] = None,
    num_splits: int = 8, interpret: Optional[bool] = None,
):
    """Page-indirect split-KV decode. q (B,1,Hq,D); k/v_pages the stacked
    pool planes (layers, Hkv, P, ps, D), read at ``layer``; cache_length
    (B,) logical lengths; block_table (B, n_pages) int32 physical page ids
    (0 = the reserved null page). Returns (o, lse) with the same contract
    as :func:`flash_decode_pallas` -- the serving engine swaps a contiguous
    cache for pool planes without touching the merge."""
    B, one, Hq, D = q.shape
    assert one == 1
    Hk = k_pages.shape[1]
    G = Hq // Hk
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qh = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qh = qh.reshape(B, Hk, G, D)
    o_parts, lse_parts = _dec.flash_decode_paged_kernel(
        qh, k_pages, v_pages, cache_length.astype(jnp.int32), block_table,
        layer, num_splits=num_splits, window=window, sink=sink,
        interpret=interpret,
    )
    # (B, ns, Hk, ...) -> (ns, B*Hk, ...): the contiguous path's merge.
    ns = o_parts.shape[1]
    o, lse = combine_lse_outputs(
        jnp.moveaxis(o_parts, 1, 0).reshape(ns, B * Hk, G, D),
        jnp.moveaxis(lse_parts[:, :, :, 0], 1, 0).reshape(ns, B * Hk, G),
    )
    return (
        o.reshape(B, 1, Hq, D).astype(q.dtype),
        lse.reshape(B, Hq, 1),
    )


def mla_decode_paged_pallas(
    q, latent_pages, cache_length, block_table, layer=0, *, v_dim: int,
    scale: float, num_splits: int = 8, interpret: Optional[bool] = None,
):
    """Latent-attention decode through the paged kernel's latent mode.
    q (B,1,H,W) absorbed latent queries; latent_pages the stacked pool
    planes (layers, 1, P, ps, W), read at ``layer``; returns (o (B,1,H,
    v_dim), lse (B,H,1)): every head reads each cached latent token once,
    as K, and its first ``v_dim`` lanes as V."""
    B, one, H, W = q.shape
    assert one == 1 and latent_pages.shape[1] == 1
    qh = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(B, 1, H, W)
    o_parts, lse_parts = _dec.flash_decode_paged_kernel(
        qh, latent_pages, None, cache_length.astype(jnp.int32), block_table,
        layer, num_splits=num_splits, v_dim=v_dim, interpret=interpret,
    )
    ns = o_parts.shape[1]
    o, lse = combine_lse_outputs(
        jnp.moveaxis(o_parts, 1, 0).reshape(ns, B, H, v_dim),
        jnp.moveaxis(lse_parts[:, :, :, 0], 1, 0).reshape(ns, B, H),
    )
    return o.reshape(B, 1, H, v_dim).astype(q.dtype), lse.reshape(B, H, 1)
