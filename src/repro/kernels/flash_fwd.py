"""FlashAttention-2 forward Pallas TPU kernel.

TPU mapping of the paper's scheme (DESIGN.md Section 2):

  * Grid: (batch x heads) is `parallel`; the KV dimension is the sequential
    (`arbitrary`) axis, which makes the VMEM scratch carry the online-
    softmax state across KV steps.
  * ``schedule="compact"`` (default): the sequential axis enumerates ONLY
    the visible (i, j) tile pairs -- flattened q-row-major into a scalar-
    prefetched schedule table (kernels/schedule.py), grid ``(BH, n_steps)``.
    Spec-masked tiles are never *visited*: the paper's Section 3.1 work
    partitioning moved from an in-kernel branch into the grid itself, so
    causal drops ~2x of the grid steps and K/V tile DMAs, sliding-window
    O(S/W)x. Packed-varlen visibility is data-dependent and cannot shrink
    the (static) grid; cross-segment tiles still occupy a step but skip
    their *compute* via a prefetched per-(batch, step) bit table -- no
    in-kernel segment-id min/max probing.
  * Occupancy-aware forward partitioning (paper Section 3.2, Figure 2):
    the compact schedule optionally splits each head's work over a second
    *parallel* grid axis -- ``num_q_bands`` q-row bands (balanced by
    visible tile count; bitwise-equal to unbanded) and/or ``kv_splits``
    contiguous KV ranges emitting (o, lse) partials merged outside the
    kernel. Grid ``(BH, bands * splits, n_steps_part)``, so small-BH /
    long-S shapes still fill the chip. See
    ``schedule.build_partitioned_schedule`` and ``ops.
    default_forward_partitions`` (the shape-aware auto policy).
  * ``schedule="dense"``: the legacy ``(BH, Tq, Tkv)`` grid that visits
    every tile and skips empty ones with ``pl.when`` (kept as the
    measurable baseline; the matmuls are skipped but the grid step and its
    tile DMA still happen).
  * "Split-Q" warp partitioning (C3) becomes q-block-stationary scheduling:
    the Q tile is fetched once per row run and stays in VMEM while K/V
    stream past; the accumulator never leaves VMEM scratch. There is no
    cross-"worker" communication, exactly as in the paper's Figure 3 right.
  * C1: the accumulator is un-rescaled until the final KV step, where we
    apply ``diag(l)^-1`` once and emit the logsumexp.
  * The logsumexp is emitted LANE-MAJOR: ``(BH, 1, Sq)`` f32 with the
    sequence on the 128-lane axis, BlockSpec ``(None, 1, block_q)`` -- 128x
    fewer softmax-stat bytes than the historical ``(BH, Sq, LANES)``
    broadcast. The unit sublane axis is what Mosaic needs: the last two
    block dims must each be a multiple of (8, 128) or the whole array dim,
    and ``1`` is the whole dim while a ``block_q`` that is a multiple of 128
    tiles the lanes. The backward consumes the same layout; decode's split
    merge reuses it.

Layout contract (set up by ops.py): q (BH, Sq, D), k/v (BHk, Skv, D) with
BH = B * Hq, BHk = B * Hkv, q head ``h`` reading kv head ``h // G``.
All sequence lengths pre-padded to the block size; KV padding masked here.
Segment ids (packed varlen) arrive UNREPLICATED as (B, Sqp)/(B, Skp) and
get the same unit sublane axis as lse (:func:`lane_rows`); the index maps
divide the head-row id by the head count.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.masks import DEFAULT_MASK_VALUE, MaskSpec
from repro.kernels.compat import resolve_interpret
from repro.kernels.schedule import (
    build_partitioned_schedule,
    build_tile_schedule,
    decode_step_bits,
    segment_step_tables,
)

LANES = 128


def lane_rows(x: jnp.ndarray) -> jnp.ndarray:
    """(N, S) -> (N, 1, S): a lane-major side array in the layout its
    ``(None, 1, block)`` BlockSpecs tile (see the module docstring)."""
    return x[:, None, :]


def lane_spec(block: int, index_map) -> pl.BlockSpec:
    """BlockSpec of one ``(block,)`` row of a :func:`lane_rows` array; the
    kernel reads and writes ``ref[0]``. ``index_map`` returns the (row,
    lane-block) pair."""
    def _map(*ids):
        row, col = index_map(*ids)
        return row, 0, col

    return pl.BlockSpec((None, 1, block), _map)


def _visibility(
    spec: MaskSpec, i, j, bq: int, bk: int, kv_valid: int,
    q_seg=None, kv_seg=None,
):
    """In-kernel scalar visibility: returns (is_empty, needs_mask) bools.

    Used by the DENSE schedule only -- the compact schedule precomputes the
    same classification host-side (kernels/schedule.py) and prefetches it.

    i/j are (traced) program ids; spec fields and block sizes are static, so
    every branch below is a static Python branch over *which* scalar ops to
    emit -- the emitted ops themselves are traced scalar arithmetic.

    q_seg/kv_seg: optional loaded (bq,)/(bk,) int32 segment-id tiles (packed
    varlen). Their min/max ranges drive *data-dependent* block skipping: a
    tile whose id ranges are disjoint cannot contain an equal pair, so it is
    empty -- sound for any id layout, and exact for contiguous packing. A
    tile is mask-free only if both sides are uniform and equal.
    """
    q_lo = i * bq + spec.q_offset
    q_hi = q_lo + bq - 1
    kv_lo = j * bk
    kv_hi = kv_lo + bk - 1
    empty = jnp.bool_(False)
    full = jnp.bool_(True)
    if spec.causal:
        empty = q_hi < kv_lo
        full = q_lo >= kv_hi
        if spec.window is not None:
            win_empty = (q_lo - kv_hi) >= spec.window
            if spec.sink:
                win_empty = win_empty & ~(kv_lo < spec.sink)
            empty = empty | win_empty
            in_win = (q_hi - kv_lo) < spec.window
            if spec.sink:
                in_win = in_win | (kv_hi < spec.sink)
            full = full & in_win
    elif spec.window is not None:
        win_empty = ((q_lo - kv_hi) >= spec.window) | ((kv_lo - q_hi) >= spec.window)
        if spec.sink:
            win_empty = win_empty & ~(kv_lo < spec.sink)
        empty = win_empty
        full = (abs_diff(q_lo, kv_hi) < spec.window) & (abs_diff(q_hi, kv_lo) < spec.window)
        if spec.sink:
            full = full | (kv_hi < spec.sink)
    if kv_valid % bk != 0:
        # last block contains padding -> not full there
        pad_block = kv_valid // bk
        empty = empty | (kv_lo >= kv_valid)
        full = full & (j != pad_block)
    if q_seg is not None:
        qs_lo, qs_hi = jnp.min(q_seg), jnp.max(q_seg)
        ks_lo, ks_hi = jnp.min(kv_seg), jnp.max(kv_seg)
        empty = empty | (qs_hi < ks_lo) | (qs_lo > ks_hi)
        full = full & (qs_lo == qs_hi) & (ks_lo == ks_hi) & (qs_lo == ks_lo)
    return jnp.bool_(empty), ~jnp.bool_(full)


def abs_diff(a, b):
    d = a - b
    return jnp.where(d < 0, -d, d)


def _tile_mask(
    spec: MaskSpec, i, j, bq: int, bk: int, kv_valid: int,
    q_seg=None, kv_seg=None,
):
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq + spec.q_offset
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
    mask = cols < kv_valid
    if q_seg is not None:
        mask = mask & (q_seg[:, None] == kv_seg[None, :])
    if spec.causal:
        mask = mask & (rows >= cols)
        if spec.window is not None:
            in_win = rows - cols < spec.window
            if spec.sink:
                in_win = in_win | (cols < spec.sink)
            mask = mask & in_win
    elif spec.window is not None:
        in_win = abs_diff(rows, cols) < spec.window
        if spec.sink:
            in_win = in_win | (cols < spec.sink)
        mask = mask & in_win
    return mask


# ---------------------------------------------------------------------------
# Shared tile-step bodies (used by both schedules)
# ---------------------------------------------------------------------------


def _init_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_softmax_step(q, k, v, mask, needs_mask, m_scr, l_scr, acc_scr):
    """One KV-tile update (FA2 Algorithm 1 lines 8-10, C1a un-rescaled)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bk)
    s = jnp.where(jnp.logical_or(~needs_mask, mask), s, DEFAULT_MASK_VALUE)

    m_prev = m_scr[:, :1]  # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_new))
    p = jnp.exp(s - m_new)
    l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # C1a: accumulate UN-rescaled; only the running-max correction.
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _finalize_state(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    """C1a final rescale + the lane-major logsumexp emit."""
    l = l_scr[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
    m = m_scr[:, :1]
    lse = jnp.where(l == 0.0, -jnp.inf, m + jnp.log(l_safe))
    lse_ref[0] = lse[:, 0]  # (bq,) on the lane axis


# ---------------------------------------------------------------------------
# Dense schedule (legacy baseline): visit every tile, branch-skip empties
# ---------------------------------------------------------------------------


def _fwd_kernel_dense(
    *refs,  # inputs [+ optional segment-id refs], outputs, VMEM scratch
    spec: MaskSpec,
    bq: int,
    bk: int,
    t_kv: int,
    kv_valid: int,
    has_segments: bool = False,
):
    if has_segments:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]  # (bq,), (bk,) int32
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        q_seg = kv_seg = None
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    empty, needs_mask = _visibility(spec, i, j, bq, bk, kv_valid, q_seg, kv_seg)

    @pl.when(~empty)
    def _compute():
        mask = _tile_mask(spec, i, j, bq, bk, kv_valid, q_seg, kv_seg)
        _online_softmax_step(
            q_ref[0], k_ref[0], v_ref[0], mask, needs_mask, m_scr, l_scr, acc_scr
        )

    @pl.when(j == t_kv - 1)
    def _finalize():
        _finalize_state(o_ref, lse_ref, m_scr, l_scr, acc_scr)


# ---------------------------------------------------------------------------
# Compact schedule: the grid IS the visible-tile list
# ---------------------------------------------------------------------------


def _fwd_kernel_compact(
    *refs,  # scalar-prefetch refs, inputs [+ seg tiles], outputs, scratch
    spec: MaskSpec,
    bq: int,
    bk: int,
    kv_valid: int,
    heads: int,
    has_segments: bool = False,
):
    if has_segments:
        (outer_ref, inner_ref, flags_ref, seg_ref,
         q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]
    else:
        (outer_ref, inner_ref, flags_ref,
         q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
        q_seg = kv_seg = None
    bh = pl.program_id(0)
    s = pl.program_id(1)
    i = outer_ref[s]
    j = inner_ref[s]
    active, first, last, needs_mask = decode_step_bits(
        flags_ref[s], seg_ref[bh // heads, s] if has_segments else None
    )

    @pl.when(first)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    @pl.when(active)
    def _compute():
        mask = _tile_mask(spec, i, j, bq, bk, kv_valid, q_seg, kv_seg)
        _online_softmax_step(
            q_ref[0], k_ref[0], v_ref[0], mask, needs_mask, m_scr, l_scr, acc_scr
        )

    @pl.when(last)
    def _finalize():
        _finalize_state(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _fwd_kernel_partitioned(
    *refs,  # scalar-prefetch refs, inputs [+ seg tiles], outputs, scratch
    spec: MaskSpec,
    bq: int,
    bk: int,
    kv_valid: int,
    heads: int,
    has_segments: bool = False,
):
    """Compact step body on the partitioned grid (BH, P, n_steps_part).

    Identical tile math to ``_fwd_kernel_compact``; the partition id ``p``
    (a *parallel* axis -- the paper's Figure 2 forward split) picks the row
    of the 2-D schedule tables. Each partition runs its own q-row runs with
    its own scratch; there is no cross-partition communication. Padding
    placeholder steps (flags == 0) run no compute and revisit the last
    emitted blocks, so they cost neither exps nor DMAs.
    """
    if has_segments:
        (outer_ref, inner_ref, flags_ref, pkv_ref, seg_ref,
         q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]
    else:
        (outer_ref, inner_ref, flags_ref, pkv_ref,
         q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
        q_seg = kv_seg = None
    del pkv_ref  # output index maps read it; the body does not
    bh = pl.program_id(0)
    p = pl.program_id(1)
    s = pl.program_id(2)
    i = outer_ref[p, s]
    j = inner_ref[p, s]
    active, first, last, needs_mask = decode_step_bits(
        flags_ref[p, s], seg_ref[bh // heads, p, s] if has_segments else None
    )

    @pl.when(first)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    @pl.when(active)
    def _compute():
        mask = _tile_mask(spec, i, j, bq, bk, kv_valid, q_seg, kv_seg)
        _online_softmax_step(
            q_ref[0], k_ref[0], v_ref[0], mask, needs_mask, m_scr, l_scr, acc_scr
        )

    @pl.when(last)
    def _finalize():
        _finalize_state(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _fwd_cost(BH, n_vis, block_q, block_kv, D, q, k, Dv=None):
    """Roofline-honest cost: count only visible tiles (block skipping)."""
    Dv = D if Dv is None else Dv
    flops_per_tile = 2 * block_q * block_kv * (D + Dv)  # QK^T + PV
    kv_tile_bytes = block_kv * (D + Dv) * k.dtype.itemsize  # K + V tiles streamed
    return pl.CostEstimate(
        flops=BH * n_vis * flops_per_tile,
        bytes_accessed=2 * q.size * q.dtype.itemsize + BH * n_vis * kv_tile_bytes,
        transcendentals=BH * n_vis * block_q * block_kv,
    )


def flash_fwd(
    q: jnp.ndarray,  # (BH, Sq, D), pre-scaled
    k: jnp.ndarray,  # (BHk, Skp, D)
    v: jnp.ndarray,
    spec: MaskSpec,
    *,
    group: int,  # G = Hq // Hkv
    block_q: int,
    block_kv: int,
    kv_valid: int,  # unpadded KV length
    q_seg: Optional[jnp.ndarray] = None,  # (B, Sqp) int32 segment ids
    kv_seg: Optional[jnp.ndarray] = None,  # (B, Skp) int32
    interpret: Optional[bool] = None,
    schedule: str = "compact",
    num_q_bands: int = 1,
    kv_splits: int = 1,
):
    """FA2 forward on prepped (head-major, padded) tensors. V may be
    narrower than Q/K (latent attention: Dv = ``v.shape[-1]``); the output
    takes V's width.

    ``num_q_bands`` / ``kv_splits`` (compact schedule only) apply the
    paper's Section 3.2 forward partitioning: the grid grows a *parallel*
    partition axis over q-row bands x contiguous kv ranges (see
    ``schedule.build_partitioned_schedule``). With ``kv_splits == 1`` the
    return contract is unchanged -- ``(o (BH, Sq, D), lse (BH, 1, Sq))``,
    bitwise-equal to the unbanded schedule. With ``kv_splits > 1`` the
    kernel returns *partials* ``(o_parts (BH, kv_splits, Sq, D) f32,
    lse_parts (BH, kv_splits, 1, Sq) f32)`` for the caller to fold with
    ``online_softmax.merge_partials`` (ops.py does).
    """
    interpret = resolve_interpret(interpret)
    BH, Sq, D = q.shape
    BHk, Skp, _ = k.shape
    Dv = v.shape[-1]
    assert Sq % block_q == 0 and Skp % block_kv == 0
    t_q, t_kv = Sq // block_q, Skp // block_kv
    has_segments = q_seg is not None
    num_q_bands = max(1, min(num_q_bands, t_q))
    kv_splits = max(1, min(kv_splits, t_kv))
    if schedule == "dense" and (num_q_bands > 1 or kv_splits > 1):
        raise ValueError(
            "num_q_bands/kv_splits partition the compact schedule; the dense "
            "grid already keeps its q-tile axis parallel"
        )

    # (Segment skipping is data-dependent, so the static spec-only count is
    # an upper bound there.)
    from repro.core.flash import _visible_pairs

    n_vis = len(_visible_pairs(spec, t_q, t_kv, block_q, block_kv)[0])
    cost = _fwd_cost(BH, n_vis, block_q, block_kv, D, q, k, Dv)
    out_shape = [
        jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
        jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),  # lane-major lse
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, Dv), jnp.float32),
    ]

    if schedule == "dense":
        kernel = functools.partial(
            _fwd_kernel_dense, spec=spec, bq=block_q, bk=block_kv, t_kv=t_kv,
            kv_valid=kv_valid, has_segments=has_segments,
        )
        in_specs = [
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda bh, i, j, g=group: (bh // g, j, 0)),
            pl.BlockSpec((1, block_kv, Dv), lambda bh, i, j, g=group: (bh // g, j, 0)),
        ]
        inputs = [q, k, v]
        if has_segments:
            heads = BH // q_seg.shape[0]
            in_specs += [
                lane_spec(block_q, lambda bh, i, j, h=heads: (bh // h, i)),
                lane_spec(block_kv, lambda bh, i, j, h=heads: (bh // h, j)),
            ]
            inputs += [lane_rows(q_seg), lane_rows(kv_seg)]
        return pl.pallas_call(
            kernel,
            grid=(BH, t_q, t_kv),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, Dv), lambda bh, i, j: (bh, i, 0)),
                lane_spec(block_q, lambda bh, i, j: (bh, i)),
            ],
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            cost_estimate=cost,
            interpret=interpret,
            name="fa2_fwd_varlen" if has_segments else "fa2_fwd",
        )(*inputs)

    if schedule != "compact":
        raise ValueError(f"unknown tile schedule: {schedule!r}")
    heads = BH // q_seg.shape[0] if has_segments else 1
    if num_q_bands > 1 or kv_splits > 1:
        return _flash_fwd_partitioned(
            q, k, v, spec, group=group, block_q=block_q, block_kv=block_kv,
            kv_valid=kv_valid, q_seg=q_seg, kv_seg=kv_seg, heads=heads,
            interpret=interpret, num_q_bands=num_q_bands, kv_splits=kv_splits,
            cost=cost, t_q=t_q, t_kv=t_kv,
        )
    sched = build_tile_schedule(spec, t_q, t_kv, block_q, block_kv, kv_valid)
    kernel = functools.partial(
        _fwd_kernel_compact, spec=spec, bq=block_q, bk=block_kv,
        kv_valid=kv_valid, heads=heads, has_segments=has_segments,
    )
    # index maps receive the scalar-prefetch refs after the grid ids
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, s, o_, i_, f_, *_: (bh, o_[s], 0)),
        pl.BlockSpec(
            (1, block_kv, D), lambda bh, s, o_, i_, f_, *_, g=group: (bh // g, i_[s], 0)
        ),
        pl.BlockSpec(
            (1, block_kv, Dv), lambda bh, s, o_, i_, f_, *_, g=group: (bh // g, i_[s], 0)
        ),
    ]
    scalar_args = [
        jnp.asarray(sched.outer), jnp.asarray(sched.inner), jnp.asarray(sched.flags)
    ]
    inputs = [q, k, v]
    if has_segments:
        scalar_args.append(
            segment_step_tables(q_seg, kv_seg, sched, block_q, block_kv)
        )
        in_specs += [
            lane_spec(block_q, lambda bh, s, o_, i_, f_, t_, h=heads: (bh // h, o_[s])),
            lane_spec(block_kv,
                      lambda bh, s, o_, i_, f_, t_, h=heads: (bh // h, i_[s])),
        ]
        inputs += [lane_rows(q_seg), lane_rows(kv_seg)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(BH, sched.n_steps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda bh, s, o_, i_, f_, *_: (bh, o_[s], 0)),
            lane_spec(block_q, lambda bh, s, o_, i_, f_, *_: (bh, o_[s])),
        ],
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=cost,
        interpret=interpret,
        name="fa2_fwd_compact_varlen" if has_segments else "fa2_fwd_compact",
    )(*scalar_args, *inputs)


def _flash_fwd_partitioned(
    q, k, v, spec: MaskSpec, *, group, block_q, block_kv, kv_valid,
    q_seg, kv_seg, heads, interpret, num_q_bands, kv_splits, cost, t_q, t_kv,
):
    """Compact forward on the partitioned grid ``(BH, P, n_steps_part)``.

    The partition axis is ``parallel`` (dimension semantics); with
    ``kv_splits > 1`` the outputs are per-split partials (see flash_fwd's
    docstring for the return contract).
    """
    BH, Sq, D = q.shape
    Dv = v.shape[-1]
    has_segments = q_seg is not None
    sched = build_partitioned_schedule(
        spec, t_q, t_kv, block_q, block_kv, kv_valid, num_q_bands, kv_splits
    )
    P, ks = sched.num_parts, sched.kv_splits
    kernel = functools.partial(
        _fwd_kernel_partitioned, spec=spec, bq=block_q, bk=block_kv,
        kv_valid=kv_valid, heads=heads, has_segments=has_segments,
    )
    # index maps receive the scalar-prefetch refs after the 3 grid ids
    in_specs = [
        pl.BlockSpec(
            (1, block_q, D), lambda bh, p, s, o_, i_, f_, k_, *_: (bh, o_[p, s], 0)
        ),
        pl.BlockSpec(
            (1, block_kv, D),
            lambda bh, p, s, o_, i_, f_, k_, *_, g=group: (bh // g, i_[p, s], 0),
        ),
        pl.BlockSpec(
            (1, block_kv, Dv),
            lambda bh, p, s, o_, i_, f_, k_, *_, g=group: (bh // g, i_[p, s], 0),
        ),
    ]
    scalar_args = [
        jnp.asarray(sched.outer), jnp.asarray(sched.inner),
        jnp.asarray(sched.flags), jnp.asarray(sched.part_kv),
    ]
    inputs = [q, k, v]
    if has_segments:
        scalar_args.append(
            segment_step_tables(q_seg, kv_seg, sched, block_q, block_kv)
        )
        in_specs += [
            lane_spec(
                block_q,
                lambda bh, p, s, o_, i_, f_, k_, t_, h=heads: (bh // h, o_[p, s]),
            ),
            lane_spec(
                block_kv,
                lambda bh, p, s, o_, i_, f_, k_, t_, h=heads: (bh // h, i_[p, s]),
            ),
        ]
        inputs += [lane_rows(q_seg), lane_rows(kv_seg)]
    if ks == 1:
        # bands only: same outputs as the unbanded schedule, bitwise-equal
        # (each q row runs its unchanged kv visit sequence, just on a
        # different parallel grid cell).
        out_shape = [
            jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ]
        out_specs = [
            pl.BlockSpec(
                (1, block_q, Dv), lambda bh, p, s, o_, i_, f_, k_, *_: (bh, o_[p, s], 0)
            ),
            lane_spec(
                block_q, lambda bh, p, s, o_, i_, f_, k_, *_: (bh, o_[p, s])
            ),
        ]
    else:
        # split-KV partials: each split emits a locally-normalized (o, lse)
        # plane, folded by merge_partials in ops.py. f32 so the fold does
        # not round through the storage dtype. Split planes are flattened
        # into the leading axis (row bh*ks + split) to keep the kernel's
        # output refs rank-identical to the unsplit path.
        out_shape = [
            jax.ShapeDtypeStruct((BH * ks, Sq, Dv), jnp.float32),
            jax.ShapeDtypeStruct((BH * ks, 1, Sq), jnp.float32),
        ]
        out_specs = [
            pl.BlockSpec(
                (1, block_q, Dv),
                lambda bh, p, s, o_, i_, f_, k_, *_, n=ks: (bh * n + k_[p], o_[p, s], 0),
            ),
            lane_spec(
                block_q,
                lambda bh, p, s, o_, i_, f_, k_, *_, n=ks: (bh * n + k_[p], o_[p, s]),
            ),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(BH, P, sched.n_steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, Dv), jnp.float32),
        ],
    )
    name = "fa2_fwd_splitkv" if ks > 1 else "fa2_fwd_banded"
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=cost,
        interpret=interpret,
        name=name + "_varlen" if has_segments else name,
    )(*scalar_args, *inputs)
    if ks == 1:
        return o, lse
    return o.reshape(BH, ks, Sq, Dv), lse.reshape(BH, ks, 1, Sq)
