"""FlashAttention-2 backward Pallas TPU kernels (the paper's Algorithm 2).

GPU->TPU adaptation (DESIGN.md Section 2): the paper parallelizes the
backward over *column* (KV) blocks, with thread blocks doing **atomic adds**
into dQ. TPUs have no HBM atomics; two TPU realizations live here:

  * ``bwd="fused"`` (default) -- :func:`flash_bwd_fused`, ONE kv-major
    launch. Each (bh, j) owns a KV block; the sequential axis streams
    visible Q tiles past it. Per tile, ``(s, p)`` is recomputed ONCE and
    feeds all five streamed matmuls (dV, dP, dK, dQ plus the s recompute),
    dK/dV accumulate in VMEM scratch across the KV run, and the tile's dQ
    contribution is read-modify-written into an f32 HBM block by explicit
    DMA (the atomic-add replacement: the grid's step axis is
    ``"arbitrary"``/sequential and each write-back is waited for, so
    revisits are ordered and race-free). ``delta = rowsum(dO o O)`` is
    fused into the q-row prologue: the schedule's STEP_QFIRST step for each
    q tile zero-inits the dq block and computes delta into a lane-major
    VMEM scratch row that later visits read back -- delta never exists in
    HBM. 3 launches -> 1, one exp per visible tile instead of two, and
    Q/dO/lse stream once instead of twice.
  * ``bwd="split"`` -- the parity baseline: ``flash_bwd_delta`` +
    ``flash_bwd_dkv`` (KV-stationary, scratch-accumulated, GQA-summed) +
    ``flash_bwd_dq`` (Q-stationary, the paper's own recompute-vs-
    communication trade). Two exps and two Q/dO streams per visible tile.

All kernels support two schedules (see flash_fwd.py / kernels/schedule.py):
``"compact"`` (default) flattens the visible tile pairs into a scalar-
prefetched table -- kv-major for dkv/fused (grid ``(BHk, n_steps, G)``),
q-major for dq (grid ``(BH, n_steps)``) -- so masked-out tiles cost no grid
steps and no DMAs; ``"dense"`` is the legacy visit-everything grid.

All recompute P = exp(S - L) from the logsumexp only (C1b, line 11).
Softmax statistics arrive LANE-MAJOR: lse and delta are ``(BH, 1, Sqp)`` f32
with the sequence on the 128-lane axis (BlockSpec ``(None, 1, block_q)``) --
the memory-diet contract shared with flash_fwd.py. In the split backward,
D = rowsum(dO o O) (line 4) is computed by :func:`flash_bwd_delta`, a
one-pass Pallas kernel, instead of an XLA elementwise pass over the
broadcast layout; the fused backward absorbs even that launch.
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
from repro.kernels.flash_fwd import _tile_mask, _visibility, lane_rows, lane_spec
from repro.kernels.schedule import (
    STEP_QFIRST,
    build_tile_schedule,
    decode_step_bits,
    segment_step_tables,
)


def _recompute_p(q, k, lse, spec, i, j, bq, bk, kv_valid, needs_mask,
                 q_seg=None, kv_seg=None):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    mask = _tile_mask(spec, i, j, bq, bk, kv_valid, q_seg, kv_seg)
    s = jnp.where(jnp.logical_or(~needs_mask, mask), s, DEFAULT_MASK_VALUE)
    return jnp.exp(s - lse), s


# ---------------------------------------------------------------------------
# delta = rowsum(dO o O) preprocess (Algorithm 2 line 4)
# ---------------------------------------------------------------------------


def _delta_kernel(o_ref, do_ref, delta_ref):
    delta_ref[0] = jnp.sum(
        o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32), axis=-1
    )


def flash_bwd_delta(o, do, *, block_q: int, interpret: Optional[bool] = None):
    """rowsum(dO o O) over prepped (BH, Sqp, D) tensors -> (BH, 1, Sqp) f32.

    One fused read of O and dO per tile, emitting the lane-major delta the
    backward kernels consume directly (no 128x broadcast round-trip).
    """
    interpret = resolve_interpret(interpret)
    BH, Sqp, D = o.shape
    assert Sqp % block_q == 0
    spec = pl.BlockSpec((1, block_q, D), lambda bh, i: (bh, i, 0))
    return pl.pallas_call(
        _delta_kernel,
        grid=(BH, Sqp // block_q),
        in_specs=[spec, spec],
        out_specs=lane_spec(block_q, lambda bh, i: (bh, i)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, Sqp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * o.size,
            bytes_accessed=o.size * o.dtype.itemsize
            + do.size * do.dtype.itemsize + BH * Sqp * 4,
            transcendentals=0,
        ),
        interpret=interpret,
        name="fa2_bwd_delta",
    )(o, do)


# ---------------------------------------------------------------------------
# dK / dV kernel
# ---------------------------------------------------------------------------


def _dkv_tile_math(q, k, v, do, lse, delta, dk_scr, dv_scr,
                   spec, i, j, bq, bk, kv_valid, needs_mask, q_seg, kv_seg):
    """Algorithm 2 lines 11-16 for one tile: accumulate dK_j, dV_j into the
    run scratch and return dS (the dq kernel / fused kernel's input for
    line 15). Shared by the split dkv kernel and the fused kernel so the
    bitwise fused==split parity contract has a single source of truth.

    q (bq, d) pre-scaled; lse/delta (bq, 1) f32 columns.
    """
    p, _ = _recompute_p(
        q, k, lse, spec, i, j, bq, bk, kv_valid, needs_mask, q_seg, kv_seg
    )  # line 11
    # dV_j += P^T dO_i                                          (line 12)
    dv_scr[...] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # dP = dO_i V_j^T                                           (line 13)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    # dS = P o (dP - D_i)                                       (line 14)
    ds = p * (dp - delta)
    # dK_j += dS^T Q_i  (q pre-scaled => scale already folded)  (line 16)
    dk_scr[...] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return ds


def _dkv_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_scr, dv_scr, spec, i, j, bq, bk, kv_valid, needs_mask,
                 q_seg, kv_seg):
    _dkv_tile_math(
        q_ref[0], k_ref[0], v_ref[0], do_ref[0],
        lse_ref[0][:, None], delta_ref[0][:, None],  # lane-major sources
        dk_scr, dv_scr, spec, i, j, bq, bk, kv_valid, needs_mask,
        q_seg, kv_seg,
    )


def _dkv_kernel_dense(
    *refs,
    spec: MaskSpec, bq: int, bk: int, t_q: int, group: int, kv_valid: int,
    has_segments: bool = False,
):
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        q_seg = kv_seg = None
    j = pl.program_id(1)
    g = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    empty, needs_mask = _visibility(spec, i, j, bq, bk, kv_valid, q_seg, kv_seg)

    @pl.when(~empty)
    def _compute():
        _dkv_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_scr, dv_scr, spec, i, j, bq, bk, kv_valid, needs_mask,
                     q_seg, kv_seg)

    @pl.when(jnp.logical_and(g == group - 1, i == t_q - 1))
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dkv_kernel_compact(
    *refs,
    spec: MaskSpec, bq: int, bk: int, group: int, kv_valid: int, heads: int,
    has_segments: bool = False,
):
    if has_segments:
        (outer_ref, inner_ref, flags_ref, seg_ref,
         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]
    else:
        (outer_ref, inner_ref, flags_ref,
         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        q_seg = kv_seg = None
    bh = pl.program_id(0)
    s = pl.program_id(1)
    g = pl.program_id(2)
    j = outer_ref[s]  # kv-major: the owned KV tile
    i = inner_ref[s]  # streamed Q tile
    active, first, last, needs_mask = decode_step_bits(
        flags_ref[s], seg_ref[bh // heads, s] if has_segments else None
    )

    @pl.when(jnp.logical_and(first, g == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(active)
    def _compute():
        _dkv_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_scr, dv_scr, spec, i, j, bq, bk, kv_valid, needs_mask,
                     q_seg, kv_seg)

    @pl.when(jnp.logical_and(last, g == group - 1))
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd_dkv(
    q, k, v, do, lse, delta, spec: MaskSpec, *,
    group: int, block_q: int, block_kv: int, kv_valid: int,
    q_seg=None, kv_seg=None, interpret: Optional[bool] = None,
    schedule: str = "compact",
):
    """Returns (dk, dv) in (BHk, Skp, D) fp32. q pre-scaled by 1/sqrt(d).

    lse/delta are lane-major (BH, 1, Sqp) f32; segment ids (if any) are
    unreplicated (B, Sqp)/(B, Skp).
    """
    interpret = resolve_interpret(interpret)
    BH, Sq, D = q.shape
    BHk, Skp, _ = k.shape
    t_q, t_kv = Sq // block_q, Skp // block_kv
    has_segments = q_seg is not None
    from repro.core.flash import _visible_pairs

    n_vis = len(_visible_pairs(spec, t_q, t_kv, block_q, block_kv)[0])
    cost = pl.CostEstimate(
        flops=BH * n_vis * 2 * block_q * block_kv * D * 3,  # 3 matmuls here
        bytes_accessed=2 * k.size * k.dtype.itemsize
        + BH * n_vis * 2 * block_q * D * q.dtype.itemsize,
        transcendentals=BH * n_vis * block_q * block_kv,
    )
    out_shape = [
        jax.ShapeDtypeStruct((BHk, Skp, D), jnp.float32),
        jax.ShapeDtypeStruct((BHk, Skp, D), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_kv, D), jnp.float32),
        pltpu.VMEM((block_kv, D), jnp.float32),
    ]

    if schedule == "dense":
        kernel = functools.partial(
            _dkv_kernel_dense, spec=spec, bq=block_q, bk=block_kv, t_q=t_q,
            group=group, kv_valid=kv_valid, has_segments=has_segments,
        )
        qspec = pl.BlockSpec(
            (1, block_q, D), lambda bh, j, g, i, grp=group: (bh * grp + g, i, 0)
        )
        lspec = lane_spec(block_q, lambda bh, j, g, i, grp=group: (bh * grp + g, i))
        kvspec = pl.BlockSpec((1, block_kv, D), lambda bh, j, g, i: (bh, j, 0))
        in_specs = [qspec, kvspec, kvspec, qspec, lspec, lspec]
        inputs = [q, k, v, do, lse, delta]
        if has_segments:
            heads = BHk // q_seg.shape[0]
            in_specs += [
                lane_spec(block_q, lambda bh, j, g, i, h=heads: (bh // h, i)),
                lane_spec(block_kv, lambda bh, j, g, i, h=heads: (bh // h, j)),
            ]
            inputs += [lane_rows(q_seg), lane_rows(kv_seg)]
        return pl.pallas_call(
            kernel,
            grid=(BHk, t_kv, group, t_q),
            in_specs=in_specs,
            out_specs=[kvspec, kvspec],
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            ),
            cost_estimate=cost,
            interpret=interpret,
            name="fa2_bwd_dkv_varlen" if has_segments else "fa2_bwd_dkv",
        )(*inputs)

    if schedule != "compact":
        raise ValueError(f"unknown tile schedule: {schedule!r}")
    sched = build_tile_schedule(
        spec, t_q, t_kv, block_q, block_kv, kv_valid, kv_major=True
    )
    heads = BHk // q_seg.shape[0] if has_segments else 1
    kernel = functools.partial(
        _dkv_kernel_compact, spec=spec, bq=block_q, bk=block_kv, group=group,
        kv_valid=kv_valid, heads=heads, has_segments=has_segments,
    )
    qspec = pl.BlockSpec(
        (1, block_q, D),
        lambda bh, s, g, o_, i_, f_, *_, grp=group: (bh * grp + g, i_[s], 0),
    )
    lspec = lane_spec(
        block_q,
        lambda bh, s, g, o_, i_, f_, *_, grp=group: (bh * grp + g, i_[s]),
    )
    kvspec = pl.BlockSpec(
        (1, block_kv, D), lambda bh, s, g, o_, i_, f_, *_: (bh, o_[s], 0)
    )
    in_specs = [qspec, kvspec, kvspec, qspec, lspec, lspec]
    scalar_args = [
        jnp.asarray(sched.outer), jnp.asarray(sched.inner), jnp.asarray(sched.flags)
    ]
    inputs = [q, k, v, do, lse, delta]
    if has_segments:
        scalar_args.append(
            segment_step_tables(q_seg, kv_seg, sched, block_q, block_kv, kv_major=True)
        )
        in_specs += [
            lane_spec(
                block_q,
                lambda bh, s, g, o_, i_, f_, t_, h=heads: (bh // h, i_[s]),
            ),
            lane_spec(
                block_kv,
                lambda bh, s, g, o_, i_, f_, t_, h=heads: (bh // h, o_[s]),
            ),
        ]
        inputs += [lane_rows(q_seg), lane_rows(kv_seg)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(BHk, sched.n_steps, group),
        in_specs=in_specs,
        out_specs=[kvspec, kvspec],
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        cost_estimate=cost,
        interpret=interpret,
        name="fa2_bwd_dkv_compact_varlen" if has_segments else "fa2_bwd_dkv_compact",
    )(*scalar_args, *inputs)


# ---------------------------------------------------------------------------
# dQ kernel
# ---------------------------------------------------------------------------


def _dq_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_scr,
                spec, i, j, bq, bk, kv_valid, needs_mask, q_seg, kv_seg):
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, None]
    delta = delta_ref[0][:, None]
    p, _ = _recompute_p(
        q, k, lse, spec, i, j, bq, bk, kv_valid, needs_mask, q_seg, kv_seg
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    # dQ_i += dS K_j                                            (line 15)
    dq_scr[...] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _dq_kernel_dense(
    *refs,
    spec: MaskSpec, bq: int, bk: int, t_kv: int, kv_valid: int,
    has_segments: bool = False,
):
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, dq_scr) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        q_seg = kv_seg = None
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    empty, needs_mask = _visibility(spec, i, j, bq, bk, kv_valid, q_seg, kv_seg)

    @pl.when(~empty)
    def _compute():
        _dq_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_scr,
                    spec, i, j, bq, bk, kv_valid, needs_mask, q_seg, kv_seg)

    @pl.when(j == t_kv - 1)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dq_kernel_compact(
    *refs,
    spec: MaskSpec, bq: int, bk: int, kv_valid: int, heads: int,
    has_segments: bool = False,
):
    if has_segments:
        (outer_ref, inner_ref, flags_ref, seg_ref,
         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, dq_scr) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]
    else:
        (outer_ref, inner_ref, flags_ref,
         q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
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
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(active)
    def _compute():
        _dq_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_scr,
                    spec, i, j, bq, bk, kv_valid, needs_mask, q_seg, kv_seg)

    @pl.when(last)
    def _emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def flash_bwd_dq(
    q, k, v, do, lse, delta, spec: MaskSpec, *,
    group: int, block_q: int, block_kv: int, kv_valid: int,
    q_seg=None, kv_seg=None, interpret: Optional[bool] = None,
    schedule: str = "compact",
):
    """Returns dq in (BH, Sq, D) fp32 (gradient w.r.t. *scaled* q).

    lse/delta are lane-major (BH, 1, Sqp) f32; segment ids (if any) are
    unreplicated (B, Sqp)/(B, Skp).
    """
    interpret = resolve_interpret(interpret)
    BH, Sq, D = q.shape
    BHk, Skp, _ = k.shape
    t_q, t_kv = Sq // block_q, Skp // block_kv
    has_segments = q_seg is not None
    from repro.core.flash import _visible_pairs

    n_vis = len(_visible_pairs(spec, t_q, t_kv, block_q, block_kv)[0])
    cost = pl.CostEstimate(
        flops=BH * n_vis * 2 * block_q * block_kv * D * 3,
        bytes_accessed=2 * q.size * q.dtype.itemsize
        + BH * n_vis * 2 * block_kv * D * k.dtype.itemsize,
        transcendentals=BH * n_vis * block_q * block_kv,
    )
    out_shape = jax.ShapeDtypeStruct((BH, Sq, D), jnp.float32)
    scratch_shapes = [pltpu.VMEM((block_q, D), jnp.float32)]

    if schedule == "dense":
        kernel = functools.partial(
            _dq_kernel_dense, spec=spec, bq=block_q, bk=block_kv, t_kv=t_kv,
            kv_valid=kv_valid, has_segments=has_segments,
        )
        qspec = pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0))
        lspec = lane_spec(block_q, lambda bh, i, j: (bh, i))
        kvspec = pl.BlockSpec((1, block_kv, D), lambda bh, i, j, g=group: (bh // g, j, 0))
        in_specs = [qspec, kvspec, kvspec, qspec, lspec, lspec]
        inputs = [q, k, v, do, lse, delta]
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
            out_specs=qspec,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            cost_estimate=cost,
            interpret=interpret,
            name="fa2_bwd_dq_varlen" if has_segments else "fa2_bwd_dq",
        )(*inputs)

    if schedule != "compact":
        raise ValueError(f"unknown tile schedule: {schedule!r}")
    sched = build_tile_schedule(spec, t_q, t_kv, block_q, block_kv, kv_valid)
    heads = BH // q_seg.shape[0] if has_segments else 1
    kernel = functools.partial(
        _dq_kernel_compact, spec=spec, bq=block_q, bk=block_kv,
        kv_valid=kv_valid, heads=heads, has_segments=has_segments,
    )
    qspec = pl.BlockSpec(
        (1, block_q, D), lambda bh, s, o_, i_, f_, *_: (bh, o_[s], 0)
    )
    lspec = lane_spec(block_q, lambda bh, s, o_, i_, f_, *_: (bh, o_[s]))
    kvspec = pl.BlockSpec(
        (1, block_kv, D), lambda bh, s, o_, i_, f_, *_, g=group: (bh // g, i_[s], 0)
    )
    in_specs = [qspec, kvspec, kvspec, qspec, lspec, lspec]
    scalar_args = [
        jnp.asarray(sched.outer), jnp.asarray(sched.inner), jnp.asarray(sched.flags)
    ]
    inputs = [q, k, v, do, lse, delta]
    if has_segments:
        scalar_args.append(
            segment_step_tables(q_seg, kv_seg, sched, block_q, block_kv)
        )
        in_specs += [
            lane_spec(block_q, lambda bh, s, o_, i_, f_, t_, h=heads: (bh // h, o_[s])),
            lane_spec(
                block_kv,
                lambda bh, s, o_, i_, f_, t_, h=heads: (bh // h, i_[s]),
            ),
        ]
        inputs += [lane_rows(q_seg), lane_rows(kv_seg)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(BH, sched.n_steps),
        in_specs=in_specs,
        out_specs=qspec,
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
        name="fa2_bwd_dq_compact_varlen" if has_segments else "fa2_bwd_dq_compact",
    )(*scalar_args, *inputs)


# ---------------------------------------------------------------------------
# Fused one-pass backward: delta + dK + dV + dQ in a single launch
# ---------------------------------------------------------------------------
#
# kv-major like the dkv kernel, but the step body also emits the tile's dQ
# contribution, so (s, p) is recomputed once per visible tile instead of
# twice and Q/dO/lse tiles stream once instead of twice. dQ is an f32 HBM
# output (memory space ANY) that each visit read-modify-writes by explicit
# DMA through a (block_q, D) VMEM buffer: the kv-major sweep returns to a
# dq block after other blocks, and a pipelined output block is never read
# back from HBM when the sweep returns to it, so accumulating into one is
# wrong under Mosaic. Each visit waits for its own write-back, so the next
# visit's read sees it. The schedule's STEP_QFIRST bit marks each q tile's
# first visit: start the dq block from zero instead of reading it, and
# compute delta = rowsum(dO o O) into a lane-major VMEM scratch row, keyed
# by (g, q_tile) so it survives the revisits of that q tile later in the
# sweep; no separate flash_bwd_delta launch, no delta HBM array at all.


def _fused_qrow_prologue(o_ref, do_ref, delta_scr, dq_buf, g, i, q_first):
    """QFIRST work: delta = rowsum(dO o O) (Algorithm 2 line 4) + dq = 0.

    Runs before the tile compute so the same step can consume the delta it
    just wrote. Returns the (bq, 1) delta column for the current q tile.
    """

    @pl.when(q_first)
    def _init():
        delta_scr[g, i] = jnp.sum(
            o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32), axis=-1
        )
        dq_buf[...] = jnp.zeros_like(dq_buf)

    return delta_scr[g, i][:, None]


def _fused_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta,
                   dk_scr, dv_scr, dq_buf, dq_read, q_first, spec, i, j, bq,
                   bk, kv_valid, needs_mask, q_seg, kv_seg):
    """One visible tile of the fused backward: 5 streamed matmuls total.

    The (s, p) recompute and the dK/dV/dS math are the shared
    :func:`_dkv_tile_math`; the fused kernel adds only the lse cleanup (the
    split path does it outside the kernel) and the dQ contribution, added
    to ``dq_buf`` once ``dq_read`` (started by the caller unless
    ``q_first``) has landed.
    """
    k = k_ref[0]      # (bk, d)
    lse = lse_ref[0]  # (bq,), lane-major source
    # Fully-masked rows carry lse = -inf; zero it so exp(S - lse) stays 0
    # (S is DEFAULT_MASK_VALUE there) instead of producing inf.
    lse = jnp.where(jnp.isneginf(lse), 0.0, lse)[:, None]
    ds = _dkv_tile_math(
        q_ref[0], k, v_ref[0], do_ref[0], lse, delta,
        dk_scr, dv_scr, spec, i, j, bq, bk, kv_valid, needs_mask,
        q_seg, kv_seg,
    )
    dq_tile = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(~q_first)
    def _land():
        dq_read.wait()

    # dQ_i += dS K_j -- accumulated across the sweep's visits  (line 15)
    dq_buf[...] += dq_tile


def _fused_dq_step(o_ref, do_ref, delta_scr, dq_hbm, dq_buf, dq_sems,
                   row, g, i, bq, q_first, active, compute):
    """The dq read-modify-write around one fused step.

    A visible tile that is not its q tile's first visit starts reading the
    tile's dq so far from HBM before ``compute(delta, dq_read)`` runs the
    tile's matmuls (which wait for it only before the add); every step that
    touched ``dq_buf`` writes it back and waits, so the next visit of the
    same block reads the sum.
    """
    rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
    dq_read = pltpu.make_async_copy(dq_hbm.at[row, rows], dq_buf, dq_sems.at[0])
    dq_write = pltpu.make_async_copy(dq_buf, dq_hbm.at[row, rows], dq_sems.at[1])

    @pl.when(jnp.logical_and(active, ~q_first))
    def _fetch():
        dq_read.start()

    delta = _fused_qrow_prologue(o_ref, do_ref, delta_scr, dq_buf, g, i, q_first)

    @pl.when(active)
    def _compute():
        compute(delta, dq_read)

    @pl.when(jnp.logical_or(active, q_first))
    def _store():
        dq_write.start()
        dq_write.wait()


def _fused_kernel_dense(
    *refs,
    spec: MaskSpec, bq: int, bk: int, t_q: int, group: int, kv_valid: int,
    has_segments: bool = False,
):
    if has_segments:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dq_hbm, dk_scr, dv_scr, delta_scr, dq_buf,
         dq_sems) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dk_ref, dv_ref, dq_hbm, dk_scr, dv_scr, delta_scr, dq_buf,
         dq_sems) = refs
        q_seg = kv_seg = None
    bh = pl.program_id(0)
    j = pl.program_id(1)
    g = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    empty, needs_mask = _visibility(spec, i, j, bq, bk, kv_valid, q_seg, kv_seg)
    q_first = j == 0  # dense: every (i, g) is first visited at j == 0

    def compute(delta, dq_read):
        _fused_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta,
                       dk_scr, dv_scr, dq_buf, dq_read, q_first, spec, i, j,
                       bq, bk, kv_valid, needs_mask, q_seg, kv_seg)

    _fused_dq_step(o_ref, do_ref, delta_scr, dq_hbm, dq_buf, dq_sems,
                   bh * group + g, g, i, bq, q_first, ~empty, compute)

    @pl.when(jnp.logical_and(g == group - 1, i == t_q - 1))
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fused_kernel_compact(
    *refs,
    spec: MaskSpec, bq: int, bk: int, group: int, kv_valid: int, heads: int,
    has_segments: bool = False,
):
    if has_segments:
        (outer_ref, inner_ref, flags_ref, seg_ref,
         q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dq_hbm, dk_scr, dv_scr, delta_scr, dq_buf,
         dq_sems) = refs
        q_seg, kv_seg = qs_ref[0], ks_ref[0]
    else:
        (outer_ref, inner_ref, flags_ref,
         q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dk_ref, dv_ref, dq_hbm, dk_scr, dv_scr, delta_scr, dq_buf,
         dq_sems) = refs
        q_seg = kv_seg = None
    bh = pl.program_id(0)
    s = pl.program_id(1)
    g = pl.program_id(2)
    j = outer_ref[s]  # kv-major: the owned KV tile
    i = inner_ref[s]  # streamed Q tile
    flags = flags_ref[s]
    active, first, last, needs_mask = decode_step_bits(
        flags, seg_ref[bh // heads, s] if has_segments else None
    )

    @pl.when(jnp.logical_and(first, g == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_first = (flags & STEP_QFIRST) != 0

    def compute(delta, dq_read):
        _fused_compute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta,
                       dk_scr, dv_scr, dq_buf, dq_read, q_first, spec, i, j,
                       bq, bk, kv_valid, needs_mask, q_seg, kv_seg)

    _fused_dq_step(o_ref, do_ref, delta_scr, dq_hbm, dq_buf, dq_sems,
                   bh * group + g, g, i, bq, q_first, active, compute)

    @pl.when(jnp.logical_and(last, g == group - 1))
    def _emit():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd_fused(
    q, k, v, o, do, lse, spec: MaskSpec, *,
    group: int, block_q: int, block_kv: int, kv_valid: int,
    q_seg=None, kv_seg=None, interpret: Optional[bool] = None,
    schedule: str = "compact",
):
    """One-pass Algorithm 2: (dk, dv, dq) from a single pallas_call.

    q pre-scaled by 1/sqrt(d); o/do are the prepped (BH, Sqp, D) residual
    and cotangent; lse is the RAW lane-major (BH, 1, Sqp) f32 logsumexp (the
    -inf cleanup for fully-masked rows happens in-kernel). Returns

      dk, dv  (BHk, Skp, D) f32
      dq      (BH, Sqp, D) f32, w.r.t. the *scaled* q

    delta = rowsum(dO o O) never touches HBM at all: each q tile's first
    visit computes its (block_q,) row into the lane-major (G, t_q, block_q)
    VMEM scratch and revisits read it back from there. That scratch is
    O(G * Sqp) f32 -- the caller (ops._resolve_bwd) falls back to
    bwd="split" when it would not fit the VMEM budget.

    Per visible tile this runs 5 matmuls and ONE exp; the split baseline
    (delta + dkv + dq launches) runs 7 matmuls (+ the delta rowsum pass)
    and two exps.
    """
    interpret = resolve_interpret(interpret)
    BH, Sq, D = q.shape
    BHk, Skp, _ = k.shape
    t_q, t_kv = Sq // block_q, Skp // block_kv
    has_segments = q_seg is not None
    from repro.core.flash import _visible_pairs

    n_vis = len(_visible_pairs(spec, t_q, t_kv, block_q, block_kv)[0])
    cost = pl.CostEstimate(
        flops=BH * n_vis * 2 * block_q * block_kv * D * 5,  # 5 matmuls/tile
        bytes_accessed=2 * k.size * k.dtype.itemsize
        + BH * n_vis * 3 * block_q * D * q.dtype.itemsize   # q, do, o tiles
        + BH * n_vis * 2 * block_q * D * 4,                 # dq DMA r/w
        transcendentals=BH * n_vis * block_q * block_kv,    # ONE exp/tile
    )
    out_shape = [
        jax.ShapeDtypeStruct((BHk, Skp, D), jnp.float32),  # dk
        jax.ShapeDtypeStruct((BHk, Skp, D), jnp.float32),  # dv
        jax.ShapeDtypeStruct((BH, Sq, D), jnp.float32),    # dq (read-modify-write)
    ]
    scratch_shapes = [
        pltpu.VMEM((block_kv, D), jnp.float32),             # dk run scratch
        pltpu.VMEM((block_kv, D), jnp.float32),             # dv run scratch
        pltpu.VMEM((group, t_q, block_q), jnp.float32),     # delta rows
        pltpu.VMEM((block_q, D), jnp.float32),              # dq in flight
        pltpu.SemaphoreType.DMA((2,)),                      # dq read, write
    ]
    # dq stays in HBM: _fused_dq_step moves its blocks by explicit DMA.
    dq_out = pl.BlockSpec(memory_space=pl.ANY)

    if schedule == "dense":
        kernel = functools.partial(
            _fused_kernel_dense, spec=spec, bq=block_q, bk=block_kv, t_q=t_q,
            group=group, kv_valid=kv_valid, has_segments=has_segments,
        )
        qspec = pl.BlockSpec(
            (1, block_q, D), lambda bh, j, g, i, grp=group: (bh * grp + g, i, 0)
        )
        lspec = lane_spec(block_q, lambda bh, j, g, i, grp=group: (bh * grp + g, i))
        kvspec = pl.BlockSpec((1, block_kv, D), lambda bh, j, g, i: (bh, j, 0))
        in_specs = [qspec, kvspec, kvspec, qspec, qspec, lspec]
        inputs = [q, k, v, do, o, lse]
        if has_segments:
            heads = BHk // q_seg.shape[0]
            in_specs += [
                lane_spec(block_q, lambda bh, j, g, i, h=heads: (bh // h, i)),
                lane_spec(block_kv, lambda bh, j, g, i, h=heads: (bh // h, j)),
            ]
            inputs += [lane_rows(q_seg), lane_rows(kv_seg)]
        return pl.pallas_call(
            kernel,
            grid=(BHk, t_kv, group, t_q),
            in_specs=in_specs,
            out_specs=[kvspec, kvspec, dq_out],
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            compiler_params=pltpu.CompilerParams(
                # j is sequential here (dq accumulates across KV runs) --
                # the dense-fused baseline gives up dkv's parallel j axis.
                dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
            ),
            cost_estimate=cost,
            interpret=interpret,
            name="fa2_bwd_fused_varlen" if has_segments else "fa2_bwd_fused",
        )(*inputs)

    if schedule != "compact":
        raise ValueError(f"unknown tile schedule: {schedule!r}")
    sched = build_tile_schedule(
        spec, t_q, t_kv, block_q, block_kv, kv_valid, kv_major=True
    )
    heads = BHk // q_seg.shape[0] if has_segments else 1
    kernel = functools.partial(
        _fused_kernel_compact, spec=spec, bq=block_q, bk=block_kv, group=group,
        kv_valid=kv_valid, heads=heads, has_segments=has_segments,
    )
    qspec = pl.BlockSpec(
        (1, block_q, D),
        lambda bh, s, g, o_, i_, f_, *_, grp=group: (bh * grp + g, i_[s], 0),
    )
    lspec = lane_spec(
        block_q,
        lambda bh, s, g, o_, i_, f_, *_, grp=group: (bh * grp + g, i_[s]),
    )
    kvspec = pl.BlockSpec(
        (1, block_kv, D), lambda bh, s, g, o_, i_, f_, *_: (bh, o_[s], 0)
    )
    in_specs = [qspec, kvspec, kvspec, qspec, qspec, lspec]
    scalar_args = [
        jnp.asarray(sched.outer), jnp.asarray(sched.inner), jnp.asarray(sched.flags)
    ]
    inputs = [q, k, v, do, o, lse]
    if has_segments:
        scalar_args.append(
            segment_step_tables(q_seg, kv_seg, sched, block_q, block_kv, kv_major=True)
        )
        in_specs += [
            lane_spec(
                block_q,
                lambda bh, s, g, o_, i_, f_, t_, h=heads: (bh // h, i_[s]),
            ),
            lane_spec(
                block_kv,
                lambda bh, s, g, o_, i_, f_, t_, h=heads: (bh // h, o_[s]),
            ),
        ]
        inputs += [lane_rows(q_seg), lane_rows(kv_seg)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(BHk, sched.n_steps, group),
        in_specs=in_specs,
        out_specs=[kvspec, kvspec, dq_out],
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        cost_estimate=cost,
        interpret=interpret,
        name="fa2_bwd_fused_compact_varlen" if has_segments else "fa2_bwd_fused_compact",
    )(*scalar_args, *inputs)
