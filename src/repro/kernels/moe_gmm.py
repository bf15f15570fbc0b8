"""Grouped matmul over tokens sorted by expert (the dropless MoE's kernel).

``gmm(lhs, rhs, group_sizes)`` computes ``lhs[rows of group e] @ rhs[e]``
for every expert ``e``: ``lhs`` (m, k) holds the routed token copies sorted
by expert, ``group_sizes`` (E,) how many rows each expert owns, in order,
and ``rhs`` (E, k, n) the experts' weights. It is megablox's grouped
matmul (``jax.experimental.pallas.ops.tpu.megablox``) cut to what the MoE
layer uses -- no transposed or sharded right-hand side, no output to
accumulate into -- under a kernel name of its own (device events
``moe_gmm*``).

The grid walks ``(n tiles, active m-tile visits, k tiles)``; the visits
are megablox's group metadata with empty groups squeezed out, so an
expert with no rows is neither read nor computed. A group that shares an
m tile with its neighbours visits that tile once each, writing only its
own rows. Rows past ``sum(group_sizes)`` belong to no group and are left
unwritten: the caller masks them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.kernels.compat import resolve_interpret


def tiling(m: int, k: int, n: int):
    """(tm, tk, tn) for an ``m x k @ k x n`` grouped product: weight tiles
    of at most 1536 x 1536 (one DMA of ~1.4 MiB at DeepSeek's expert widths,
    2048 x 1408 and 1408 x 2048), and row tiles of 512 for prefill-sized
    ``m`` but 32 for a decode step's few rows per expert, where a wide row
    tile would spend its compute on rows of other experts."""
    tm = 512 if m >= 4096 else 32
    tk = k if k <= 1536 else 512
    tn = n if n <= 1536 else 512
    return tm, tk, tn


def _kernel(meta_ref, lhs_ref, rhs_ref, out_ref, acc, *, tm: int, tn: int,
            tiles_k: int, k_rem: int):
    offsets, group_ids, m_tile_ids = meta_ref
    grid_id = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    def accum(last: bool):
        x, w = lhs_ref[...], rhs_ref[...]
        if last and k_rem:
            x = jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, 1) < k_rem,
                          x, 0)
            w = jnp.where(lax.broadcasted_iota(jnp.int32, w.shape, 0) < k_rem,
                          w, 0)
        acc[...] += lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        if last:
            g = group_ids[grid_id]
            rows = (lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
                    + m_tile_ids[grid_id] * tm)
            mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
            out_ref[...] = jnp.where(mine, acc[...],
                                     out_ref[...].astype(jnp.float32)
                                     ).astype(out_ref.dtype)

    lax.cond(k_i == tiles_k - 1, functools.partial(accum, True),
             functools.partial(accum, False))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray, *,
        tiles=None, interpret=None) -> jnp.ndarray:
    """``lhs`` (m, k) rows sorted by group, ``rhs`` (E, k, n),
    ``group_sizes`` (E,) int32 -> (m, n) in ``lhs``'s dtype (f32
    accumulation). ``m`` must be a multiple of the row tile
    (:func:`tiling`); rows past the groups are left unwritten."""
    interpret = resolve_interpret(interpret)
    m, k = lhs.shape
    E, _, n = rhs.shape
    tm, tk, tn = tiles or tiling(m, k, n)
    if m % tm:
        raise ValueError(f"{m} rows are not a multiple of the row tile {tm}")
    tiles_k, k_rem = -(-k // tk), k % tk
    tiles_n = -(-n // tn)
    meta, active = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=E,
        visit_empty_groups=False)
    cost = pl.CostEstimate(
        flops=2 * m * k * n,
        bytes_accessed=lhs.size * lhs.dtype.itemsize * tiles_n
        + rhs.size * rhs.dtype.itemsize + m * n * lhs.dtype.itemsize,
        transcendentals=0)
    kernel = functools.partial(_kernel, tm=tm, tn=tn, tiles_k=tiles_k,
                               k_rem=k_rem)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles_n, active, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, g, k_i, md: (md[2][g], k_i)),
                pl.BlockSpec((None, tk, tn),
                             lambda n_i, g, k_i, md: (md[1][g], k_i, n_i)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n_i, g, k_i, md: (md[2][g], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=cost,
        interpret=interpret,
        name="moe_gmm",
    )(meta, lhs, rhs)
