"""Split-KV flash decode Pallas TPU kernel (C2 applied to inference).

One query token per sequence: the (batch x kv-heads) grid alone cannot fill
a TPU pod, so -- exactly as the paper parallelizes the forward over the
sequence axis -- we add a ``num_splits`` grid axis over the KV cache. Each
grid step computes a locally-normalized partial (o_c, lse_c) for its chunk;
the (cheap, O(splits)) merge runs in XLA via the associative online-softmax
combine. All G queries of a GQA group are processed against their shared KV
head in one step (the paper's MQA/GQA indexing note).

Layouts (ops.py): q (B*Hkv, G, D) pre-scaled; kv (B*Hkv, S, D);
lengths (B*Hkv,) int32 in SMEM. Outputs o_parts (B*Hkv, ns, G, D) fp32 and
lse_parts (B*Hkv, ns, 1, G) fp32 -- lane-major with a unit sublane axis, the
same softmax-stat layout contract as flash_fwd.py (DESIGN.md Section 2),
merged in XLA by ``online_softmax.combine_lse_outputs``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.masks import DEFAULT_MASK_VALUE
from repro.kernels.compat import resolve_interpret
from repro.kernels.flash_fwd import LANES, lane_rows, lane_spec


def _lse_spec(G: int, index_map) -> pl.BlockSpec:
    """One split's ``(G,)`` row of the (BHk, ns, 1, G) lse partials: the
    last two block dims equal the whole array dims, which Mosaic accepts
    for any G."""
    return pl.BlockSpec((None, None, 1, G), index_map)


def _decode_kernel(
    *refs,  # SMEM lens [+ q segment], q/k/v [+ kv segment ids], outputs
    chunk: int, window: Optional[int], sink: int, has_segments: bool = False,
):
    if has_segments:
        len_ref, qseg_ref, q_ref, k_ref, v_ref, kseg_ref, o_ref, lse_ref = refs
    else:
        len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    bh = pl.program_id(0)
    c = pl.program_id(1)
    L = len_ref[bh]

    q = q_ref[0]  # (G, D)
    k = k_ref[0]  # (chunk, D)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + c * chunk
    valid = cols < L
    if has_segments:
        # Packed cache: never read across a segment boundary (the query
        # belongs to exactly one segment of its cache row).
        valid = valid & (kseg_ref[0][None, :] == qseg_ref[bh])
    if window is not None:
        in_win = cols >= L - window
        if sink:
            in_win = in_win | (cols < sink)
        valid = valid & in_win
    s = jnp.where(valid, s, DEFAULT_MASK_VALUE)

    m = jnp.max(s, axis=-1, keepdims=True)  # (G, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    any_valid = jnp.any(valid, axis=-1, keepdims=True)
    l = jnp.where(any_valid, l, 0.0)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) / l_safe
    lse = jnp.where(l == 0.0, -jnp.inf, m + jnp.log(l_safe))
    o_ref[0, 0] = jnp.where(any_valid, o, 0.0)
    lse_ref[0] = lse[:, 0]  # (G,) lane-major


def flash_decode_kernel(
    q: jnp.ndarray,  # (BHk, G, D) pre-scaled
    k: jnp.ndarray,  # (BHk, S, D)
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # (BHk,) int32
    *,
    num_splits: int = 8,
    window: Optional[int] = None,
    sink: int = 0,
    kv_seg: Optional[jnp.ndarray] = None,  # (BHk, S) int32 packed-cache ids
    q_seg: Optional[jnp.ndarray] = None,  # (BHk,) int32 query's segment
    interpret: Optional[bool] = None,
):
    interpret = resolve_interpret(interpret)
    BHk, G, D = q.shape
    _, S, _ = k.shape
    # Ceil-div split resolution. The historical `while S % ns: ns -= 1`
    # silently degraded to ns=1 for prime/odd cache lengths -- the C2
    # parallelism gone exactly when the cache is ragged. Instead: 8-aligned
    # (sublane) ceil-div chunks, the cache padded up to ns*chunk, and the
    # tail masked by the existing `cols < L` guard (pad cols sit at logical
    # positions >= S >= L), so the partial merge stays exact. Packed-cache
    # segment ids put the chunk on the lane axis, so there it is 128-aligned.
    align = LANES if kv_seg is not None else 8
    ns = max(1, min(num_splits, -(-S // align)))
    chunk = -(-(-(-S // ns)) // align) * align  # ceil(ceil(S/ns) / align) * align
    ns = -(-S // chunk)
    pad = ns * chunk - S
    if pad:
        # jnp.pad copies the whole cache; serving allocates chunk-aligned
        # caches (prompt_pad buckets) so this triggers only for genuinely
        # ragged capacities -- allocate aligned if decode is hot there.
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        if kv_seg is not None:
            # any id never equal to a real q segment: pad cols are masked by
            # cols < L already; -1 keeps them inert even if L were wrong
            kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad)), constant_values=-1)
    has_segments = kv_seg is not None
    kernel = functools.partial(
        _decode_kernel, chunk=chunk, window=window, sink=sink,
        has_segments=has_segments,
    )
    cost = pl.CostEstimate(
        flops=2 * BHk * G * S * D * 2,
        bytes_accessed=2 * k.size * k.dtype.itemsize + 2 * q.size * q.dtype.itemsize,
        transcendentals=BHk * G * S,
    )
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, G, D), lambda bh, c: (bh, 0, 0)),
        pl.BlockSpec((1, chunk, D), lambda bh, c: (bh, c, 0)),
        pl.BlockSpec((1, chunk, D), lambda bh, c: (bh, c, 0)),
    ]
    inputs = [lengths, q, k, v]
    if has_segments:
        in_specs.insert(1, pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.insert(1, q_seg)
        in_specs.append(lane_spec(chunk, lambda bh, c: (bh, c)))
        inputs.append(lane_rows(kv_seg))
    return pl.pallas_call(
        kernel,
        grid=(BHk, ns),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, G, D), lambda bh, c: (bh, c, 0, 0)),
            _lse_spec(G, lambda bh, c: (bh, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHk, ns, G, D), jnp.float32),
            jax.ShapeDtypeStruct((BHk, ns, 1, G), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=cost,
        interpret=interpret,
        name="fa2_decode_varlen" if has_segments else "fa2_decode",
    )(*inputs)


# ---------------------------------------------------------------------------
# Paged (block-table) split-KV decode
# ---------------------------------------------------------------------------


def _paged_decode_kernel(
    tbl_ref,  # scalar prefetch: (B, n_pages) int32 block table (read by maps)
    len_ref,  # scalar prefetch: (BHk,) int32 logical lengths
    q_ref,    # (1, G, D)
    k_ref,    # (1, 1, ps, D) -- the page the index map named
    v_ref,
    o_ref,    # (1, 1, G, D)
    lse_ref,  # (1, G)
    m_scr,    # VMEM (G, LANES) f32
    l_scr,    # VMEM (G, LANES) f32
    acc_scr,  # VMEM (G, D) f32
    *, ps: int, pp: int, window: Optional[int], sink: int,
):
    """One (split, page) step of the page-indirect decode.

    The sequential ``p`` axis walks the split's pages with flash_fwd-style
    online-softmax scratch. A page is *skipped entirely* (``pl.when``) when
    the scalar arithmetic on (L, base, window, sink) proves every column
    masked -- so a free/finished slot (L == 0, all-null table row) issues
    zero compute, and the per-page update for an *active* page is
    op-for-op the contiguous kernel's chunk math (bitwise-equal partials
    whenever one split == one page -- tests/test_paged.py pins it).
    """
    del tbl_ref  # index maps read it; the body only needs lengths
    bh = pl.program_id(0)
    c = pl.program_id(1)
    p = pl.program_id(2)
    L = len_ref[bh]
    base = (c * pp + p) * ps  # logical position of this page's column 0

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Page-level visibility, purely from scalars: an active page always has
    # >= 1 valid column (proof in DESIGN.md Section 5.1), so the in-page
    # masking below never needs the contiguous kernel's any_valid guard --
    # fully-masked pages (which would corrupt l with exp(0) garbage) are
    # exactly the skipped ones.
    active = base < L
    if window is not None:
        in_win = base + ps > L - window
        if sink:
            in_win = in_win | (base < sink)
        active = active & in_win

    @pl.when(active)
    def _step():
        q = q_ref[0]      # (G, D)
        k = k_ref[0, 0]   # (ps, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + base
        valid = cols < L
        if window is not None:
            in_win = cols >= L - window
            if sink:
                in_win = in_win | (cols < sink)
            valid = valid & in_win
        s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # First touched page: m_prev = -inf -> alpha = 0, and 0 * prev + x
        # leaves x bitwise intact -- the single-page path IS the contiguous
        # kernel's math.
        alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_new))
        pexp = jnp.exp(s - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(p == pp - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = acc_scr[...] / l_safe
        lse = jnp.where(l == 0.0, -jnp.inf, m_scr[:, :1] + jnp.log(l_safe))
        lse_ref[0] = lse[:, 0]  # (G,) lane-major


def flash_decode_paged_kernel(
    q: jnp.ndarray,  # (BHk, G, D) pre-scaled
    k_pages: jnp.ndarray,  # (Hk, P, ps, D) physical page planes
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,  # (BHk,) int32 logical lengths
    block_table: jnp.ndarray,  # (B, n_pages) int32 logical -> physical page
    *,
    num_splits: int = 8,
    window: Optional[int] = None,
    sink: int = 0,
    interpret: Optional[bool] = None,
):
    """Split-KV decode that never sees a contiguous cache.

    Each KV split covers ``pp = ceil(n_pages / num_splits)`` *logical*
    pages; the k/v index maps dereference the prefetched block table
    (``PrefetchScalarGridSpec`` -- the same scalar-prefetch contract as
    kernels/schedule.py) so the DMA engine fetches physical page
    ``tbl[b, c*pp + p]`` directly from the pool plane. Physical page order
    is irrelevant to the math (shuffle-invariance is tested bitwise).
    Table entries past a sequence's live pages must point at the null page
    (0): their DMA is a cheap repeat and their compute is skipped.

    Returns per-split partials ``(o_parts (BHk, ns, G, D) f32,
    lse_parts (BHk, ns, 1, G) f32)`` for ``combine_lse_outputs``.
    """
    interpret = resolve_interpret(interpret)
    BHk, G, D = q.shape
    Hk, _, ps, _ = k_pages.shape
    B, n_pages = block_table.shape
    assert BHk == B * Hk, (BHk, B, Hk)
    ns = max(1, min(num_splits, n_pages))
    pp = -(-n_pages // ns)  # logical pages per split
    ns = -(-n_pages // pp)
    pad = ns * pp - n_pages
    tbl = block_table.astype(jnp.int32)
    if pad:
        # Padded table columns are logical positions >= n_pages*ps >= L:
        # never active; the null page keeps their DMA well-defined.
        tbl = jnp.pad(tbl, ((0, 0), (0, pad)))
    kernel = functools.partial(
        _paged_decode_kernel, ps=ps, pp=pp, window=window, sink=sink,
    )
    cost = pl.CostEstimate(
        flops=2 * BHk * G * n_pages * ps * D * 2,
        bytes_accessed=2 * B * n_pages * ps * D * k_pages.dtype.itemsize
        + 2 * q.size * q.dtype.itemsize,
        transcendentals=BHk * G * n_pages * ps,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block table + lengths
        grid=(BHk, ns, pp),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bh, c, p, tbl_, len_: (bh, 0, 0)),
            pl.BlockSpec(
                (1, 1, ps, D),
                lambda bh, c, p, tbl_, len_, h=Hk, n=pp: (
                    bh % h, tbl_[bh // h, c * n + p], 0, 0
                ),
            ),
            pl.BlockSpec(
                (1, 1, ps, D),
                lambda bh, c, p, tbl_, len_, h=Hk, n=pp: (
                    bh % h, tbl_[bh // h, c * n + p], 0, 0
                ),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, D), lambda bh, c, p, *_: (bh, c, 0, 0)),
            _lse_spec(G, lambda bh, c, p, *_: (bh, c, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BHk, ns, G, D), jnp.float32),
            jax.ShapeDtypeStruct((BHk, ns, 1, G), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=cost,
        interpret=interpret,
        name="fa2_decode_paged",
    )(tbl, lengths.astype(jnp.int32), q, k_pages, v_pages)
