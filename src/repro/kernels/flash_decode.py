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
import numpy as np
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

# Tokens one grid step of the paged decode gathers (per KV head): enough
# pages that a step's fixed cost is small against its DMA, few enough that
# two buffers of K and V for every KV head stay far inside scoped VMEM
# (2 x 2 x 8 heads x 512 x 128 x bf16 = 4 MiB at qwen3-8b's widths).
BLOCK_TOKENS = 512


def paged_decode_geometry(n_pages: int, ps: int, num_splits: int):
    """Grid geometry of :func:`flash_decode_paged_kernel` for a block table
    of ``n_pages`` logical pages of ``ps`` tokens -> ``(ns, nb, ppb)``:
    ``ns`` KV splits of ``nb`` blocks of ``ppb`` logical pages.

    A split's ``ceil(n_pages / num_splits)`` pages are tiled by the fewest
    blocks of at most ``BLOCK_TOKENS`` tokens, sized evenly so the tiling
    pads the least; never more than a split's pages, so one page per split
    keeps one page per block."""
    ns = max(1, min(num_splits, n_pages))
    pp = -(-n_pages // ns)
    nb = -(-pp // max(1, BLOCK_TOKENS // ps))
    ppb = -(-pp // nb)
    ns = -(-n_pages // (nb * ppb))
    return ns, nb, ppb


def _visible(base, span, L, window: Optional[int], sink: int):
    """Whether logical columns ``[base, base + span)`` hold one the query
    sees: one below the length ``L`` and, with a window, inside it or in
    the ``sink`` prefix (proof in DESIGN.md Section 5.1). Plain operators,
    so the kernel applies it to SMEM scalars and the host to numpy arrays."""
    vis = base < L
    if window is not None:
        in_win = base + span > L - window
        if sink:
            in_win = in_win | (base < sink)
        vis = vis & in_win
    return vis


def _block_pages(L, page0, ps: int, ppb: int, xp=jnp):
    """Pages of the block starting at logical page ``page0`` that hold
    cached tokens (the ones the kernel copies): below ``ceil(L / ps)``."""
    return xp.clip((L + ps - 1) // ps - page0, 0, ppb)


def paged_decode_work(lengths, n_pages: int, ps: int, num_splits: int, *,
                      window: Optional[int] = None, sink: int = 0) -> dict:
    """What one :func:`flash_decode_paged_kernel` call does, counted on the
    host from the kernel's own lengths (B,), geometry and visibility rule:
    ``kv_pages`` pages copied, ``kv_blocks`` grid steps that copy and
    compute, ``kv_blocks_launched`` grid steps in all."""
    ns, nb, ppb = paged_decode_geometry(n_pages, ps, num_splits)
    L = np.asarray(lengths, np.int64)[:, None]
    page0 = np.arange(ns * nb)[None, :] * ppb
    live = _visible(page0 * ps, ppb * ps, L, window, sink)
    pages = _block_pages(L, page0, ps, ppb, np)
    return {"kv_pages": int((pages * live).sum()),
            "kv_blocks": int(live.sum()),
            "kv_blocks_launched": int(L.shape[0] * ns * nb)}


def _paged_decode_kernel(
    *refs, ps: int, ppb: int, nb: int, ns: int, window: Optional[int],
    sink: int, v_dim: Optional[int],
):
    """One (slot, split, block) step of the page-indirect decode.

    Refs, in order: scalar prefetch ``tbl_ref`` (B, ns*nb*ppb) int32 block
    table, ``len_ref`` (B,) int32 logical lengths and ``layer_ref`` (1,)
    int32, the layer whose planes are read; ``q_ref`` (Hk, G, D), the
    slot's queries for every KV head; the stacked page planes ``k_hbm`` and
    ``v_hbm`` (layers, Hk, P, ps, D), left in HBM; ``o_ref`` (Hk, G, Dv) and
    ``lse_ref`` (Hk, 1, G); scratch ``k_buf`` and ``v_buf`` VMEM
    (2, Hk, ppb*ps, D) (the block being computed + the next), ``sems`` DMA
    (2, 2) [buffer, k|v], ``walk`` SMEM (2,) int32 [buffer of this block, a
    block was started], ``m_scr`` / ``l_scr`` VMEM (Hk, G, LANES) f32 and
    ``acc_scr`` (Hk, G, Dv) f32. Latent mode (``v_dim``, latent attention)
    has no V plane, buffer or semaphore: V is the first ``v_dim`` lanes of
    the K block, so every cached byte is copied once.

    The grid walks slots, then splits, then blocks, in order. A block is
    ``ppb`` logical pages; a live one (``_visible``) gathers its cached
    pages for all KV heads by explicit DMA, one descriptor per page and
    tensor, and runs the online-softmax update per head. Before computing,
    it starts the copy of the next live block of the walk (this slot's or
    a later one's) into the other buffer, so the copies of one step
    overlap the compute of the one before. A dead block copies and
    computes nothing. With one page per block the per-head update is
    op-for-op the contiguous kernel's chunk math (bitwise-equal partials
    when one split == one page -- tests/test_paged.py pins it).
    """
    if v_dim is None:
        (tbl_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, lse_ref,
         k_buf, v_buf, sems, walk, m_scr, l_scr, acc_scr) = refs
        planes = ((k_hbm, k_buf), (v_hbm, v_buf))
    else:
        (tbl_ref, len_ref, layer_ref, q_ref, k_hbm, o_ref, lse_ref, k_buf,
         sems, walk, m_scr, l_scr, acc_scr) = refs
        planes = ((k_hbm, k_buf),)
    b, c, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    B = pl.num_programs(0)
    Hk, G, D = q_ref.shape
    bk = ppb * ps
    per_slot = ns * nb  # blocks a slot's table row holds
    t = (b * ns + c) * nb + j  # position in the walk
    T = B * per_slot

    def live(u):  # is walk step u a live block? (u < T)
        s = jnp.minimum(u // per_slot, B - 1)
        return _visible((u % per_slot) * bk, bk, len_ref[s], window, sink)

    def block_dma(u, buf, act: str):
        """``act`` ("start" or "wait") the copies of walk step u's cached
        pages into buffer ``buf``: one descriptor per page and tensor, all
        KV heads at once."""
        s, page0 = u // per_slot, (u % per_slot) * ppb
        layer = layer_ref[0]

        @pl.loop(0, _block_pages(len_ref[s], page0, ps, ppb))
        def _(i):
            phys = tbl_ref[s, page0 + i]
            rows = pl.ds(pl.multiple_of(i * ps, ps), ps)
            for x, (src, dst) in enumerate(planes):
                getattr(pltpu.make_async_copy(src.at[layer, :, phys],
                                              dst.at[buf, :, rows],
                                              sems.at[buf, x]), act)()

    @pl.when(t == 0)
    def _walk_init():
        walk[0] = 0
        walk[1] = 0

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    L = len_ref[b]
    base = (c * nb + j) * bk  # logical position of the block's column 0

    # An active block always has >= 1 valid column (DESIGN.md Section 5.1),
    # so the in-block masking below never needs the contiguous kernel's
    # any_valid guard -- fully-masked blocks are exactly the skipped ones.
    @pl.when(live(t))
    def _step():
        buf = walk[0]

        @pl.when(walk[1] == 0)
        def _first():  # nothing before it in the walk prefetched it
            block_dma(t, buf, "start")

        nxt = jax.lax.while_loop(lambda u: (u < T) & ~live(u),
                                 lambda u: u + 1, t + 1)

        @pl.when(nxt < T)
        def _prefetch():
            block_dma(nxt, 1 - buf, "start")

        walk[0] = 1 - buf
        walk[1] = 1
        block_dma(t, buf, "wait")

        cols = jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1) + base
        valid = cols < L
        if window is not None:
            in_win = cols >= L - window
            if sink:
                in_win = in_win | (cols < sink)
            valid = valid & in_win
        # Rows past the length hold stale VMEM (pages not copied) or stale
        # pool rows: their p is 0, but 0 * NaN is NaN, so zero them.
        Dv = acc_scr.shape[-1]
        cached = jax.lax.broadcasted_iota(jnp.int32, (bk, Dv), 0) + base < L
        for h in range(Hk):
            q = q_ref[h]  # (G, D)
            k = k_buf[buf, h]  # (bk, D)
            v = k[:, :v_dim] if v_dim is not None else v_buf[buf, h]
            v = jnp.where(cached, v, 0)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            s = jnp.where(valid, s, DEFAULT_MASK_VALUE)
            m_prev = m_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # First touched block: m_prev = -inf -> alpha = 0, and
            # 0 * prev + x leaves x bitwise intact -- the single-page path IS
            # the contiguous kernel's math.
            alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                              jnp.exp(m_prev - m_new))
            pexp = jnp.exp(s - m_new)
            l_new = l_scr[h, :, :1] * alpha + jnp.sum(pexp, axis=-1,
                                                      keepdims=True)
            pv = jax.lax.dot_general(
                pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[h] = acc_scr[h] * alpha + pv
            m_scr[h] = jnp.broadcast_to(m_new, (G, LANES))
            l_scr[h] = jnp.broadcast_to(l_new, (G, LANES))

    @pl.when(j == nb - 1)
    def _finalize():
        for h in range(Hk):
            l = l_scr[h, :, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[h] = acc_scr[h] / l_safe
            lse = jnp.where(l == 0.0, -jnp.inf, m_scr[h, :, :1] + jnp.log(l_safe))
            lse_ref[h, 0] = lse[:, 0]  # (G,) lane-major


def flash_decode_paged_kernel(
    q: jnp.ndarray,  # (B, Hk, G, D) pre-scaled
    k_pages: jnp.ndarray,  # (layers, Hk, P, ps, D) stacked page planes
    v_pages: Optional[jnp.ndarray],  # same, or None in latent mode
    lengths: jnp.ndarray,  # (B,) int32 logical lengths
    block_table: jnp.ndarray,  # (B, n_pages) int32 logical -> physical page
    layer=0,  # int32 scalar: the layer of the stack to read
    *,
    num_splits: int = 8,
    window: Optional[int] = None,
    sink: int = 0,
    v_dim: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Split-KV decode that never sees a contiguous cache.

    Grid ``(B, ns, nb)`` (``paged_decode_geometry``): each KV split covers
    ``nb`` blocks of ``ppb`` *logical* pages; a step copies the physical
    pages ``tbl[b, page]`` its block holds for every KV head straight from
    the pool planes (scalar-prefetched table and lengths, the same contract
    as kernels/schedule.py), double-buffered across steps. The planes are
    the pool's stack of every layer's planes, read at ``layer`` (a third
    scalar prefetch), so a layer scan hands the kernel the stack it carries
    and no layer's plane is sliced out (DESIGN.md Section 5.3); one
    layer's planes are passed as a stack of one at layer 0. Physical page
    order is irrelevant to the math (shuffle-invariance is tested
    bitwise). Table entries past a sequence's live pages should name the
    null page (0); they are never copied, whatever they hold.

    Latent mode (``v_pages=None``, ``v_dim`` given; latent attention,
    models/mla.py) reads one plane as K and its first ``v_dim`` lanes as
    V, under the kernel name ``mla_decode_paged``.

    Returns per-split partials ``(o_parts (B, ns, Hk, G, Dv) f32,
    lse_parts (B, ns, Hk, 1, G) f32)`` for ``combine_lse_outputs``, Dv the
    V width.
    """
    interpret = resolve_interpret(interpret)
    B, Hk, G, D = q.shape
    ps = k_pages.shape[3]
    n_pages = block_table.shape[1]
    ns, nb, ppb = paged_decode_geometry(n_pages, ps, num_splits)
    pad = ns * nb * ppb - n_pages
    tbl = block_table.astype(jnp.int32)
    if pad:
        # Padded table columns are logical positions >= n_pages*ps >= L:
        # never live, never copied.
        tbl = jnp.pad(tbl, ((0, 0), (0, pad)))
    latent = v_pages is None
    Dv = v_dim if latent else D
    n_planes = 1 if latent else 2
    kernel = functools.partial(
        _paged_decode_kernel, ps=ps, ppb=ppb, nb=nb, ns=ns, window=window,
        sink=sink, v_dim=v_dim if latent else None,
    )
    # Capacity-based: the live work depends on the lengths, unknown here.
    cost = pl.CostEstimate(
        flops=2 * B * Hk * G * n_pages * ps * (D + Dv),
        bytes_accessed=n_planes * B * n_pages * ps * D * k_pages.dtype.itemsize
        + 2 * q.size * q.dtype.itemsize,
        transcendentals=B * Hk * G * n_pages * ps,
    )
    buf = pltpu.VMEM((2, Hk, ppb * ps, D), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # block table, lengths, layer
        grid=(B, ns, nb),
        in_specs=[
            pl.BlockSpec((None, Hk, G, D), lambda b, c, j, *_: (b, 0, 0, 0)),
        ] + [pl.BlockSpec(memory_space=pl.ANY)] * n_planes,
        out_specs=[
            pl.BlockSpec((None, None, Hk, G, Dv),
                         lambda b, c, j, *_: (b, c, 0, 0, 0)),
            pl.BlockSpec((None, None, Hk, 1, G),
                         lambda b, c, j, *_: (b, c, 0, 0, 0)),
        ],
        scratch_shapes=[buf] * n_planes + [
            pltpu.SemaphoreType.DMA((2, n_planes)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((Hk, G, LANES), jnp.float32),
            pltpu.VMEM((Hk, G, LANES), jnp.float32),
            pltpu.VMEM((Hk, G, Dv), jnp.float32),
        ],
    )
    planes = (k_pages,) if latent else (k_pages, v_pages)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, ns, Hk, G, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, Hk, 1, G), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # The walk prefetches across slots and splits: sequential.
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        cost_estimate=cost,
        interpret=interpret,
        name="mla_decode_paged" if latent else "fa2_decode_paged",
    )(tbl, lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q, *planes)
