"""Split-KV flash decode: FA2's sequence-dimension parallelism (C2) applied
to autoregressive inference.

At decode there is a single query per sequence, so the (batch x heads) grid
alone under-fills the device exactly as the paper describes for long
sequences. The fix is the paper's: split the *KV* axis into ``num_splits``
chunks, compute a locally-normalized (o_i, lse_i) per chunk in parallel, and
merge with the associative online-softmax combine
(``online_softmax.combine_lse_outputs``). The same function serves as the
merge step for mesh-level context-parallel decode (KV cache sharded over the
`model` axis -- see distributed/context_parallel.py).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.core.masks import DEFAULT_MASK_VALUE, MaskSpec
from repro.core.online_softmax import SoftmaxState, combine_lse_outputs, finalize


def flash_decode(
    q: jnp.ndarray,  # (B, 1, Hq, D) -- single new token per sequence
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,  # (B, S, Hkv, D)
    cache_length: jnp.ndarray,  # (B,) int32: number of valid cache entries
    *,
    window: Optional[int] = None,
    sink: int = 0,
    scale: Optional[float] = None,
    num_splits: int = 8,
    kv_segment_ids: Optional[jnp.ndarray] = None,  # (B, S) int32
    q_segment: Optional[jnp.ndarray] = None,  # (B,) int32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact attention of one query against a (padded) KV cache.

    The query attends to cache positions [max(0, L - window), L) where
    L = cache_length[b] (the query sits at position L - 1 *after* the new
    token's KV has been appended -- append before calling).

    kv_segment_ids/q_segment restrict attention to the query's own segment
    in a *packed* cache (several sequences back-to-back in one cache row):
    only positions with kv_segment_ids[b, j] == q_segment[b] are visible.
    The window (if any) still counts global tail positions, which matches
    the packed-decode case of generating into the trailing segment.

    Returns (o (B, 1, Hq, D), lse (B, Hq, 1)).
    """
    B, one, Hq, D = q.shape
    assert one == 1, "flash_decode is a single-step primitive; loop outside"
    _, S, Hk, _ = k_cache.shape
    G = Hq // Hk
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    ns = num_splits
    while S % ns != 0:  # static; S is padded cache capacity
        ns -= 1
    sc = S // ns

    qf = (q.astype(jnp.float32) * scale).reshape(B, Hk, G, D)
    kc = k_cache.transpose(0, 2, 1, 3).reshape(B, Hk, ns, sc, D)
    vc = v_cache.transpose(0, 2, 1, 3).reshape(B, Hk, ns, sc, D)

    # (B, Hk, G, ns, sc): every split computed in parallel -- C2 for decode.
    s = jnp.einsum("bhgd,bhcsd->bhgcs", qf, kc.astype(qf.dtype))
    pos = jnp.arange(S, dtype=jnp.int32).reshape(ns, sc)
    valid = pos[None] < cache_length[:, None, None]  # (B, ns, sc)
    if kv_segment_ids is not None:
        assert q_segment is not None, "packed decode needs the query's segment id"
        same_seg = kv_segment_ids.reshape(B, ns, sc) == q_segment[:, None, None]
        valid = valid & same_seg
    if window is not None:
        in_win = pos[None] >= (cache_length[:, None, None] - window)
        if sink:
            in_win = in_win | (pos[None] < sink)
        valid = valid & in_win
    s = jnp.where(valid[:, None, None], s, DEFAULT_MASK_VALUE)

    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    # Zero fully-masked splits (their m == MASK_VALUE -> p == 1 garbage).
    any_valid = jnp.any(valid, axis=-1)[:, None, None]  # (B, 1, 1, ns)
    l = jnp.where(any_valid, jnp.sum(p, axis=-1), 0.0)
    o_unscaled = jnp.einsum("bhgcs,bhcsd->bhgcd", p.astype(v_cache.dtype), vc,
                            preferred_element_type=jnp.float32)
    # Finalize each split with the shared online-softmax helper (l = 0 ->
    # lse = -inf, so fully-masked splits vanish in the merge below).
    o_part, lse_part = finalize(SoftmaxState(m=m, l=l, o=o_unscaled))

    # Merge the splits: associative combine over axis `ns`.
    o_parts = jnp.moveaxis(o_part, 3, 0)  # (ns, B, Hk, G, D)
    lse_parts = jnp.moveaxis(lse_part, 3, 0)  # (ns, B, Hk, G)
    o, lse = combine_lse_outputs(o_parts, lse_parts)
    return (
        o.reshape(B, 1, Hq, D).astype(q.dtype),
        lse.reshape(B, Hq, 1),
    )


def flash_decode_paged(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    k_pages: jnp.ndarray,  # (layers, Hkv, P, page_size, D) stacked planes
    v_pages: jnp.ndarray,
    cache_length: jnp.ndarray,  # (B,) int32 logical lengths
    block_table: jnp.ndarray,  # (B, n_pages) int32 logical -> physical page
    layer=0,  # int32 scalar: the layer of the stack to read
    *,
    window: Optional[int] = None,
    sink: int = 0,
    scale: Optional[float] = None,
    num_splits: int = 8,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """XLA fallback for page-indirect decode: gather the block table's
    pages of ``layer`` into a contiguous (B, n_pages*ps, Hkv, D) view, then
    run the plain split-KV decode. Functionally the oracle for the Pallas
    kernel (tests assert parity); positions >= cache_length are masked, so
    stale or null-page contents never contribute."""
    B = q.shape[0]
    _, Hk, _, ps, D = k_pages.shape
    n_pages = block_table.shape[1]
    tbl = block_table.astype(jnp.int32)
    # (B, n_pages, Hk, ps, D) -> (B, n_pages*ps, Hk, D)
    def gather(pages):
        g = pages[layer, :, tbl]
        return jnp.swapaxes(g, 2, 3).reshape(B, n_pages * ps, Hk, D)

    return flash_decode(
        q, gather(k_pages), gather(v_pages), cache_length,
        window=window, sink=sink, scale=scale, num_splits=num_splits,
    )


def paged_write(planes, layer, page, new, off=None):
    """Write new cache entries into the stacked pool planes
    ``(layers, H, P, ps, D)``, in place once the caller's pool is donated.

    ``off`` None writes whole pages: ``new`` (..., H, ps, D) at physical
    ``page`` of ``layer``; else token rows: ``new`` (..., H, D) at offset
    ``off`` of ``page``. ``layer``, ``page`` and ``off`` broadcast to
    ``new``'s leading dims. The write is a scatter of whole leading-axis
    slices of a flat view of the planes, ``(layers*H*P, ps, D)`` or
    ``(layers*H*P*ps, D)`` (a bitcast of their default layout), which XLA
    applies to the buffer as it is laid out; a scatter indexed by page and
    offset together makes XLA lay the planes out otherwise and copy them to
    and from that layout around the decode kernel (DESIGN.md Section 5.3).
    """
    L, H, P, ps, D = planes.shape
    idx = (jnp.asarray(layer)[..., None] * H + jnp.arange(H)) * P
    idx = idx + jnp.asarray(page)[..., None]
    if off is None:
        flat = planes.reshape(L * H * P, ps, D)
    else:
        flat = planes.reshape(L * H * P * ps, D)
        idx = idx * ps + jnp.asarray(off)[..., None]
    flat = flat.at[idx.reshape(-1)].set(
        new.reshape((-1,) + flat.shape[1:]).astype(planes.dtype))
    return flat.reshape(planes.shape)
