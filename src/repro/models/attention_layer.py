"""GQA/MQA/MHA attention layer built on the FlashAttention-2 core.

Supports: RoPE (per-layer theta override), qk-norm (qwen3), sliding windows
(mixtral/gemma3/hymba), sink prefixes (hymba meta tokens), cross-attention
(whisper), KV-cache prefill + single-token decode. The attention math itself
is always ``repro.core.attention`` -- the layer never materializes S or P.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.attention import (
    AttentionConfig,
    attention,
    decode_attention,
    decode_attention_paged,
)
from repro.core.decode import paged_write
from repro.core.masks import MaskSpec
from repro.distributed import sharding as shd
from repro.distributed.context_parallel import gather_kv
from repro.distributed.sharding import constrain
from repro.models.layers import _normal, apply_rope, rms_norm_vec


def _expand_gqa_for_sharding(cfg, k, v):
    """GQA -> MHA expansion when query heads are sharded over 'model'.

    The flash blocked layout groups heads as (Hkv, G); with Hq sharded
    16-way that split is unshardable (16@model -> (8, 2) has no valid
    SPMD mapping) and XLA *replicates the whole attention computation*
    (measured: granite prefill_32k ran ~73% of the global attention FLOPs
    on every chip -- EXPERIMENTS.md Section Perf iteration G1). Expanding
    K/V to one head per query head (the paper's MQA/GQA note: heads are
    "implicitly duplicated", dK/dV summed back by autodiff through the
    broadcast) makes G=1 so the merged (B*Hq) dim shards over
    (data, model). Per chip this *reduces* KV memory: one expanded head
    instead of all kv heads replicated."""
    state = shd.current()
    if state is None:
        return k, v
    _, rules = state
    if rules.table.get("heads") != "model":
        return k, v
    G = cfg.num_heads // cfg.num_kv_heads
    if G == 1:
        return k, v
    B, S, Hk, D = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, Hk, G, D)).reshape(B, S, Hk * G, D)
    v = jnp.broadcast_to(v[:, :, :, None, :], (B, S, Hk, G, D)).reshape(B, S, Hk * G, D)
    k = constrain(k, "batch", "kv_seq", "heads", None)
    v = constrain(v, "batch", "kv_seq", "heads", None)
    return k, v


def init_attention(key, cfg, dtype, cross: bool = False) -> dict:
    d, qd, kd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    p = {
        "wq": _normal(ks[0], (d, qd), std, dtype),
        "wk": _normal(ks[1], (d, kd), std, dtype),
        "wv": _normal(ks[2], (d, kd), std, dtype),
        "wo": _normal(ks[3], (qd, d), 1.0 / math.sqrt(qd), dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kd,), dtype)
        p["bv"] = jnp.zeros((kd,), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    return p


def _project_q(p, cfg, x):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    if "q_norm" in p:
        q = rms_norm_vec(q, p["q_norm"], cfg.norm_eps)
    return constrain(q, "batch", "seq", "heads", None)


def _project_kv(p, cfg, x):
    B, S, _ = x.shape
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if "bk" in p:
        k = k + p["bk"]
        v = v + p["bv"]
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if "k_norm" in p:
        k = rms_norm_vec(k, p["k_norm"], cfg.norm_eps)
    k = constrain(k, "batch", "kv_seq", "kv_heads", None)
    v = constrain(v, "batch", "kv_seq", "kv_heads", None)
    return k, v


def _out(p, cfg, o):
    B, S, _, _ = o.shape
    y = jnp.einsum("bsq,qd->bsd", o.reshape(B, S, cfg.q_dim), p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return constrain(y, "batch", "seq", "embed")


def apply_attention(
    p: dict,
    cfg,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    spec: MaskSpec,
    attn_cfg: AttentionConfig,
    *,
    rope_theta: Optional[float] = None,
    x_kv: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence attention (train / encoder / cross). x (B,S,d).

    segment_ids (B, S) enables packed varlen training: attention never
    crosses a segment boundary (``packed=True`` mode; the caller supplies
    within-segment RoPE positions). Not combined with context parallelism
    -- packed rows are data-sharded like any other batch row.
    """
    q = _project_q(p, cfg, x)
    k, v = _project_kv(p, cfg, x_kv if x_kv is not None else x)
    if x_kv is None and rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    # Context parallelism (C2 at mesh level): gather KV once per layer; the
    # flash scan then runs sharded Q rows against full KV. No-op when the
    # 'kv_seq' logical axis is unsharded (heads-sharded archs, CPU tests)
    # and for ring-mode self-attention (KV stays sharded and rotates); a
    # cross-attention call always keeps the gather -- the ring only covers
    # Sq == Skv self-attention.
    k, v = gather_kv(k, v, cross=x_kv is not None)
    k, v = _expand_gqa_for_sharding(cfg, k, v)
    o = attention(q, k, v, spec, attn_cfg, segment_ids=segment_ids)
    return _out(p, cfg, o)


def prefill_attention(
    p, cfg, x, positions, spec, attn_cfg, *, rope_theta=None,
    cache_size: Optional[int] = None,
):
    """Like apply_attention but also returns the KV cache (padded to
    cache_size along seq)."""
    q = _project_q(p, cfg, x)
    k, v = _project_kv(p, cfg, x)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    kg, vg = gather_kv(k, v)
    kg, vg = _expand_gqa_for_sharding(cfg, kg, vg)
    o = attention(q, kg, vg, spec, attn_cfg)
    S = k.shape[1]
    if cache_size is not None and cache_size > S:
        pad = cache_size - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    k = constrain(k, "batch", "cache_seq", "kv_heads", None)
    v = constrain(v, "batch", "cache_seq", "kv_heads", None)
    return _out(p, cfg, o), {"k": k, "v": v}


def decode_attention_step(
    p, cfg, x_new: jnp.ndarray, cache: dict, cache_len: jnp.ndarray,
    attn_cfg: AttentionConfig, *, rope_theta=None, window=None, sink: int = 0,
    block_table: Optional[jnp.ndarray] = None, layer=0,
) -> Tuple[jnp.ndarray, dict]:
    """One decode step. x_new (B,1,d); cache_len (B,) = number of valid
    entries BEFORE this token.

    Contiguous cache (``block_table=None``): cache k/v (B,S,Hk,hd), the new
    KV is inserted at position cache_len.

    Paged cache (``block_table`` (B, n_pages) int32): cache k/v are the
    pool's physical page planes stacked over layers (L, Hk, P, page_size,
    hd), this layer's at index ``layer``; the new KV is written into page
    ``table[b, L // ps]`` at offset ``L % ps`` of that layer
    (core.decode.paged_write, in place) and attention runs page-indirect on
    the stack (core.attention.decode_attention_paged). Rows with
    cache_len == 0 are *inactive slots* (a real sequence always has a
    non-empty prompt): their write lands in the reserved null page 0 and
    their attention length is forced to 0, so a free/finished slot costs no
    KV reads at all."""
    B = x_new.shape[0]
    q = _project_q(p, cfg, x_new)
    k_new, v_new = _project_kv(p, cfg, x_new)
    if rope_theta is not None:
        pos = cache_len[:, None]  # (B,1) absolute position of the new token
        q = apply_rope(q, pos, rope_theta)
        k_new = apply_rope(k_new, pos, rope_theta)

    if block_table is not None:
        ps = cache["k"].shape[3]
        page = jnp.take_along_axis(
            block_table, (cache_len // ps)[:, None], axis=1
        )[:, 0]  # (B,) physical page of the write position
        off = cache_len % ps
        k_pages = paged_write(cache["k"], layer, page, k_new[:, 0], off)
        v_pages = paged_write(cache["v"], layer, page, v_new[:, 0], off)
        lengths = jnp.where(cache_len > 0, cache_len + 1, 0)
        o = decode_attention_paged(
            q, k_pages, v_pages, lengths, block_table, attn_cfg,
            window=window, sink=sink, layer=layer,
        )
        return _out(p, cfg, o), {"k": k_pages, "v": v_pages}

    def insert(buf, new):
        def one(b_row, n_row, idx):
            return jax.lax.dynamic_update_slice_in_dim(b_row, n_row, idx, axis=0)
        return jax.vmap(one)(buf, new, cache_len)

    k_cache = insert(cache["k"], k_new)
    v_cache = insert(cache["v"], v_new)
    o = decode_attention(
        q, k_cache, v_cache, cache_len + 1, attn_cfg, window=window, sink=sink
    )
    return _out(p, cfg, o), {"k": k_cache, "v": v_cache}


def cross_attention_step(p, cfg, x_new, enc_cache, enc_len, attn_cfg):
    """Decode-time cross attention: q from x_new, kv precomputed from the
    encoder output (enc_cache = {'k','v'}), enc_len (B,)."""
    q = _project_q(p, cfg, x_new)
    o = decode_attention(q, enc_cache["k"], enc_cache["v"], enc_len, attn_cfg)
    return _out(p, cfg, o)
