"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 Section 2.1),
without a query LoRA, on the FlashAttention-2 core.

Per token ``x`` (d) and head ``h``, as HF ``modeling_deepseek`` computes it:

  [q_nope_h, q_pe_h] = (x Wq)_h                      widths nope, rope
  [c, k_pe]          = x Wkv_a;  c = rmsnorm(c) * kv_norm     widths r, rope
  [k_nope_h, v_h]    = (c Wkv_b)_h                    widths nope, v
  q_h = [q_nope_h, rope(q_pe_h)],  k_h = [k_nope_h, rope(k_pe)]
  o   = (softmax(q_h k_h^T * scale) v_h)_h  Wo

with YaRN rope on interleaved pairs (layers.apply_rope_interleaved) and
``scale = (nope + rope)^-1/2 * mscale(factor, mscale_all_dim)^2``.

The cache holds one latent "KV head" per token: ``[c, rope(k_pe)]``
(width r + rope), zero-padded to ``mla.cache_dim`` lanes. Prefill expands
it to per-head K and V and runs attention with V narrower than Q/K.
Decode uses the absorbed form: the query ``[q_nope_h Wkb_h^T,
rope(q_pe_h)]`` (padded alike) attends to the latent tokens themselves,
reading c as V, and ``Wvb_h`` maps each head's r-wide result to its
v-wide output -- so the cache is read once for all heads (paged:
kernels/flash_decode's latent mode).
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
    decode_attention_latent_paged,
)
from repro.core.decode import paged_write
from repro.core.masks import MaskSpec
from repro.distributed.sharding import constrain
from repro.models.layers import (
    _normal,
    apply_rope_interleaved,
    rms_norm_vec,
    yarn_inv_freq,
    yarn_mscale,
)


def init_mla(key, cfg, dtype) -> dict:
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    r = m.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": _normal(ks[0], (d, H * cfg.head_dim), 1.0 / math.sqrt(d), dtype),
        "wkv_a": _normal(ks[1], (d, m.latent_dim), 1.0 / math.sqrt(d), dtype),
        "kv_norm": {"scale": jnp.ones((r,), dtype)},
        "wkv_b": _normal(ks[2], (r, H * (m.qk_nope_head_dim + m.v_head_dim)),
                         1.0 / math.sqrt(r), dtype),
        "wo": _normal(ks[3], (H * m.v_head_dim, d),
                      1.0 / math.sqrt(H * m.v_head_dim), dtype),
    }


def softmax_scale(cfg) -> float:
    s = cfg.head_dim ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def _rope(cfg, x, positions):
    y = cfg.rope_scaling
    inv = yarn_inv_freq(cfg.mla.qk_rope_head_dim, cfg.rope_theta, y)
    ratio = 1.0
    if y is not None:
        ratio = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor,
                                                              y.mscale_all_dim)
    return apply_rope_interleaved(x, positions, inv, ratio)


def _project(p, cfg, x, positions):
    """x (B, S, d) -> q_nope (B,S,H,nope), roped q_pe (B,S,H,rope), the
    normalised latent c (B,S,r) and the roped shared k_pe (B,S,1,rope)."""
    m = cfg.mla
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(B, S, cfg.num_heads,
                                                       cfg.head_dim)
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    ckv = jnp.einsum("bsd,dc->bsc", x, p["wkv_a"])
    c = rms_norm_vec(ckv[..., : m.kv_lora_rank], p["kv_norm"]["scale"],
                     cfg.norm_eps)
    k_pe = ckv[..., m.kv_lora_rank:][:, :, None, :]
    return (q_nope, _rope(cfg, q_pe, positions), c,
            _rope(cfg, k_pe, positions))


def _wkv_b(p, cfg):
    m = cfg.mla
    return p["wkv_b"].reshape(m.kv_lora_rank, cfg.num_heads,
                              m.qk_nope_head_dim + m.v_head_dim)


def _out(p, cfg, o):
    B, S = o.shape[:2]
    y = jnp.einsum("bsq,qd->bsd", o.reshape(B, S, -1), p["wo"])
    return constrain(y, "batch", "seq", "embed")


def _attend(q, k, v, spec, attn_cfg: AttentionConfig, scale, segment_ids,
            native: bool):
    """Attention with V narrower than Q/K. ``native`` (prefill on the
    Pallas forward) hands the kernel its own V width; otherwise (training,
    whose fused backward needs equal widths, and the XLA backends) V is
    zero-padded to the Q/K width and the output cut back."""
    dv = v.shape[-1]
    if native and attn_cfg.impl == "flash_pallas":
        return attention(q, k, v, spec, attn_cfg, scale=scale)
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
    o = attention(q, k, v, spec, attn_cfg, scale=scale, segment_ids=segment_ids)
    return o[..., :dv]


def _full(p, cfg, x, positions, spec, attn_cfg, segment_ids, native):
    m = cfg.mla
    q_nope, q_pe, c, k_pe = _project(p, cfg, x, positions)
    kv = jnp.einsum("bsr,rhe->bshe", c, _wkv_b(p, cfg))
    k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    q = constrain(jnp.concatenate([q_nope, q_pe], -1), "batch", "seq", "heads", None)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, q_pe.shape[:2] + (
        cfg.num_heads, m.qk_rope_head_dim))], -1)
    o = _attend(q, k, v, spec, attn_cfg, softmax_scale(cfg), segment_ids, native)
    return _out(p, cfg, o), _latent_token(cfg, c, k_pe)[:, :, None, :]


def _latent_token(cfg, c, k_pe):
    """The cached latent of each token, (B, S, cache_dim)."""
    x = jnp.concatenate([c, k_pe[:, :, 0]], -1)
    return jnp.pad(x, ((0, 0), (0, 0), (0, cfg.mla.cache_dim - x.shape[-1])))


def apply_mla(p, cfg, x, positions, spec: MaskSpec, attn_cfg: AttentionConfig,
              segment_ids=None) -> jnp.ndarray:
    """Full-sequence latent attention (training / forward). x (B,S,d)."""
    return _full(p, cfg, x, positions, spec, attn_cfg, segment_ids, False)[0]


def prefill_mla(p, cfg, x, positions, spec, attn_cfg, *,
                cache_size: Optional[int] = None):
    """Like :func:`apply_mla`, with V at its own width on the Pallas
    forward; also returns the latent cache ``{"latent": (B, cache_size, 1,
    cache_dim)}`` (one latent head, padded along the sequence)."""
    y, latent = _full(p, cfg, x, positions, spec, attn_cfg, None, True)
    S = latent.shape[1]
    if cache_size is not None and cache_size > S:
        latent = jnp.pad(latent, ((0, 0), (0, cache_size - S), (0, 0), (0, 0)))
    return y, {"latent": constrain(latent, "batch", "cache_seq", None, None)}


def decode_mla_step(p, cfg, x_new, cache: dict, cache_len: jnp.ndarray,
                    attn_cfg: AttentionConfig, *,
                    block_table: Optional[jnp.ndarray] = None, layer=0
                    ) -> Tuple[jnp.ndarray, dict]:
    """One absorbed-form decode step. x_new (B,1,d); cache_len (B,) entries
    before this token. The new latent token is written at ``cache_len``
    (contiguous ``(B,S,1,w)`` cache, or through ``block_table`` into the
    paged planes stacked over layers ``(L,1,P,ps,w)`` at ``layer``,
    inactive rows to the null page as in
    attention_layer.decode_attention_step), then the latent query attends
    to the cache."""
    m = cfg.mla
    r = m.kv_lora_rank
    q_nope, q_pe, c, k_pe = _project(p, cfg, x_new, cache_len[:, None])
    wkv_b = _wkv_b(p, cfg)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wkv_b[..., : m.qk_nope_head_dim])
    q = jnp.concatenate([q_lat, q_pe], -1)  # (B, 1, H, r + rope)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, m.cache_dim - q.shape[-1]),))
    new = _latent_token(cfg, c, k_pe)  # (B, 1, cache_dim)
    scale = softmax_scale(cfg)
    lat = cache["latent"]
    if block_table is not None:
        ps = lat.shape[3]
        page = jnp.take_along_axis(block_table, (cache_len // ps)[:, None],
                                   axis=1)[:, 0]
        lat = paged_write(lat, layer, page, new, cache_len % ps)
        lengths = jnp.where(cache_len > 0, cache_len + 1, 0)
        o_lat = decode_attention_latent_paged(
            q, lat, lengths, block_table, attn_cfg, v_dim=r, scale=scale,
            layer=layer)
    else:
        lat = jax.vmap(lambda b, n, i: jax.lax.dynamic_update_slice_in_dim(
            b, n, i, axis=0))(lat, new[:, :, None].astype(lat.dtype), cache_len)
        v = jnp.where(jnp.arange(lat.shape[-1]) < r, lat, 0)
        o_lat = decode_attention(q, lat, v, cache_len + 1, attn_cfg,
                                 scale=scale)[..., :r]
    o = jnp.einsum("bshr,rhv->bshv", o_lat.astype(x_new.dtype),
                   wkv_b[..., m.qk_nope_head_dim:])
    return _out(p, cfg, o), {"latent": lat}
