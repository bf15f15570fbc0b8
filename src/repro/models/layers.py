"""Shared neural-net layers (pure JAX, param trees are plain dicts).

Initializers return {name: array} trees; apply functions are pure. Param
naming is stable -- the sharding rules in distributed/sharding.py match on
path suffixes, and checkpoints key on the same paths.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape) * std).astype(dtype)


# --------------------------------------------------------------------- norms


def init_norm(cfg, dtype) -> dict:
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def apply_norm(p: dict, x: jnp.ndarray, eps: float, kind: str) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


def rms_norm_vec(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm over the last axis with an arbitrary-width scale (qk-norm etc)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------- rope


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Half-split (NeoX-style) rotary embedding.

    x: (B, S, H, D); positions: (B, S) or (S,) absolute token positions.
    """
    B, S, H, D = x.shape
    half = D // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, yarn) -> jnp.ndarray:
    """YaRN inverse frequencies of a ``dim``-wide rotary embedding, as
    DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` computes them: each
    pair's frequency blends the interpolated ``theta^(-2i/dim) / factor``
    and the original one by a linear ramp over the pairs between the
    correction dims of ``beta_fast`` and ``beta_slow`` rotations in
    ``original_max_position`` tokens (interpolated below, original above).
    Without ``yarn``, the plain ``theta^(-2i/dim)``."""
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return extra

    def corr(rot):
        return dim * math.log(yarn.original_max_position / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    keep = 1.0 - ramp  # 1: the original frequency
    return extra / yarn.factor * (1.0 - keep) + extra * keep


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1 * mscale * ln(scale) + 1``."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           inv_freq: jnp.ndarray, cos_scale: float = 1.0
                           ) -> jnp.ndarray:
    """Rotary embedding on interleaved pairs ``(x[2i], x[2i+1])`` at angle
    ``position * inv_freq[i]`` (DeepSeek-V2's layout); cos and sin scaled
    by ``cos_scale`` (YaRN's mscale ratio).

    x: (B, S, H, D); positions: (B, S) or (S,).
    """
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (B, S, D/2)
    cos = (jnp.cos(ang) * cos_scale)[:, :, None, :]
    sin = (jnp.sin(ang) * cos_scale)[:, :, None, :]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ----------------------------------------------------------------------- mlp


def init_mlp(key, cfg, d_ff: int, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    std_in, std_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(d_ff)
    if cfg.mlp == "swiglu":
        return {
            "w_gate": _normal(ks[0], (d, d_ff), std_in, dtype),
            "w_up": _normal(ks[1], (d, d_ff), std_in, dtype),
            "w_down": _normal(ks[2], (d_ff, d), std_out, dtype),
        }
    p = {
        "w_in": _normal(ks[0], (d, d_ff), std_in, dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": _normal(ks[1], (d_ff, d), std_out, dtype),
        "b_out": jnp.zeros((d,), dtype),
    }
    return p


def apply_mlp(p: dict, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    from repro.distributed.sharding import constrain

    if kind == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        h = constrain(h, "batch", "seq", "ff_act")
        return jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"]) + p["b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = constrain(h, "batch", "seq", "ff_act")
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"]) + p["b_out"]


# ----------------------------------------------------------------- embedding


def init_embedding(key, cfg, dtype) -> dict:
    V = cfg.padded_vocab
    p = {"tokens": _normal(key, (V, cfg.d_model), 1.0, dtype)}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["unembed"] = _normal(k2, (cfg.d_model, V), 1.0 / math.sqrt(cfg.d_model), dtype)
    if cfg.learned_pos_embed:
        k3 = jax.random.fold_in(key, 2)
        p["positions"] = _normal(k3, (cfg.learned_pos_embed, cfg.d_model), 0.02, dtype)
    return p


def embed_tokens(p: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["tokens"], tokens, axis=0)


def unembed(p: dict, x: jnp.ndarray, tie: bool) -> jnp.ndarray:
    if tie:
        return jnp.einsum("bsd,vd->bsv", x, p["tokens"])
    return jnp.einsum("bsd,dv->bsv", x, p["unembed"])


def sinusoidal_positions(n: int, d: int) -> jnp.ndarray:
    """Whisper-style sinusoidal table (n, d)."""
    half = d // 2
    log_timescale = math.log(10_000.0) / max(half - 1, 1)
    inv = jnp.exp(-log_timescale * jnp.arange(half, dtype=jnp.float32))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
