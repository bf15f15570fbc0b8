"""Plain float32 reference of the DeepSeek-V2 forward pass (latent
attention, YaRN rope, shared-expert MoE), from the published description
(HF ``modeling_deepseek``, arXiv:2405.04434 Section 2): no kernels, no
cache, no batching, every matmul under ``precision="highest"``. The tests
hold the serving path (prefill, then decode through the latent pages) to
it on logits.

Departures from the published code, none of which changes the result:
the rope lanes stay in the checkpoint's interleaved layout and are rotated
in pairs (HF de-interleaves both q_pe and k_pe before rotating by halves:
the same dot products); routed experts are computed for every token and
weighted by zero where not routed (no dispatch); ``seq_aux`` and the
auxiliary losses are training-only and absent.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, yarn):
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return extra

    def corr(rot):
        return (dim * math.log(yarn.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
                    0, 1)
    mask = 1.0 - ramp
    return extra / yarn.factor * (1 - mask) + extra * mask


def _rope(x, pos, cfg):
    """x (T, H, D) interleaved pairs rotated by position * inv_freq."""
    y = cfg.rope_scaling
    inv = yarn_inv_freq(x.shape[-1], cfg.rope_theta, y)
    ms = 1.0 if y is None else _mscale(y.factor, y.mscale) / _mscale(
        y.factor, y.mscale_all_dim)
    ang = pos[:, None].astype(jnp.float32) * inv[None]
    cos, sin = (jnp.cos(ang) * ms)[:, None], (jnp.sin(ang) * ms)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def _attention(p, cfg, x, pos):
    m, H = cfg.mla, cfg.num_heads
    T = x.shape[0]
    q = (x @ p["wq"]).reshape(T, H, cfg.head_dim)
    ckv = x @ p["wkv_a"]
    c = _rmsnorm(ckv[:, : m.kv_lora_rank], p["kv_norm"]["scale"], cfg.norm_eps)
    kv = (c @ p["wkv_b"]).reshape(T, H, m.qk_nope_head_dim + m.v_head_dim)
    q = jnp.concatenate([q[..., : m.qk_nope_head_dim],
                         _rope(q[..., m.qk_nope_head_dim:], pos, cfg)], -1)
    k_pe = _rope(ckv[:, None, m.kv_lora_rank:], pos, cfg)
    k = jnp.concatenate([kv[..., : m.qk_nope_head_dim],
                         jnp.broadcast_to(k_pe, (T, H, m.qk_rope_head_dim))], -1)
    v = kv[..., m.qk_nope_head_dim:]
    scale = cfg.head_dim ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= _mscale(y.factor, y.mscale_all_dim) ** 2
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return o.reshape(T, -1) @ p["wo"]


def _swiglu(f, x):
    return (jax.nn.silu(x @ f["w_gate"]) * (x @ f["w_up"])) @ f["w_down"]


def moe(f, cfg, x):
    """Routed experts and shared experts of one MoE layer, x (T, d) f32."""
    m = cfg.moe
    probs = jax.nn.softmax(x @ f["router"], -1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(
        w * m.routed_scale)
    outs = jax.vmap(lambda g, u, d: _swiglu({"w_gate": g, "w_up": u,
                                            "w_down": d}, x))(
        f["we_gate"], f["we_up"], f["we_down"])  # (E, T, d)
    y = jnp.einsum("te,etd->td", gate, outs)
    if "shared" in f:
        y = y + _swiglu(f["shared"], x)
    return y


def layers(params):
    """Per-layer weights in order: lead, grouped (unstacked), tail."""
    out = list(params.get("lead", []))
    groups = params.get("groups")
    if isinstance(groups, dict):
        n = jax.tree.leaves(groups)[0].shape[0]
        for i in range(n):
            g = jax.tree.map(lambda a, i=i: a[i], groups)
            out.extend(g[k] for k in sorted(g))
    elif groups:
        for g in groups:
            out.extend(g[k] for k in sorted(g))
    return out + list(params.get("tail", []))


def forward(cfg, params, tokens):
    """tokens (T,) -> logits (T, vocab_size) in float32."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    params = f32(params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens]
        pos = jnp.arange(tokens.shape[0])
        for p in layers(params):
            h = _rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps)
            x = x + _attention(p["mixer"], cfg, h, pos)
            h = _rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
            x = x + (moe(p["mlp"], cfg, h) if "router" in p["mlp"]
                     else _swiglu(p["mlp"], h))
        x = _rmsnorm(x, params["ln_f"]["scale"], cfg.norm_eps)
        return (x @ params["embed"]["unembed"])[:, : cfg.vocab_size]
