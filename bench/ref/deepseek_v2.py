"""Plain float32 reference of the DeepSeek-V2 decoder (latent attention,
shared-expert MoE), written from the published description (HF
``modeling_deepseek``, arXiv:2405.04434 Section 2) and imported from
nothing in the program.

Layer i, as DeepSeek-V2 publishes it (no query LoRA):

  h   = x + Wo . attn(q, k, v),   x' = rmsnorm(x) * ln1
        [q_nope, q_pe] = x' Wq (per head)
        [c, k_pe] = x' Wkv_a,  c = rmsnorm(c) * kv_norm
        [k_nope, v] = c Wkv_b (per head)
        q = [q_nope, rope(q_pe)],  k = [k_nope, rope(k_pe)] (k_pe shared)
  out = h + mlp(h'),   h' = rmsnorm(h) * ln2

with causal softmax attention scaled by ``q_head_dim^-1/2 *
mscale(factor, mscale_all_dim)^2`` and YaRN rotary embedding as
``DeepseekV2YarnRotaryEmbedding`` computes it: the interleaved rope lanes
are de-interleaved and rotated by halves. ``mlp`` is a dense SwiGLU of
width ``intermediate_size`` in the first ``first_k_dense_replace`` layers;
after them the sum of the shared experts (one SwiGLU of width
``n_shared_experts * moe_intermediate_size``) and the routed experts: a
softmax over all ``n_routed_experts`` router logits, the greedy top
``num_experts_per_tok`` weights, not renormalised (``norm_topk_prob``
false), times ``routed_scaling_factor``, each weighting its expert's
SwiGLU. Logits are ``rmsnorm(x_L) * ln_f . Wunembed``.

Everything is float32 under ``precision=HIGHEST``. Attention runs in query
blocks; the MoE computes every expert for a chunk of tokens and keeps the
routed ones (no capacity, no gather), so a 30k-token sequence fits beside
the program's weights.

``quant`` rounds every matmul operand to a lower precision (fp8 e4m3,
per-tensor scaled): the control that the comparison must fail.

Weights come in the tree layout the benchmark made them in:
``embed/{tokens,unembed}``, ``ln_f/scale``, ``lead/<i>/...`` (dense
layers) and ``groups/slot_0/...`` stacked over the MoE layers on a leading
axis, each ``{ln1,ln2}/scale``, ``mixer/{wq,wkv_a,kv_norm/scale,wkv_b,
wo}`` and ``mlp/{w_gate,w_up,w_down}`` or ``mlp/{router,we_gate,we_up,
we_down,shared/{w_gate,w_up,w_down}}``. A cached latent is read by nothing
here: the reference recomputes every position from the tokens.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    heads: int
    r: int
    nope: int
    rope: int
    v: int
    experts: int
    top_k: int
    norm_topk: bool
    routed_scale: float
    vocab: int
    eps: float
    theta: float
    yarn: Optional[tuple]  # (factor, original, beta_fast, beta_slow, mscale, mscale_all_dim)

    @staticmethod
    def from_config(c: Dict[str, Any]) -> "Dims":
        y = c.get("rope_scaling")
        yarn = None if not y else (
            float(y["factor"]), int(y["original_max_position_embeddings"]),
            float(y.get("beta_fast", 32)), float(y.get("beta_slow", 1)),
            float(y.get("mscale", 1)), float(y.get("mscale_all_dim", 0)))
        return Dims(c["hidden_size"], c["num_attention_heads"],
                    c["kv_lora_rank"], c["qk_nope_head_dim"],
                    c["qk_rope_head_dim"], c["v_head_dim"],
                    c["n_routed_experts"], c["num_experts_per_tok"],
                    bool(c["norm_topk_prob"]),
                    float(c["routed_scaling_factor"]), c["vocab_size"],
                    float(c["rms_norm_eps"]), float(c["rope_theta"]), yarn)


# ------------------------------------------------------------ precision


def round_fp8(x):
    """Round to float8 e4m3 (4 significant bits, largest 448, subnormal
    step 2^-9) after scaling the tensor so its largest magnitude is 448: a
    per-tensor-scaled fp8 operand, returned in float32."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    y = x / scale
    m, e = jnp.frexp(y)
    normal = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    subnormal = jnp.round(y * 512.0) / 512.0
    y = jnp.where(jnp.abs(y) >= 2.0 ** -6, normal, subnormal)
    return jnp.clip(y, -448.0, 448.0) * scale


QUANT = {None: None, "fp8": round_fp8}


def make_dot(quant: Optional[str] = None):
    """``dot(spec, a, b)``: an f32 einsum; with ``quant`` (a key of
    :data:`QUANT`), both operands are rounded first."""
    q = QUANT[quant]
    if q is None:
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    return lambda spec, a, b: jnp.einsum(spec, q(a), q(b), precision=HIGHEST)


# ----------------------------------------------------------------- model


def rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(dims: Dims, pos):
    """cos and sin (T, rope) of ``DeepseekV2YarnRotaryEmbedding``."""
    dim, base = dims.rope, dims.theta
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv_freq = freq_extra
    mscale = 1.0
    if dims.yarn is not None:
        factor, orig, beta_fast, beta_slow, ms, ms_all = dims.yarn
        freq_inter = freq_extra / factor

        def corr_dim(rot):
            return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
        mask = 1.0 - ramp
        inv_freq = freq_inter * (1 - mask) + freq_extra * mask
        mscale = yarn_get_mscale(factor, ms) / yarn_get_mscale(factor, ms_all)
    freqs = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)
    return jnp.cos(emb) * mscale, jnp.sin(emb) * mscale


def apply_rope(x, cos, sin):
    """x (T, H, rope) in the checkpoint's interleaved layout: de-interleave,
    then ``x * cos + rotate_half(x) * sin`` (``apply_rotary_pos_emb``)."""
    T, H, D = x.shape
    x = x.reshape(T, H, D // 2, 2).swapaxes(-1, -2).reshape(T, H, D)
    half = D // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def softmax_scale(dims: Dims) -> float:
    s = (dims.nope + dims.rope) ** -0.5
    if dims.yarn is not None and dims.yarn[5]:
        s *= yarn_get_mscale(dims.yarn[0], dims.yarn[5]) ** 2
    return s


def layers_of(params) -> List[Dict[str, Any]]:
    """Per-layer weight dicts in order: the lead layers, the grouped ones
    unstacked from their leading axis, then any tail."""
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
    out.extend(params.get("tail", []))
    return out


def attention(p, x, pos, dims: Dims, dot, block):
    T = x.shape[0]
    H = dims.heads
    q = dot("td,dq->tq", x, p["wq"]).reshape(T, H, dims.nope + dims.rope)
    ckv = dot("td,dc->tc", x, p["wkv_a"])
    c = rmsnorm(ckv[:, : dims.r], p["kv_norm"]["scale"], dims.eps)
    kv = dot("tr,re->te", c, p["wkv_b"]).reshape(T, H, dims.nope + dims.v)
    cos, sin = rope_tables(dims, pos)
    q_pe = apply_rope(q[..., dims.nope:], cos, sin)
    k_pe = apply_rope(ckv[:, None, dims.r:], cos, sin)
    q = jnp.concatenate([q[..., : dims.nope], q_pe], -1)
    k = jnp.concatenate([kv[..., : dims.nope],
                         jnp.broadcast_to(k_pe, (T, H, dims.rope))], -1)
    v = kv[..., dims.nope:]
    scale = softmax_scale(dims)
    block = min(block, T)
    cols = jnp.arange(T)

    def one(args):
        qi, i = args
        s = dot("qhd,thd->hqt", qi, k) * scale
        rows = i * block + jnp.arange(block)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        return dot("hqt,thd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, (q.reshape(T // block, block, H, -1),
                          jnp.arange(T // block)))
    return dot("tq,qd->td", o.reshape(T, H * dims.v), p["wo"])


def swiglu(f, x, dot):
    g = dot("td,df->tf", x, f["w_gate"])
    u = dot("td,df->tf", x, f["w_up"])
    return dot("tf,fd->td", jax.nn.silu(g) * u, f["w_down"])


def moe(f, x, dims: Dims, dot):
    """Routed experts (every expert computed, the routed ones kept) plus
    the shared experts, for a chunk of tokens x (t, d)."""
    logits = dot("td,de->te", x, f["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * dims.routed_scale
    gate = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(w)

    def expert(y, e):
        we = {k: f[k][e].astype(jnp.float32)
              for k in ("we_gate", "we_up", "we_down")}
        out = swiglu({"w_gate": we["we_gate"], "w_up": we["we_up"],
                      "w_down": we["we_down"]}, x, dot)
        return y + gate[:, e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(dims.experts))
    if "shared" in f:
        y = y + swiglu(jax.tree.map(lambda a: a.astype(jnp.float32), f["shared"]),
                       x, dot)
    return y


def decoder_layer(p, x, pos, dims: Dims, dot, attn_block, chunk):
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    h = rmsnorm(x, p["ln1"]["scale"].astype(jnp.float32), dims.eps)
    x = x + attention(f32(p["mixer"]), h, pos, dims, dot, attn_block)
    f = p["mlp"]

    def mlp(xc):
        hc = rmsnorm(xc, p["ln2"]["scale"].astype(jnp.float32), dims.eps)
        if "router" in f:
            return moe(f, hc, dims, dot)
        return swiglu(f32(f), hc, dot)

    T = x.shape[0]
    n = max(1, T // chunk)
    y = jax.lax.map(mlp, x.reshape(n, T // n, -1)).reshape(T, -1)
    return x + y


def hidden(params, tokens, dims: Dims, dot, attn_block=256, chunk=512):
    """Final normalised hidden states of one row: tokens (T,) -> (T, d)."""
    x = params["embed"]["tokens"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    for p in layers_of(params):
        x = decoder_layer(p, x, pos, dims, dot, attn_block, chunk)
    return rmsnorm(x, params["ln_f"]["scale"].astype(jnp.float32), dims.eps)


def logits(params, h, dims: Dims, dot):
    w = params["embed"]["unembed"][:, : dims.vocab].astype(jnp.float32)
    return dot("td,dv->tv", h, w)


@functools.lru_cache(maxsize=None)
def _served_fn(dims: Dims, quant: Optional[str], chunk: int):
    dot = make_dot(quant)

    @jax.jit
    def run(params, ids, want):
        h = hidden(params, ids, dims, dot)

        def part(args):
            hc, wc = args
            z = logits(params, hc, dims, dot)
            return (jnp.max(z, -1) - jnp.take_along_axis(z, wc[:, None], -1)[:, 0],
                    jnp.argmax(z, -1).astype(jnp.int32), jnp.sum(z * z, -1))

        T = h.shape[0]
        out = jax.lax.map(part, (h.reshape(T // chunk, chunk, -1),
                                 want.reshape(T // chunk, chunk)))
        return tuple(o.reshape(T) for o in out)

    return run


def position_logits(params, seq: np.ndarray, pad_to: int, dims: Dims, *,
                    quant: Optional[str] = None, chunk: int = 512,
                    want: Optional[np.ndarray] = None):
    """At every position t of the token sequence ``seq`` (padded to
    ``pad_to``; causality keeps the padding out), the reference's logits
    for token t + 1, reduced to: best logit minus the logit of ``want[t]``
    (default: the next token of ``seq``), the argmax, and the sum of
    squared logits. One compile per ``pad_to``. Returns numpy arrays cut
    to ``len(seq) - 1`` positions."""
    n = len(seq) - 1
    ids = np.zeros(pad_to, np.int32)
    ids[: len(seq)] = seq
    tgt = np.zeros(pad_to, np.int32)
    tgt[:n] = seq[1:] if want is None else want
    run = _served_fn(dims, quant, chunk)
    gap, top, ss = run(params, jnp.asarray(ids), jnp.asarray(tgt))
    return np.asarray(gap)[:n], np.asarray(top)[:n], np.asarray(ss)[:n]
