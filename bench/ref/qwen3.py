"""Plain float32 reference of the Qwen3 decoder (dense GQA with qk-norm), its
loss, gradients and AdamW, written from the published description and
imported from nothing in the program.

Layer, as Qwen3 publishes it (Qwen3ForCausalLM):

  h   = x + Wo . attn(rope(qnorm(x' Wq)), rope(knorm(x' Wk)), x' Wv),
        x' = rmsnorm(x) * ln1
  out = h + Wdown (silu(h' Wgate) * h' Wup),   h' = rmsnorm(h) * ln2

with causal softmax attention scaled by 1/sqrt(head_dim), query head j
reading key/value head j // (heads / kv_heads), half-split ("NeoX") rotary
embedding of base ``rope_theta``, and RMSNorm ``x / sqrt(mean(x^2) + eps)``.
Logits are ``rmsnorm(x_L) * ln_f . Wunembed`` over the configuration's
vocabulary; the loss is their mean token cross-entropy.

Everything is float32 under ``precision=HIGHEST``. Attention runs in query
blocks and the MLP and logits in token chunks, each rematerialised in the
backward pass, so a 16k-token row fits beside the optimizer state.

``quant`` rounds every matmul operand (and the cotangents of the backward
matmuls) to a lower precision: the control that the comparison must fail.

Weights come in the tree layout the benchmark made them in:
``embed/{tokens,unembed}``, ``ln_f/scale`` and ``groups/slot_0/{ln1,ln2}/
scale``, ``.../mixer/{wq,wk,wv,wo,q_norm,k_norm}``,
``.../mlp/{w_gate,w_up,w_down}``, stacked over layers on a leading axis.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int
    eps: float
    theta: float

    @staticmethod
    def from_config(c: Dict[str, Any]) -> "Dims":
        return Dims(c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"],
                    c["intermediate_size"], c["vocab_size"],
                    c["num_hidden_layers"], float(c["rms_norm_eps"]),
                    float(c["rope_theta"]))


# ------------------------------------------------------------ precision


def round_fp8(x):
    """Round to float8 e4m3 (4 significant bits, largest 448, subnormal
    step 2^-9) after scaling the tensor so its largest magnitude is 448: a
    per-tensor-scaled fp8 operand, returned in float32."""
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0)
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
    :data:`QUANT`), operands and the backward's cotangent are rounded first."""
    quant = QUANT[quant]
    plain = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if quant is None:
        return plain

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def dot(spec, a, b):
        return plain(spec, quant(a), quant(b))

    def fwd(spec, a, b):
        qa, qb = quant(a), quant(b)
        return plain(spec, qa, qb), (qa, qb)

    def bwd(spec, res, g):
        qa, qb = res
        _, vjp = jax.vjp(lambda x, y: plain(spec, x, y), qa, qb)
        return vjp(quant(g))

    dot.defvjp(fwd, bwd)
    return dot


# ----------------------------------------------------------------- model


def rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """Half-split rotary embedding; x (T, H, D), pos (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layers_of(params) -> List[Dict[str, Any]]:
    """Per-layer weight dicts, unstacked from the leading layer axis."""
    out = []
    groups = params["groups"]
    if isinstance(groups, dict):
        n = jax.tree.leaves(groups)[0].shape[0]
        for i in range(n):
            g = jax.tree.map(lambda a, i=i: a[i], groups)
            out.extend(g[k] for k in sorted(g))
    else:
        for g in groups:
            out.extend(g[k] for k in sorted(g))
    return out


def causal_attention(q, k, v, dot, block):
    """q (T, H, D), k/v (T, Hk, D) -> (T, H, D), query head j reading
    key/value head j // G, in query blocks of ``block`` rows, each
    recomputed in the backward pass."""
    T, H, D = q.shape
    Hk = k.shape[1]
    G = H // Hk
    block = min(block, T)
    nb = T // block
    qb = q.reshape(nb, block, Hk, G, D)
    cols = jnp.arange(T)

    @jax.checkpoint
    def one(args):
        qi, i = args
        s = dot("qkgd,tkd->kgqt", qi, k) / math.sqrt(D)
        rows = i * block + jnp.arange(block)
        s = jnp.where(cols[None, None, None, :] <= rows[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return dot("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(one, (qb, jnp.arange(nb)))
    return o.reshape(T, H, D)


def chunked(fn, x, chunk):
    """Apply ``fn`` to ``x`` (T, ...) in token chunks, rematerialised."""
    T = x.shape[0]
    n = max(1, T // chunk)
    y = jax.lax.map(jax.checkpoint(fn), x.reshape(n, T // n, *x.shape[1:]))
    return y.reshape(T, *y.shape[2:])


def decoder_layer(p, x, pos, dims: Dims, dot, attn_block, chunk):
    m, f = p["mixer"], p["mlp"]
    T = x.shape[0]
    h = rmsnorm(x, p["ln1"]["scale"], dims.eps)
    q = dot("td,dq->tq", h, m["wq"]).reshape(T, dims.heads, dims.head_dim)
    k = dot("td,dq->tq", h, m["wk"]).reshape(T, dims.kv_heads, dims.head_dim)
    v = dot("td,dq->tq", h, m["wv"]).reshape(T, dims.kv_heads, dims.head_dim)
    q = rope(rmsnorm(q, m["q_norm"], dims.eps), pos, dims.theta)
    k = rope(rmsnorm(k, m["k_norm"], dims.eps), pos, dims.theta)
    o = causal_attention(q, k, v, dot, attn_block).reshape(T, -1)
    x = x + dot("tq,qd->td", o, m["wo"])

    def mlp(xc):
        hc = rmsnorm(xc, p["ln2"]["scale"], dims.eps)
        g = dot("td,df->tf", hc, f["w_gate"])
        u = dot("td,df->tf", hc, f["w_up"])
        return dot("tf,fd->td", jax.nn.silu(g) * u, f["w_down"])

    return x + chunked(mlp, x, chunk)


def hidden(params, tokens, dims: Dims, dot, attn_block=128, chunk=2048):
    """Final normalised hidden states of one row: tokens (T,) -> (T, d)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = params["embed"]["tokens"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])
    for p in layers_of(params):
        x = jax.checkpoint(lambda p, x: decoder_layer(
            f32(p), x, pos, dims, dot, attn_block, chunk))(p, x)
    return rmsnorm(x, params["ln_f"]["scale"].astype(jnp.float32), dims.eps)


def logits(params, h, dims: Dims, dot):
    w = params["embed"]["unembed"][:, : dims.vocab].astype(jnp.float32)
    return dot("td,dv->tv", h, w)


# ---------------------------------------------------------------- training


def row_nll_sum(params, tokens, targets, dims: Dims, dot, chunk=1024):
    """Summed next-token cross-entropy of one row."""
    h = hidden(params, tokens, dims, dot)

    def part(args):
        hc, tc = args
        z = logits(params, hc, dims, dot)
        return jnp.sum(jax.nn.logsumexp(z, -1)
                       - jnp.take_along_axis(z, tc[:, None], -1)[:, 0])

    n = max(1, h.shape[0] // chunk)
    parts = jax.lax.map(jax.checkpoint(part),
                        (h.reshape(n, -1, h.shape[-1]), targets.reshape(n, -1)))
    return jnp.sum(parts)


@dataclasses.dataclass(frozen=True)
class Adam:
    """The job's AdamW: linear warm-up then cosine decay of the rate,
    clipping by the global gradient norm, decoupled weight decay on the
    matrices (not on the norm gains), bias-corrected moments."""

    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float
    warmup_steps: int
    total_steps: int
    min_lr_ratio: float

    def rate(self, step: int) -> float:
        warm = min(1.0, (step + 1) / max(self.warmup_steps, 1))
        t = min(max((step - self.warmup_steps)
                    / max(self.total_steps - self.warmup_steps, 1), 0.0), 1.0)
        cos = 0.5 * (1.0 + math.cos(math.pi * t))
        return self.lr * warm * (self.min_lr_ratio + (1 - self.min_lr_ratio) * cos)


GAINS = ("scale", "q_norm", "k_norm")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def _adam_update(opt: Adam, step_lr, params, mu, nu, grads, clip, t):
    b1, b2 = opt.b1, opt.b2
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    def one(path, p, m, v, g):
        g = g * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + opt.eps)
        if _leaf_name(path) not in GAINS:
            upd = upd + opt.weight_decay * p
        return p - step_lr * upd, m, v

    out = jax.tree_util.tree_map_with_path(one, params, mu, nu, grads)
    is_triple = lambda o: isinstance(o, tuple)
    return tuple(jax.tree.map(lambda o, i=i: o[i], out, is_leaf=is_triple)
                 for i in range(3))


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of each leaf, keyed by its path."""
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return dict(zip(paths, (float(n) for n in _norms(tree))))


@functools.lru_cache(maxsize=None)
def _row_grad(dims: Dims, quant: Optional[str]):
    dot = make_dot(quant)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def row_grad(params, acc, tok, tgt, scale):
        """acc + the gradient of this row's summed loss times ``scale``."""
        nll, g = jax.value_and_grad(
            lambda p: row_nll_sum(p, tok, tgt, dims, dot) * scale)(params)
        return nll / scale, jax.tree.map(jnp.add, acc, g)

    return row_grad


def train_readings(weights: Callable[[], Any],
                   batches: List[Tuple[np.ndarray, np.ndarray]],
                   dims: Dims, opt: Adam, *, quant: Optional[str] = None,
                   steps: int = 3, rows: Optional[int] = None) -> Dict[str, Any]:
    """Follow ``steps`` AdamW steps from the weights ``weights()`` makes, on
    ``batches``. The weights are made again at the end to measure the
    change, so no copy of them is held meanwhile.

    Returns each step's loss, each leaf's norm of the first step's
    gradient (before clipping) and of the parameters' change after
    ``steps``. ``rows`` keeps only the first rows of every batch (the
    half-batch fault). Rows are processed one at a time, their gradients
    summed, so one row's activations are live at a time.
    """
    row_grad = _row_grad(dims, quant)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), weights())
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, grad0 = [], None
    for s in range(steps):
        inputs, targets = batches[s]
        if rows is not None:
            inputs, targets = inputs[:rows], targets[:rows]
        grads = jax.tree.map(jnp.zeros_like, params)
        total, n_tok = 0.0, inputs.size
        for r in range(inputs.shape[0]):
            nll, grads = row_grad(params, grads, jnp.asarray(inputs[r]),
                                  jnp.asarray(targets[r]),
                                  jnp.float32(1.0 / n_tok))
            total += float(nll)
        losses.append(total / n_tok)
        norms = leaf_norms(grads)
        if s == 0:
            grad0 = norms
        gnorm = math.sqrt(sum(v * v for v in norms.values()))
        clip = min(1.0, opt.grad_clip / (gnorm + 1e-9))
        params, mu, nu = _adam_update(opt, opt.rate(s), params, mu, nu, grads,
                                      jnp.float32(clip), jnp.float32(s + 1))
    del mu, nu
    change = leaf_norms(jax.tree.map(
        lambda p, p0: p - p0.astype(jnp.float32), params, weights()))
    return {"losses": losses, "grad0": grad0, "change": change}


# ----------------------------------------------------------------- serving


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
