"""Mixture-of-Experts layer (granite 32e/top-8, mixtral 8e/top-2,
deepseek-v2-lite 64e/top-6 + 2 shared).

Routing follows the config (``MoEConfig.norm_topk``): Mixtral's softmax
over the top-k logits, or DeepSeek-V2's softmax over every expert in f32,
a greedy top-k and no renormalisation, scaled by ``routed_scale``. Shared
experts (``num_shared``) are one dense SwiGLU every token passes through.

Serving (prefill and decode) runs :func:`apply_moe_dropless`: every routed
token copy is computed, sorted by expert, through one grouped matmul
(kernels/moe_gmm.py), so a token's output never depends on the rest of the
batch. Training (:func:`apply_moe`) keeps the capacity paths below:

* ``_moe_local`` -- single-device path (CPU tests, no mesh context):
  TPU-idiomatic sort-based capacity dispatch, all static shapes.

* ``_moe_shard_map`` -- the production expert-parallel path. Activations are
  sharded over `data` and *replicated* over `model`; expert weights are
  sharded over `model` (by expert for granite-32e, by FFN dim for
  mixtral-8e whose expert count doesn't divide the axis). Each chip
  therefore: routes its local tokens (replicated compute, negligible),
  gathers the tokens assigned to *its* experts (local gather -- the
  dispatch "all-to-all" degenerates because tokens are already present),
  runs its expert FFN slice, scatter-adds its partial outputs locally, and
  contributes them to one bf16 ``psum`` over `model` -- the only collective
  in the layer, the same activation-sized all-reduce Megatron TP pays.
  This replaced a naive pjit scatter that XLA replicated (241 GB/device of
  all-reduce in the dry run -- see EXPERIMENTS.md Section Perf).

Capacity: per data-shard, C = ceil(T_local * k / E * capacity_factor);
overflow tokens are dropped (standard GShard-style token dropping).
Aux loss: switch load-balancing loss, computed on the pjit level.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import sharding as shd
from repro.models.layers import _normal, apply_mlp, init_mlp


def init_moe(key, cfg, dtype) -> dict:
    m = cfg.moe
    d, de, E = cfg.d_model, m.d_expert, m.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _normal(ks[0], (d, E), 1.0 / math.sqrt(d), jnp.float32),
        "we_gate": _normal(ks[1], (E, d, de), 1.0 / math.sqrt(d), dtype),
        "we_up": _normal(ks[2], (E, d, de), 1.0 / math.sqrt(d), dtype),
        "we_down": _normal(ks[3], (E, de, d), 1.0 / math.sqrt(de), dtype),
    }
    if m.num_shared:
        p["shared"] = init_mlp(ks[4], cfg, m.num_shared * de, dtype)
    return p


def _route(router, xf):
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
    return logits


def route(m, logits):
    """(weights (T, k) f32, experts (T, k) int32) of the router ``logits``
    (T, E) under the config's scoring (module docstring)."""
    if m.norm_topk:
        top_logit, top_e = jax.lax.top_k(logits, m.top_k)
        return jax.nn.softmax(top_logit, axis=-1), top_e
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, top_e = jax.lax.top_k(probs, m.top_k)
    return w * m.routed_scale, top_e


def _shared(p, x):
    """The shared experts' output (zero-free: absent without them)."""
    return apply_mlp(p["shared"], x, "swiglu") if "shared" in p else None


def _capacity(m, T: int) -> int:
    cap = int(math.ceil(T * m.top_k / m.num_experts * m.capacity_factor))
    return max(4, -(-cap // 4) * 4)


def _dispatch_indices(flat_e, E_total: int, e_lo: int, E_local: int, cap: int, k: int):
    """Sorted-dispatch bookkeeping for experts [e_lo, e_lo+E_local).

    Returns (token_of, dest, keep) over the sorted assignment slots, where
    dest indexes a (E_local * cap) group buffer (OOB == dropped/foreign).
    """
    n = flat_e.shape[0]
    local_e = flat_e - e_lo
    mine = (local_e >= 0) & (local_e < E_local)
    sort_key = jnp.where(mine, local_e, E_local)
    order = jnp.argsort(sort_key, stable=True)
    sorted_e = sort_key[order]
    counts = jnp.bincount(sort_key, length=E_local + 1)
    offsets = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(n, dtype=jnp.int32) - offsets[sorted_e]
    keep = (sorted_e < E_local) & (pos_in_e < cap)
    token_of = order // k
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, E_local * cap)
    return token_of, dest, keep, order


def _expert_ffn(x_groups, wg, wu, wd, act_dtype):
    g = jnp.einsum("ecd,edf->ecf", x_groups, wg)
    u = jnp.einsum("ecd,edf->ecf", x_groups, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(act_dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _moe_body(xf, router, wg, wu, wd, m, e_lo, cap, k):
    """Shared per-shard MoE computation. xf (T, d) local tokens; expert
    weights are this shard's slice. Returns local partial y (T, d)."""
    T, d = xf.shape
    E_local = wg.shape[0]
    logits = _route(router, xf)
    gates, top_e = route(m, logits)
    flat_e = top_e.reshape(-1).astype(jnp.int32)
    token_of, dest, keep, order = _dispatch_indices(
        flat_e, m.num_experts, e_lo, E_local, cap, k
    )
    # dispatch: int scatter to build the slot->token map, then GATHER tokens
    token_at = (
        jnp.zeros((E_local * cap,), jnp.int32).at[dest].set(token_of, mode="drop")
    )
    slot_used = (
        jnp.zeros((E_local * cap,), jnp.bool_).at[dest].set(keep, mode="drop")
    )
    x_groups = xf[token_at] * slot_used[:, None].astype(xf.dtype)
    y_groups = _expert_ffn(x_groups.reshape(E_local, cap, d), wg, wu, wd, xf.dtype)
    # combine: local scatter-add weighted by gates
    y_slots = y_groups.reshape(E_local * cap, d)[jnp.minimum(dest, E_local * cap - 1)]
    w = jnp.where(keep, gates.reshape(-1)[order], 0.0).astype(jnp.float32)
    y = (
        jnp.zeros((T, d), jnp.float32)
        .at[token_of]
        .add(y_slots.astype(jnp.float32) * w[:, None], mode="drop")
    )
    return y.astype(xf.dtype)


def _aux_loss(m, logits, top_e):
    probs = jax.nn.softmax(logits, axis=-1)
    E = m.num_experts
    f = jnp.mean(
        jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(axis=-2), axis=0
    ) / m.top_k
    p = jnp.mean(probs, axis=0)
    return m.router_aux_weight * E * jnp.sum(f * p)


def apply_moe(p: dict, cfg, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,d) -> (y, aux_loss). Picks the expert-parallel shard_map path
    when a mesh context is installed and the model axis is >1."""
    m = cfg.moe
    B, S, d = x.shape
    state = shd.current()
    use_shard_map = False
    if state is not None:
        mesh, rules = state
        model_ax = "model"
        if model_ax in mesh.shape and mesh.shape[model_ax] > 1:
            use_shard_map = True

    # aux loss on the pjit level (local elementwise; batch stays sharded)
    xf_flat = x.reshape(B * S, d)
    logits = _route(p["router"], xf_flat)
    _, top_e = jax.lax.top_k(logits, m.top_k)
    aux = _aux_loss(m, logits, top_e)

    shared = _shared(p, x)
    if not use_shard_map:
        T = B * S
        y = _moe_body(
            xf_flat, p["router"], p["we_gate"], p["we_up"], p["we_down"],
            m, 0, _capacity(m, T), m.top_k,
        ).reshape(B, S, d)
        return (y if shared is None else y + shared), aux

    mesh, rules = state
    P = jax.sharding.PartitionSpec
    batch_ax = rules.table.get("batch")
    experts_sharded = rules.table.get("p_experts") == "model"
    w_spec = P("model", None, None) if experts_sharded else P(None, None, "model")
    wd_spec = P("model", None, None) if experts_sharded else P(None, "model", None)
    x_spec = P(batch_ax, None, None)
    n_data = math.prod(
        mesh.shape[a] for a in (batch_ax if isinstance(batch_ax, tuple) else (batch_ax,))
        if a is not None
    ) if batch_ax else 1
    T_local = (B // max(n_data, 1)) * S
    cap = _capacity(m, T_local)
    n_model = mesh.shape["model"]
    E_local = m.num_experts // n_model if experts_sharded else m.num_experts

    def shard_body(x_blk, router, wg, wu, wd):
        Bl, Sl, _ = x_blk.shape
        xf = x_blk.reshape(Bl * Sl, d)
        e_lo = jax.lax.axis_index("model") * E_local if experts_sharded else 0
        y = _moe_body(xf, router, wg, wu, wd, m, e_lo, cap, m.top_k)
        # the only collective: combine partial expert outputs (bf16)
        y = jax.lax.psum(y.astype(jnp.bfloat16), "model")
        return y.reshape(Bl, Sl, d).astype(x_blk.dtype)

    # shd.shard_map: replication checks off -- the in-body psum is
    # invisible to the checker.
    y = shd.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, wd_spec),
        out_specs=x_spec,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])
    return (y if shared is None else y + shared), aux


# Per-layer routing counters of one dropless call, as one int32 row:
# [tokens routed, experts with at least one token, most tokens on one expert]
STATS = ("tokens", "experts_touched", "expert_load_max")

# Routed token copies one grouped-matmul dispatch holds at most: a longer
# prefill routes its tokens in chunks, so the sorted copies and the
# experts' activations of a 28k-token prompt (172k copies at top-6) take a
# fifth of the memory; each chunk reads the touched experts' weights again.
DISPATCH_ROWS = 1 << 15


def _sort_by_expert(key, classes: int, block: int):
    """Stable counting sort of ``key`` (n,) int32 in ``[0, classes)``:
    -> (order (n,): the index at each sorted position, pos (n,): the sorted
    position of each index, sizes (classes,) int32). Counts by a
    triangular matmul within blocks of ``block`` rows and a cumulative sum
    across them (exact: 0/1 operands, f32 sums), where XLA's stable sort
    takes ~18 s to compile for the TPU at prefill sizes."""
    n = key.shape[0]
    hot = (key[:, None] == jnp.arange(classes, dtype=jnp.int32)).astype(jnp.float32)
    tri = jnp.tril(jnp.ones((block, block), jnp.float32))
    within = jnp.einsum("ij,bjc->bic", tri, hot.reshape(n // block, block, classes))
    last = within[:, -1]
    seen = (within + (jnp.cumsum(last, 0) - last)[:, None]).reshape(n, classes)
    sizes = seen[-1]
    start = jnp.cumsum(sizes) - sizes
    pos = jnp.sum((seen - 1 + start) * hot, axis=1).astype(jnp.int32)
    order = jnp.zeros((n,), jnp.int32).at[pos].set(jnp.arange(n, dtype=jnp.int32))
    return order, pos, sizes.astype(jnp.int32)


def _dispatch(p, m, xf, ok, interpret):
    """Routed experts of tokens ``xf`` (T, d) where ``ok`` (T,): ->
    (y (T, d) f32, tokens per expert (E,) int32)."""
    from repro.kernels.moe_gmm import gmm, tiling

    T, d = xf.shape
    E, k = m.num_experts, m.top_k
    w, top_e = route(m, _route(p["router"], xf))
    n = T * k
    tm = tiling(n, d, m.d_expert)[0]
    rows = -(-n // tm) * tm
    # copies of tokens outside ``ok``, and the padding to whole row tiles,
    # take class E: sorted past every expert's rows, never computed
    key = jnp.where(ok[:, None], top_e, E).reshape(-1).astype(jnp.int32)
    order, pos, sizes = _sort_by_expert(jnp.pad(key, (0, rows - n),
                                                constant_values=E),
                                        E + 1, min(256, tm))
    sizes = sizes[:E]
    token_of = jnp.where(order < n, order // k, 0)
    used = jnp.arange(rows) < jnp.sum(sizes)
    xs = xf[token_of]
    g = gmm(xs, p["we_gate"], sizes, interpret=interpret)
    u = gmm(xs, p["we_up"], sizes, interpret=interpret)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(xf.dtype)
    ys = gmm(h, p["we_down"], sizes, interpret=interpret)
    # back to token order (rows past the groups were never written: zero
    # them first), then each token's k copies weighted and summed in f32
    ys = jnp.where(used[:, None], ys, 0)
    yk = ys[pos[:n]].reshape(T, k, d).astype(jnp.float32)
    return jnp.sum(yk * w[..., None], axis=1), sizes


def apply_moe_dropless(p: dict, cfg, x: jnp.ndarray,
                       valid: Optional[jnp.ndarray] = None,
                       interpret: Optional[bool] = None):
    """x (B, S, d) -> (y (B, S, d), stats (3,) int32, see :data:`STATS`).

    Every routed copy of every ``valid`` token (default: all) is computed:
    the copies are sorted by expert, run through the grouped matmul
    (``kernels.moe_gmm.gmm``: gate, up, then down), weighted and summed
    back per token in f32 -- in chunks of tokens when there are more than
    :data:`DISPATCH_ROWS` copies. Tokens outside ``valid`` (prefill
    padding, empty decode slots) go to no expert and cost no expert
    compute; their output is the shared experts' alone. No capacity, so a
    token's output does not depend on the batch it is in."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    ok = jnp.ones((T,), bool) if valid is None else valid.reshape(T)
    chunks = -(-T * m.top_k // DISPATCH_ROWS)
    if chunks == 1:
        y, sizes = _dispatch(p, m, xf, ok, interpret)
    else:
        c = -(-T // chunks)
        pad = chunks * c - T
        xc = jnp.pad(xf, ((0, pad), (0, 0))).reshape(chunks, c, d)
        okc = jnp.pad(ok, (0, pad)).reshape(chunks, c)
        y, sizes = jax.lax.map(lambda a: _dispatch(p, m, a[0], a[1], interpret),
                               (xc, okc))
        y, sizes = y.reshape(chunks * c, d)[:T], sizes.sum(0)
    y = y.reshape(B, S, d).astype(x.dtype)
    shared = _shared(p, x)
    if shared is not None:
        y = y + shared
    stats = jnp.stack([ok.sum(), (sizes > 0).sum(), sizes.max()]).astype(jnp.int32)
    return y, stats
