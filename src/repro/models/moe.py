"""Mixture-of-Experts layer (granite 32e/top-8, mixtral 8e/top-2).

Two execution paths, same math:

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
from repro.models.layers import _normal


def init_moe(key, cfg, dtype) -> dict:
    m = cfg.moe
    d, de, E = cfg.d_model, m.d_expert, m.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _normal(ks[0], (d, E), 1.0 / math.sqrt(d), jnp.float32),
        "we_gate": _normal(ks[1], (E, d, de), 1.0 / math.sqrt(d), dtype),
        "we_up": _normal(ks[2], (E, d, de), 1.0 / math.sqrt(d), dtype),
        "we_down": _normal(ks[3], (E, de, d), 1.0 / math.sqrt(de), dtype),
    }


def _route(router, xf):
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
    return logits


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
    top_logit, top_e = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top_logit, axis=-1)
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

    if not use_shard_map:
        T = B * S
        y = _moe_body(
            xf_flat, p["router"], p["we_gate"], p["we_up"], p["we_down"],
            m, 0, _capacity(m, T), m.top_k,
        )
        return y.reshape(B, S, d), aux

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
    return y, aux
