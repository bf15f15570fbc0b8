"""Logical-axis sharding: rules, contexts, and constraint helpers.

Model code never names mesh axes. It annotates activations/params with
*logical* axes ('batch', 'seq', 'heads', 'embed', 'ff', 'vocab', 'experts',
'kv_seq', 'inner', ...); a ``ShardingRules`` table maps logical axes to mesh
axes. ``use_rules(mesh, rules)`` installs a context; outside a context every
constraint is a no-op, so models run unmodified on CPU tests.

Three attention strategies (DESIGN.md Section 3):
  'heads'    : 'heads' -> 'model'; 'seq' unsharded.
  'sequence' : context parallelism -- 'seq' -> 'model' (FA2's C2 lifted to
               the mesh); 'heads' unsharded; KV all-gathered per layer.
  'ring'     : same activation sharding as 'sequence', but KV *stays*
               sharded and rotates around the 'model' axis
               (distributed/ring_attention.py) -- per-device KV memory is
               O(S / P) instead of O(S).
FSDP: parameter 'embed'/'ff' input dims additionally sharded over 'data'
(all-gathered per scan step by XLA SPMD).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


class ShardingRules:
    """logical axis name -> mesh axis (str | tuple | None).

    ``attn_sharding`` records which attention strategy built the table so
    runtime dispatch (context_parallel.attn_context_mode) can tell the
    all-gather and ring context-parallel modes apart — they share the same
    activation sharding.
    """

    def __init__(self, table: Dict[str, object], attn_sharding: str = "heads"):
        self.table = dict(table)
        self.attn_sharding = attn_sharding

    def spec(self, *names: Optional[str]) -> P:
        return P(*[self.table.get(n) if n else None for n in names])


def lm_rules(
    cfg=None,
    *,
    attn_sharding: str = "heads",
    fsdp: bool = True,
    pods: bool = False,
    model_axis: int = 16,
    data_axis: int = 16,
    decode: bool = False,
    batch_size: int = 0,
) -> ShardingRules:
    """Build the rule table for one arch on the (pod?, data, model) mesh.

    The mesh is 2D (``data`` x ``model``): the ring / sequence context
    parallelism runs over ``model`` *inside each* data-parallel group, and
    the table carries both axes (batch over ``data``, seq over ``model``)
    so the trainer composes them freely (train.py --data-axis
    --model-axis). Divisibility-aware: kv heads / experts that don't
    divide the model axis fall back to replication (kv) or per-expert-FFN
    sharding (MoE); archs whose q heads don't divide use
    attn_sharding='sequence' (context parallelism) or 'ring' (the same
    activation layout with rotating KV shards). batch=1 decode (long_500k)
    leaves `data` to the KV-seq split instead of the batch.
    """
    if cfg is not None:
        attn_sharding = cfg.attn_sharding
        kv_ok = cfg.num_kv_heads % model_axis == 0
        heads_ok = cfg.num_heads % model_axis == 0
        experts_ok = bool(cfg.moe) and cfg.moe.num_experts % model_axis == 0
        has_ssm = cfg.ssm is not None
        # FSDP over data*model on the embed dim needs d_model divisible by
        # the full product (gemma3: 1152 % 256 != 0 -> fall back to data).
        embed_2d_ok = cfg.d_model % (model_axis * data_axis) == 0
    else:
        kv_ok = heads_ok = True
        experts_ok = True
        has_ssm = False
        embed_2d_ok = True
    if attn_sharding not in ("heads", "sequence", "ring"):
        raise ValueError(f"unknown attn_sharding: {attn_sharding!r}")
    seqsh = attn_sharding in ("sequence", "ring")
    heads_ax = None if seqsh or not heads_ok else "model"
    kv_ax = None if seqsh or not kv_ok else "model"
    batch = (("pod", "data") if pods else ("data",))
    batch_ok = batch_size == 0 or batch_size % (
        2 * data_axis if pods else data_axis
    ) == 0
    if not batch_ok:  # batch=1 long-context decode
        batch = ("pod",) if pods and batch_size % 2 == 0 else None
    # decode caches are always sequence-split (split-KV / context-parallel
    # decode -- C2); with an unshardable batch we split over data too.
    cache_ax = ("data", "model") if not batch_ok else "model"
    t = {
        # activations
        "batch": batch,
        "seq": "model" if seqsh else None,
        "kv_seq": "model" if seqsh else None,
        "heads": heads_ax,
        "kv_heads": kv_ax,
        "embed": None,
        "ff_act": None if seqsh else "model",
        "vocab": "model",
        "experts": "model" if experts_ok else None,
        "moe_ff": None if experts_ok else "model",
        "inner": "model",
        "ssm_seq": None,
        "cache_seq": cache_ax if decode else ("model" if seqsh else None),
        # params
        "p_embed": (
            ("data", "model") if (fsdp and seqsh and not has_ssm and embed_2d_ok)
            else ("data" if fsdp else None)
        ),
        "p_embed_tbl": "data" if fsdp else None,
        "p_ff": None if seqsh else "model",
        "p_heads": heads_ax,
        "p_kv_heads": kv_ax,
        "p_vocab": "model",
        "p_experts": "model" if experts_ok else None,
        "p_moe_ff": None if experts_ok else "model",
        "p_inner": "model",
        "layers": None,
    }
    return ShardingRules(t, attn_sharding=attn_sharding)


# --- trace-cache staleness guard -------------------------------------------
#
# attn_context_mode() is read at TRACE time, but jax's jit cache keys on
# function identity + avals, not on this thread-local context: jitting the
# *same* closure under a different rule context would silently replay the
# first context's trace (wrong collectives, or none). The guard records
# which effective mode each trace consulted and flushes jax's caches at
# every use_rules boundary where the effective mode changes, forcing a
# retrace under the new rules. Process-wide (jax caches are process-wide).

_traced_modes: set = set()


def _mode_of(state) -> Optional[str]:
    """Effective context-parallel mode of a (mesh, rules) state (or None).

    Mirrors context_parallel.attn_context_mode, which cannot be imported
    here (it imports this module)."""
    if state is None:
        return None
    mesh, rules = state
    mode = getattr(rules, "attn_sharding", "heads")
    if mode == "ring":
        return "ring" if mesh.shape.get("model", 1) > 1 else None
    if mode == "sequence":
        return "gather"
    return None


def record_traced_mode(mode: Optional[str]) -> None:
    """Note that attn_context_mode was consulted while tracing (mode baked
    into some cached trace). Called by context_parallel, not user code."""
    _traced_modes.add(mode)


def _flush_stale_traces(state) -> None:
    mode = _mode_of(state)
    if _traced_modes and any(m != mode for m in _traced_modes):
        jax.clear_caches()
        _traced_modes.clear()
        from repro.obs.metrics import default_registry

        default_registry().counter("sharding/trace_cache_flushes").inc()


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: ShardingRules):
    prev = getattr(_ctx, "state", None)
    state = (mesh, rules)
    _flush_stale_traces(state)
    _ctx.state = state
    try:
        yield
    finally:
        _ctx.state = prev
        _flush_stale_traces(prev)


def current() -> Optional[Tuple[Mesh, ShardingRules]]:
    return getattr(_ctx, "state", None)


def constrain(x, *names: Optional[str]):
    """with_sharding_constraint on logical axes; no-op outside a context."""
    state = current()
    if state is None:
        return x
    mesh, rules = state
    spec = rules.spec(*names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(*names: Optional[str]) -> Optional[NamedSharding]:
    state = current()
    if state is None:
        return None
    mesh, rules = state
    return NamedSharding(mesh, rules.spec(*names))


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication checks off.

    The manual-collective bodies here (MoE expert parallelism, ring
    attention) always want the checker off — ppermute/psum patterns it
    cannot verify.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
