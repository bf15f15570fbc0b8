"""Context-parallel attention: FA2's sequence-dimension parallelism (C2)
lifted from thread blocks to the device mesh.

Two strategies share the same activation sharding ('seq' -> 'model'; see
DESIGN.md Section 3):

  'sequence' (all-gather): K/V are all-gathered over the model axis ONCE
  per layer and the flash scan runs each chip's Q rows against the full KV.
  Under GQA the gathered KV is small (kv_heads * head_dim << q rows), which
  is what makes this profitable for archs whose head counts cannot shard
  16-way (whisper 8H, gemma3 4H, hymba 25H, deepseek 56H). The gather is
  expressed as a sharding *constraint* (kv seq axis -> None), so XLA SPMD
  inserts exactly one all-gather per layer and keeps everything else
  sharded. The flash implementation must then never dynamic-index a
  seq-sharded axis: dense mode keeps Q whole in the forward, and the dense
  backward (core.flash._bwd_dense_unblocked) scans KV blocks with dQ
  carried whole -- measured in EXPERIMENTS.md Section Perf (deepseek
  train_4k), the blocked alternative forced a 470 MB fp32 all-gather of
  q_blocks per tile step. Per-device KV memory is O(S): fine at training
  lengths, the hard cap for long context.

  'ring' (distributed/ring_attention.py): K/V *stay* sharded and rotate
  around the model axis; per-device KV memory is O(S / P) and the rotation
  overlaps compute. This is the long-context mode. KV must NOT be gathered
  -- :func:`gather_kv` is a no-op under ring rules, and
  ``core.attention.attention`` routes to the ring implementation.

:func:`attn_context_mode` is the single dispatch point both rely on.
"""

from __future__ import annotations

from typing import Optional

from repro.distributed import sharding as shd
from repro.distributed.sharding import constrain


def attn_context_mode() -> Optional[str]:
    """The active context-parallel strategy: 'ring' | 'gather' | None.

    'ring' requires ring rules AND a model axis actually > 1 (a 1-wide ring
    is just the local kernel); 'gather' is the all-gather 'sequence' mode.
    Outside any sharding context both constraints and routing are no-ops.

    The mode is read at TRACE time, and jax's tracing cache keys on
    function identity + avals, not on this thread-local context — so a
    closure traced under one mode would silently replay under another.
    That reuse is guarded: every read is recorded
    (sharding.record_traced_mode) -- eager reads too, since a read cannot
    tell whether it is being traced -- and ``use_rules`` flushes jax's
    caches whenever a *different* mode was recorded across a context
    boundary, forcing a retrace (counted as 'sharding/trace_cache_flushes').
    Distinct closures per mode (train()'s per-run step_fn) stay the cheap
    path — they never trigger a flush.
    """
    state = shd.current()
    if state is None:
        mode = None
    else:
        mesh, rules = state
        attn = getattr(rules, "attn_sharding", "heads")
        if attn == "ring":
            mode = "ring" if mesh.shape.get("model", 1) > 1 else None
        elif attn == "sequence":
            mode = "gather"
        else:
            mode = None
    shd.record_traced_mode(mode)
    return mode


def gather_kv(k, v, *, cross: bool = False):
    """Constrain K/V (B, S, Hkv, D) to be replicated along the sequence axis.

    Inside a sharding-rules context with 'kv_seq' -> 'model' this makes XLA
    insert one all-gather; outside any context it is a no-op. Under *ring*
    rules self-attention KV must NOT be gathered (the whole point of the
    ring is that KV stays sequence-sharded; ring_attention rotates it), but
    *cross*-attention (``cross=True``) keeps the deliberate one-gather-per-
    layer constraint even then -- the ring only handles Sq == Skv
    self-attention, and leaving encoder KV unconstrained would hand its
    collective placement to GSPMD guesswork.
    """
    if attn_context_mode() == "ring" and not cross:
        return k, v
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return k, v
