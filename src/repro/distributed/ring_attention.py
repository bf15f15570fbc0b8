"""Ring flash attention: context parallelism with KV sharded, not gathered.

The 'sequence' strategy (context_parallel.gather_kv) shards Q over the mesh
but replicates the full KV on every chip once per layer — per-device KV
memory is O(S), which caps context length. This module is the scalable
form (DISTFLASHATTN / Sequence Parallelism lineage, DESIGN.md Section 3):

  * Q *and* KV stay sharded over the 'model' axis. Each ring step, every
    device runs the existing flash kernel on its local Q shard against the
    KV shard currently visiting, then the shards rotate one hop
    (``jax.lax.ppermute``). After P steps every Q row has seen every key.
  * The per-step partial outputs carry the lane-major lse the kernels
    already emit; steps are folded with the associative finalized merge
    (``online_softmax.merge_partials``) — the same primitive as split-KV
    decode, one level up.
  * Causal masks get **zigzag** sharding (ring_schedule.make_layout) so all
    devices do equal work each step; fully-masked (device, step)
    rectangles are dropped from the static schedule before tracing — no
    kernel launch, no DMA. Sparse masks (window/sink) additionally get a
    *rebalanced* itinerary (ring_schedule.visit_order): heavy pairs are
    packed into the same steps and all-empty tail steps are truncated
    outright — fewer hops, fewer sync points. Inside a visible rectangle
    the PR-2 compact tile schedule (built from the rectangle's shifted
    MaskSpec) skips masked tiles.
  * **Double buffer, pinned**: step *t*'s kernels read buffer A while step
    *t+1*'s shard is already in flight into buffer B. Trace order alone
    does not make that true — the scheduler is free to sink the hop past
    the step's fusions (and the CPU backend does exactly that) — so
    ``_prefetch`` pins it with an ``optimization_barrier`` grouping both
    buffers: the step's compute consumes the barrier's A outputs and the
    barrier depends on the hop, forcing the collective to be issued before
    any of the step's compute. tests/test_ring.py asserts the resulting
    schedule in the compiled HLO, fwd and bwd, the same way it asserts
    no-all-gathers.
  * Backward is a second ring pass (custom_vjp): each rectangle's
    Algorithm-2 contribution is computed against the *globally merged*
    (o, lse) residuals (kernels/ops.flash_attention_pallas_shard_bwd,
    f32 out so bf16 inputs don't round-trip per rectangle); (dK, dV)
    accumulators travel with their KV shard — but on the far side of the
    compute (they depend on it), so the KV hop is prefetched into its own
    buffer exactly like the forward, and only the (dK, dV) hop trails the
    step. A final home hop returns each accumulator to its shard's owner.

Per-device geometry differs (device d owns chunks (d, 2P-1-d)), but a
shard_map body traces once — the per-device static schedules are dispatched
with ``lax.switch`` over ``axis_index``. Collectives stay OUTSIDE the
switch (all branches are pure compute), so every device reaches the same
``ppermute`` sequence. The O(P) traced branches bound this design to
single-pod ring sizes, the regime this repo targets.

``core.attention.attention`` routes here when the installed sharding rules
say ``attn_sharding='ring'``; ``ring_flash_attention`` is also directly
callable (tests, benchmarks, examples).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.masks import MaskSpec
from repro.core.online_softmax import merge_partials
from repro.distributed import ring_schedule as rs
from repro.distributed import sharding as shd


class _RingMeta(NamedTuple):
    """Static (hashable) call contract of the ring custom_vjp core."""

    spec: MaskSpec
    layout: rs.RingLayout
    mesh: Mesh
    axis: str                    # ring mesh axis ('model')
    batch_axes: object           # mesh axes of the batch dim (str|tuple|None)
    impl: str                    # 'flash_pallas' | 'flash_xla'
    block_q: Optional[int]       # None -> ops.default_block_sizes (Pallas)
    block_kv: Optional[int]
    scale: Optional[float]
    interpret: Optional[bool]
    schedule: Optional[str]      # None -> 'compact' per rect
    bwd: Optional[str]           # Pallas backward: 'fused' | 'split' | None
    num_q_bands: Optional[int]   # fwd occupancy partitioning of each
    kv_splits: Optional[int]     # rectangle kernel (None -> shape auto)


# ---------------------------------------------------------------------------
# Layout reorder (natural <-> zigzag chunk order)
# ---------------------------------------------------------------------------
#
# The zigzag layout is realized INSIDE the shard_map body with two
# half-shard ppermutes per tensor. Doing it outside as a global chunk
# permutation reads nicer, but GSPMD lowers that static gather along a
# sharded axis to a full-S all-gather per device — silently re-replicating
# exactly the O(S) arrays the ring exists to avoid (caught by inspecting
# the partitioned HLO; tests/test_ring.py now asserts the compiled ring
# program contains no all-gather at all). A production system would keep
# activations in zigzag order end-to-end and skip even these hops; here
# the boundary conversion keeps the public API order-agnostic.
#
# Geometry: device d's natural contiguous shard holds global chunks
# (2d, 2d+1); its zigzag shard holds (d, 2P-1-d). Every device owns exactly
# one even and one odd global chunk in either layout (d and 2P-1-d have
# opposite parity), so one ppermute routes all even chunks and a second all
# odd chunks — each a bijection. Only the receive/send slot of the even
# chunk depends on the device's own parity, handled by an elementwise
# select on ``axis_index % 2``.


def _zigzag_target(c: int, P: int) -> int:
    """Zigzag owner of global chunk c (slot 0 holds chunk d, slot 1 holds
    chunk 2P-1-d)."""
    return c if c < P else 2 * P - 1 - c


def _shard_to_zigzag(x, axis_name: str, layout: rs.RingLayout, seq_axis: int = 1):
    """Natural-order local shard -> zigzag-order local shard (collective)."""
    P = layout.num_devices
    if layout.chunks_per_device == 1 or P == 1:
        return x
    C = layout.chunk
    lo_nat, hi_nat = jnp.split(x, [C], axis=seq_axis)  # chunks 2d (even), 2d+1 (odd)
    perm_even = [(d, _zigzag_target(2 * d, P)) for d in range(P)]
    perm_odd = [(d, _zigzag_target(2 * d + 1, P)) for d in range(P)]
    recv_even = jax.lax.ppermute(lo_nat, axis_name, perm_even)
    recv_odd = jax.lax.ppermute(hi_nat, axis_name, perm_odd)
    # zigzag slot 0 holds chunk d: even iff the device index is even.
    d_even = jax.lax.axis_index(axis_name) % 2 == 0
    lo = jnp.where(d_even, recv_even, recv_odd)
    hi = jnp.where(d_even, recv_odd, recv_even)
    return jnp.concatenate([lo, hi], axis=seq_axis)


def _zigzag_to_shard(x, axis_name: str, layout: rs.RingLayout, seq_axis: int = 1):
    """Zigzag-order local shard -> natural-order local shard (inverse)."""
    P = layout.num_devices
    if layout.chunks_per_device == 1 or P == 1:
        return x
    C = layout.chunk
    lo, hi = jnp.split(x, [C], axis=seq_axis)  # chunks d, 2P-1-d
    d = jax.lax.axis_index(axis_name)
    d_even = d % 2 == 0
    send_even = jnp.where(d_even, lo, hi)  # the even chunk: d or 2P-1-d
    send_odd = jnp.where(d_even, hi, lo)
    # even chunk c goes home to device c // 2 (it is chunk 2(c//2) there);
    # the odd chunk likewise. Receivers get exactly chunks (2m, 2m+1).
    even_chunk = [d_ if d_ % 2 == 0 else 2 * P - 1 - d_ for d_ in range(P)]
    odd_chunk = [d_ if d_ % 2 == 1 else 2 * P - 1 - d_ for d_ in range(P)]
    perm_even = [(d_, even_chunk[d_] // 2) for d_ in range(P)]
    perm_odd = [(d_, odd_chunk[d_] // 2) for d_ in range(P)]
    lo_nat = jax.lax.ppermute(send_even, axis_name, perm_even)
    hi_nat = jax.lax.ppermute(send_odd, axis_name, perm_odd)
    return jnp.concatenate([lo_nat, hi_nat], axis=seq_axis)


def _to_layout(x: jnp.ndarray, layout: rs.RingLayout) -> jnp.ndarray:
    """(B, S, ...) natural order -> zigzag chunk order, as a *global* array
    op. Host-side reference semantics of the in-body conversion above
    (tests assert the two agree); not used on the sharded path."""
    if layout.chunks_per_device == 1:
        return x
    B, S = x.shape[:2]
    perm = layout.permutation()
    xc = x.reshape(B, layout.num_chunks, layout.chunk, *x.shape[2:])
    return xc[:, perm].reshape(B, S, *x.shape[2:])


def _from_layout(x: jnp.ndarray, layout: rs.RingLayout) -> jnp.ndarray:
    if layout.chunks_per_device == 1:
        return x
    import numpy as np

    B, S = x.shape[:2]
    inv = np.argsort(layout.permutation())
    xc = x.reshape(B, layout.num_chunks, layout.chunk, *x.shape[2:])
    return xc[:, inv].reshape(B, S, *x.shape[2:])


# ---------------------------------------------------------------------------
# Shard-local kernels (one rectangle = one kernel launch)
# ---------------------------------------------------------------------------


def _rect_fwd(q, k, v, spec: MaskSpec, meta: _RingMeta):
    """(o (B,Sq,H,D), lse (B,H,Sq)) for one (q_chunk, kv_chunk) rectangle."""
    if meta.impl == "flash_pallas":
        from repro.kernels.ops import flash_attention_pallas_with_lse

        return flash_attention_pallas_with_lse(
            q, k, v, spec, scale=meta.scale, block_q=meta.block_q,
            block_kv=meta.block_kv, interpret=meta.interpret,
            schedule=meta.schedule, num_q_bands=meta.num_q_bands,
            kv_splits=meta.kv_splits,
        )
    from repro.core.flash import flash_attention_with_lse

    return flash_attention_with_lse(
        q, k, v, spec, scale=meta.scale, block_q=meta.block_q or 512,
        block_kv=meta.block_kv or 512,
    )


def _rect_bwd(q, k, v, o, lse, do, spec: MaskSpec, meta: _RingMeta):
    """Algorithm-2 contribution of one rectangle, given the globally merged
    (o, lse) for its q chunk. Returns (dq, dk, dv)."""
    if meta.impl == "flash_pallas":
        from repro.kernels.ops import flash_attention_pallas_shard_bwd

        return flash_attention_pallas_shard_bwd(
            q, k, v, o, lse, do, spec, scale=meta.scale, block_q=meta.block_q,
            block_kv=meta.block_kv, interpret=meta.interpret,
            schedule=meta.schedule, bwd=meta.bwd,
            out_dtype=jnp.float32,
        )
    from repro.core.flash import FlashConfig, _bwd_impl

    cfg = FlashConfig(spec=spec, block_q=meta.block_q or 512,
                      block_kv=meta.block_kv or 512, scale=meta.scale)
    return _bwd_impl(q, k, v, o, lse, do, cfg)


# ---------------------------------------------------------------------------
# Per-(device, step) branches: static schedules under lax.switch
# ---------------------------------------------------------------------------


def _step_fwd_branch(meta: _RingMeta, d: int, t: int):
    """Forward compute for device ``d`` at ring step ``t`` (static
    geometry). Returns (o_partial (B,H,S_loc,D) f32, lse (B,H,S_loc) f32);
    q slots with no visible rectangle contribute lse = -inf."""
    C = meta.layout.chunk
    pairs = rs.step_pairs(meta.layout, meta.spec, d, t)

    def branch(q_loc, k_loc, v_loc):
        B, _, Hq, D = q_loc.shape
        slots = [
            (jnp.zeros((B, Hq, C, D), jnp.float32),
             jnp.full((B, Hq, C), -jnp.inf, jnp.float32))
            for _ in range(meta.layout.chunks_per_device)
        ]
        for p in pairs:
            q_a = q_loc[:, p.q_slot * C : (p.q_slot + 1) * C]
            k_b = k_loc[:, p.kv_slot * C : (p.kv_slot + 1) * C]
            v_b = v_loc[:, p.kv_slot * C : (p.kv_slot + 1) * C]
            o_p, lse_p = _rect_fwd(q_a, k_b, v_b, p.spec, meta)
            o_p = o_p.transpose(0, 2, 1, 3).astype(jnp.float32)  # (B,H,C,D)
            slots[p.q_slot] = merge_partials(*slots[p.q_slot], o_p, lse_p)
        o = jnp.concatenate([s[0] for s in slots], axis=2)
        lse = jnp.concatenate([s[1] for s in slots], axis=2)
        return o, lse

    return branch


def _step_bwd_branch(meta: _RingMeta, d: int, t: int):
    """Backward compute for device ``d`` at step ``t``. Returns per-step
    (dq (B,S_loc,H,D), dk (B,S_loc,Hk,D), dv) in f32 (zeros where this
    step's rectangles don't touch)."""
    C = meta.layout.chunk
    cpd = meta.layout.chunks_per_device
    pairs = rs.step_pairs(meta.layout, meta.spec, d, t)

    def branch(q_loc, k_loc, v_loc, o_loc, lse_loc, do_loc):
        B, _, Hq, D = q_loc.shape
        Hk = k_loc.shape[2]
        dq = [jnp.zeros((B, C, Hq, D), jnp.float32) for _ in range(cpd)]
        dk = [jnp.zeros((B, C, Hk, D), jnp.float32) for _ in range(cpd)]
        dv = [jnp.zeros((B, C, Hk, D), jnp.float32) for _ in range(cpd)]
        for p in pairs:
            sl_q = slice(p.q_slot * C, (p.q_slot + 1) * C)
            sl_kv = slice(p.kv_slot * C, (p.kv_slot + 1) * C)
            dq_p, dk_p, dv_p = _rect_bwd(
                q_loc[:, sl_q], k_loc[:, sl_kv], v_loc[:, sl_kv],
                o_loc[:, sl_q], lse_loc[:, :, sl_q], do_loc[:, sl_q],
                p.spec, meta,
            )
            dq[p.q_slot] = dq[p.q_slot] + dq_p.astype(jnp.float32)
            dk[p.kv_slot] = dk[p.kv_slot] + dk_p.astype(jnp.float32)
            dv[p.kv_slot] = dv[p.kv_slot] + dv_p.astype(jnp.float32)
        return (
            jnp.concatenate(dq, axis=1),
            jnp.concatenate(dk, axis=1),
            jnp.concatenate(dv, axis=1),
        )

    return branch


def _dispatch(meta: _RingMeta, branches, *operands):
    """Run the per-device branch: a single trace when the schedule is
    device-uniform, otherwise lax.switch over axis_index (branches are pure
    compute — collectives stay outside)."""
    if rs.uniform_steps(meta.layout, meta.spec):
        return branches[0](*operands)
    return jax.lax.switch(
        jax.lax.axis_index(meta.axis), branches, *operands
    )


# ---------------------------------------------------------------------------
# Shard-local ring loops (inside shard_map)
# ---------------------------------------------------------------------------


def _prefetch(kv, perm, meta: _RingMeta, scope: str):
    """Issue the next KV hop and *pin* it ahead of this step's compute.

    The explicit double buffer: ``kv`` (buffer A) feeds this step's
    kernels while the returned ``kv_next`` (buffer B) is already in
    flight. Trace order alone is a hope, not a guarantee — the backend
    scheduler may sink the collective past the step's fusions (the CPU
    backend does). The ``optimization_barrier`` groups both buffers: the
    step's kernels consume the barrier's A outputs and the barrier
    depends on the hop, so the collective must be issued before any of
    the step's compute retires. ``perm=None`` (last step) reuses A.
    """
    if perm is None:
        return kv, kv
    with jax.named_scope(scope):
        nxt = jax.lax.ppermute(kv, meta.axis, list(perm))
    k, v, nk, nv = jax.lax.optimization_barrier((kv[0], kv[1], nxt[0], nxt[1]))
    return (k, v), (nk, nv)


def _local_fwd(q_loc, k_loc, v_loc, *, meta: _RingMeta):
    """One device's forward ring pass. q_loc (B, S/P, Hq, D) in natural
    shard order; returns (o_loc (B, S/P, Hq, D), lse_loc (B, Hq, S/P) f32),
    also natural order (zigzag conversion happens at the body boundary)."""
    P = meta.layout.num_devices
    T = rs.num_steps(meta.layout, meta.spec)
    perms = rs.step_perms(meta.layout, meta.spec)
    q_loc = _shard_to_zigzag(q_loc, meta.axis, meta.layout)
    k_loc = _shard_to_zigzag(k_loc, meta.axis, meta.layout)
    v_loc = _shard_to_zigzag(v_loc, meta.axis, meta.layout)
    B, S_loc, Hq, D = q_loc.shape
    acc_o = jnp.zeros((B, Hq, S_loc, D), jnp.float32)
    acc_lse = jnp.full((B, Hq, S_loc), -jnp.inf, jnp.float32)
    kv = (k_loc, v_loc)
    for t in range(T):
        kv, kv_next = _prefetch(
            kv, perms[t] if t < T - 1 else None, meta, f"ring_fwd_hop{t + 1}"
        )
        with jax.named_scope(f"ring_fwd_step{t}"):
            branches = [_step_fwd_branch(meta, d, t) for d in range(P)]
            o_p, lse_p = _dispatch(meta, branches, q_loc, kv[0], kv[1])
            acc_o, acc_lse = merge_partials(acc_o, acc_lse, o_p, lse_p)
        kv = kv_next
    o = acc_o.transpose(0, 2, 1, 3).astype(q_loc.dtype)
    return (
        _zigzag_to_shard(o, meta.axis, meta.layout),
        _zigzag_to_shard(acc_lse, meta.axis, meta.layout, seq_axis=2),
    )


def _local_bwd(q_loc, k_loc, v_loc, o_loc, lse_loc, do_loc, *, meta: _RingMeta):
    """One device's backward ring pass (natural shard order in and out).

    The KV shard is prefetched into its second buffer exactly like the
    forward (the old combined hop rotated (KV, dKV) together *after* the
    step's kernels, putting the KV movement on the critical path). The
    (dK, dV) accumulators genuinely depend on the step's compute, so
    their hop trails the step — it overlaps the *next* step's kernels,
    which read the already-prefetched KV, not the accumulators. A final
    home hop returns each accumulator to its shard's owner. Returns
    (dq, dk, dv) for the local shards, f32."""
    P = meta.layout.num_devices
    T = rs.num_steps(meta.layout, meta.spec)
    perms = rs.step_perms(meta.layout, meta.spec)
    to_zig = functools.partial(_shard_to_zigzag, axis_name=meta.axis, layout=meta.layout)
    q_loc, k_loc, v_loc, o_loc, do_loc = (
        to_zig(x) for x in (q_loc, k_loc, v_loc, o_loc, do_loc)
    )
    lse_loc = to_zig(lse_loc, seq_axis=2)
    dq = jnp.zeros(q_loc.shape, jnp.float32)
    kv = (k_loc, v_loc)
    dkv = (jnp.zeros(k_loc.shape, jnp.float32), jnp.zeros(v_loc.shape, jnp.float32))
    for t in range(T):
        kv, kv_next = _prefetch(
            kv, perms[t] if t < T - 1 else None, meta, f"ring_bwd_hop{t + 1}"
        )
        with jax.named_scope(f"ring_bwd_step{t}"):
            branches = [_step_bwd_branch(meta, d, t) for d in range(P)]
            dq_p, dk_p, dv_p = _dispatch(
                meta, branches, q_loc, kv[0], kv[1], o_loc, lse_loc, do_loc
            )
            dq = dq + dq_p
            dkv = (dkv[0] + dk_p, dkv[1] + dv_p)
        perm_out = perms[t] if t < T - 1 else rs.home_perm(meta.layout, meta.spec)
        with jax.named_scope(f"ring_bwd_dkv_hop{t}"):
            dkv = jax.lax.ppermute(dkv, meta.axis, list(perm_out))
        kv = kv_next
    from_zig = functools.partial(_zigzag_to_shard, axis_name=meta.axis, layout=meta.layout)
    return from_zig(dq), from_zig(dkv[0]), from_zig(dkv[1])


# ---------------------------------------------------------------------------
# Telemetry (host-side, at trace time: each jit trace counts once, cached
# executions don't re-count)
# ---------------------------------------------------------------------------

_RING_TRACE_TID = 3  # dedicated Perfetto track for ring-schedule structure


def _record_ring_pass(meta: _RingMeta, k, *, backward: bool) -> None:
    """Count one ring pass into the default registry and, when a default
    TraceRecorder is installed (launch/train.py --trace-out), emit its
    per-step span structure so an overlap/truncation regression (extra
    steps, fatter hops, lost empty-step skips) is visible in the Perfetto
    output next to the train-step spans. This runs while JAX traces the
    step, so its spans stay off the profiler's run-time clock."""
    from repro.obs.metrics import default_registry
    from repro.obs.trace import get_default_recorder

    layout, spec = meta.layout, meta.spec
    T = rs.num_steps(layout, spec)
    kv_heads, head_dim = k.shape[2], k.shape[3]
    hop_bytes = rs.comm_bytes_per_device(
        layout, kv_heads, head_dim, jnp.dtype(k.dtype).itemsize,
        backward=backward, spec=spec,
    )
    reg = default_registry()
    reg.counter("ring/steps").inc(T)
    reg.counter("ring/hop_bytes").inc(hop_bytes)
    reg.counter("ring/empty_steps_skipped").inc(rs.empty_slot_count(layout, spec))
    rec = get_default_recorder()
    if rec is None:
        return
    name = "ring_bwd" if backward else "ring_fwd"
    bq, bk = meta.block_q or 128, meta.block_kv or 128
    tiles = rs.per_step_tile_counts(layout, spec, bq, bk)
    rec.name_thread(_RING_TRACE_TID, "ring schedule")
    with rec.span(name, tid=_RING_TRACE_TID,
                  args={"steps": T, "devices": layout.num_devices,
                        "hop_bytes_per_device": hop_bytes}, profile=False):
        for t in range(T):
            if t < T - 1:
                rec.instant(f"{name}_hop{t + 1}", tid=_RING_TRACE_TID,
                            args={"in_flight_during_step": t})
            with rec.span(f"{name}_step{t}", tid=_RING_TRACE_TID,
                          args={"max_tiles": int(tiles[t].max()),
                                "tiles_per_device": tiles[t].tolist()},
                          profile=False):
                pass


# ---------------------------------------------------------------------------
# custom_vjp wrapper (everything sharded: no global-order ops at all)
# ---------------------------------------------------------------------------


def _specs(meta: _RingMeta):
    from jax.sharding import PartitionSpec as P

    seq = P(meta.batch_axes, meta.axis, None, None)
    lse = P(meta.batch_axes, None, meta.axis)
    return seq, lse


def _shard_fwd(q, k, v, meta: _RingMeta):
    seq, lse = _specs(meta)
    return shd.shard_map(
        functools.partial(_local_fwd, meta=meta), meta.mesh,
        in_specs=(seq, seq, seq), out_specs=(seq, lse),
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ring(q, k, v, meta: _RingMeta):
    return _shard_fwd(q, k, v, meta)[0]


def _ring_vjp_fwd(q, k, v, meta: _RingMeta):
    o, lse = _shard_fwd(q, k, v, meta)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(meta: _RingMeta, res, do):
    q, k, v, o, lse = res
    _record_ring_pass(meta, k, backward=True)
    seq, lse_spec = _specs(meta)
    dq, dk, dv = shd.shard_map(
        functools.partial(_local_bwd, meta=meta), meta.mesh,
        in_specs=(seq, seq, seq, seq, lse_spec, seq),
        out_specs=(seq, seq, seq),
    )(q, k, v, o, lse, do)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def ring_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    spec: MaskSpec = MaskSpec(causal=True),
    *,
    mesh: Optional[Mesh] = None,
    axis: str = "model",
    batch_axes: object = None,
    impl: str = "flash_pallas",
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
    schedule: Optional[str] = None,
    bwd: Optional[str] = None,
    num_q_bands: Optional[int] = None,
    kv_splits: Optional[int] = None,
) -> jnp.ndarray:
    """Differentiable ring flash attention over the ``axis`` mesh axis.

    q (B, S, Hq, D); k/v (B, S, Hkv, D) GQA. Self-attention only: the ring
    schedule assumes q and kv index the same sequence (Sq == Skv,
    spec.q_offset == 0). ``mesh``/``batch_axes`` default from the installed
    sharding context (distributed.sharding.use_rules); with a 1-device ring
    the layout degenerates and the single-device flash path runs directly.

    ``impl`` picks the shard-local kernel: the Pallas kernels
    (``flash_attention_pallas_with_lse`` + the shard bwd entry) or the XLA
    flash scan — both emit the lane-major lse the ring merge consumes.
    ``bwd`` (Pallas only) picks each rectangle's backward: the fused
    one-pass kernel (default) or the 3-launch split baseline.
    """
    if q.shape[1] != k.shape[1] or spec.q_offset != 0:
        raise ValueError(
            "ring attention is self-attention over one sequence layout "
            f"(Sq == Skv, q_offset == 0); got Sq={q.shape[1]}, "
            f"Skv={k.shape[1]}, q_offset={spec.q_offset}"
        )
    if mesh is None:
        state = shd.current()
        if state is None:
            raise ValueError("ring_flash_attention needs a mesh (argument or "
                             "sharding.use_rules context)")
        mesh, rules = state
        batch_axes = rules.table.get("batch")
    num = mesh.shape[axis] if axis in mesh.shape else 1
    if num == 1:
        # Degenerate ring: run the plain single-device flash path.
        if impl == "flash_pallas":
            from repro.kernels.ops import flash_attention_pallas

            return flash_attention_pallas(
                q, k, v, spec, scale=scale, block_q=block_q, block_kv=block_kv,
                interpret=interpret, schedule=schedule, bwd=bwd,
                num_q_bands=num_q_bands, kv_splits=kv_splits,
            )
        from repro.core.flash import flash_attention

        return flash_attention(
            q, k, v, spec, scale=scale, block_q=block_q or 512,
            block_kv=block_kv or 512,
        )
    layout = rs.make_layout(q.shape[1], num, spec)
    if isinstance(batch_axes, list):
        batch_axes = tuple(batch_axes)
    meta = _RingMeta(
        spec=spec, layout=layout, mesh=mesh, axis=axis, batch_axes=batch_axes,
        impl=impl, block_q=block_q, block_kv=block_kv, scale=scale,
        interpret=interpret, schedule=schedule, bwd=bwd,
        num_q_bands=num_q_bands, kv_splits=kv_splits,
    )
    _record_ring_pass(meta, k, backward=False)
    return _ring(q, k, v, meta)
