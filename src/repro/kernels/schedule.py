"""Compact tile schedules for the Pallas kernels (DESIGN.md Section 2).

FlashAttention-2's Section 3.1 argument is about *work partitioning*: a
causal/window mask empties whole (q_block, kv_block) tiles, and a good
schedule never visits them. The historical kernels here visited every tile
and branch-skipped with ``pl.when`` -- the matmuls were saved but the grid
steps (and their K/V tile DMAs) were not. This module precomputes, per
kernel launch, the flattened list of *visible* tile pairs plus per-step
control flags; the kernels feed it through scalar prefetch
(``pltpu.PrefetchScalarGridSpec``) so the sequential grid axis has exactly
``n_steps`` entries and the index maps DMA only the tiles the schedule
names. Causal drops ~2x of the steps, sliding-window O(S/W)x.

Two orientations of the same schedule:

  * q-major (``kv_major=False``) -- used by ``flash_fwd`` / ``flash_bwd_dq``:
    steps are grouped by owning q tile ``i`` (the ``outer`` array), streaming
    its visible kv tiles ``j`` (``inner``).
  * kv-major (``kv_major=True``) -- used by ``flash_bwd_dkv``: grouped by
    owning kv tile ``j`` (``outer``), streaming visible q tiles ``i``.

An outer tile with *zero* visible partners still gets one placeholder step
(ACTIVE bit clear) so its init/finalize run and its output block is written
(zeros / -inf lse); that is the ``+ t_q`` slack in the step-count bound
``n_steps <= n_visible + n_outer``.

The static schedule is spec-only. Packed-varlen (segment) visibility is
data-dependent, so it rides along as a second, *dynamic* table built by
:func:`segment_step_tables` -- per (batch, step) bits computed with O(B * S)
jnp work outside the kernel and scalar-prefetched, replacing the in-kernel
per-tile segment-id min/max probing.

The step count is cross-checked against ``core.flash._visible_pairs`` -- the
shared schedule oracle -- at build time.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.masks import MaskSpec, tile_visibility

# Static per-step flag bits (TileSchedule.flags).
STEP_ACTIVE = 1  # tile contributes compute (clear on placeholder steps)
STEP_FIRST = 2   # first step of its outer-tile run -> init VMEM scratch
STEP_LAST = 4    # last step of its outer-tile run -> finalize / emit
STEP_MASKED = 8  # partial tile (or KV padding): apply the element mask
# kv-major only (the fused one-pass backward, flash_bwd.flash_bwd_fused):
# first/last visit of the *streamed q tile* anywhere in the flattened
# schedule. The fused kernel consumes QFIRST (start its dq block from zero
# instead of reading it back + compute delta = rowsum(dO o O), so neither
# needs its own pass). QLAST is schedule metadata only today: the fused
# kernel writes dq back on every visit, so there is no emit step -- the
# bit exists for accounting (tests assert the pair brackets each q tile's
# visits) and for an emit-style consumer (e.g. a variant that downcasts dq
# on last visit).
STEP_QFIRST = 16
STEP_QLAST = 32

# Dynamic per-(batch, step) segment bits (segment_step_tables).
SEG_ACTIVE = 1   # tile id ranges overlap (range-disjointness skip)
SEG_UNIFORM = 2  # both sides uniform and equal -> tile is mask-free


class TileSchedule(NamedTuple):
    """Flattened compact schedule (host-side numpy; static per launch)."""

    outer: np.ndarray  # (n_steps,) int32 -- owning tile index per step
    inner: np.ndarray  # (n_steps,) int32 -- streamed tile index per step
    flags: np.ndarray  # (n_steps,) int32 -- STEP_* bitmask
    n_active: int      # number of ACTIVE steps == visible tile count

    @property
    def n_steps(self) -> int:
        return len(self.outer)


class PartitionedSchedule(NamedTuple):
    """Forward compact schedule split into parallel partitions.

    The paper's Section 3.2 forward partitioning applied to the compact
    schedule: the q tiles of each head are dealt into ``num_q_bands``
    bands (balanced by *visible* tile count) and, orthogonally, the kv
    tiles into ``kv_splits`` contiguous ranges. Each partition
    ``p = split * num_q_bands + band`` runs its band's q rows against its
    split's kv range on its own grid cell along a *parallel* axis -- no
    cross-partition communication, each band keeps its own online-softmax
    scratch. Tables are padded to the longest partition with compute-free
    placeholder steps (flags == 0, repeating the partition's final
    (outer, inner) so no extra tile is DMA'd).
    """

    outer: np.ndarray        # (P, n_steps) int32 -- owning q tile per step
    inner: np.ndarray        # (P, n_steps) int32 -- streamed kv tile per step
    flags: np.ndarray        # (P, n_steps) int32 -- STEP_* bitmask
    part_kv: np.ndarray      # (P,) int32 -- kv split index of each partition
    part_active: np.ndarray  # (P,) int64 -- visible tiles per partition
    n_active: int            # total visible tiles (== sum(part_active))
    num_q_bands: int
    kv_splits: int

    @property
    def n_steps(self) -> int:
        return self.outer.shape[1]

    @property
    def num_parts(self) -> int:
        return self.outer.shape[0]


def _tile_class(spec: MaskSpec, i: int, j: int, bq: int, bk: int, kv_valid: int):
    """None if tile (i, j) is spec-empty, else whether it needs the mask.

    THE shared per-tile classifier of both schedule builders (flat and
    partitioned) -- the bitwise-equality contract between them rides on
    the empty/masked predicate living in exactly one place.
    """
    q_lo = i * bq + spec.q_offset
    vis = tile_visibility(spec, q_lo, q_lo + bq, j * bk, j * bk + bk)
    if vis == "empty":
        return None
    return vis == "partial" or (j + 1) * bk > kv_valid


@functools.lru_cache(maxsize=256)  # bounded: chunked prefill varies q_offset
def build_tile_schedule(
    spec: MaskSpec, t_q: int, t_kv: int, bq: int, bk: int, kv_valid: int,
    kv_major: bool = False,
) -> TileSchedule:
    """Build the compact schedule for a (t_q x t_kv) tile grid under spec.

    ``kv_valid`` is the unpadded KV length: tiles touching KV padding are
    flagged STEP_MASKED (never dropped -- the last tile always holds some
    real keys because padding is < one block).

    kv-major schedules additionally carry STEP_QFIRST / STEP_QLAST on the
    first / last step that streams each q tile (QFIRST drives the fused
    backward's dq zero-init + delta prologue; QLAST is accounting metadata,
    see the bit definitions above). A q tile no step streams
    (possible under exotic window / q_offset specs: its row attends
    nothing) gets an inactive placeholder appended at the tail so its dq
    block is still zeroed and its delta still written; the tail placeholder
    reuses the final outer tile, whose dk/dv windows were already emitted.
    """
    n_outer = t_kv if kv_major else t_q
    n_inner = t_q if kv_major else t_kv
    outer, inner, flags = [], [], []
    n_active = 0
    for a in range(n_outer):
        run = []
        for b in range(n_inner):
            i, j = (b, a) if kv_major else (a, b)
            masked = _tile_class(spec, i, j, bq, bk, kv_valid)
            if masked is None:
                continue
            run.append((b, masked))
        if not run:
            # placeholder so the outer tile still inits + emits (zeros).
            outer.append(a)
            inner.append(0)
            flags.append(STEP_FIRST | STEP_LAST)
            continue
        for pos, (b, masked) in enumerate(run):
            f = STEP_ACTIVE
            f |= STEP_FIRST if pos == 0 else 0
            f |= STEP_LAST if pos == len(run) - 1 else 0
            f |= STEP_MASKED if masked else 0
            outer.append(a)
            inner.append(b)
            flags.append(f)
        n_active += len(run)
    if kv_major:
        # q-row visit bits for the fused backward (see docstring).
        first_seen: dict = {}
        last_seen: dict = {}
        for s, b in enumerate(inner):
            first_seen.setdefault(b, s)
            last_seen[b] = s
        tail = outer[-1] if outer else 0
        for b in range(n_inner):
            if b not in first_seen:
                outer.append(tail)
                inner.append(b)
                flags.append(0)
                first_seen[b] = last_seen[b] = len(inner) - 1
        for s in first_seen.values():
            flags[s] |= STEP_QFIRST
        for s in last_seen.values():
            flags[s] |= STEP_QLAST
    sched = TileSchedule(
        outer=np.asarray(outer, np.int32),
        inner=np.asarray(inner, np.int32),
        flags=np.asarray(flags, np.int32),
        n_active=n_active,
    )
    # Accounting invariant: the schedule's active steps are exactly the
    # oracle's visible tiles (core.flash._visible_pairs, row-major).
    from repro.core.flash import _visible_pairs

    assert sched.n_active == len(_visible_pairs(spec, t_q, t_kv, bq, bk)[0]), (
        "compact schedule disagrees with the _visible_pairs oracle"
    )
    return sched


def band_assignment(counts, num_bands: int):
    """Deal q rows into ``num_bands`` bands balanced by visible-tile count.

    Load of a row is ``max(count, 1)`` -- a fully-masked row still costs one
    placeholder step, and charging it spreads such rows across bands (every
    band keeps >= 1 row when ``num_bands <= len(counts)``).

    Two deterministic passes:

      1. *Quota fill*: per-band targets ``floor/ceil(total / num_bands)``,
         each band greedily taking the largest unassigned row that still
         fits its remaining quota. For a causal mask the row loads are the
         consecutive integers ``1..t_q`` (the regime where this always
         lands exactly on quota): the largest row pairs with its
         complement, reproducing ``ring_schedule``'s zigzag trick -- row
         ``i`` opposite row ``t_q - 1 - i`` -- so per-band visible totals
         balance to within ONE tile (tests/test_occupancy.py asserts the
         bound).
      2. If some band cannot reach its quota (irregular window/varlen
         count distributions), fall back to longest-processing-time: rows
         by (load desc, index asc), each to the lightest band.

    Returns ``num_bands`` ascending row-index lists.
    """
    loads = {r: max(c, 1) for r, c in enumerate(counts)}
    order = sorted(loads, key=lambda r: (-loads[r], r))
    total = sum(loads.values())
    q, rem = divmod(total, num_bands)
    quotas = [q + 1] * rem + [q] * (num_bands - rem)
    bands: list = [[] for _ in range(num_bands)]
    remaining = list(order)
    ok = True
    for b, quota in enumerate(quotas):
        while quota > 0 and remaining:
            pick = next((r for r in remaining if loads[r] <= quota), None)
            if pick is None:
                ok = False
                break
            remaining.remove(pick)
            bands[b].append(pick)
            quota -= loads[pick]
        if not ok or (quota > 0 and not remaining):
            ok = False
            break
    if not ok or remaining or any(not b for b in bands):
        # LPT fallback: near-balanced for arbitrary load distributions.
        band_loads = [0] * num_bands
        bands = [[] for _ in range(num_bands)]
        for r in order:
            b = min(range(num_bands), key=lambda i: (band_loads[i], i))
            band_loads[b] += loads[r]
            bands[b].append(r)
    for rows in bands:
        rows.sort()
    return bands


def kv_split_edges(t_kv: int, kv_splits: int):
    """Ceil-div contiguous kv-tile ranges [(j0, j1), ...] covering 0..t_kv.

    The first ``t_kv % kv_splits`` splits carry one extra tile
    (``np.array_split`` semantics) -- no silent degrade for prime/odd tile
    counts, mirroring the decode split fix.
    """
    base, extra = divmod(t_kv, kv_splits)
    edges, j0 = [], 0
    for s in range(kv_splits):
        j1 = j0 + base + (1 if s < extra else 0)
        edges.append((j0, j1))
        j0 = j1
    return edges


@functools.lru_cache(maxsize=256)
def build_partitioned_schedule(
    spec: MaskSpec, t_q: int, t_kv: int, bq: int, bk: int, kv_valid: int,
    num_q_bands: int = 1, kv_splits: int = 1,
) -> PartitionedSchedule:
    """Build the q-banded / split-KV forward schedule (paper Section 3.2).

    Same per-step contract as :func:`build_tile_schedule` q-major
    schedules, but the steps of each head are spread over
    ``num_q_bands * kv_splits`` partitions that the kernel runs on a
    *parallel* grid axis:

      * every q row belongs to exactly one band (``band_assignment``;
        balanced by visible tiles), and its kv visit order within a
        partition is unchanged ascending -- so with ``kv_splits == 1`` the
        banded kernel's per-row update sequence is IDENTICAL to the
        unbanded compact schedule (bitwise-equal outputs);
      * with ``kv_splits > 1`` each partition covers one contiguous kv-tile
        range; its finalize emits a *partial* (o, lse) for its rows, folded
        outside the kernel by ``online_softmax.merge_partials``. A row with
        no visible tile in some split gets the usual placeholder step
        (FIRST|LAST, ACTIVE clear), emitting the merge identity
        (o = 0, lse = -inf).

    Partition tables are padded to the longest partition with flags == 0
    steps that repeat the partition's last real (outer, inner) pair: the
    revisited blocks cost no new DMA and the step runs no compute (the
    occupancy benchmark's exp census asserts banding adds zero exps per
    visible tile).
    """
    num_q_bands = max(1, min(num_q_bands, t_q))
    kv_splits = max(1, min(kv_splits, t_kv))
    runs, counts = [], []
    for i in range(t_q):
        run = []
        for j in range(t_kv):
            masked = _tile_class(spec, i, j, bq, bk, kv_valid)
            if masked is None:
                continue
            run.append((j, masked))
        runs.append(run)
        counts.append(len(run))
    bands = band_assignment(tuple(counts), num_q_bands)
    parts, part_kv, part_active = [], [], []
    for s_idx, (j0, j1) in enumerate(kv_split_edges(t_kv, kv_splits)):
        for rows in bands:
            steps = []
            n_act = 0
            for i in rows:
                seg = [(j, m) for (j, m) in runs[i] if j0 <= j < j1]
                if not seg:
                    # placeholder: init + emit zeros / -inf (merge identity)
                    steps.append((i, j0, STEP_FIRST | STEP_LAST))
                    continue
                for pos, (j, m) in enumerate(seg):
                    f = STEP_ACTIVE
                    f |= STEP_FIRST if pos == 0 else 0
                    f |= STEP_LAST if pos == len(seg) - 1 else 0
                    f |= STEP_MASKED if m else 0
                    steps.append((i, j, f))
                n_act += len(seg)
            parts.append(steps)
            part_kv.append(s_idx)
            part_active.append(n_act)
    n_steps = max(len(p) for p in parts)
    P = len(parts)
    outer = np.zeros((P, n_steps), np.int32)
    inner = np.zeros((P, n_steps), np.int32)
    flags = np.zeros((P, n_steps), np.int32)
    for p, steps in enumerate(parts):
        for s, (i, j, f) in enumerate(steps):
            outer[p, s], inner[p, s], flags[p, s] = i, j, f
        # padding placeholders: repeat the last real pair, flags stay 0
        outer[p, len(steps):] = steps[-1][0]
        inner[p, len(steps):] = steps[-1][1]
    sched = PartitionedSchedule(
        outer=outer, inner=inner, flags=flags,
        part_kv=np.asarray(part_kv, np.int32),
        part_active=np.asarray(part_active, np.int64),
        n_active=int(sum(part_active)),
        num_q_bands=num_q_bands, kv_splits=kv_splits,
    )
    # Accounting invariant: partitions tile the oracle's visible set.
    from repro.core.flash import _visible_pairs

    assert sched.n_active == len(_visible_pairs(spec, t_q, t_kv, bq, bk)[0]), (
        "partitioned schedule disagrees with the _visible_pairs oracle"
    )
    return sched


def decode_step_bits(flags, seg_bits=None):
    """Shared in-kernel step decode: (active, first, last, needs_mask).

    ``flags`` is the loaded STEP_* bitmask for the current step;
    ``seg_bits`` the loaded (batch, step) segment bits or None. Used by all
    three compact kernels so a schedule-format change lands in one place.
    """
    active = (flags & STEP_ACTIVE) != 0
    needs_mask = (flags & STEP_MASKED) != 0
    if seg_bits is not None:
        active = jnp.logical_and(active, (seg_bits & SEG_ACTIVE) != 0)
        needs_mask = jnp.logical_or(needs_mask, (seg_bits & SEG_UNIFORM) == 0)
    return active, (flags & STEP_FIRST) != 0, (flags & STEP_LAST) != 0, needs_mask


def segment_step_tables(
    q_seg: jnp.ndarray,  # (B, Sqp) int32, padded with the masks.py sentinels
    kv_seg: jnp.ndarray,  # (B, Skp) int32
    sched: TileSchedule,
    bq: int,
    bk: int,
    kv_major: bool = False,
) -> jnp.ndarray:
    """Dynamic per-(batch, step) visibility bits for a packed batch.

    Returns (B, n_steps) int32 with SEG_ACTIVE / SEG_UNIFORM bits (for a
    :class:`PartitionedSchedule`, whose tables are (P, n_steps), the fancy
    indexing broadcasts to (B, P, n_steps) -- same bits per step). ACTIVE
    uses per-tile id-range disjointness (sound for any id layout, exact for
    contiguous packing); UNIFORM means both tiles are constant and equal, so
    the element mask can be skipped. Computed as O(B * S) jnp reductions at
    trace time and scalar-prefetched -- no in-kernel min/max probing.
    """
    B = q_seg.shape[0]
    qt = q_seg.reshape(B, -1, bq)
    kt = kv_seg.reshape(B, -1, bk)
    q_lo, q_hi = qt.min(axis=-1), qt.max(axis=-1)  # (B, t_q)
    k_lo, k_hi = kt.min(axis=-1), kt.max(axis=-1)  # (B, t_kv)
    ii = jnp.asarray(sched.inner if kv_major else sched.outer)
    jj = jnp.asarray(sched.outer if kv_major else sched.inner)
    qlo, qhi = q_lo[:, ii], q_hi[:, ii]  # (B, n_steps)
    klo, khi = k_lo[:, jj], k_hi[:, jj]
    overlap = ~((qhi < klo) | (qlo > khi))
    uniform = (qlo == qhi) & (klo == khi) & (qlo == klo)
    return overlap.astype(jnp.int32) | (uniform.astype(jnp.int32) << 1)
