"""Occupancy-aware forward partitioning (ISSUE 5 / DESIGN.md Section 2.1b).

Four claims:
  (a) q-banding is *semantics-free to the bit*: each q row runs its
      unchanged kv visit sequence, just on a different parallel grid cell,
      so banded == unbanded compact bitwise on f32 (and still bitwise in
      bf16; vs the oracle with the usual tolerance) -- across MaskSpecs,
      GQA, packed varlen.
  (b) the band partition is balanced: under a causal mask the LPT deal
      (the zigzag pairing, band_assignment) keeps per-band visible-tile
      totals within one tile, and padding placeholder steps are
      compute-free flag-0 steps that revisit the last real tiles.
  (c) split-KV forward partials fold through merge_partials to the
      single-pass result (the decode/ring merge contract, applied to the
      forward), including the short-q/long-kv shapes the split exists for.
  (d) the partitioned grid really is a partitioned grid: a band axis is
      present and `parallel`, and the auto policy engages it exactly for
      the small-BH regime (degrading to 1 band when BH fills the target).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.masks import MaskSpec
from repro.kernels.ops import (
    _TARGET_PARALLEL_CELLS,
    default_forward_partitions,
    flash_attention_pallas,
    flash_attention_pallas_varlen_with_lse,
    flash_attention_pallas_with_lse,
)
from repro.kernels.ref import attention_reference
from repro.kernels.schedule import (
    STEP_ACTIVE,
    STEP_FIRST,
    STEP_LAST,
    band_assignment,
    build_partitioned_schedule,
    build_tile_schedule,
    kv_split_edges,
)

KEY = jax.random.PRNGKey(11)

SPECS = {
    "causal": MaskSpec(causal=True),
    "window": MaskSpec(causal=True, window=64),
    "sink": MaskSpec(causal=True, window=64, sink=16),
    "full": MaskSpec(),
}


def _mk(B, Sq, Sk, Hq, Hk, D, dtype=jnp.float32):
    ks = jax.random.split(KEY, 4)
    return (
        jax.random.normal(ks[0], (B, Sq, Hq, D), dtype),
        jax.random.normal(ks[1], (B, Sk, Hk, D), dtype),
        jax.random.normal(ks[2], (B, Sk, Hk, D), dtype),
        jax.random.normal(ks[3], (B, Sq, Hq, D), dtype),
    )


def _mk_segments(B, S, seed=0):
    rng = np.random.default_rng(seed)
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(8, S - 8), 2, replace=False))
        seg[b, : cuts[0]] = 1
        seg[b, cuts[0] : cuts[1]] = 2
        seg[b, cuts[1] :] = 3 if b % 2 == 0 else 0
    return jnp.asarray(seg)


# ---------------------------------------------------------------------------
# (a) banded == unbanded, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec_name", ["causal", "window", "sink", "full"])
@pytest.mark.parametrize(
    "nb", [2, pytest.param(3, marks=pytest.mark.slow)]
)
def test_banded_bitwise_matches_unbanded(spec_name, nb):
    spec = SPECS[spec_name]
    B, Sq, Sk, Hq, Hk, D = 2, 192, 192, 4, 2, 32  # GQA group 2
    q, k, v, _ = _mk(B, Sq, Sk, Hq, Hk, D)
    kw = dict(block_q=64, block_kv=64, kv_splits=1)
    o1, l1 = flash_attention_pallas_with_lse(q, k, v, spec, num_q_bands=1, **kw)
    o2, l2 = flash_attention_pallas_with_lse(q, k, v, spec, num_q_bands=nb, **kw)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


@pytest.mark.parametrize("spec_name", ["causal", pytest.param("full", marks=pytest.mark.slow)])
def test_banded_varlen_bitwise(spec_name):
    spec = SPECS[spec_name]
    B, S, Hq, Hk, D = 2, 192, 4, 2, 32
    q, k, v, _ = _mk(B, S, S, Hq, Hk, D)
    seg = _mk_segments(B, S)
    kw = dict(block_q=64, block_kv=64, kv_splits=1)
    o1, l1 = flash_attention_pallas_varlen_with_lse(q, k, v, seg, spec, num_q_bands=1, **kw)
    o2, l2 = flash_attention_pallas_varlen_with_lse(q, k, v, seg, spec, num_q_bands=3, **kw)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_banded_bf16():
    spec = MaskSpec(causal=True)
    q, k, v, _ = _mk(2, 128, 128, 4, 2, 64, jnp.bfloat16)
    o_ref, _ = attention_reference(q, k, v, spec)
    o1 = flash_attention_pallas(q, k, v, spec, block_q=64, block_kv=64, num_q_bands=1)
    o2 = flash_attention_pallas(q, k, v, spec, block_q=64, block_kv=64, num_q_bands=2)
    assert o2.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(o1, np.float32), np.asarray(o2, np.float32))
    np.testing.assert_allclose(
        np.asarray(o2, np.float32), np.asarray(o_ref, np.float32), atol=2e-2, rtol=2e-2
    )


def test_banded_grads_bitwise():
    """Bands are a forward-only regrouping: residuals (o, lse) are bitwise
    identical, and the backward kernels never see the band axis."""
    spec = MaskSpec(causal=True)
    q, k, v, do = _mk(2, 192, 192, 4, 2, 32)

    def grads(nb):
        f = lambda q, k, v: (
            flash_attention_pallas(
                q, k, v, spec, block_q=64, block_kv=64, num_q_bands=nb, kv_splits=1
            ) * do
        ).sum()
        return jax.grad(f, (0, 1, 2))(q, k, v)

    for a, b in zip(grads(1), grads(3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_banded_nondivisible_padding():
    """Sq=Sk=200 with 64-blocks: KV padding tiles stay masked under bands."""
    spec = MaskSpec(causal=True)
    q, k, v, _ = _mk(1, 200, 200, 2, 1, 32)
    o_ref, _ = attention_reference(q, k, v, spec)
    o = flash_attention_pallas(
        q, k, v, spec, block_q=64, block_kv=64, num_q_bands=4, kv_splits=1
    )
    np.testing.assert_allclose(o, o_ref, atol=3e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# (b) band balance + placeholder-step contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t_q,nb", [(16, 4), (16, 3), (12, 5), (7, 2), (9, 4)])
def test_causal_band_balance_bound(t_q, nb):
    """Causal zigzag/LPT balance: per-band visible totals within one tile."""
    sched = build_partitioned_schedule(
        MaskSpec(causal=True), t_q, t_q, 64, 64, t_q * 64, num_q_bands=nb
    )
    assert sched.part_active.max() - sched.part_active.min() <= 1, sched.part_active
    assert sched.part_active.sum() == t_q * (t_q + 1) // 2


def test_band_assignment_covers_all_rows():
    bands = band_assignment((1, 2, 3, 4, 5, 6, 7, 8), 3)
    rows = sorted(r for b in bands for r in b)
    assert rows == list(range(8))
    assert all(b for b in bands)  # no empty band
    # fully-masked rows still spread (placeholder-step load, not 0)
    bands0 = band_assignment((0, 0, 0, 0), 2)
    assert all(len(b) == 2 for b in bands0)


def test_partition_placeholder_contract():
    """Padding steps are flags==0 and revisit the partition's last real
    (outer, inner) pair -- no compute, no fresh DMA; every q row inits and
    emits exactly once per kv split."""
    spec = MaskSpec(causal=True, window=128)
    t = 8
    sched = build_partitioned_schedule(spec, t, t, 64, 64, t * 64, num_q_bands=3, kv_splits=2)
    for p in range(sched.num_parts):
        flags = sched.flags[p]
        real = np.nonzero((flags & (STEP_FIRST | STEP_LAST | STEP_ACTIVE)) != 0)[0]
        last_real = real.max()
        tail = np.arange(last_real + 1, sched.n_steps)
        assert (flags[tail] == 0).all()
        assert (sched.outer[p, tail] == sched.outer[p, last_real]).all()
        assert (sched.inner[p, tail] == sched.inner[p, last_real]).all()
    # per split: every q row is owned by exactly one band -> one FIRST and
    # one LAST per (row, split)
    for s in range(sched.kv_splits):
        parts = [p for p in range(sched.num_parts) if sched.part_kv[p] == s]
        firsts = sum((sched.flags[p] & STEP_FIRST != 0).sum() for p in parts)
        lasts = sum((sched.flags[p] & STEP_LAST != 0).sum() for p in parts)
        assert firsts == t and lasts == t


@pytest.mark.parametrize("spec_name", ["causal", "window", "sink", "full"])
def test_partitions_tile_the_oracle(spec_name):
    """Active steps across all partitions == the unbanded compact schedule
    == the _visible_pairs oracle, with no duplicates."""
    spec = SPECS[spec_name]
    t = 8
    flat = build_tile_schedule(spec, t, t, 64, 64, t * 64)
    sched = build_partitioned_schedule(spec, t, t, 64, 64, t * 64, num_q_bands=3, kv_splits=3)
    assert sched.n_active == flat.n_active
    act = sched.flags & STEP_ACTIVE != 0
    got = list(zip(sched.outer[act].tolist(), sched.inner[act].tolist()))
    ref = set(zip(flat.outer[flat.flags & STEP_ACTIVE != 0].tolist(),
                  flat.inner[flat.flags & STEP_ACTIVE != 0].tolist()))
    assert len(got) == len(set(got))  # each visible tile in exactly one partition
    assert set(got) == ref


def test_kv_split_edges_ceil_div():
    assert kv_split_edges(7, 3) == [(0, 3), (3, 5), (5, 7)]
    assert kv_split_edges(4, 4) == [(0, 1), (1, 2), (2, 3), (3, 4)]


# ---------------------------------------------------------------------------
# (c) split-KV forward == single pass (merge_partials roundtrip)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec_name", ["causal", "full"])
@pytest.mark.parametrize(
    "kvs", [2, pytest.param(3, marks=pytest.mark.slow)]
)
def test_splitkv_matches_single_pass(spec_name, kvs):
    spec = SPECS[spec_name]
    q, k, v, _ = _mk(2, 192, 192, 4, 2, 32)
    kw = dict(block_q=64, block_kv=64, num_q_bands=1)
    o1, l1 = flash_attention_pallas_with_lse(q, k, v, spec, kv_splits=1, **kw)
    o2, l2 = flash_attention_pallas_with_lse(q, k, v, spec, kv_splits=kvs, **kw)
    np.testing.assert_allclose(o2, o1, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(l2, l1, atol=1e-5, rtol=1e-5)


def test_splitkv_short_q_long_kv():
    """The shape the split exists for: one q tile vs many kv tiles
    (cross-attention and causal chunked prefill)."""
    B, Sq, Sk, Hq, Hk, D = 1, 64, 512, 2, 2, 32
    q, k, v, _ = _mk(B, Sq, Sk, Hq, Hk, D)
    for spec in (MaskSpec(), MaskSpec(causal=True, q_offset=Sk - Sq)):
        o_ref, lse_ref = attention_reference(q, k, v, spec)
        o1, l1 = flash_attention_pallas_with_lse(
            q, k, v, spec, block_q=64, block_kv=64, num_q_bands=1, kv_splits=1
        )
        o4, l4 = flash_attention_pallas_with_lse(
            q, k, v, spec, block_q=64, block_kv=64, num_q_bands=1, kv_splits=4
        )
        np.testing.assert_allclose(o4, o1, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(l4, l1, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(o4, o_ref, atol=2e-3, rtol=1e-4)
    # auto policy engages the split here: 1 q tile, 8 kv tiles, BH = 2
    nb, ks = default_forward_partitions(2, 1, 8)
    assert nb == 1 and ks > 1


def test_splitkv_grads_match():
    spec = MaskSpec(causal=True)
    q, k, v, do = _mk(2, 192, 192, 4, 2, 32)

    def grads(kvs):
        f = lambda q, k, v: (
            flash_attention_pallas(
                q, k, v, spec, block_q=64, block_kv=64, num_q_bands=1, kv_splits=kvs
            ) * do
        ).sum()
        return jax.grad(f, (0, 1, 2))(q, k, v)

    for a, b in zip(grads(1), grads(3)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_splitkv_varlen_matches_single_pass():
    spec = MaskSpec(causal=True)
    B, S = 2, 192
    q, k, v, _ = _mk(B, S, S, 4, 2, 32)
    seg = _mk_segments(B, S)
    kw = dict(block_q=64, block_kv=64, num_q_bands=1)
    o1, l1 = flash_attention_pallas_varlen_with_lse(q, k, v, seg, spec, kv_splits=1, **kw)
    o2, l2 = flash_attention_pallas_varlen_with_lse(q, k, v, seg, spec, kv_splits=3, **kw)
    np.testing.assert_allclose(o2, o1, atol=1e-5, rtol=1e-5)
    m = ~np.isneginf(np.asarray(l1))
    np.testing.assert_allclose(np.asarray(l2)[m], np.asarray(l1)[m], atol=1e-5, rtol=1e-5)
    assert np.array_equal(np.isneginf(np.asarray(l2)), ~m)  # padded rows stay -inf


# ---------------------------------------------------------------------------
# (d) grid shape + auto policy
# ---------------------------------------------------------------------------


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_eqns(sub.jaxpr if hasattr(sub, "jaxpr") else sub)


def test_banded_grid_shape_and_parallel_axis():
    """Regression: the banded launch has grid (BH, bands, n_steps_band)
    with the band axis `parallel` -- the paper's Figure 2 forward split
    realized in the grid, in ONE launch (not bands separate kernels)."""
    B, S, Hq, Hk, D, nb = 1, 192, 2, 1, 32, 3
    q = jnp.ones((B, S, Hq, D))
    k = jnp.ones((B, S, Hk, D))
    v = jnp.ones((B, S, Hk, D))
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: flash_attention_pallas_with_lse(
            q, k, v, MaskSpec(causal=True), block_q=64, block_kv=64,
            num_q_bands=nb, kv_splits=1,
        )
    )(q, k, v)
    eqns = list(_pallas_eqns(jaxpr.jaxpr))
    assert len(eqns) == 1
    grid = eqns[0].params["grid_mapping"].grid
    sched = build_partitioned_schedule(
        MaskSpec(causal=True), 3, 3, 64, 64, S, num_q_bands=nb
    )
    assert grid == (B * Hq, nb, sched.n_steps), grid
    sem = eqns[0].params["compiler_params"]["mosaic_tpu"].dimension_semantics
    assert tuple(sem) == ("parallel", "parallel", "arbitrary")


def test_default_forward_partitions_policy():
    T = _TARGET_PARALLEL_CELLS
    # large BH: no bands, no padding cost
    assert default_forward_partitions(T, 16, 16) == (1, 1)
    assert default_forward_partitions(4 * T, 16, 16) == (1, 1)
    # small BH, long S: bands up to the target (capped at t_q)
    nb, ks = default_forward_partitions(4, 64, 64)
    assert 4 * nb >= T and ks == 1
    assert default_forward_partitions(1, 8, 8) == (8, 1)
    # short q: bands degrade to 1 (nothing to band)
    assert default_forward_partitions(4, 1, 1) == (1, 1)
    # single-q-tile long-kv corner: kv splits take over
    nb, ks = default_forward_partitions(2, 1, 32)
    assert nb == 1 and ks == 32
    # dense schedule / explicit override handled in ops._resolve_partitions
    from repro.kernels.ops import PallasFlashConfig, _resolve_partitions

    cfg = PallasFlashConfig(spec=MaskSpec(causal=True), schedule="dense", num_q_bands=2)
    with pytest.raises(ValueError):
        _resolve_partitions(cfg, {}, "dense", 4, 8, 8)
    cfg = PallasFlashConfig(spec=MaskSpec(causal=True), num_q_bands=5, kv_splits=2)
    # explicit knobs clamp to t_q and win over a tuned entry
    assert _resolve_partitions(cfg, {}, "compact", 4, 3, 8) == (3, 2)
    assert _resolve_partitions(
        cfg, {"num_q_bands": 1, "kv_splits": 1}, "compact", 4, 3, 8
    ) == (3, 2)
