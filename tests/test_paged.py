"""Paged-KV serving: page pool, block-table indirect decode kernel
(bitwise vs contiguous), and the paged continuous-batching engine
(token parity under join/leave/preemption, zero decode recompiles,
batched single-compile admission)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.attention import AttentionConfig
from repro.core.decode import flash_decode_paged
from repro.kernels.flash_decode import paged_decode_geometry, paged_decode_work
from repro.kernels.ops import flash_decode_pallas, flash_decode_paged_pallas
from repro.models import lm
from repro.obs import TraceRecorder
from repro.serving.engine import PagedServingEngine, Request, ServingEngine
from repro.serving.kv_pool import NULL_PAGE, KVPagePool

# ---------------------------------------------------------------------------
# Pool
# ---------------------------------------------------------------------------


def test_pool_alloc_free_roundtrip():
    pool = KVPagePool(num_pages=8, page_size=16)
    assert pool.usable_pages == 7 and pool.free_pages == 7
    a = pool.alloc(1, 3)
    assert len(a) == 3 and NULL_PAGE not in a and len(set(a)) == 3
    assert pool.used_pages == 3 and pool.pages_of(1) == a
    b = pool.alloc(2, 4)
    assert set(a).isdisjoint(b) and pool.free_pages == 0
    assert pool.free(1) == 3 and pool.free_pages == 3
    assert pool.pages_of(1) == []
    assert pool.free(2) == 4 and pool.free_pages == 7


def test_pool_alloc_all_or_nothing():
    pool = KVPagePool(num_pages=4, page_size=8)
    assert pool.alloc(1, 5) is None  # over capacity: no partial grant
    assert pool.free_pages == 3 and pool.pages_of(1) == []
    assert pool.alloc(1, 3) is not None
    assert pool.alloc(2, 1) is None  # empty pool


def test_pool_extend_and_oom():
    pool = KVPagePool(num_pages=4, page_size=8)
    first = pool.alloc(7, 2)
    p = pool.extend(7)
    assert p is not None and pool.pages_of(7) == first + [p]
    assert pool.extend(7) is None  # OOM signals the engine to preempt
    assert pool.page_utilization() == 1.0


def test_pool_pages_for_tokens():
    pool = KVPagePool(num_pages=4, page_size=16)
    assert pool.pages_for_tokens(1) == 1
    assert pool.pages_for_tokens(16) == 1
    assert pool.pages_for_tokens(17) == 2


# ---------------------------------------------------------------------------
# Kernel: page-indirect decode vs contiguous
# ---------------------------------------------------------------------------

B, S, PS, Hq, Hk, D = 3, 128, 16, 8, 2, 64
NPAGES = S // PS
# Several blocks a split (kernels/flash_decode.paged_decode_geometry): 70
# pages tile as 3 blocks of 24 at one split and 2 x 2 blocks of 18 at two,
# neither evenly. Lengths end on a block edge at one split (768) and at two
# (288), mid-page in the last block (1100); the empty slot reads nothing.
# At two splits 288 leaves split 1 dead; a window of 200 with a sink of 16
# skips the middle block between the sink and the window at 1100.
B_LONG, NPAGES_LONG = 4, 70
LENS_LONG = (768, 1100, 288, 0)
WINDOW_SINK = {"short": (32, 8), "long": (200, 16)}


# The pool's planes are a stack of every layer's (LAYERS, Hk, P, ps, D);
# the kernel reads one layer of it.
LAYERS = 3


def _paginate(kc, vc, seed=0, layer=0):
    """Contiguous (B,S,Hk,D) caches -> shuffled physical page planes at
    ``layer`` of a (LAYERS,Hk,P,ps,D) stack whose other layers hold NaN,
    + block table, page 0 reserved null."""
    kc, vc = np.asarray(kc), np.asarray(vc)
    batch, npages = kc.shape[0], kc.shape[1] // PS
    P = batch * npages + 1
    perm = np.random.default_rng(seed).permutation(P - 1) + 1
    table = perm.reshape(batch, npages).astype(np.int32)
    k_pages = np.full((LAYERS, Hk, P, PS, D), np.nan, kc.dtype)
    v_pages = np.full((LAYERS, Hk, P, PS, D), np.nan, vc.dtype)
    k_pages[layer] = v_pages[layer] = 0
    for b in range(batch):
        for i in range(npages):
            phys = table[b, i]
            k_pages[layer, :, phys] = kc[b, i * PS: (i + 1) * PS].transpose(1, 0, 2)
            v_pages[layer, :, phys] = vc[b, i * PS: (i + 1) * PS].transpose(1, 0, 2)
    return jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table)


@pytest.fixture(scope="module")
def kv():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    kc = jax.random.normal(ks[0], (B, S, Hk, D))
    vc = jax.random.normal(ks[1], (B, S, Hk, D))
    q = jax.random.normal(ks[2], (B, 1, Hq, D))
    lens = jnp.array([128, 97, 37], jnp.int32)  # full / prime / odd-page
    return q, kc, vc, lens


@pytest.fixture(scope="module")
def kv_long():
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    kc = jax.random.normal(ks[0], (B_LONG, NPAGES_LONG * PS, Hk, D))
    vc = jax.random.normal(ks[1], (B_LONG, NPAGES_LONG * PS, Hk, D))
    q = jax.random.normal(ks[2], (B_LONG, 1, Hq, D))
    assert paged_decode_geometry(NPAGES_LONG, PS, 1) == (1, 3, 24)
    assert paged_decode_geometry(NPAGES_LONG, PS, 2) == (2, 2, 18)
    return q, kc, vc, jnp.array(LENS_LONG, jnp.int32)


@pytest.fixture(params=["short", "long"])
def geometry(request):
    """(name, the kv fixture): one page a block, or several blocks a split."""
    return request.param, request.getfixturevalue(
        "kv" if request.param == "short" else "kv_long")


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_bitwise_parity_one_page_per_split(kv, dtype, layer):
    """One split == one page makes the paged kernel's per-split math and
    merge tree identical to the contiguous kernel's -> (o, lse) must be
    BITWISE equal, independent of physical page placement. GQA (Hq=8 over
    Hk=2) and ragged prime/odd lengths included. Read at ``layer`` of the
    stack, it is bitwise the call on that layer's planes alone (a stack of
    one at layer 0), and no other layer's NaN reaches it."""
    q, kc, vc = (t.astype(dtype) for t in kv[:3])
    lens = kv[3]
    k_pages, v_pages, table = _paginate(kc, vc, layer=layer)
    o_c, lse_c = flash_decode_pallas(q, kc, vc, lens, num_splits=NPAGES)
    o_p, lse_p = flash_decode_paged_pallas(
        q, k_pages, v_pages, lens, table, layer, num_splits=NPAGES
    )
    np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_c))
    np.testing.assert_array_equal(np.asarray(lse_p), np.asarray(lse_c))
    o_1, lse_1 = flash_decode_paged_pallas(
        q, k_pages[layer][None], v_pages[layer][None], lens, table, 0,
        num_splits=NPAGES
    )
    np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_1))
    np.testing.assert_array_equal(np.asarray(lse_p), np.asarray(lse_1))


def test_paged_multi_page_splits_match(geometry):
    """pp > 1 (several pages a split, gathered into blocks walked in order)
    changes the reduction order, so parity is allclose, not bitwise."""
    q, kc, vc, lens = geometry[1]
    k_pages, v_pages, table = _paginate(kc, vc)
    o_c, lse_c = flash_decode_pallas(q, kc, vc, lens,
                                     num_splits=table.shape[1])
    for splits in (1, 2, 4):
        o_p, lse_p = flash_decode_paged_pallas(
            q, k_pages, v_pages, lens, table, num_splits=splits
        )
        np.testing.assert_allclose(o_p, o_c, atol=5e-6, rtol=1e-5)
        np.testing.assert_allclose(lse_p, lse_c, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_shuffle_invariance(kv, layer):
    """The physical placement of pages is pure bookkeeping: two different
    shuffles must produce BITWISE identical results, at any layer of the
    stack."""
    q, kc, vc, lens = kv
    outs = []
    for seed in (0, 1):
        k_pages, v_pages, table = _paginate(kc, vc, seed=seed, layer=layer)
        outs.append(
            flash_decode_paged_pallas(
                q, k_pages, v_pages, lens, table, layer, num_splits=4
            )
        )
    np.testing.assert_array_equal(np.asarray(outs[0][0]), np.asarray(outs[1][0]))
    np.testing.assert_array_equal(np.asarray(outs[0][1]), np.asarray(outs[1][1]))


def test_paged_window_sink_bitwise(kv):
    q, kc, vc, lens = kv
    k_pages, v_pages, table = _paginate(kc, vc)
    o_c, lse_c = flash_decode_pallas(
        q, kc, vc, lens, window=32, sink=8, num_splits=NPAGES
    )
    o_p, lse_p = flash_decode_paged_pallas(
        q, k_pages, v_pages, lens, table, window=32, sink=8, num_splits=NPAGES
    )
    np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_c))
    np.testing.assert_array_equal(np.asarray(lse_p), np.asarray(lse_c))


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window_sink"])
def test_paged_xla_fallback_matches(geometry, windowed):
    name, (q, kc, vc, lens) = geometry
    k_pages, v_pages, table = _paginate(kc, vc)
    window, sink = WINDOW_SINK[name] if windowed else (None, 0)
    o_p, lse_p = flash_decode_paged_pallas(
        q, k_pages, v_pages, lens, table, num_splits=4, window=window,
        sink=sink,
    )
    o_x, lse_x = flash_decode_paged(
        q, k_pages, v_pages, lens, table, num_splits=4, window=window,
        sink=sink,
    )
    np.testing.assert_allclose(o_p, o_x, atol=5e-6, rtol=1e-5)
    np.testing.assert_allclose(lse_p, lse_x, atol=1e-5, rtol=1e-5)


def test_paged_empty_slot_masked(kv):
    """ISSUE 7 satellite: a free/finished slot (length 0, all-null table
    row) must read no KV: its pages are never active, so o == 0 and
    lse == -inf, regardless of what garbage sits in the null page."""
    q, kc, vc, _ = kv
    k_pages, v_pages, table = _paginate(kc, vc)
    # poison the null page: masked-out reads would show up immediately
    k_pages = k_pages.at[:, :, 0].set(1e9)
    v_pages = v_pages.at[:, :, 0].set(1e9)
    lens = jnp.array([128, 0, 37], jnp.int32)
    table = table.at[1].set(0)
    o, lse = flash_decode_paged_pallas(
        q, k_pages, v_pages, lens, table, num_splits=4
    )
    assert np.all(np.asarray(o[1]) == 0.0)
    assert np.all(np.isneginf(np.asarray(lse[1])))
    # live rows unaffected by the poisoned null page
    o_ref, _ = flash_decode_paged(
        q, k_pages, v_pages, lens, table, num_splits=4
    )
    np.testing.assert_allclose(o[0], o_ref[0], atol=5e-6, rtol=1e-5)
    np.testing.assert_allclose(o[2], o_ref[2], atol=5e-6, rtol=1e-5)


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("splits", [1, 2])
def test_paged_nan_past_live_pages(geometry, splits, layer):
    """What lies past a slot's cached tokens never reaches the output, not
    even as 0 * NaN: NaN in the null page, in every page a table names past
    a slot's live pages, and in the rows past the length of its last live
    page leaves (o, lse) bitwise equal to a clean pool's (the stack's other
    layers NaN throughout)."""
    q, kc, vc, lens = geometry[1]
    k_pages, v_pages, table = _paginate(kc, vc, layer=layer)
    lens_np = np.asarray(lens)
    kp, vp = np.array(k_pages), np.array(v_pages)
    dead = np.arange(table.shape[1])[None, :] >= -(-lens_np[:, None] // PS)
    for pages in (kp, vp):
        pages[layer, :, 0] = np.nan
        pages[layer, :, np.asarray(table)[dead]] = np.nan
        for b, n in enumerate(lens_np):
            if n % PS:
                pages[layer, :, table[b, n // PS], n % PS:] = np.nan
    clean = flash_decode_paged_pallas(q, k_pages, v_pages, lens, table, layer,
                                      num_splits=splits)
    nulled = flash_decode_paged_pallas(q, kp, vp, lens, table.at[dead].set(0),
                                       layer, num_splits=splits)
    poisoned = flash_decode_paged_pallas(q, kp, vp, lens, table, layer,
                                         num_splits=splits)
    for got in (nulled, poisoned):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(clean[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(clean[1]))


@pytest.mark.parametrize("window,sink", [(None, 0), (200, 16), (40, 0)])
@pytest.mark.parametrize("n_pages,ps,splits", [(70, 16, 1), (70, 16, 2),
                                               (289, 16, 8), (8, 16, 8)])
def test_paged_decode_work_matches_page_count(n_pages, ps, splits, window,
                                              sink):
    """The block-level skip is the page-level one lifted: a block is live
    exactly when one of its pages is (each page tested alone, DESIGN.md
    Section 5.1), and it copies its pages below the length -- every cached
    page once, without a window."""
    ns, nb, ppb = paged_decode_geometry(n_pages, ps, splits)
    assert ns * nb * ppb >= n_pages and ppb * ps <= max(512, ps)
    lens = np.random.default_rng(n_pages + splits).integers(
        0, n_pages * ps + 1, size=64)
    lens[:3] = (0, 1, n_pages * ps)
    work = paged_decode_work(lens, n_pages, ps, splits, window=window,
                             sink=sink)
    blocks = pages = 0
    for L in lens:
        for blk in range(ns * nb):
            page_live = []
            for p in range(blk * ppb, (blk + 1) * ppb):
                cols = np.arange(p * ps, (p + 1) * ps)
                seen = cols < L
                if window is not None:
                    seen &= (cols >= L - window) | (cols < sink)
                page_live.append(seen.any())
            if any(page_live):
                blocks += 1
                pages += sum(p * ps < L for p in range(blk * ppb,
                                                       (blk + 1) * ppb))
    assert work == {"kv_pages": pages, "kv_blocks": blocks,
                    "kv_blocks_launched": len(lens) * ns * nb}
    if window is None:
        assert pages == sum(-(-int(L) // ps) for L in lens)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

ATTN = AttentionConfig(impl="flash_xla", block_q=64, block_kv=64, decode_splits=2)


@pytest.fixture(scope="module")
def model():
    cfg = registry.reduce_config(registry.get("qwen3-8b"))
    params = lm.init_lm(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _assert_pool_in_place(eng):
    """Every decode and admit call consumed the donated pool in place:
    each call's span says so, and the engine counted every one of them
    in place and none copied."""
    spans = [e["args"] for e in eng.tracer.events
             if e["name"] in ("engine.decode", "engine.admit")]
    snap = eng.snapshot()
    assert spans and all(a["pool_in_place"] == 1 for a in spans)
    assert snap["serving/pool_in_place_calls"] == len(spans)
    assert snap["serving/pool_copied_calls"] == 0


def _sequential_refs(cfg, params, prompts, max_new):
    """Oracle: each request alone through the fixed-slot engine."""
    refs = {}
    for i, p in enumerate(prompts):
        solo = ServingEngine(cfg, params, ATTN, max_batch=1, cache_size=64,
                             prompt_pad=16)
        solo.submit(Request(rid=i, prompt=list(p), max_new_tokens=max_new))
        refs[i] = solo.run(max_ticks=200)[i].generated
    return refs


def test_paged_engine_token_parity_and_compiles(model):
    """Requests joining and leaving mid-flight through the paged engine
    generate exactly the sequential-oracle tokens; the decode step compiles
    ONCE for the whole run and admission compiles once per (bucket, width);
    every step updates the donated pool in place."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 100, rng.integers(2, 20))))
               for _ in range(5)]
    refs = _sequential_refs(cfg, params, prompts, max_new=6)
    eng = PagedServingEngine(cfg, params, ATTN, max_batch=2, num_pages=17,
                             page_size=8, pages_per_seq_max=8, prompt_pad=16,
                             tracer=TraceRecorder(process="t"))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=6))
    done = eng.run(max_ticks=400)
    assert sorted(done) == list(range(5))
    for i in range(5):
        assert done[i].generated == refs[i], i
    assert eng.decode_compiles == 1  # zero recompiles across join/leave
    # 5 prompts, 2 buckets (pad 16 / 32), widths bounded by max_batch=2:
    # a handful of admit traces, never one per request
    assert eng.admit_compiles <= 4
    # free-on-retire returned every page
    assert eng.pool.used_pages == 0
    assert eng.pool.free_pages == eng.pool.usable_pages
    _assert_pool_in_place(eng)


def test_paged_engine_batched_admission_one_compile(model):
    """All same-bucket queued prompts are admitted in ONE batched prefill:
    3 different same-bucket lengths into an empty 4-slot engine -> exactly
    one admit trace, and slot reuse later sticks to it."""
    cfg, params = model
    eng = PagedServingEngine(cfg, params, ATTN, max_batch=4, num_pages=33,
                             page_size=8, pages_per_seq_max=8, prompt_pad=16,
                             tracer=TraceRecorder(process="t"))
    for i, L in enumerate((3, 7, 11)):
        eng.submit(Request(rid=i, prompt=[2 + i] * L, max_new_tokens=4))
    eng.tick()  # admits all three in one call (width padded to 4)
    assert eng.admit_compiles == 1
    for i, L in enumerate((5, 9, 13)):
        eng.submit(Request(rid=10 + i, prompt=[1 + i] * L, max_new_tokens=4))
    done = eng.run(max_ticks=200)
    assert sorted(done) == [0, 1, 2, 10, 11, 12]
    # one bucket, pow2 widths only: at most 1 + log2(max_batch) traces ever,
    # however requests trickle in (here widths 4, then 1/2 as slots free)
    assert eng.admit_compiles <= 3
    assert eng.decode_compiles == 1
    _assert_pool_in_place(eng)


def test_paged_engine_preemption_resume(model):
    """A pool too small for concurrent growth forces preempt-youngest;
    requeued requests resume (prompt+generated re-prefill) and still
    produce exactly the oracle tokens."""
    cfg, params = model
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(1, 100, 6))) for _ in range(4)]
    refs = _sequential_refs(cfg, params, prompts, max_new=24)
    eng = PagedServingEngine(cfg, params, ATTN, max_batch=4, num_pages=14,
                             page_size=4, pages_per_seq_max=8, prompt_pad=16,
                             tracer=TraceRecorder(process="t"))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=24))
    done = eng.run(max_ticks=1000)
    assert sorted(done) == list(range(4))
    for i in range(4):
        assert done[i].generated == refs[i], i
    assert eng.preemptions > 0, "pool was sized to force preemption"
    assert eng.decode_compiles == 1  # preemption churn never recompiles
    _assert_pool_in_place(eng)


def test_paged_engine_rejects_oversized(model):
    cfg, params = model
    eng = PagedServingEngine(cfg, params, ATTN, max_batch=2, num_pages=9,
                             page_size=8, pages_per_seq_max=4)
    with pytest.raises(AssertionError):
        eng.submit(Request(rid=0, prompt=[1] * 20, max_new_tokens=20))
