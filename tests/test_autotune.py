"""ISSUE 6: the empirical knob autotuner and the timing fix it stands on.

Covers: tuned-cache round-trip + schema validation, the resolution
precedence order (explicit arg > tuned cache > heuristic) pinned as a
regression test, the committed tuned.json actually being consulted by an
all-``None`` PallasFlashConfig, bitwise-identical outputs for tuned vs
heuristic knobs on a fixed shape, block-size legalization, decode-split
resolution, timer sanity (fwd <= fwd+bwd from the shared interleaved
min-of-N helper -- the exact inversion the old mean-of-3 produced), and
the benchmark trajectory's tolerant load / dedupe / prune.
"""

import json
import pathlib
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention import AttentionConfig, decode_attention
from repro.core.masks import MaskSpec
from repro.kernels import autotune
from repro.kernels.ops import (
    PallasFlashConfig,
    default_block_sizes,
    flash_attention_pallas,
    resolve_pallas_knobs,
)
from repro.kernels.ref import attention_reference
from repro.utils.timing import interleaved_timeit

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # for `import benchmarks.run`

CAUSAL = MaskSpec(causal=True)


@pytest.fixture(autouse=True)
def _fresh_cache_state(monkeypatch):
    """Isolate every test from the process-level load cache and env."""
    monkeypatch.delenv(autotune.ENV_DISABLE, raising=False)
    monkeypatch.delenv(autotune.ENV_PATH, raising=False)
    autotune.clear_cache()
    yield
    autotune.clear_cache()


def _write_cache(path, entries, backend=None):
    doc = autotune.new_doc(backend or f"{jax.default_backend()}/test", entries)
    with open(path, "w") as f:
        json.dump(doc, f)
    autotune.clear_cache()
    return str(path)


# ---------------------------------------------------------------------------
# Cache file: key format, schema, round-trip, tolerant load
# ---------------------------------------------------------------------------


def test_cache_key_roundtrip():
    key = autotune.cache_key("flash_pallas", True, 512, 4, 64, jnp.float32)
    assert key == "flash_pallas/causal=1/seq=512/heads=4/hd=64/dtype=float32"
    meta = autotune.parse_key(key)
    assert meta == dict(impl="flash_pallas", causal=True, seq=512, heads=4,
                        head_dim=64, dtype="float32")


def test_validate_doc_rejects_bad_schema():
    good_key = autotune.cache_key("flash_pallas", True, 128, 2, 32, "float32")
    autotune.validate_doc(autotune.new_doc("x", {good_key: {"block_q": 64}}))
    for bad in (
        [],  # not an object
        {"version": 99, "backend": "x", "entries": {}},  # wrong version
        {"version": 1, "entries": {}},  # missing backend
        {"version": 1, "backend": "x"},  # missing entries
        {"version": 1, "backend": "x", "entries": {"nonsense": {}}},  # bad key
        {"version": 1, "backend": "x",
         "entries": {good_key: {"blocksize": 64}}},  # unknown knob
        {"version": 1, "backend": "x",
         "entries": {good_key: {"block_q": "big"}}},  # mis-typed knob
        {"version": 1, "backend": "x",
         "entries": {good_key: {"schedule": "zigzag"}}},  # bad enum
        {"version": 1, "backend": "x",
         "entries": {good_key: {"block_q": 0}}},  # < 1
    ):
        with pytest.raises(ValueError):
            autotune.validate_doc(bad)


def test_save_load_roundtrip(tmp_path):
    key = autotune.cache_key("flash_pallas", False, 256, 4, 64, "float32")
    doc = autotune.new_doc(f"{jax.default_backend()}/test",
                           {key: {"block_q": 64, "block_kv": 64,
                                  "us_fwd": 12.5}})
    path = str(tmp_path / "tuned.json")
    autotune.save_cache(doc, path)
    loaded = autotune.load_cache(path)
    assert loaded["entries"] == doc["entries"]
    # lookup strips provenance, returns only knobs
    knobs = autotune.lookup("flash_pallas", False, 256, 4, 64, jnp.float32,
                            path=path)
    assert knobs == {"block_q": 64, "block_kv": 64}


def test_load_tolerant_on_corrupt_file(tmp_path, monkeypatch):
    path = tmp_path / "tuned.json"
    path.write_text('{"version": 1, "backend": "x", "entr')  # truncated
    with pytest.warns(UserWarning, match="invalid tuned cache"):
        doc = autotune.load_cache(str(path))
    assert doc["entries"] == {}  # disabled, not crashed
    # and resolution against the corrupt file falls back to pure
    # heuristics without raising
    monkeypatch.setenv(autotune.ENV_PATH, str(path))
    autotune.clear_cache()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        r = resolve_pallas_knobs(
            PallasFlashConfig(spec=CAUSAL), (1, 64, 2, 32), (1, 64, 2, 32)
        )
    assert r["tuned"] == {}


def test_missing_file_is_empty(tmp_path):
    doc = autotune.load_cache(str(tmp_path / "nope.json"))
    assert doc["entries"] == {}


# ---------------------------------------------------------------------------
# Lookup: exact key, nearest-shape fallback, mask-family guards
# ---------------------------------------------------------------------------


def test_lookup_nearest_shape(tmp_path):
    key = autotune.cache_key("flash_pallas", True, 256, 4, 64, "float32")
    path = _write_cache(tmp_path / "t.json", {key: {"block_q": 64}})
    # exact
    assert autotune.lookup("flash_pallas", True, 256, 4, 64, jnp.float32,
                           path=path) == {"block_q": 64}
    # nearest within the 2x radius, heads relax too
    assert autotune.lookup("flash_pallas", True, 320, 8, 64, jnp.float32,
                           path=path) == {"block_q": 64}
    # beyond the radius: miss
    assert autotune.lookup("flash_pallas", True, 1024, 4, 64, jnp.float32,
                           path=path) == {}
    # causal / head-dim / dtype never relax
    assert autotune.lookup("flash_pallas", False, 256, 4, 64, jnp.float32,
                           path=path) == {}
    assert autotune.lookup("flash_pallas", True, 256, 4, 128, jnp.float32,
                           path=path) == {}
    assert autotune.lookup("flash_pallas", True, 256, 4, 64, jnp.bfloat16,
                           path=path) == {}


def test_lookup_prefers_heads_match_then_seq(tmp_path):
    k1 = autotune.cache_key("flash_pallas", True, 512, 4, 64, "float32")
    k2 = autotune.cache_key("flash_pallas", True, 384, 8, 64, "float32")
    path = _write_cache(tmp_path / "t.json",
                        {k1: {"block_q": 512}, k2: {"block_q": 128}})
    # same heads wins over closer seq
    assert autotune.lookup("flash_pallas", True, 400, 4, 64, jnp.float32,
                           path=path) == {"block_q": 512}


def test_cache_from_another_platform_is_ignored(tmp_path, monkeypatch):
    """A cache swept on another platform (here: a TPU cache read on the
    CPU, or the committed CPU cache read on a TPU) pins no knob: every
    knob falls back to the heuristics."""
    key = autotune.cache_key("flash_pallas", True, 256, 2, 32, "float32")
    knobs = {"block_q": 64, "block_kv": 64, "bwd": "split"}
    other = "cpu" if jax.default_backend() == "tpu" else "tpu"
    path = _write_cache(tmp_path / "t.json", {key: knobs},
                        backend=f"{other}/v5e")
    assert autotune.lookup("flash_pallas", True, 256, 2, 32, "float32",
                           path=path) == {}
    monkeypatch.setenv(autotune.ENV_PATH, path)
    shape = (2, 256, 2, 32)
    r = resolve_pallas_knobs(PallasFlashConfig(spec=CAUSAL), shape, shape)
    heur = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, use_tuned=False), shape, shape)
    assert r["tuned"] == {}
    assert {k: r[k] for k in ("block_q", "block_kv", "bwd")} == \
        {k: heur[k] for k in ("block_q", "block_kv", "bwd")}
    # the same entry under the running platform's label is consulted
    path = _write_cache(tmp_path / "u.json", {key: knobs})
    assert autotune.lookup("flash_pallas", True, 256, 2, 32, "float32",
                           path=path) == knobs


def test_window_and_sink_specs_skip_cache(tmp_path, monkeypatch):
    key = autotune.cache_key("flash_pallas", True, 256, 2, 32, "float32")
    path = _write_cache(tmp_path / "t.json", {key: {"block_q": 64}})
    monkeypatch.setenv(autotune.ENV_PATH, path)
    shape = (1, 256, 2, 32)
    r = resolve_pallas_knobs(
        PallasFlashConfig(spec=MaskSpec(causal=True, window=64)), shape, shape
    )
    assert r["tuned"] == {} and r["block_q"] == 256  # heuristic, not 64


# ---------------------------------------------------------------------------
# Precedence: explicit arg > tuned cache > heuristic (the regression pin)
# ---------------------------------------------------------------------------


def test_precedence_order(tmp_path, monkeypatch):
    shape = (2, 256, 2, 32)
    key = autotune.cache_key("flash_pallas", True, 256, 2, 32, "float32")
    tuned_knobs = {"block_q": 64, "block_kv": 64, "schedule": "dense",
                   "bwd": "split", "num_q_bands": 1, "kv_splits": 1}
    path = _write_cache(tmp_path / "t.json", dict([(key, tuned_knobs)]))
    monkeypatch.setenv(autotune.ENV_PATH, path)

    # all-None knobs -> the tuned entry, verbatim
    r = resolve_pallas_knobs(PallasFlashConfig(spec=CAUSAL), shape, shape)
    for k, v in tuned_knobs.items():
        assert r[k] == v, (k, r)
    assert r["tuned"] == tuned_knobs

    # explicit args win over the cache, knob by knob
    r = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, block_q=128, schedule="compact"),
        shape, shape,
    )
    assert r["block_q"] == 128 and r["schedule"] == "compact"
    assert r["block_kv"] == 64 and r["bwd"] == "split"  # rest still tuned

    # use_tuned=False -> pure heuristics
    r = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, use_tuned=False), shape, shape
    )
    bq_def, bk_def = default_block_sizes(256, 256, 32)
    assert (r["block_q"], r["block_kv"]) == (bq_def, bk_def)
    assert r["schedule"] == "compact" and r["bwd"] == "fused"
    assert r["tuned"] == {}

    # env escape hatch disables globally
    monkeypatch.setenv(autotune.ENV_DISABLE, "0")
    r = resolve_pallas_knobs(PallasFlashConfig(spec=CAUSAL), shape, shape)
    assert r["tuned"] == {} and r["schedule"] == "compact"


def test_committed_cache_consulted_by_all_none_config():
    """Acceptance: PallasFlashConfig with every knob None consults the
    COMMITTED tuned.json (no env overrides, no monkeypatching)."""
    doc = autotune.load_cache(autotune.DEFAULT_PATH)
    keys = [k for k in doc["entries"] if k.startswith("flash_pallas/")]
    assert keys, "committed tuned.json must ship flash_pallas entries"
    for key in keys:
        m = autotune.parse_key(key)
        shape = (2, m["seq"], m["heads"], m["head_dim"])
        r = resolve_pallas_knobs(
            PallasFlashConfig(spec=MaskSpec(causal=m["causal"])),
            shape, shape, dtype=m["dtype"],
        )
        entry = autotune.lookup(m["impl"], m["causal"], m["seq"], m["heads"],
                                m["head_dim"], m["dtype"],
                                path=autotune.DEFAULT_PATH)
        assert r["tuned"] == entry and entry, key
        for knob in ("block_q", "block_kv", "schedule"):
            if knob in entry:
                assert r[knob] == entry[knob], (key, knob, r)


# ---------------------------------------------------------------------------
# Tuned vs heuristic outputs
# ---------------------------------------------------------------------------


def test_tuned_vs_heuristic_bitwise(tmp_path, monkeypatch):
    """On a fixed shape, tuned knobs that only re-tile/band the q axis give
    BITWISE the heuristic's forward output (per-row kv visit order is
    unchanged); grads stay allclose."""
    B, S, H, D = 2, 256, 2, 32
    bq_def, bk_def = default_block_sizes(S, S, D)
    key = autotune.cache_key("flash_pallas", True, S, H, D, "float32")
    path = _write_cache(
        tmp_path / "t.json",
        {key: {"block_q": 64, "block_kv": bk_def, "num_q_bands": 2,
               "schedule": "compact", "bwd": "fused"}},
    )
    monkeypatch.setenv(autotune.ENV_PATH, path)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(k_, (B, S, H, D), jnp.float32) for k_ in ks)
    shape = (B, S, H, D)
    r_tuned = resolve_pallas_knobs(PallasFlashConfig(spec=CAUSAL), shape, shape)
    r_heur = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, use_tuned=False), shape, shape
    )
    assert r_tuned["block_q"] == 64 and r_heur["block_q"] == bq_def
    o_tuned = flash_attention_pallas(q, k, v, CAUSAL, use_tuned=True)
    o_heur = flash_attention_pallas(q, k, v, CAUSAL, use_tuned=False)
    assert np.array_equal(np.asarray(o_tuned), np.asarray(o_heur))

    def loss(fn_use_tuned):
        return jax.grad(lambda q: flash_attention_pallas(
            q, k, v, CAUSAL, use_tuned=fn_use_tuned).sum())(q)

    np.testing.assert_allclose(np.asarray(loss(True)), np.asarray(loss(False)),
                               atol=1e-5, rtol=1e-5)


def test_tuned_knobs_match_reference_oracle():
    """Whatever the committed cache resolves to must still be exact."""
    doc = autotune.load_cache(autotune.DEFAULT_PATH)
    keys = [k for k in doc["entries"]
            if k.startswith("flash_pallas/") and "/seq=256/" in k]
    assert keys
    m = autotune.parse_key(keys[0])
    spec = MaskSpec(causal=m["causal"])
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(k_, (2, m["seq"], m["heads"], m["head_dim"]),
                                 jnp.float32) for k_ in ks)
    o = flash_attention_pallas(q, k, v, spec)  # all knobs None -> tuned
    o_ref = attention_reference(q, k, v, spec)[0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Block-size legalization (satellite: no silent mis-padding)
# ---------------------------------------------------------------------------


def test_block_legalization_rounds_and_warns():
    shape = (1, 512, 2, 32)
    with pytest.warns(UserWarning, match="block_q=100 is not legal"):
        r = resolve_pallas_knobs(
            PallasFlashConfig(spec=CAUSAL, block_q=100, use_tuned=False),
            shape, shape,
        )
    assert r["block_q"] == 104  # rounded up to the 8-sublane contract
    with pytest.warns(UserWarning, match="block_kv=4096"):
        r = resolve_pallas_knobs(
            PallasFlashConfig(spec=CAUSAL, block_kv=4096, use_tuned=False),
            shape, shape,
        )
    assert r["block_kv"] == 512  # clamped to the padded sequence


def test_block_legalization_lane_aligned_for_mosaic():
    """Compiled by Mosaic (interpret=False), a block is the lane axis of the
    lse / delta / segment-id rows, so it rounds up to 128 -- unless it
    covers the whole padded sequence, which any block shape may."""
    shape = (1, 512, 2, 32)
    with pytest.warns(UserWarning, match="128-aligned"):
        r = resolve_pallas_knobs(
            PallasFlashConfig(spec=CAUSAL, block_q=100, block_kv=64,
                              interpret=False, use_tuned=False),
            shape, shape,
        )
    assert (r["block_q"], r["block_kv"]) == (128, 128)
    short = (1, 100, 2, 32)
    r = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, interpret=False, use_tuned=False),
        short, short,
    )
    assert r["block_q"] == r["block_kv"] == 104  # the whole padded axis


@pytest.mark.parametrize("bad", [0, -8, 2.5, "128", True])
def test_block_legalization_rejects_garbage(bad):
    shape = (1, 128, 2, 32)
    with pytest.raises(ValueError):
        resolve_pallas_knobs(
            PallasFlashConfig(spec=CAUSAL, block_q=bad, use_tuned=False),
            shape, shape,
        )


def test_misaligned_explicit_block_still_exact():
    """A legalized (rounded) explicit block must produce oracle-exact
    output -- the pre-fix behavior let block=100 corrupt the padding."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(k_, (1, 200, 2, 32), jnp.float32)
               for k_ in ks)
    with pytest.warns(UserWarning):
        o = flash_attention_pallas(q, k, v, CAUSAL, block_q=100, block_kv=60,
                                   use_tuned=False)
    o_ref = attention_reference(q, k, v, CAUSAL)[0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Decode-split resolution
# ---------------------------------------------------------------------------


def test_decode_splits_resolution(tmp_path, monkeypatch):
    key = autotune.cache_key("flash_decode", True, 128, 2, 32, "float32")
    path = _write_cache(tmp_path / "t.json", {key: {"num_splits": 2}})
    monkeypatch.setenv(autotune.ENV_PATH, path)
    assert autotune.resolve_decode_splits(128, 2, 32, jnp.float32) == 2
    assert autotune.resolve_decode_splits(
        128, 2, 32, jnp.float32, use_tuned=False) == 8
    # and the attention-layer decode path consumes it (None -> tuned)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (1, 1, 2, 32), jnp.float32)
    kc = jax.random.normal(kk, (1, 128, 2, 32), jnp.float32)
    vc = jax.random.normal(kv, (1, 128, 2, 32), jnp.float32)
    lens = jnp.array([100], jnp.int32)
    o_tuned = decode_attention(q, kc, vc, lens, AttentionConfig())
    o_explicit = decode_attention(
        q, kc, vc, lens, AttentionConfig(decode_splits=2))
    assert np.array_equal(np.asarray(o_tuned), np.asarray(o_explicit))


# ---------------------------------------------------------------------------
# Timer sanity (the satellite for the original inversion bug)
# ---------------------------------------------------------------------------


def test_timer_fwd_not_slower_than_fwdbwd():
    """The shared interleaved min-of-N helper must never report a strict
    subset of the work as slower: fwd <= fwd+bwd on a toy fn. This is the
    exact inversion BENCH_attn.json recorded under the old single-warmup
    mean-of-3 (`ref/causal=0/seq=512`: 438ms fwd vs 356ms fwd+bwd)."""
    x = jnp.ones((384, 384), jnp.float32) * 0.01
    fwd = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    fwdbwd = jax.jit(jax.grad(lambda x: jnp.tanh(x @ x).sum()))
    best = interleaved_timeit({"fwd": fwd, "fwdbwd": fwdbwd}, x, iters=10)
    assert best["fwd"] <= best["fwdbwd"], best


def test_rebaselined_trajectory_has_no_inversions():
    """Acceptance: the committed BENCH_attn.json has no fwd-slower-than-
    fwd+bwd inversion for any impl/shape (fig4/fig5 and sched_cmp pairs)."""
    rows = json.loads((ROOT / "BENCH_attn.json").read_text())
    by_key = {(r["bench"], r["config"]): r["us_per_call"] for r in rows}
    pairs = [
        (("fig5_fwd", c), ("fig4_fwdbwd", c))
        for (b, c) in by_key if b == "fig5_fwd"
    ] + [
        (("sched_cmp_fwd", c), ("sched_cmp_fwdbwd", c.replace("fwd", "fwdbwd")))
        for (b, c) in by_key if b == "sched_cmp_fwd"
    ]
    assert pairs, "trajectory must contain fwd/fwdbwd pairs"
    for fwd_key, bwd_key in pairs:
        if bwd_key not in by_key:
            continue
        assert by_key[fwd_key] <= by_key[bwd_key], (
            "fwd slower than fwd+bwd -- the timing bug is back", fwd_key,
            by_key[fwd_key], by_key[bwd_key],
        )


# ---------------------------------------------------------------------------
# Benchmark trajectory durability (run.py satellites)
# ---------------------------------------------------------------------------


def test_trajectory_load_tolerant_and_dedupes(tmp_path, capsys):
    from benchmarks.run import _load_existing

    path = tmp_path / "bench.json"
    # corrupt file: backed up, not fatal
    path.write_text('[{"bench": "a", "config": "x", "us')
    assert _load_existing(str(path)) == []
    assert not path.exists() and (tmp_path / "bench.json.bad").exists()
    # wrong shape: also backed up
    path.write_text('{"not": "a list"}')
    assert _load_existing(str(path)) == []
    # duplicate (bench, config): last write wins
    rows = [
        {"bench": "a", "config": "x", "us_per_call": 1.0},
        {"bench": "a", "config": "x", "us_per_call": 2.0},
        {"bench": "b", "config": "y", "us_per_call": 3.0},
    ]
    path.write_text(json.dumps(rows))
    out = _load_existing(str(path))
    assert sorted((r["bench"], r["us_per_call"]) for r in out) == [
        ("a", 2.0), ("b", 3.0),
    ]


# ---------------------------------------------------------------------------
# The paged-decode sweep and drift check drive the serving kernel's contract
# ---------------------------------------------------------------------------

PAGED_SMOKE = next(s for s in autotune.SMOKE_SHAPES if s[0] == "paged_decode")


def test_paged_decode_sweep_runs_on_the_smoke_shape():
    """The CLI's `--smoke` and `--smoke --check` paths build their paged
    operands with `_paged_fixture`; both must call the kernel with the pool
    contract it takes (stacked planes read at a layer index)."""
    key, entry = autotune._sweep_one(PAGED_SMOKE, 1, None)
    assert key == autotune.cache_key(
        "flash_decode_paged32", True, 128, 2, 32, "float32")
    assert entry["num_splits"] in (1, 2, 4)
    autotune.validate_doc(autotune.new_doc("cpu/test", {key: entry}))
    failures = autotune.check_cache([PAGED_SMOKE], iters=1,
                                    tol=float("inf"), log=lambda *_: None)
    assert failures == [], failures
