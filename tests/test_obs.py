"""Unified telemetry layer (ISSUE 8): metrics registry, lifecycle
tracing, MFU accounting -- and the load-bearing pin that attaching ANY of
it adds zero compiles and leaves jitted step shapes untouched."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as cfg_registry
from repro.configs.base import ModelConfig
from repro.core.attention import AttentionConfig
from repro.core.masks import MaskSpec
from repro.models import lm
from repro.obs import (
    MetricsRegistry,
    TraceRecorder,
    TrainEfficiency,
    count_knob,
    default_registry,
    peak_flops,
    reset_default_registry,
    validate_trace,
)
from repro.serving.engine import PagedServingEngine, Request, ServingEngine

# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("x/hits")
    c.inc()
    c.inc(2.5)
    reg.gauge("x/level").set(0.75)
    assert reg.snapshot() == {"x/hits": 3.5, "x/level": 0.75}
    # re-requesting a name returns the same instrument
    assert reg.counter("x/hits") is c
    with pytest.raises(ValueError):
        c.inc(-1)


def test_histogram_cumulative_le_schema():
    reg = MetricsRegistry()
    h = reg.histogram("lat", (1.0, 4.0, 16.0))
    for v in (0.5, 3.0, 3.0, 20.0):
        h.observe(v)
    snap = reg.snapshot()
    # Prometheus cumulative semantics: le_B counts everything <= B
    assert snap["lat/le_1"] == 1.0
    assert snap["lat/le_4"] == 3.0
    assert snap["lat/le_16"] == 3.0
    assert snap["lat/le_inf"] == 4.0
    assert snap["lat/count"] == 4.0
    assert snap["lat/sum"] == pytest.approx(26.5)
    with pytest.raises(ValueError):
        reg.histogram("lat", (1.0, 2.0))  # different buckets
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("bad", (4.0, 1.0))  # not ascending


def test_gauge_fn_lazy_and_fault_isolated():
    reg = MetricsRegistry()
    state = {"v": 1.0}
    reg.gauge_fn("pool/fill", lambda: state["v"])
    state["v"] = 0.5  # sampled at snapshot time, not registration time
    assert reg.snapshot()["pool/fill"] == 0.5

    def boom():
        raise RuntimeError("pool is gone")

    reg.gauge_fn("pool/fill", boom)  # re-register replaces the sampler
    assert math.isnan(reg.snapshot()["pool/fill"])  # never raises


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("n")
    with pytest.raises(ValueError):
        reg.gauge("n")
    with pytest.raises(ValueError):
        reg.histogram("n", (1.0,))


def test_count_knob_default_registry():
    reset_default_registry()
    count_knob("flash_pallas", "tuned", 3)
    count_knob("flash_pallas", "explicit")
    assert default_registry().snapshot() == {
        "knobs/flash_pallas/tuned": 3.0,
        "knobs/flash_pallas/explicit": 1.0,
    }
    with pytest.raises(ValueError):
        count_knob("flash_pallas", "vibes")
    reset_default_registry()
    assert default_registry().snapshot() == {}


def test_knob_resolution_sources_counted():
    """resolve_pallas_knobs classifies each knob's winning tier."""
    from repro.kernels.ops import PallasFlashConfig, resolve_pallas_knobs

    shapes = ((1, 128, 2, 32), (1, 128, 2, 32))
    reset_default_registry()
    # all four knobs explicit, dense schedule -> no partition knobs in play
    resolve_pallas_knobs(
        PallasFlashConfig(spec=MaskSpec(causal=True), block_q=64, block_kv=64,
                          schedule="dense", bwd="fused", use_tuned=False),
        *shapes,
    )
    assert default_registry().snapshot() == {"knobs/flash_pallas/explicit": 4.0}

    reset_default_registry()
    # nothing explicit, cache off -> heuristics fill every knob (compact
    # schedule puts num_q_bands/kv_splits in play: 6 total)
    resolve_pallas_knobs(
        PallasFlashConfig(spec=MaskSpec(causal=True), use_tuned=False), *shapes
    )
    snap = default_registry().snapshot()
    assert snap == {"knobs/flash_pallas/heuristic": 6.0}
    reset_default_registry()


def test_decode_splits_source_counted():
    from repro.kernels.autotune import resolve_decode_splits

    reset_default_registry()
    resolve_decode_splits(256, 4, 64, jnp.float32, use_tuned=False, default=4)
    resolve_decode_splits(256, 4, 64, jnp.float32, page_size=8,
                          use_tuned=False, default=4)
    snap = default_registry().snapshot()
    assert snap["knobs/flash_decode/heuristic"] == 1.0
    assert snap["knobs/flash_decode_paged8/heuristic"] == 1.0
    reset_default_registry()


# ---------------------------------------------------------------------------
# Trace recorder + validator
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_trace_spans_nest_and_validate(tmp_path):
    clk = _FakeClock()
    tr = TraceRecorder(process="unit", clock=clk)
    with tr.span("outer", tid=1):
        clk.t += 1e-3
        with tr.span("inner", tid=1):
            clk.t += 1e-3
        tr.instant("mark", tid=1, args={"rid": 7})
        clk.t += 1e-3
    tr.counter("occupancy", {"slots": 2})
    path = tmp_path / "t.json"
    tr.save(str(path))
    with open(path) as f:
        doc = json.load(f)
    events = validate_trace(doc)
    by_name = {e["name"]: e for e in events if e["ph"] in ("X", "i")}
    assert by_name["inner"]["ts"] >= by_name["outer"]["ts"]
    assert (by_name["inner"]["ts"] + by_name["inner"]["dur"]
            <= by_name["outer"]["ts"] + by_name["outer"]["dur"])
    assert by_name["outer"]["dur"] == pytest.approx(3e3)
    # process metadata event is present and first
    assert doc["traceEvents"][0]["ph"] == "M"


def test_trace_validator_rejects_bad_events():
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"name": "x", "ph": "X", "ts": 0}]})
    with pytest.raises(ValueError):
        validate_trace(
            {"traceEvents": [{"ph": "X", "ts": 0, "pid": 1, "dur": -5}]}
        )
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "i", "pid": 1}]})  # no ts
    # straddling spans on one track: [0, 10) vs [5, 15) neither nests
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 1},
            {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 1},
        ]})
    # different tracks may overlap freely
    validate_trace({"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 2},
    ]})


# ---------------------------------------------------------------------------
# MFU accounting
# ---------------------------------------------------------------------------

TINY = ModelConfig(
    name="obs-tiny", family="dense", num_layers=2, d_model=64, num_heads=2,
    num_kv_heads=2, head_dim=32, d_ff=128, vocab_size=256, vocab_pad_to=64,
    dtype="float32",
)


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PEAK_FLOPS", "2.5e12")
    assert peak_flops() == 2.5e12
    monkeypatch.delenv("REPRO_PEAK_FLOPS")
    assert peak_flops("TPU v5 lite") == 197e12
    # no silent stand-in: a device kind missing from the table raises
    with pytest.raises(KeyError, match="unknown-chip"):
        peak_flops("unknown-chip")


def test_train_efficiency_gauges():
    reg = MetricsRegistry()
    eff = TrainEfficiency(TINY, batch_size=2, seq_len=128, registry=reg,
                          peak=1e12)
    eff.step(0.5)
    eff.step(0.5)
    snap = reg.snapshot()
    assert snap["train/steps"] == 2.0
    assert snap["train/tokens"] == 512.0
    assert snap["train/tokens_per_s"] == pytest.approx(512.0)
    assert snap["train/mfu"] > 0 and math.isfinite(snap["train/mfu"])
    # causal mask: the kernels launch less attention work than the
    # Megatron numerator charges, so HFU (achieved/launched) <= MFU basis
    assert eff.hardware_flops_per_step <= eff.model_flops_per_step
    assert 0 < snap["train/hfu"] <= snap["train/mfu"]
    # cumulative utilization equals the per-step value for equal steps
    assert snap["train/mfu"] == pytest.approx(
        eff.model_flops_per_step / 0.5 / 1e12
    )


# ---------------------------------------------------------------------------
# Engine integration: common snapshot interface + THE zero-overhead pin
# ---------------------------------------------------------------------------

ATTN = AttentionConfig(impl="flash_xla", block_q=64, block_kv=64,
                       decode_splits=2)


@pytest.fixture(scope="module")
def model():
    cfg = cfg_registry.reduce_config(cfg_registry.get("qwen3-8b"))
    params = lm.init_lm(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_fixed_engine_snapshot_and_compiles(model):
    """The fixed engine now speaks the same snapshot()/decode_compiles
    interface as the paged one (satellite a)."""
    cfg, params = model
    reg = MetricsRegistry()
    eng = ServingEngine(cfg, params, ATTN, max_batch=2, cache_size=64,
                        prompt_pad=16, registry=reg)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=[3 + i] * (4 + i), max_new_tokens=4))
    done = eng.run(max_ticks=200)
    assert sorted(done) == [0, 1, 2]
    assert eng.decode_compiles == 1  # telemetry attached, still one trace
    snap = eng.snapshot()
    assert snap is not reg  # flat dict export
    assert snap["serving/admissions"] == 3.0
    assert snap["serving/retirements"] == 3.0
    assert snap["serving/admit_bucket/count"] == 3.0
    assert snap["serving/kv_cells_capacity"] == 2 * 64
    assert snap["serving/active_slots"] == 0.0  # all retired by now
    # every admission waited a finite time; prompts 4..6 pad to bucket 16
    assert snap["serving/queue_wait_s/count"] == 3.0
    assert math.isfinite(snap["serving/queue_wait_s/sum"])
    assert snap["serving/queue_wait_s/sum"] >= 0
    assert snap["serving/prefill_tokens"] == 4 + 5 + 6
    assert snap["serving/prefill_launched_tokens"] == 3 * 16


def test_paged_engine_zero_compile_overhead_with_full_telemetry(model):
    """THE acceptance pin: registry + tracer attached, driven through the
    join/leave/preempt trace of test_paged -- decode still compiles ONCE,
    and the exported trace is schema-valid with paired preempt/resume."""
    cfg, params = model
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(1, 100, 6))) for _ in range(4)]
    reg = MetricsRegistry()
    tracer = TraceRecorder(process="test-paged")
    eng = PagedServingEngine(cfg, params, ATTN, max_batch=4, num_pages=14,
                             page_size=4, pages_per_seq_max=8, prompt_pad=16,
                             registry=reg, tracer=tracer)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=24))
    done = eng.run(max_ticks=1000)
    assert sorted(done) == list(range(4))
    assert eng.preemptions > 0, "pool was sized to force preemption"
    assert eng.decode_compiles == 1  # telemetry adds ZERO compiles

    snap = eng.snapshot()
    assert snap["serving/preemptions"] == eng.preemptions
    assert snap["kv_pool/num_pages"] == eng.pool.usable_pages
    assert snap["kv_pool/used_pages"] == 0.0  # everything freed on retire
    assert snap["serving/admit_bucket/count"] == snap["serving/admissions"]
    assert snap["serving/admissions"] == 4 + eng.preemptions  # re-admits
    assert snap["serving/queue_wait_s/count"] == snap["serving/admissions"]
    assert math.isfinite(snap["serving/queue_wait_s/sum"])
    # real feed tokens never exceed what the launches padded them to
    assert 0 < snap["serving/prefill_tokens"] <= snap[
        "serving/prefill_launched_tokens"]

    events = validate_trace(tracer.to_json())  # raises on schema violation
    # every request track carries the full lifecycle span chain
    for rid in range(4):
        names = {e["name"] for e in events if e.get("tid") == rid}
        assert {"submit", "queue_wait", "prefill", "decode", "retire"} <= names
    # forced preemption emits preempt + resume instants for the SAME rid
    preempted = {e["args"]["rid"] for e in events if e["name"] == "preempt"}
    resumed = {e["args"]["rid"] for e in events if e["name"] == "resume"}
    assert preempted and preempted == resumed
    # the engine track saw decode spans and resident-counter samples
    assert any(e["name"] == "engine.decode" and e["ph"] == "X" for e in events)
    assert not any(e["name"] == "decode_tick" for e in events)
    assert any(e["ph"] == "C" and e["name"] == "resident" for e in events)


def test_train_step_jaxpr_unchanged_by_telemetry():
    """The jitted train step's jaxpr is bit-identical whether or not a
    registry and MFU meter are attached -- telemetry is host-side only."""
    from repro.launch.steps import build_train_step
    from repro.training.optimizer import AdamWConfig, init_opt_state

    params = lm.init_lm(TINY, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    batch = {"inputs": jnp.zeros((2, 32), jnp.int32),
             "targets": jnp.ones((2, 32), jnp.int32)}
    attn = AttentionConfig(impl="ref")
    step = build_train_step(TINY, attn, AdamWConfig(), ce_chunk=64)
    plain = str(jax.make_jaxpr(step)(params, opt, batch))

    reg = MetricsRegistry()
    eff = TrainEfficiency(TINY, batch_size=2, seq_len=32, registry=reg)
    tracer = TraceRecorder(process="train-test")
    with tracer.span("step"):
        eff.step(0.01)
    instrumented = str(jax.make_jaxpr(step)(params, opt, batch))
    assert plain == instrumented


# ---------------------------------------------------------------------------
# The bridge onto the jax.profiler trace
# ---------------------------------------------------------------------------


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation and records its use."""

    made: list = []

    def __init__(self, name, **kwargs):
        self.name, self.meta = name, dict(kwargs)
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kwargs):
        self.meta.update(kwargs)


class _NoAnnotation:
    def __init__(self, *a, **kw):
        raise AssertionError("a profiler annotation was built")


def test_span_forwards_numeric_args_to_the_profiler(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.made = []
    tr = TraceRecorder(process="unit")
    with tr.span("outer", args={"n": 3, "share": 0.5, "ids": [1, 2],
                                "label": "x"}) as args:
        args["late"] = 7  # known only once the work is done
    with tr.span("structure", profile=False):
        pass
    (ann,) = _FakeAnnotation.made  # profile=False built none
    assert ann.name == "repro.outer"
    assert ann.meta == {"n": 3, "share": 0.5, "late": 7}
    outer = next(e for e in tr.events if e.get("name") == "outer")
    assert outer["args"] == {"n": 3, "share": 0.5, "ids": [1, 2],
                             "label": "x", "late": 7}
    assert any(e.get("name") == "structure" for e in tr.events)


def _profiled_host_events(tmp_path, fn, prefix="repro."):
    """Run ``fn`` under a jax.profiler session; the host events whose name
    starts with ``prefix``, as (name, start_ns, end_ns, stats)."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


ENGINE_SPANS = {  # span -> (parent span, its args)
    "engine.tick": (None, {"live", "queued"}),
    "engine.schedule": ("engine.tick", {"picked"}),
    "engine.admit": ("engine.tick", {"n", "width", "bucket", "tokens",
                                     "launched", "pool_in_place"}),
    "engine.decode": ("engine.tick", {"live", "pool_in_place"}),
    "engine.decode.dispatch": ("engine.decode", set()),
    "engine.decode.wait": ("engine.decode", set()),
    "engine.bookkeep": ("engine.tick", {"retired"}),
}


def test_engine_spans_reach_the_profiler(model, tmp_path):
    """Every engine span is on the profiler's host plane as
    ``repro.<name>``, as often as in the recorder's JSON, nested in its
    parent, with the same args, all plain numbers."""
    cfg, params = model
    tracer = TraceRecorder(process="test-profile")
    eng = PagedServingEngine(cfg, params, ATTN, max_batch=4, num_pages=32,
                             page_size=4, pages_per_seq_max=8, prompt_pad=16,
                             tracer=tracer)
    for i, n in enumerate((5, 9, 20, 3, 11)):
        eng.submit(Request(rid=i, prompt=[2 + i] * n, max_new_tokens=6))
    got = _profiled_host_events(tmp_path, lambda: eng.run(max_ticks=100))
    assert sorted(eng.finished) == list(range(5))
    recorded = [e for e in tracer.to_json()["traceEvents"]
                if e["ph"] == "X" and e["name"].startswith("engine.")]
    assert {e["name"] for e in recorded} == set(ENGINE_SPANS)
    for name, (parent, keys) in ENGINE_SPANS.items():
        mine = [e for e in got if e[0] == "repro." + name]
        theirs = [e for e in recorded if e["name"] == name]
        assert len(mine) == len(theirs) > 0, name
        key = lambda a: json.dumps(a, sort_keys=True)
        assert sorted(map(key, (m[3] for m in mine))) == sorted(
            key(e.get("args", {})) for e in theirs), name
        for _, a, b, stats in mine:
            assert set(stats) == keys, (name, stats)
            assert all(type(v) in (int, float) for v in stats.values())
            if parent is not None:
                assert any(p[1] <= a and b <= p[2] for p in got
                           if p[0] == "repro." + parent), name
    for *_, st in (e for e in got if e[0] == "repro.engine.admit"):
        assert st["launched"] == st["width"] * st["bucket"]
        assert 0 < st["tokens"] <= st["launched"] and st["n"] <= st["width"]


def test_paged_engine_decode_span_counts_kernel_work(model):
    """On the Pallas kernel each ``engine.decode`` span carries that tick's
    kernel work -- pages copied, live and launched grid steps -- equal to a
    hand count from the lengths the decode step got, on the grid the kernel
    launches."""
    from repro.kernels.flash_decode import paged_decode_geometry
    from repro.kernels.ops import flash_decode_paged_pallas

    cfg, params = model
    attn = AttentionConfig(impl="flash_pallas", decode_splits=2)
    tracer = TraceRecorder(process="test-kv-work")
    eng = PagedServingEngine(cfg, params, attn, max_batch=4, num_pages=32,
                             page_size=4, pages_per_seq_max=8, prompt_pad=16,
                             tracer=tracer)
    step, seen = eng._step, []

    def spy(params, token, caches, table, cache_len):
        seen.append(np.array(cache_len))  # a copy: the host array moves on
        return step(params, token, caches, table, cache_len)

    eng._step = spy
    for i, n in enumerate((5, 9, 20, 3)):
        eng.submit(Request(rid=i, prompt=[2 + i] * n, max_new_tokens=6))
    eng.run(max_ticks=100)
    assert sorted(eng.finished) == list(range(4))
    spans = [e["args"] for e in tracer.to_json()["traceEvents"]
             if e["ph"] == "X" and e["name"] == "engine.decode"]
    assert len(spans) == len(seen) > 0
    # 8 pages of 4 tokens in 2 splits: one block of 4 pages a split
    assert paged_decode_geometry(8, 4, 2) == (2, 1, 4)
    for args, cache_len in zip(spans, seen):
        pages = -(-np.where(cache_len > 0, cache_len + 1, 0) // 4)
        assert args["kv_pages"] == pages.sum()  # each cached page once
        assert args["kv_blocks"] == (-(-pages // 4)).sum()
        assert args["kv_blocks_launched"] == 4 * 2
    assert any(0 < a["kv_blocks"] < a["kv_blocks_launched"] for a in spans)

    hd = cfg.head_dim
    closed = jax.make_jaxpr(lambda q, kp, vp, lens, tbl: (
        flash_decode_paged_pallas(q, kp, vp, lens, tbl, num_splits=2)))(
        jnp.zeros((4, 1, cfg.num_heads, hd)),
        jnp.zeros((cfg.num_layers, cfg.num_kv_heads, 32, 4, hd)),
        jnp.zeros((cfg.num_layers, cfg.num_kv_heads, 32, 4, hd)),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4, 8), jnp.int32))
    assert [e.params["grid_mapping"].grid for e in closed.jaxpr.eqns
            if e.primitive.name == "pallas_call"] == [(4, 2, 1)]


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "fixed"])
def test_engine_without_tracer_builds_no_annotation(model, monkeypatch,
                                                    paged):
    cfg, params = model
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _NoAnnotation)

    def serve(tracer):
        if paged:
            eng = PagedServingEngine(cfg, params, ATTN, max_batch=2,
                                     num_pages=16, page_size=4,
                                     pages_per_seq_max=8, prompt_pad=16,
                                     tracer=tracer)
        else:
            eng = ServingEngine(cfg, params, ATTN, max_batch=2, cache_size=32,
                                prompt_pad=16, tracer=tracer)
        for i in range(3):
            eng.submit(Request(rid=i, prompt=[3 + i] * 5, max_new_tokens=3))
        return eng.run(max_ticks=50)

    assert sorted(serve(None)) == [0, 1, 2]
    with pytest.raises(AssertionError, match="annotation was built"):
        serve(TraceRecorder(process="on"))  # the same path, traced, does


def test_jit_trace_time_spans_stay_off_the_profiler(monkeypatch):
    """The ring schedule records its structure while JAX traces the step:
    those spans stay in the recorder and build no profiler annotation."""
    from repro.distributed import ring_attention as ra
    from repro.distributed import ring_schedule as rs
    from repro.obs import set_default_recorder

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _NoAnnotation)
    spec = MaskSpec(causal=True)
    meta = ra._RingMeta(spec=spec, layout=rs.make_layout(512, 4, spec),
                        mesh=None, axis="model", batch_axes=None,
                        impl="flash_xla", block_q=64, block_kv=64, scale=None,
                        interpret=None, schedule=None, bwd=None,
                        num_q_bands=None, kv_splits=None)

    def f(k):
        ra._record_ring_pass(meta, k, backward=False)
        return k * 2

    rec = TraceRecorder(process="ring")
    set_default_recorder(rec)
    try:
        jax.jit(f)(jnp.zeros((1, 128, 2, 32), jnp.float32)).block_until_ready()
    finally:
        set_default_recorder(None)
    names = [e["name"] for e in rec.events if e["ph"] == "X"]
    assert "ring_fwd" in names and "ring_fwd_step0" in names


# ---------------------------------------------------------------------------
# Satellites: ledger schema check + timing provenance
# ---------------------------------------------------------------------------


def test_bench_schema_check_tags_nonconforming(capsys):
    import sys

    sys.path.insert(0, "benchmarks")
    try:
        from run import _check_schema
    finally:
        sys.path.pop(0)

    rows = [
        {"bench": "ok", "config": "a", "us_per_call": 1.0, "derived": ""},
        {"bench": "ok2", "config": "b", "us_per_call": None, "derived": "x=1"},
        {"bench": "", "config": "c", "us_per_call": 1.0, "derived": ""},
        {"bench": "no_units", "config": "d", "us_per_call": None, "derived": ""},
        {"bench": "missing"},
        {"bench": "fixed", "config": "e", "us_per_call": 2.0, "derived": "",
         "schema": "nonconforming: stale tag"},
    ]
    out = _check_schema(rows)
    assert out is rows  # warn-and-tag, never drop
    assert "schema" not in rows[0] and "schema" not in rows[1]
    assert rows[2]["schema"] == "nonconforming: empty bench name"
    assert rows[3]["schema"].startswith("nonconforming: no units field")
    assert rows[4]["schema"].startswith("nonconforming: missing keys")
    assert "schema" not in rows[5]  # conforming again -> stale tag cleared
    assert "3 ledger rows are nonconforming" in capsys.readouterr().err


def test_timing_result_provenance():
    from repro.utils.timing import interleaved_timeit

    res = interleaved_timeit({"a": lambda: jnp.zeros(()),
                              "b": lambda: jnp.ones(())}, iters=2, warmup=1)
    assert set(res) == {"a", "b"}  # still a plain mapping
    assert res.iters == 2 and res.warmup == 1
    assert res.provenance == "min_of_2w1"
    # clamping: zero iters/warmup are promoted to 1, and recorded as such
    res0 = interleaved_timeit({"a": lambda: jnp.zeros(())}, iters=0, warmup=0)
    assert res0.provenance == "min_of_1w1"
