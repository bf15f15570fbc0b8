"""The program's own spans on the profiler trace (``program_trace``) and the
``engine`` layer's readers of them: read back from a real profile on the
CPU, on a hand-made record whose answers are known, on a record cut from a
traced chip run (``fixtures/chat_program.rec.json``), and, on the records
that hold no program spans, exactly what ``devtrace`` reads."""

import json
import math
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import devtrace  # noqa: E402
import harness  # noqa: E402
import program_trace  # noqa: E402

FIXTURES = BENCH / "tests" / "fixtures"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ENGINE_READERS = ("prefill_p90_ms.serve", "prefill_pad_share.serve",
                  "engine_host_ms_per_tick.serve")


class Ctx:
    peak = PEAK

    def work(self, family):
        return harness.work_family(ROOT, family)


def _reader(name):
    return harness.load_module(BENCH / "layer_metrics" / f"{name}.py")


def hand_record():
    """A 1 s window on one device, idle in (0.3, 0.32) and (0.6, 0.66);
    two engine ticks inside it, one span before it and one after."""
    admit = lambda n, w, bucket, tokens: {
        "n": n, "width": w, "bucket": bucket, "tokens": tokens,
        "launched": w * bucket}
    return {
        "window_s": 1.0,
        "host": [["bench.tick", 0.0, 0.5], ["bench.tick", 0.5, 1.0]],
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 0.0, 0.3], ["fusion.2", 0.32, 0.28],
                    ["fusion.3", 0.66, 0.34]],
            "modules": []}},
        "program": [
            ["repro.engine.admit", -0.2, -0.1, admit(1, 1, 1024, 100)],
            ["repro.engine.tick", 0.0, 0.5, {"live": 2, "queued": 1}],
            ["repro.engine.admit", 0.01, 0.315, admit(1, 1, 1024, 600)],
            ["repro.engine.decode", 0.315, 0.5, {"live": 3}],
            ["repro.engine.tick", 0.5, 1.0, {"live": 3, "queued": 2}],
            ["repro.engine.admit", 0.52, 0.62, admit(2, 2, 2048, 3000)],
            ["repro.engine.bookkeep", 0.62, 0.67, {"retired": 1}],
            ["repro.engine.tick", 1.2, 1.3, {"live": 3, "queued": 0}],
        ],
        "runtime": [],
        "host_record": {"kind": "serve"},
    }


def test_engine_readers_on_hand_record():
    rec = hand_record()
    # launches in the window: 0.305 s for one request, 0.1 s for two
    assert _reader("prefill_p90_ms.serve").read(rec, Ctx()) == pytest.approx(
        305.0)
    # 3,600 real tokens in 1,024 + 2 x 2,048 launched
    assert _reader("prefill_pad_share.serve").read(
        rec, Ctx()) == pytest.approx(100 * (1 - 3600 / 5120))
    # 20 ms idle in the first tick, 60 ms in the second
    assert _reader("engine_host_ms_per_tick.serve").read(
        rec, Ctx()) == pytest.approx(40.0)


def test_idle_named_by_innermost_program_span():
    rec = hand_record()
    assert dict(devtrace.idle_by_host(rec)) == pytest.approx(
        {"bench.tick": 0.08})
    assert dict(program_trace.idle_by_span(rec)) == pytest.approx(
        {"repro.engine.admit": 0.02, "repro.engine.bookkeep": 0.06})


def test_runtime_lines_overlap_long_gaps():
    rec = hand_record()
    assert program_trace.long_gaps(rec) == [pytest.approx((0.6, 0.66))]
    lines = [["python", "x", 0.64, 0.7], ["tpu", "Execute", 0.1, 0.2],
             ["python", "TransferFromDevice", 0.59, 0.61],
             ["python", "short gap", 0.305, 0.31]]
    assert program_trace.runtime_lines(rec, lines) == [lines[2], lines[0]]


@pytest.mark.parametrize("name", ENGINE_READERS)
def test_engine_readers_find_nothing_without_program_spans(name):
    rec = dict(hand_record())
    del rec["program"]
    assert _reader(name).read(rec, Ctx()) is None


# What the existing readers and the breakdown read on the records taken
# before the program had spans (the same code before and after them).
BEFORE = {
    "causal16k": ({"fa2_bwd_roofline.train": 52.150260411536465,
                   "fa2_fwd_roofline.train": 15.302311354763376,
                   "idle_share.train": 1.2190080265169434,
                   "step_mfu.train": 46.48571736124402},
                  [["bench.step", 0.026191366],
                   ["bench.loss_sync", 0.005103674000000686],
                   ["host idle", 1.999999943436137e-09]]),
    "causal2k": ({"fa2_bwd_roofline.train": 38.733449323298835,
                  "fa2_fwd_roofline.train": 10.857100798639472,
                  "idle_share.train": 1.7064047955098416,
                  "step_mfu.train": 53.892908801913414},
                 [["bench.step", 0.025863395000000185],
                  ["bench.loss_sync", 0.0045010919999975245]]),
    "chat": ({"fa2_decode_roofline.serve": 0.9333467267945603,
              "idle_share.serve": 2.203857824701083,
              "queue_wait_p90_ms.serve": 0.2844699999988079,
              "step_mfu.serve": 4.673020980868539},
             [["bench.tick", 0.02025636399995387]]),
}


@pytest.mark.parametrize("fixture", sorted(BEFORE))
def test_records_without_program_spans_read_as_before(fixture):
    rec = json.loads((FIXTURES / f"{fixture}.rec.json").read_text())
    metrics, idle = BEFORE[fixture]
    got = {}
    for f in sorted((BENCH / "layer_metrics").glob("*.py")):
        v = harness.load_module(f).read(rec, Ctx())
        if v is not None:
            got[f.stem] = v
    assert got == pytest.approx(metrics, rel=1e-12)
    assert program_trace.breakdown(rec) == devtrace.breakdown(rec)
    gaps = devtrace.idle_by_host(rec)
    assert [g[0] for g in gaps] == [g[0] for g in idle]
    assert [g[1] for g in gaps] == pytest.approx([g[1] for g in idle],
                                                 rel=1e-12)


def test_recorded_program_spans():
    """The cut of a traced chat run: every engine reader reads a finite
    number, and the engine's spans name nine tenths or more of the idle time
    that ``devtrace`` files under ``bench.tick``."""
    rec = json.loads((FIXTURES / "chat_program.rec.json").read_text())
    assert program_trace.spans(rec, "repro.engine.admit")
    for name in ENGINE_READERS:
        v = _reader(name).read(rec, Ctx())
        assert v is not None and math.isfinite(v) and v >= 0, (name, v)
    under_tick = dict(devtrace.idle_by_host(rec)).get("bench.tick", 0.0)
    engine = sum(t for n, t in program_trace.idle_by_span(rec)
                 if n.startswith("repro.engine."))
    assert under_tick > 0 and engine >= 0.9 * under_tick


def test_program_spans_read_from_a_profile(monkeypatch):
    """A window traced on the CPU: the recorder's scoped spans come back
    as ``repro.*`` on the window's clock with their numeric args; spans it
    keeps off the profiler and the ``bench.*`` record do not change."""
    from repro.obs import TraceRecorder

    tr = TraceRecorder(process="test")
    monkeypatch.setattr(devtrace, "read_trace", program_trace.read_trace)
    with devtrace.Window(trace=True) as win:
        with devtrace.span("bench.tick"):
            with tr.span("engine.tick", args={"live": 2, "label": "x"}):
                with tr.span("engine.admit", args={"tokens": 600}) as args:
                    args["launched"] = 1024
            with tr.span("structure", profile=False):
                pass
    rec = win.record()
    assert [h[0] for h in rec["host"]] == ["bench.tick"]
    prog = {n: (a, b, args) for n, a, b, args in rec["program"]}
    assert set(prog) == {"repro.engine.tick", "repro.engine.admit"}
    tick, admit = prog["repro.engine.tick"], prog["repro.engine.admit"]
    assert tick[2] == {"live": 2}
    assert admit[2] == {"tokens": 600, "launched": 1024}
    (bench_tick,) = devtrace.host_spans(rec, "bench.tick")
    assert 0 <= bench_tick[0] <= tick[0] <= admit[0]
    assert admit[1] <= tick[1] <= bench_tick[1] <= rec["window_s"]


def test_fixture_cut_starts_at_an_admission_tick():
    tool = harness.load_module(BENCH / "tools" / "trace_program.py",
                               "bench_trace_program")
    rec = hand_record()
    rec["host"] = [["bench.tick", -0.4, -0.05], ["bench.tick", 0.0, 0.5],
                   ["bench.tick", 0.5, 1.0]]
    rec["host_record"] = {"kind": "serve", "window_s": 1.0, "ticks": [
        [-0.4, -0.05, [5]], [0.0, 0.5, [5, 6]], [0.5, 1.0, [6, 7]]]}
    cut = tool.fixture(rec, 0.4)
    # from 1 ms before the first tick with an admission to its end
    assert cut["window_s"] == pytest.approx(0.501)
    assert cut["host"] == [["bench.tick", pytest.approx(0.001),
                            pytest.approx(0.501)]]
    assert [p[0] for p in cut["program"]] == [
        "repro.engine.tick", "repro.engine.admit", "repro.engine.decode"]
    assert cut["host_record"]["ticks"] == [
        [pytest.approx(0.001), pytest.approx(0.501), [5, 6]]]
    assert [o[0] for o in cut["devices"]["/device:TPU:0"]["ops"]] == [
        "fusion.1", "fusion.2"]
