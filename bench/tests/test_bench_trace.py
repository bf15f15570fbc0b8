"""The reduction from a trace record to device busy time, idle gaps, kernel
and module time, and the per-layer metrics read from it: on a hand-made
record whose answers are known, and on a small record taken from a traced
run on a TPU v5e (``fixtures/``)."""

import json
import math
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402

FIXTURES = BENCH / "tests" / "fixtures"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


class Ctx:
    peak = PEAK

    def work(self, family):
        return harness.work_family(ROOT, family)


def hand_record():
    """A 1 s window on one device: ops cover [0.1, 0.3] and [0.25, 0.5]
    (overlapping) and [0.8, 0.9]; one op starts before the window."""
    return {
        "window_s": 1.0,
        "host": [["bench.step", 0.05, 0.6], ["bench.loss_sync", 0.6, 0.95]],
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", -0.2, 0.25],
                    ["fa2_fwd_compact.2", 0.1, 0.2],
                    ["fa2_bwd_fused_compact.3", 0.25, 0.25],
                    ["while.4", 0.1, 0.4],
                    ["fusion.1", 0.8, 0.1]],
            "modules": [["jit_train_step", 0.1, 0.4]]}},
    }


def test_union_and_busy():
    assert devtrace.union([(0.3, 0.4), (0.1, 0.2), (0.15, 0.25)]) == [
        (0.1, 0.25), (0.3, 0.4)]
    rec = hand_record()
    # [0, 0.05] from the early op, [0.1, 0.5], [0.8, 0.9]
    assert devtrace.busy_s(rec) == pytest.approx(0.05 + 0.4 + 0.1)
    assert devtrace.idle_share(rec) == pytest.approx(0.45)


def test_op_name_and_family():
    assert devtrace.op_name(
        "%fa2_fwd_compact.18 = (bf16[64,16384,128]) custom-call(...)"
    ) == "fa2_fwd_compact.18"
    rec = hand_record()
    assert [o[0] for o in devtrace.family_ops(rec, "fa2_fwd")] == [
        "fa2_fwd_compact.2"]
    assert [o[0] for o in devtrace.family_ops(rec, "fa2_bwd")] == [
        "fa2_bwd_fused_compact.3"]


def test_idle_named_by_host_span():
    gaps = dict(devtrace.idle_by_host(hand_record()))
    # gaps (0.05, 0.1): midpoint under bench.step; (0.5, 0.8) and
    # (0.9, 1.0): midpoints 0.65 and 0.95 under bench.loss_sync
    assert gaps["bench.step"] == pytest.approx(0.05)
    assert gaps["bench.loss_sync"] == pytest.approx(0.3 + 0.1)
    assert sum(gaps.values()) == pytest.approx(0.45)


def test_top_ops_sums_by_name_without_loops():
    top = dict(devtrace.top_ops(hand_record()))
    assert top["fusion.1"] == pytest.approx(0.05 + 0.1)
    assert top["fa2_bwd_fused_compact.3"] == pytest.approx(0.25)
    assert "while.4" not in top  # its body's ops are listed themselves


def _reader(name):
    return harness.load_module(BENCH / "layer_metrics" / f"{name}.py")


def test_train_readers_on_hand_record():
    rec = hand_record()
    cfg = json.loads((BENCH / "configs" / "qwen3-8b-2L-train.json").read_text())
    rec["host_record"] = {"kind": "train", "steps": 1, "window_s": 1.0,
                          "rows": 2, "seq": 16384, "chips": 1, "config": cfg}
    fwd = Ctx().work("fa2_fwd").work(batch=2, s_q=16384, s_kv=16384, heads=32,
                                     kv_heads=8, head_dim=128)
    least = max(fwd[0] / PEAK["flops_per_s"], fwd[1] / PEAK["hbm_bytes_per_s"])
    got = _reader("fa2_fwd_roofline.train").read(rec, Ctx())
    assert got == pytest.approx(100 * 2 * least / 0.2)  # 2 layers, 1 step
    assert _reader("idle_share.train").read(rec, Ctx()) == pytest.approx(45.0)
    flops = Ctx().work("train_step").flops(cfg, 2, 16384)
    assert _reader("step_mfu.train").read(rec, Ctx()) == pytest.approx(
        100 * flops / PEAK["flops_per_s"])
    assert _reader("queue_wait_p90_ms.serve").read(rec, Ctx()) is None


def test_readers_find_nothing_in_an_empty_window():
    rec = {"window_s": 1.0, "host": [], "devices": {},
           "host_record": {"kind": "serve", "ticks": [], "queue_wait_s": [],
                           "config": {}, "chips": 1, "window_s": 1.0}}
    for name in ("step_mfu.serve", "fa2_decode_roofline.serve",
                 "idle_share.serve", "queue_wait_p90_ms.serve"):
        assert _reader(name).read(rec, Ctx()) is None, name


@pytest.mark.parametrize("path", sorted(FIXTURES.glob("*.rec.json")),
                         ids=lambda p: p.name)
def test_recorded_trace(path):
    """Every per-layer metric that reads something from a real record reads
    a share within (0, 100] and the breakdown holds at most ten entries."""
    rec = json.loads(path.read_text())
    assert 0 < devtrace.busy_s(rec) <= rec["window_s"]
    out = {}
    for f in sorted((BENCH / "layer_metrics").glob("*.py")):
        v = harness.load_module(f).read(rec, Ctx())
        if v is not None:
            out[f.stem] = v
            assert math.isfinite(v) and v > 0, (f.stem, v)
            if f.stem.split(".")[0].endswith(("roofline", "mfu", "share")):
                assert v <= 100.0, (f.stem, v)
    assert out, "no metric read anything"
    b = devtrace.breakdown(rec)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
