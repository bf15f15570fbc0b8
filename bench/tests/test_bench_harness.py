"""The harness without a chip: the result line's schema, the exit without a
TPU, and that a configuration, a traffic mix and a per-layer metric are
added as new files only."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402


def test_result_line_schema():
    checks = harness.judge({"loss_gap": 0.001},
                           {"loss_gap": {"limit": 0.01, "why": "x"}})
    line = json.loads(harness.result_line(
        correct=harness.checks_pass(checks), attempted=12, failed=0,
        metrics={"train_tokens_per_s": harness.metric_entry(1.5, "tokens/s")},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 7},
        checks=checks))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]  # checks last
    assert line["correct"] is True
    assert line["metrics"]["train_tokens_per_s"] == {"value": 1.5,
                                                     "unit": "tokens/s"}
    assert line["checks"]["loss_gap"] == {"value": 0.001, "limit": 0.01}


@pytest.mark.parametrize("value,ok", [(0.009, True), (0.011, False),
                                      (float("nan"), False)])
def test_checks_pass(value, ok):
    assert harness.checks_pass({"x": {"value": value, "limit": 0.01}}) is ok


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks(ROOT, "cpu")


def _no_tpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    """Run from a directory holding only BENCHMARK.json and the benchmark's
    files, with the CPU as the only backend."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = bench["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, *bench["command"], "--workload", name, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_no_tpu_env(), capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert "correct" not in out.stdout


def test_cells_name_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert (BENCH / "traffic" / f"{cell.traffic['kind']}.py").exists()
        assert cell.per_layer and cell.end_to_end
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert (BENCH / "layer_metrics" / f"{m['name']}.py").exists()


def test_new_config_traffic_and_metric_are_new_files_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "qwen3-8b-2L-train.json").read_text())
    cfg["num_hidden_layers"] = 4
    (b / "configs" / "qwen3-8b-4L-train.json").write_text(json.dumps(cfg))
    tr = json.loads((b / "traffic" / "causal16k.json").read_text())
    tr["seq_len"] = 8192
    (b / "traffic" / "causal8k.json").write_text(json.dumps(tr))
    (b / "limits" / "qwen3-4L.causal8k.json").write_text(
        (b / "limits" / "qwen3-train.causal16k.json").read_text())
    (b / "layer_metrics" / "steps.train.py").write_text(
        "def read(rec, ctx):\n    return float(rec['host_record']['steps'])\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="qwen3-8b-4L-train",
                                 file="bench/configs/qwen3-8b-4L-train.json"))
    bench["workloads"].append({"name": "qwen3-4L.causal8k",
                               "config": "qwen3-8b-4L-train",
                               "traffic": "causal8k", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "steps.train", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "model step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["qwen3-4L.causal8k"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(tmp_path, "qwen3-4L.causal8k")
    assert cell.config["num_hidden_layers"] == 4
    assert cell.traffic["seq_len"] == 8192
    assert harness.traffic_kind(cell).run
    metrics = harness.read_layer_metrics(
        cell, {"host_record": {"kind": "train", "steps": 3}, "devices": {}},
        None)
    assert metrics["steps.train"] == {"value": 3.0, "unit": "steps"}
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file that was there changed


def _serve_kind():
    return harness.traffic_kind(harness.load_cell(ROOT, "qwen3-serve.chat"))


def test_serve_schedule_is_fixed_and_at_the_quantiles():
    kind = _serve_kind()
    tr = harness.load_cell(ROOT, "qwen3-serve.chat").traffic
    plans, n_warm, n_win = kind.schedule(tr, 51)
    again, _, _ = kind.schedule(tr, 51)
    assert plans == again  # no seed reaches lengths, order or arrival times
    window = plans[n_warm:n_warm + n_win]
    p = tr["prompt"]
    assert sorted(x.prompt_len for x in window) == sorted(
        kind.lognormal_quantiles(n_win, p["median"], p["sigma"], p["min"],
                                 p["max"]).tolist())
    assert tr["warmup_s"] < window[0].due and window[-1].due == pytest.approx(
        tr["warmup_s"] + 51)


def test_window_metrics_counts_what_the_window_saw():
    kind = _serve_kind()

    class Seen:  # what a client recorded: due times and token times
        due = {1: 9.5, 2: 10.5, 3: 19.0}
        token_times = {1: [9.8, 10.2, 10.6], 2: [11.0, 11.5], 3: [],
                       0: [10.1]}

    seen = kind.window_metrics(Seen, [1, 2, 3], 10.0, 20.0, drain_end=30.0)
    assert seen["delivered"] == 5  # 10.2, 10.6, 11.0, 11.5 and 10.1
    assert seen["ttft"] == pytest.approx([0.3, 0.5, 11.0])
    assert seen["failed"] == 1  # request 3: no first token by the drain end
    assert sorted(seen["gaps"]) == pytest.approx([0.4, 0.4, 0.5])
