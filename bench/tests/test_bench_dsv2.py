"""The DeepSeek-V2 serving cell (``dsv2lite-serve.longctx``) on the CPU: its
configuration file against the program, the weights it makes, its work
functions and readers on hand-made records, and a whole run at tiny widths
through ``run.py`` (sound runs correct, the fp8 control not)."""

import dataclasses
import json
import math
import pathlib
import sys

import jax
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import model  # noqa: E402
from bench_tiny import make_tiny  # noqa: E402

CELL = "dsv2lite-serve.longctx"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**33 + 29
run = harness.load_module(BENCH / "run.py", "bench_run_dsv2")


class Ctx:
    peak = PEAK

    def work(self, family):
        return harness.work_family(ROOT, family)


def _reader(name):
    return harness.load_module(BENCH / "layer_metrics" / f"{name}.py")


def test_program_config_accepts_the_file():
    cell = harness.load_cell(ROOT, CELL)
    cfg = model.program_config(cell.config)
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.num_groups) == (6, 1, 5)
    assert cfg.vocab_size == 102_400 and cfg.mla.kv_lora_rank == 512
    harness.traffic_kind(cell).check_config(cell.config, cfg)
    bad = dict(cell.config, n_routed_experts=8)
    with pytest.raises(ValueError, match="n_routed_experts"):
        harness.traffic_kind(cell).check_config(bad, cfg)


def test_make_weights_builds_the_program_tree():
    """Shapes only (the real tree is 6.85 GB): the program's layout and
    types, every 1-D leaf a gain."""
    from repro.models import lm

    cfg = model.program_config(harness.load_cell(ROOT, CELL).config)
    got = jax.eval_shape(lambda: model.make_weights(cfg, jax.random.PRNGKey(0)))
    want = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    leaves = jax.tree_util.tree_flatten_with_path(got)[0]
    for (path, g), w in zip(leaves, jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype), path
        if g.ndim == 1:
            assert str(path[-1].key) in model.GAINS, path
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(got))
    assert 3.40e9 < n < 3.45e9  # 3.42 B parameters, 6.85 GB in bf16


def test_mla_decode_hand_count():
    w = harness.work_family(ROOT, "mla_decode").work
    flops, io = w(lens=[10, 20], heads=16, kv_lora_rank=512, rope_dim=64)
    assert flops == 2 * 16 * (576 + 512) * 30
    assert io == 2 * (576 * 30 + 16 * (576 + 512) * 2)


def test_moe_ffn_hand_count():
    w = harness.work_family(ROOT, "moe_ffn").work
    flops, io = w(tokens=7, experts_touched=40, d=2048, d_expert=1408, top_k=6)
    assert flops == 6 * 3 * 2 * 2048 * 1408 * 7
    assert io == 2 * 3 * 2048 * 1408 * 40


def hand_record():
    """A 1 s window: two decode ticks (2 and 3 requests), one prefill
    launch; mla_decode_paged 1 ms and moe_gmm 30 ms of device time."""
    c = json.loads((ROOT / "bench/configs/deepseek-v2-lite-6L-serve.json")
                   .read_text())
    ops = [["mla_decode_paged.1", 0.1, 0.0004], ["mla_decode_paged.2", 0.5, 0.0006],
           ["moe_gmm.3", 0.2, 0.01], ["moe_gmm.4", 0.6, 0.02],
           ["moe_gmm.5", 1.5, 0.5]]  # the last one after the window
    return {"window_s": 1.0, "host": [],
            "devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "host_record": {
                "kind": "serve", "config": c,
                "ticks": [[0.1, 0.2, [1000, 3000]], [0.5, 0.6, [100, 200, 300]]],
                "moe": {"admit": [[0.05, 4096, 320]],
                        "decode": [[0.1, 2, 50], [0.5, 3, 70]]}}}


def test_readers_on_hand_record():
    rec = hand_record()
    per_tok = 2 * 576
    least = (max(2 * 16 * 1088 * 4000 / 197e12,
                 (per_tok * 4000 + 2 * 16 * 1088 * 2) / 819e9)
             + max(2 * 16 * 1088 * 600 / 197e12,
                   (per_tok * 600 + 2 * 16 * 1088 * 3) / 819e9))
    assert _reader("mla_decode_roofline.serve").read(rec, Ctx()) == (
        pytest.approx(100 * least * 6 / 0.001))
    expert = 3 * 2048 * 1408
    least = sum(max(2 * expert * 6 * t * 5 / 197e12, 2 * expert * x / 819e9)
                for t, x in ((4096, 320), (2, 50), (3, 70)))
    assert _reader("moe_ffn_roofline.serve").read(rec, Ctx()) == (
        pytest.approx(100 * least / 0.03))


def test_readers_find_nothing_without_the_model():
    rec = hand_record()
    rec["host_record"]["config"] = {"num_attention_heads": 32}
    del rec["host_record"]["moe"]
    for name in ("mla_decode_roofline.serve", "moe_ffn_roofline.serve"):
        assert _reader(name).read(rec, Ctx()) is None, name


# ------------------------------------------------------------ a tiny run


def _tiny_arch():
    from repro.configs import registry
    from repro.configs.base import MLAConfig

    cfg = registry.get("deepseek-v2-lite")
    return dataclasses.replace(
        cfg, d_model=256, num_heads=4, num_kv_heads=4, head_dim=48, d_ff=512,
        vocab_pad_to=64,
        moe=dataclasses.replace(cfg.moe, num_experts=8, top_k=2, d_expert=128),
        mla=MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                      v_head_dim=32))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The benchmark tree with this cell at tiny widths: the published
    routing, norm and rope constants, small sizes."""
    root = make_tiny(tmp_path_factory.mktemp("tiny_dsv2"))
    path = root / "bench/configs/deepseek-v2-lite-6L-serve.json"
    c = json.loads((ROOT / "bench/configs/deepseek-v2-lite-6L-serve.json")
                   .read_text())
    c.update(hidden_size=256, num_attention_heads=4, num_key_value_heads=4,
             head_dim=48, intermediate_size=512, kv_lora_rank=64,
             qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
             n_routed_experts=8, num_experts_per_tok=2,
             moe_intermediate_size=128, vocab_size=2000,
             num_hidden_layers=3)
    c["program"]["override"] = {"vocab_pad_to": 64}
    c["engine"].update(max_batch=4, num_pages=200, pages_per_seq_max=24,
                       prompt_pad=128, prefill_token_budget=256,
                       admit_widths_warmed=[1, 2])
    path.write_text(json.dumps(c))
    tr = root / "bench/traffic/longctx.json"
    t = json.loads(tr.read_text())
    t.update(rate_per_s=2.0, warmup_s=1, drain_s=60, check={"requests": 3},
             prompt={"median": 100, "sigma": 0.5, "min": 16, "max": 256},
             output={"median": 8, "sigma": 0.5, "min": 2, "max": 32})
    tr.write_text(json.dumps(t))
    lim = root / f"bench/limits/{CELL}.json"
    limits = json.loads(lim.read_text())
    limits["served_gap"]["limit"] = TINY_LIMIT
    limits["served_gap_mean"]["limit"] = TINY_MEAN_LIMIT
    lim.write_text(json.dumps(limits))
    return root


# at these widths, on the test's three requests over 5 seeds (CPU readings):
# the widest gap reads 0 to 0.040 in sound runs, 0.202 to 0.460 in the fp8
# control; the mean gap 0 to 7.2e-4 sound, 0.0123 to 0.0359 control
TINY_LIMIT = 0.1
TINY_MEAN_LIMIT = 0.003


@pytest.fixture()
def tiny_arch(monkeypatch):
    from repro.configs import registry

    monkeypatch.setitem(registry._BY_NAME, "deepseek-v2-lite", _tiny_arch())


def test_sound_run_is_correct(tiny, tiny_arch, monkeypatch, capsys):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    cell = harness.load_cell(tiny, CELL)
    kind = harness.traffic_kind(cell)
    assert set(kind.warm_plan(kind.build_engine(cell, None), cell)) == {
        (128, 1), (128, 2), (256, 1)}  # 256 x 2 is over the token budget
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                   "--trace", "0"], root=tiny,
                  require=lambda n: jax.devices()[:n])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0


def test_control_fails_the_serve_comparison(tiny, tiny_arch):
    """The reference in fp8, the precision below the configuration's bf16,
    put in the program's place, reads above the limit; the program's own
    tokens read under it. The engine's routing spans reach the host
    record's reader."""
    cell = harness.load_cell(tiny, CELL)
    kind = harness.traffic_kind(cell)
    from repro.serving.engine import Request

    cfg = model.program_config(cell.config)
    params = model.make_weights(cfg, harness.jax_key(SEED))
    engine = kind.build_engine(cell, params, trace=True)
    rng = jax.random.PRNGKey(0)
    reqs = []
    for i, n in enumerate((40, 200, 90)):
        prompt = jax.random.randint(jax.random.fold_in(rng, i), (n,), 1,
                                    cfg.vocab_size)
        reqs.append(Request(rid=i, prompt=[int(t) for t in prompt],
                            max_new_tokens=24))
        engine.submit(reqs[-1])
    engine.run()
    moe = kind.routing(engine, engine.tracer._t0, engine.tracer._t0 + 1e9)
    assert [a[1] for a in moe["admit"]] == [130, 200]  # 40 + 90, then 200
    assert all(0 < a[2] <= 2 * 8 for a in moe["admit"] + moe["decode"])
    control = kind.served_readings(params, cell, reqs, quant="fp8")
    assert control["served_gap_mean"] > cell.limits["served_gap_mean"]["limit"]
    assert not harness.checks_pass(harness.judge(control, cell.limits))
    sound = kind.served_readings(params, cell, reqs)
    assert harness.checks_pass(harness.judge(sound, cell.limits)), sound
    assert kind.served_gaps(params, cell, reqs) == sound["served_gap"]


def test_readers_on_recorded_window():
    """A 0.4-s cut of a traced chip run of the cell (seed 3150015001 at
    0.8 req/s): one whole 5,178-token admission and four decode ticks. The
    two kernel shares read within (0, 100], from the families' own events."""
    import devtrace

    rec = json.loads((BENCH / "tests/fixtures/longctx.rec.json").read_text())
    moe = rec["host_record"]["moe"]
    assert [a[1] for a in moe["admit"]] == [5178] and len(moe["decode"]) == 4
    assert devtrace.family_ops(rec, "mla_decode_paged")
    assert devtrace.family_ops(rec, "moe_gmm")
    for name in ("mla_decode_roofline.serve", "moe_ffn_roofline.serve"):
        v = _reader(name).read(rec, Ctx())
        assert v is not None and 0 < v <= 100, (name, v)
