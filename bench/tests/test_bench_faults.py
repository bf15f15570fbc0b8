"""A whole run of each cell, on the CPU at tiny widths, with the timed path
broken underneath: ``correct`` has to come out false for every fault the
cell can have. The same run unbroken comes out correct. The look for a chip
is skipped; everything else is what ``run.py`` does."""

import json
import pathlib
import sys

import jax
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import harness  # noqa: E402
from bench_tiny import make_tiny  # noqa: E402

# under a name of its own: other test files import a module named ``run``
run = harness.load_module(BENCH / "run.py", "bench_run")

SEED = 2**33 + 17  # wider than 32 bits, as the driver's seeds are


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny(tmp_path_factory.mktemp("tiny"))


def run_cell(root, workload, monkeypatch, capsys):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    rc = run.main(["--workload", workload, "--seed", str(SEED), "--seconds",
                   "1", "--trace", "0"], root=root,
                  require=lambda n: jax.devices()[:n])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


TRAIN = "qwen3-train.causal16k"
SERVE = "qwen3-serve.chat"


def test_sound_train_run_is_correct(tiny, monkeypatch, capsys):
    assert run_cell(tiny, TRAIN, monkeypatch, capsys)["correct"] is True


def _broken_train_step(monkeypatch, fault):
    from repro.launch import steps

    build = steps.build_train_step

    def broken(*a, **kw):
        step = build(*a, **kw)

        def faulty(params, opt, batch):
            if fault == "half_batch":
                half = batch["inputs"].shape[0] // 2
                return step(params, opt, {k: v[:half] for k, v in batch.items()})
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics  # state unchanged

        return faulty

    monkeypatch.setattr(steps, "build_train_step", broken)


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_broken_train_step_is_not_correct(tiny, monkeypatch, capsys, fault):
    _broken_train_step(monkeypatch, fault)
    line = run_cell(tiny, TRAIN, monkeypatch, capsys)
    assert line["correct"] is False, line["checks"]


def test_sound_serve_run_is_correct(tiny, monkeypatch, capsys):
    assert run_cell(tiny, SERVE, monkeypatch, capsys)["correct"] is True


def test_altered_token_is_not_correct(tiny, monkeypatch, capsys):
    from repro.serving import engine

    build = engine.build_paged_serve_step

    def broken(cfg, attn):
        step = build(cfg, attn)

        def faulty(*args):
            tok, caches = step(*args)
            return (tok + 1) % cfg.vocab_size, caches  # a token altered

        return faulty

    monkeypatch.setattr(engine, "build_paged_serve_step", broken)
    line = run_cell(tiny, SERVE, monkeypatch, capsys)
    assert line["correct"] is False, line["checks"]


def test_control_fails_the_train_comparison(tiny):
    """The reference in fp8, the precision below the configuration's bf16,
    put in the program's place, fails at least one compared number."""
    cell = harness.load_cell(tiny, TRAIN)
    kind = harness.traffic_kind(cell)
    from ref import qwen3 as ref

    dims = ref.Dims.from_config(cell.config)
    opt = ref.Adam(**cell.traffic["optimizer"])
    batches = kind.build_batches(cell, SEED)
    weights = lambda: kind._host_weights(cell, SEED)
    want = ref.train_readings(weights, batches, dims, opt)
    got = ref.train_readings(weights, batches, dims, opt, quant="fp8")
    checks = harness.judge(kind.compare(got, want), cell.limits)
    assert not harness.checks_pass(checks), checks


def test_control_fails_the_serve_comparison(tiny):
    cell = harness.load_cell(tiny, SERVE)
    kind = harness.traffic_kind(cell)
    import model
    from repro.serving.engine import Request

    cfg = model.program_config(cell.config)
    params = model.make_weights(cfg, harness.jax_key(SEED))
    engine = kind.build_engine(cell, params)
    rng = jax.random.PRNGKey(0)
    reqs = []
    for i, n in enumerate((40, 200, 90)):
        prompt = jax.random.randint(jax.random.fold_in(rng, i), (n,), 1,
                                    cfg.vocab_size)
        reqs.append(Request(rid=i, prompt=[int(t) for t in prompt],
                            max_new_tokens=24))
        engine.submit(reqs[-1])
    engine.run()
    checks = harness.judge(
        {"served_gap": kind.served_gaps(params, cell, reqs, quant="fp8")},
        cell.limits)
    assert not harness.checks_pass(checks), checks
    sound = kind.served_gaps(params, cell, reqs)
    assert sound <= cell.limits["served_gap"]["limit"], sound
