"""End-to-end behaviour tests for the paper's system: learning happens,
restarts resume exactly, grad accumulation is equivalent, the registry
matches the assigned table, every dry-run cell has well-formed specs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import SHAPES
from repro.core.attention import AttentionConfig
from repro.launch.steps import build_train_step
from repro.launch.train import PRESETS, TrainLoopConfig, train
from repro.models import lm
from repro.training.optimizer import AdamWConfig, init_opt_state

ATTN = AttentionConfig(impl="flash_xla", block_q=64, block_kv=64)


@pytest.mark.slow
def test_training_learns(tmp_path):
    cfg = PRESETS["gpt-20m"]
    loop = TrainLoopConfig(steps=25, seq_len=64, batch_size=4,
                           ckpt_dir=None, log_every=100)
    _, _, hist = train(cfg, loop, AdamWConfig(lr=2e-3, warmup_steps=5, total_steps=25))
    assert np.mean(hist["loss"][-3:]) < np.mean(hist["loss"][:3]) - 0.1


def test_packed_training_learns(tmp_path):
    """Varlen packed batches: loss drops AND the loss mask keeps padding /
    cross-segment boundaries out of the objective."""
    cfg = PRESETS["gpt-20m"]
    loop = TrainLoopConfig(steps=12, seq_len=64, batch_size=4,
                           ckpt_dir=None, log_every=100, packed=True)
    _, _, hist = train(cfg, loop, AdamWConfig(lr=2e-3, warmup_steps=4, total_steps=12))
    assert np.isfinite(hist["loss"]).all()
    assert np.mean(hist["loss"][-3:]) < np.mean(hist["loss"][:3])


@pytest.mark.slow
def test_restart_resumes_exactly(tmp_path):
    """Train 8 steps straight vs 4 + restore + 4: identical final loss."""
    cfg = PRESETS["gpt-20m"]
    kw = dict(seq_len=64, batch_size=4, log_every=100)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=8)

    _, _, h_straight = train(cfg, TrainLoopConfig(steps=8, **kw), opt)

    ckpt = str(tmp_path / "ck")
    _, _, _ = train(cfg, TrainLoopConfig(steps=4, ckpt_dir=ckpt, ckpt_every=4, **kw), opt)
    _, _, h_resumed = train(cfg, TrainLoopConfig(steps=8, ckpt_dir=ckpt, ckpt_every=4, **kw), opt)

    assert h_resumed["restored_at"] == 4
    np.testing.assert_allclose(
        h_straight["loss"][4:], h_resumed["loss"], rtol=2e-4, atol=2e-4,
    )


def test_grad_accumulation_equivalent():
    cfg = registry.reduce_config(registry.get("qwen3-8b"))
    params = lm.init_lm(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    batch = {
        "inputs": jnp.asarray(np.random.default_rng(0).integers(0, 100, (4, 32)), jnp.int32),
        "targets": jnp.asarray(np.random.default_rng(1).integers(0, 100, (4, 32)), jnp.int32),
    }
    p1, _, m1 = jax.jit(build_train_step(cfg, ATTN, AdamWConfig()))(params, opt, batch)
    p2, _, m2 = jax.jit(
        build_train_step(cfg, ATTN, AdamWConfig(), microbatches=2)
    )(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------- registry

_ASSIGNED = {
    "whisper-base": dict(num_layers=6, d_model=512, num_heads=8, d_ff=2048),
    "granite-moe-1b-a400m": dict(num_layers=24, d_model=1024, num_heads=16),
    "mixtral-8x22b": dict(num_layers=56, d_model=6144, num_heads=48, d_ff=16384),
    "gemma3-1b": dict(num_layers=26, d_model=1152, num_heads=4, num_kv_heads=1),
    "qwen3-8b": dict(num_layers=36, d_model=4096, num_heads=32, num_kv_heads=8),
    "deepseek-coder-33b": dict(num_layers=62, d_model=7168, num_heads=56, d_ff=19200),
    "stablelm-12b": dict(num_layers=40, d_model=5120, num_heads=32, d_ff=13824),
    "falcon-mamba-7b": dict(num_layers=64, d_model=4096),
    "internvl2-76b": dict(num_layers=80, d_model=8192, num_heads=64, d_ff=28672),
    "hymba-1.5b": dict(num_layers=32, d_model=1600, num_heads=25, num_kv_heads=5),
    "deepseek-v2-lite": dict(num_layers=27, d_model=2048, num_heads=16,
                             d_ff=10944, vocab_size=102400, first_dense_layers=1),
}


def test_all_assigned_archs_present():
    assert sorted(registry.names()) == sorted(_ASSIGNED)


@pytest.mark.parametrize("arch", sorted(_ASSIGNED))
def test_assigned_dims_exact(arch):
    cfg = registry.get(arch)
    for field, want in _ASSIGNED[arch].items():
        assert getattr(cfg, field) == want, f"{arch}.{field}"


def test_moe_configs():
    g = registry.get("granite-moe-1b-a400m").moe
    assert (g.num_experts, g.top_k) == (32, 8)
    m = registry.get("mixtral-8x22b").moe
    assert (m.num_experts, m.top_k) == (8, 2)
    d = registry.get("deepseek-v2-lite").moe
    assert (d.num_experts, d.top_k, d.num_shared, d.norm_topk) == (64, 6, 2, False)


def test_every_cell_has_specs_or_skip():
    """All 44 (arch x shape) cells: either a skip reason or well-formed
    ShapeDtypeStruct specs with the cell's batch/seq."""
    n_ok = n_skip = 0
    for arch in registry.names():
        cfg = registry.get(arch)
        for shape in SHAPES.values():
            if registry.skip_reason(cfg, shape):
                n_skip += 1
                continue
            specs = registry.input_specs(cfg, shape)
            n_ok += 1
            if shape.kind in ("train", "prefill"):
                assert specs["inputs"].shape == (shape.global_batch, shape.seq_len)
            else:
                assert specs["token"].shape == (shape.global_batch, 1)
                leaves = jax.tree.leaves(specs["caches"])
                assert leaves, f"{arch}: empty cache specs"
    assert n_ok + n_skip == 44 and n_skip == 7
