"""DeepSeek-V2 on the serving path at a small size on the CPU: latent
attention (prefill, then absorbed decode through latent pages), YaRN rope,
the dropless shared-expert MoE, and the kernels they add -- the forward
with V narrower than Q/K and the paged decode's latent mode -- each
against a plain reference (models/deepseek_v2_ref.py, kernels/ref.py)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.attention import AttentionConfig
from repro.core.masks import MaskSpec
from repro.kernels import ops
from repro.kernels.ref import attention_reference
from repro.launch.steps import build_paged_admit_step
from repro.models import deepseek_v2_ref as ref
from repro.models import layers as L
from repro.models import lm
from repro.models.mla import softmax_scale
from repro.models.moe import apply_moe_dropless
from repro.serving.engine import PagedServingEngine, Request

PALLAS = AttentionConfig(impl="flash_pallas", decode_splits=2)


@pytest.fixture(scope="module")
def small():
    cfg = registry.reduce_config(registry.get("deepseek-v2-lite"))
    return cfg, lm.init_lm(cfg, jax.random.PRNGKey(3))


def test_reduce_config_keeps_latent_attention_and_shared_experts(small):
    cfg, params = small
    full = registry.get("deepseek-v2-lite")
    assert cfg.mla is not None and cfg.moe.num_shared == full.moe.num_shared
    assert (cfg.moe.norm_topk, cfg.first_dense_layers) == (False, 1)
    assert cfg.layer_kinds() == ("attn",) * 3
    assert "router" not in params["lead"][0]["mlp"]
    assert "shared" in params["groups"]["slot_0"]["mlp"]


def test_yarn_frequencies_and_scale_match_the_formula():
    """DeepSeek-V2-Lite's published YaRN (theta 1e4, factor 40, original
    4,096, beta 32/1, rope width 64): pairs below the fast correction dim
    keep theta^(-2i/64), pairs above the slow one are divided by 40, and
    the pairs between blend linearly."""
    y = registry.get("deepseek-v2-lite").rope_scaling
    inv = np.asarray(L.yarn_inv_freq(64, 1e4, y))
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    corr = lambda rot: 64 * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(1e4))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    np.testing.assert_allclose(inv[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(64, 1e4, y)), want,
                               rtol=1e-6)
    cfg = registry.get("deepseek-v2-lite")
    assert softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)


def test_interleaved_rope_rotates_each_pair():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    inv = jnp.array([1.0, 0.5, 0.1, 0.01])
    pos = jnp.arange(5) * 3
    got = np.asarray(L.apply_rope_interleaved(x, pos, inv))
    xs = np.asarray(x)
    for t in range(5):
        for i in range(4):
            a = pos[t] * inv[i]
            rot = np.array([[np.cos(a), -np.sin(a)], [np.sin(a), np.cos(a)]])
            np.testing.assert_allclose(got[0, t, :, 2 * i: 2 * i + 2],
                                       xs[0, t, :, 2 * i: 2 * i + 2] @ rot.T,
                                       rtol=1e-5, atol=1e-6)


def test_dropless_moe_same_alone_and_in_a_batch(small):
    """A token's output does not depend on the tokens routed beside it,
    and matches the reference's routed + shared experts."""
    cfg, params = small
    p = params["groups"]["slot_0"]["mlp"]
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, cfg.d_model))
    batch, stats = apply_moe_dropless(p, cfg, x)
    for b, s in ((0, 0), (1, 4), (2, 6)):
        alone, _ = apply_moe_dropless(p, cfg, x[b:b + 1, s:s + 1])
        np.testing.assert_allclose(np.asarray(alone[0, 0]),
                                   np.asarray(batch[b, s]), rtol=1e-5, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, cfg, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(batch).reshape(-1, cfg.d_model),
                               np.asarray(want), rtol=2e-4, atol=2e-5)
    E = cfg.moe.num_experts
    assert int(stats[0]) == 21 and 0 < int(stats[1]) <= E
    assert int(stats[2]) <= 21
    # tokens outside ``valid`` go to no expert: the shared experts alone
    valid = jnp.zeros((3, 7), bool).at[0, 0].set(True)
    masked, st = apply_moe_dropless(p, cfg, x, valid)
    assert int(st[0]) == 1 and int(st[1]) == cfg.moe.top_k
    np.testing.assert_allclose(np.asarray(masked[0, 0]), np.asarray(batch[0, 0]),
                               rtol=1e-5, atol=1e-6)
    shared = L.apply_mlp(p["shared"], x, "swiglu")
    np.testing.assert_allclose(np.asarray(masked[1:]), np.asarray(shared[1:]),
                               rtol=1e-5, atol=1e-6)


def test_prefill_then_paged_decode_matches_the_reference(small):
    """Two sequences prefilled (right-padded to one bucket), their latent
    caches written to shuffled pages by the admission step, then six
    absorbed decode steps through the latent kernel: every logit as the
    reference's full forward over the same tokens. f32 weights and
    activations; the reference at highest precision. Tolerance: 1e-4 of
    the logits' scale, float32 reassociation across the kernels' blocks."""
    cfg, params = small
    ps, pool, n_pages = 8, 40, 10
    seqs = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (2, 48), 1,
                                         cfg.vocab_size))
    lens = np.array([29, 18])
    admit = jax.jit(build_paged_admit_step(cfg, PALLAS, ps))
    order = np.random.default_rng(0).permutation(np.arange(1, pool))
    table = np.zeros((2, n_pages), np.int32)
    table[0], table[1] = order[:n_pages], order[n_pages: 2 * n_pages]
    inputs = np.zeros((2, 32), np.int32)
    for b in range(2):
        inputs[b, : lens[b]] = seqs[b, : lens[b]]
    from repro.configs.registry import paged_cache_specs

    caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          paged_cache_specs(cfg, pool, ps))
    dest = table[:, :4]
    tok, lens_total, caches, stats = admit(
        params, {"inputs": jnp.asarray(inputs), "lens": jnp.asarray(lens)},
        caches, jnp.asarray(dest))
    assert list(np.asarray(lens_total)) == list(lens)
    assert np.asarray(stats).shape == (cfg.num_layers - 1, 3)
    assert list(np.asarray(stats)[:, 0]) == [sum(lens)] * (cfg.num_layers - 1)
    want = [np.asarray(ref.forward(cfg, params, jnp.asarray(seqs[b])))
            for b in range(2)]
    scale = max(float(np.abs(w).max()) for w in want)
    for b in range(2):
        assert int(tok[b, 0]) == int(np.argmax(want[b][lens[b] - 1]))
    step = jax.jit(lambda p, t, c, tb, n: lm.decode_step(
        cfg, p, t, c, n, PALLAS, block_table=tb, with_stats=True))
    cache_len = lens.copy()
    for _ in range(6):
        token = jnp.asarray(seqs[np.arange(2), cache_len][:, None])
        logits, caches, st = step(params, token, caches, jnp.asarray(table),
                                  jnp.asarray(cache_len))
        for b in range(2):
            np.testing.assert_allclose(
                np.asarray(logits[b, 0, : cfg.vocab_size]),
                want[b][cache_len[b]], atol=1e-4 * scale, rtol=0)
        assert list(np.asarray(st)[:, 0]) == [2] * (cfg.num_layers - 1)
        cache_len = cache_len + 1


def test_paged_engine_serves_the_reference_greedy_tokens(small):
    """The normal serving path: PagedServingEngine (bucketed admission
    split by the token budget, latent pages, absorbed decode) emits the
    reference's greedy tokens; its routing counters reach the registry,
    and every step updated the donated latent pool in place."""
    from repro.obs import TraceRecorder

    cfg, params = small
    eng = PagedServingEngine(cfg, params, PALLAS, max_batch=4, num_pages=48,
                             page_size=8, pages_per_seq_max=16, prompt_pad=32,
                             prefill_token_budget=64,
                             tracer=TraceRecorder(process="t"))
    assert [eng.admit_widths(b) for b in (32, 64, 96)] == [[1, 2], [1], [1]]
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                    max_new_tokens=5) for i, n in enumerate((20, 30, 25, 70))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        seq = jnp.asarray(r.prompt + r.generated)
        want = np.argmax(np.asarray(ref.forward(cfg, params, seq)), -1)
        n = len(r.prompt)
        assert r.generated == list(want[n - 1: n - 1 + len(r.generated)]), r.rid
    # three 32-token-bucket prompts split into launches of 2 and 1 rows
    launches = sorted((e["args"]["n"], e["args"]["width"], e["args"]["bucket"])
                      for e in eng.tracer.events if e["name"] == "engine.admit")
    assert launches == [(1, 1, 32), (1, 1, 96), (2, 2, 32)]
    decodes = [e["args"] for e in eng.tracer.events if e["name"] == "engine.decode"]
    assert all(0 < a["experts_touched"] <= a["live"] * 2 * 2 for a in decodes)
    snap = eng.snapshot()
    assert snap["serving/moe_experts_touched"] > 0
    assert snap["serving/moe_expert_load_max/count"] > 0
    calls = [e["args"]["pool_in_place"] for e in eng.tracer.events
             if e["name"] in ("engine.admit", "engine.decode")]
    assert calls and set(calls) == {1}
    assert snap["serving/pool_in_place_calls"] == len(calls)
    assert snap["serving/pool_copied_calls"] == 0


@pytest.mark.parametrize("schedule,splits", [("compact", 1), ("dense", 1),
                                             ("compact", 2)])
def test_forward_with_narrow_v_matches_reference(schedule, splits):
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k[0], (2, 96, 4, 24))
    kk = jax.random.normal(k[1], (2, 96, 4, 24))
    v = jax.random.normal(k[2], (2, 96, 4, 16))
    spec = MaskSpec(causal=True)
    o = ops.flash_attention_pallas(q, kk, v, spec, block_q=32, block_kv=32,
                                   schedule=schedule, kv_splits=splits)
    want = attention_reference(q, kk, v, spec)[0]
    assert o.shape == (2, 96, 4, 16)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("layer", [0, 2])
def test_latent_paged_kernel_matches_reference_and_ignores_unowned_pages(layer):
    """The paged kernel's latent mode (one 1-head plane read as K, its
    first r lanes as V) at ``layer`` of a stack of three layers' planes,
    against the dense reference on that layer's gathered cache and bitwise
    the call on that layer's planes alone; NaN in every page no slot owns,
    and in the stack's other layers, leaves the output bitwise equal."""
    B, H, W, r, ps, n_pages, pool = 3, 4, 128, 64, 8, 6, 24
    k = jax.random.split(jax.random.PRNGKey(4), 2)
    q = jax.random.normal(k[0], (B, 1, H, W))
    planes = jax.random.normal(k[1], (3, 1, pool, ps, W))
    lengths = jnp.array([37, 0, 9], jnp.int32)
    table = np.zeros((B, n_pages), np.int32)
    owned = np.random.default_rng(2).permutation(np.arange(1, pool))
    table[0, :5] = owned[:5]
    table[2, :2] = owned[5:7]
    run = lambda pl_, i=layer: ops.mla_decode_paged_pallas(
        q, pl_, lengths, jnp.asarray(table), i, v_dim=r, scale=0.3,
        num_splits=2)
    o, _ = run(planes)
    cache = planes[layer, 0][table].reshape(B, n_pages * ps, 1, W)
    want = attention_reference(q, cache, cache[..., :r], kv_length=lengths,
                               scale=0.3)[0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=1e-5)
    assert np.array_equal(np.asarray(run(planes[layer][None], 0)[0]),
                          np.asarray(o))
    assert not np.asarray(o[1]).any()  # an empty slot reads nothing
    mine = np.zeros((3, pool), bool)
    mine[layer, owned[:7]] = True
    # stale rows past a length inside owned pages too
    poisoned = jnp.where(jnp.asarray(mine)[:, None, :, None, None], planes,
                         jnp.nan)
    tail = np.zeros((pool, ps), bool)
    tail[owned[4], 37 % ps:] = True
    tail[owned[6], 9 % ps:] = True
    poisoned = jnp.where(jnp.asarray(tail)[None, None, :, :, None], jnp.nan,
                         poisoned)
    o2, _ = run(poisoned)
    assert np.array_equal(np.asarray(o2), np.asarray(o))
