"""Kernel-knob resolution and the timing discipline benchmarks stand on.

Covers: each knob's resolution (an explicit argument wins, a ``None`` knob
takes the shape heuristic), the decode split count (explicit, else 8),
resolution that is the same on the CPU as on the chip, the knobs the
benchmark cells' attention calls resolve to (the kernels their traces
name), block-size legalization, timer sanity (fwd <= fwd+bwd from the
shared interleaved min-of-N helper -- the exact inversion the old mean-of-3
produced), and the benchmark trajectory's tolerant load / dedupe / prune.
"""

import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decode as _decode
from repro.core.attention import (
    AttentionConfig,
    decode_attention,
    decode_attention_paged,
)
from repro.core.masks import MaskSpec
from repro.kernels.ops import (
    _FUSED_DELTA_VMEM_BUDGET,
    PallasFlashConfig,
    default_block_sizes,
    default_forward_partitions,
    flash_attention_pallas,
    resolve_pallas_knobs,
)
from repro.kernels.ref import attention_reference
from repro.utils.timing import interleaved_timeit

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # for `import benchmarks.run`

CAUSAL = MaskSpec(causal=True)
KNOBS = ("block_q", "block_kv", "schedule", "bwd", "num_q_bands", "kv_splits")


def _knobs(r: dict) -> dict:
    return {k: r[k] for k in KNOBS}


# ---------------------------------------------------------------------------
# Resolution: explicit argument, else the shape heuristic
# ---------------------------------------------------------------------------

# (1, 1024, 2, 32) f32 in interpret mode resolves to blocks of 512, compact,
# fused, 2 q bands, 1 kv split; each value below differs from that.
EXPLICIT = {"block_q": 128, "block_kv": 128, "schedule": "dense",
            "bwd": "split", "num_q_bands": 1, "kv_splits": 2}


@pytest.mark.parametrize("knob", KNOBS)
def test_explicit_knobs_override_heuristics(knob):
    B, S, H, D = shape = (1, 1024, 2, 32)
    heuristic = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, interpret=True), shape, shape)
    assert _knobs(heuristic) == dict(
        block_q=512, block_kv=512, schedule="compact", bwd="fused",
        num_q_bands=2, kv_splits=1)
    r = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, interpret=True,
                          **{knob: EXPLICIT[knob]}), shape, shape)
    assert r[knob] == EXPLICIT[knob]
    # every other knob is the heuristic's, given the explicit one
    bq, bk = default_block_sizes(S, S, D)
    want = dict(block_q=bq, block_kv=bk, schedule="compact", bwd="fused")
    want[knob] = EXPLICIT[knob]
    t_q = math.ceil(S / want["block_q"])
    t_kv = math.ceil(S / want["block_kv"])
    nb, ks = default_forward_partitions(B * H, t_q, t_kv)
    if want["schedule"] == "dense":
        nb = ks = 1
    want = dict(want, num_q_bands=nb, kv_splits=ks)
    want[knob] = EXPLICIT[knob]
    assert _knobs(r) == want


def _spy(monkeypatch, name, seen):
    real = getattr(_decode, name)

    def spy(*args, **kwargs):
        seen.append(kwargs["num_splits"])
        return real(*args, **kwargs)

    monkeypatch.setattr(_decode, name, spy)


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_decode_splits_explicit_else_eight(layout, monkeypatch):
    """The contiguous and the paged decode fan out over
    ``cfg.decode_splits``: 8 unless the config says otherwise, whatever
    the cache's size (here a 128-token cache of 2 heads x 32)."""
    assert AttentionConfig().decode_splits == 8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (1, 1, 2, 32), jnp.float32)
    lens = jnp.array([100], jnp.int32)
    seen = []
    if layout == "contiguous":
        _spy(monkeypatch, "flash_decode", seen)
        kc = jax.random.normal(kk, (1, 128, 2, 32), jnp.float32)
        vc = jax.random.normal(kv, (1, 128, 2, 32), jnp.float32)
        run = lambda cfg: decode_attention(q, kc, vc, lens, cfg)  # noqa: E731
    else:
        _spy(monkeypatch, "flash_decode_paged", seen)
        # one layer, 2 KV heads, 5 physical pages of 32 tokens (page 0 null)
        kp = jax.random.normal(kk, (1, 2, 5, 32, 32), jnp.float32)
        vp = jax.random.normal(kv, (1, 2, 5, 32, 32), jnp.float32)
        table = jnp.array([[1, 2, 3, 4]], jnp.int32)
        run = lambda cfg: decode_attention_paged(  # noqa: E731
            q, kp, vp, lens, table, cfg)
    o_default = run(AttentionConfig())
    o_eight = run(AttentionConfig(decode_splits=8))
    run(AttentionConfig(decode_splits=2))
    assert seen == [8, 8, 2]
    assert np.array_equal(np.asarray(o_default), np.asarray(o_eight))


# The flash_pallas shapes a CPU-measured knob cache once held, as
# (causal, seq, heads, head_dim, dtype, batch); on those the CPU ran other
# kernels than the chip would have.
CACHE_SHAPES = [
    (False, 128, 2, 32, "float32", 2),
    (False, 256, 4, 64, "float32", 16),
    (False, 512, 4, 64, "bfloat16", 8),
    (False, 512, 4, 64, "float32", 8),
    (True, 1024, 4, 64, "bfloat16", 4),
    (True, 1024, 4, 64, "float32", 4),
    (True, 128, 2, 32, "bfloat16", 2),
    (True, 128, 2, 32, "float32", 2),
    (True, 2048, 4, 64, "float32", 2),
    (True, 256, 4, 64, "float32", 16),
    (True, 512, 4, 64, "bfloat16", 8),
    (True, 512, 4, 64, "float32", 8),
]


@pytest.mark.parametrize("causal,seq,heads,hd,dtype,batch", CACHE_SHAPES)
def test_cpu_resolves_the_chip_knobs(causal, seq, heads, hd, dtype, batch,
                                     monkeypatch):
    """Knob resolution reads no platform: with interpret mode fixed, the
    CPU resolves every knob as the chip does, so the kernel tests here
    run the chip's kernels."""
    cfg = PallasFlashConfig(spec=MaskSpec(causal=causal), interpret=True)
    shape = (batch, seq, heads, hd)
    on_cpu = resolve_pallas_knobs(cfg, shape, shape, jnp.dtype(dtype))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_tpu = resolve_pallas_knobs(cfg, shape, shape, jnp.dtype(dtype))
    assert _knobs(on_cpu) == _knobs(on_tpu)


def _fwd_kernel(r: dict) -> str:
    """The forward kernel a resolution launches (kernels/flash_fwd.py)."""
    if r["schedule"] == "dense":
        return "fa2_fwd"
    if r["kv_splits"] > 1:
        return "fa2_fwd_splitkv"
    return "fa2_fwd_banded" if r["num_q_bands"] > 1 else "fa2_fwd_compact"


def _bwd_kernel(r: dict) -> str:
    return ("fa2_bwd_fused" if r["bwd"] == "fused" else "fa2_bwd_dkv") + (
        "_compact" if r["schedule"] == "compact" else "")


# The benchmark cells' attention calls, as (q shape, kv heads) in bf16
# compiled by Mosaic, with the knobs each resolves to. The kernel names
# are those the cells' device traces show.
CELL_CALLS = {
    # qwen3-train.causal16k: 2 rows x 16,384, 32 q / 8 kv heads x 128
    "causal16k-step": ((2, 16384, 32, 128), 8,
                       dict(block_q=512, block_kv=512, schedule="compact",
                            bwd="fused", num_q_bands=1, kv_splits=1),
                       "fa2_fwd_compact", "fa2_bwd_fused_compact"),
    # qwen3-serve.chat: one prompt's prefill at the 1,024 and 4,096 buckets
    "chat-prefill-1024": ((1, 1024, 32, 128), 8,
                          dict(block_q=512, block_kv=512, schedule="compact",
                               bwd="fused", num_q_bands=2, kv_splits=1),
                          "fa2_fwd_banded", None),
    "chat-prefill-4096": ((1, 4096, 32, 128), 8,
                          dict(block_q=512, block_kv=512, schedule="compact",
                               bwd="fused", num_q_bands=2, kv_splits=1),
                          "fa2_fwd_banded", None),
    # dsv2lite-serve.longctx: one prompt's prefill, 16 heads, Q/K 192 wide
    # (V 128 does not enter the resolution)
    "longctx-prefill-4096": ((1, 4096, 16, 192), 16,
                             dict(block_q=512, block_kv=256,
                                  schedule="compact", bwd="fused",
                                  num_q_bands=4, kv_splits=1),
                             "fa2_fwd_banded", None),
    "longctx-prefill-28672": ((1, 28672, 16, 192), 16,
                              dict(block_q=512, block_kv=256,
                                   schedule="compact", bwd="fused",
                                   num_q_bands=4, kv_splits=1),
                              "fa2_fwd_banded", None),
    # past the fused backward's VMEM budget (group 4, 131,584 padded
    # tokens): the only input on which the split backward runs
    "past-fused-budget": ((1, 131584, 32, 128), 8,
                          dict(block_q=512, block_kv=512, schedule="compact",
                               bwd="split", num_q_bands=2, kv_splits=1),
                          "fa2_fwd_banded", "fa2_bwd_dkv_compact"),
}


@pytest.mark.parametrize("call", sorted(CELL_CALLS))
def test_cell_shapes_resolve_to_the_traced_kernels(call):
    q_shape, hk, want, fwd, bwd = CELL_CALLS[call]
    B, S, Hq, D = q_shape
    r = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, interpret=False), q_shape,
        (B, S, hk, D), jnp.bfloat16)
    assert _knobs(r) == want
    assert _fwd_kernel(r) == fwd
    if bwd is not None:
        assert _bwd_kernel(r) == bwd
    assert (r["bwd"] == "split") == (
        Hq // hk * S * 4 > _FUSED_DELTA_VMEM_BUDGET)


# ---------------------------------------------------------------------------
# Block-size legalization (satellite: no silent mis-padding)
# ---------------------------------------------------------------------------


def test_block_legalization_rounds_and_warns():
    shape = (1, 512, 2, 32)
    with pytest.warns(UserWarning, match="block_q=100 is not legal"):
        r = resolve_pallas_knobs(
            PallasFlashConfig(spec=CAUSAL, block_q=100),
            shape, shape,
        )
    assert r["block_q"] == 104  # rounded up to the 8-sublane contract
    with pytest.warns(UserWarning, match="block_kv=4096"):
        r = resolve_pallas_knobs(
            PallasFlashConfig(spec=CAUSAL, block_kv=4096),
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
                              interpret=False),
            shape, shape,
        )
    assert (r["block_q"], r["block_kv"]) == (128, 128)
    short = (1, 100, 2, 32)
    r = resolve_pallas_knobs(
        PallasFlashConfig(spec=CAUSAL, interpret=False),
        short, short,
    )
    assert r["block_q"] == r["block_kv"] == 104  # the whole padded axis


@pytest.mark.parametrize("bad", [0, -8, 2.5, "128", True])
def test_block_legalization_rejects_garbage(bad):
    shape = (1, 128, 2, 32)
    with pytest.raises(ValueError):
        resolve_pallas_knobs(
            PallasFlashConfig(spec=CAUSAL, block_q=bad),
            shape, shape,
        )


def test_misaligned_explicit_block_still_exact():
    """A legalized (rounded) explicit block must produce oracle-exact
    output -- the pre-fix behavior let block=100 corrupt the padding."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(k_, (1, 200, 2, 32), jnp.float32)
               for k_ in ks)
    with pytest.warns(UserWarning):
        o = flash_attention_pallas(q, k, v, CAUSAL, block_q=100, block_kv=60)
    o_ref = attention_reference(q, k, v, CAUSAL)[0]
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)




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
