"""The benchmark's work counters against hand counts at small shapes.

A roofline share is required work over measured time, so these counts are
what keeps a share from passing 100%: causal attention counts the visible
triangle exactly, and nothing counts recomputation or launched tiles.
"""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

ROOT = BENCH.parent


def work(family):
    return harness.work_family(ROOT, family)


@pytest.mark.parametrize("s_q,s_kv,causal,pairs", [
    (4, 4, True, 10),     # 1 + 2 + 3 + 4: the triangle with its diagonal
    (4, 4, False, 16),
    (2, 4, True, 7),      # the last query sees every key: 3 + 4
    (1, 9, True, 9),      # one decode-like query
])
def test_visible_pairs(s_q, s_kv, causal, pairs):
    assert work("fa2_fwd").visible_pairs(s_q, s_kv, causal) == pairs


def test_forward_hand_count():
    flops, io = work("fa2_fwd").work(batch=1, s_q=4, s_kv=4, heads=2,
                                     kv_heads=1, head_dim=8, causal=True)
    assert flops == 2 * 2 * 10 * 2 * 8  # two matmuls, 2 FLOPs a MAC, 10 pairs
    # q, o: 4 x 2 x 8 bf16 each; k, v: 4 x 1 x 8 bf16 each; lse 4 x 2 f32
    assert io == 2 * (2 * 64 + 2 * 32) + 4 * 8


def test_causal_is_half_plus_diagonal():
    f = work("fa2_fwd").work
    full = f(batch=2, s_q=512, s_kv=512, heads=4, kv_heads=2, head_dim=16,
             causal=False)[0]
    causal = f(batch=2, s_q=512, s_kv=512, heads=4, kv_heads=2, head_dim=16,
               causal=True)[0]
    assert causal * 2 * 512 == full * 513


def test_backward_is_five_matmuls():
    kw = dict(batch=1, s_q=4, s_kv=4, heads=2, kv_heads=1, head_dim=8)
    fwd = work("fa2_fwd").work(**kw)
    bwd = work("fa2_bwd").work(**kw)
    assert bwd[0] * 2 == fwd[0] * 5
    # q, o, do, dq (4 x 2 x 8) and k, v, dk, dv (4 x 1 x 8) in bf16, lse f32
    assert bwd[1] == 2 * (4 * 64 + 4 * 32) + 4 * 8


def test_decode_hand_count():
    flops, io = work("fa2_decode").work(lens=[3, 5], heads=4, kv_heads=2,
                                        head_dim=8)
    assert flops == 2 * 2 * 8 * 4 * 8
    # 8 cached entries x (k, v) x 2 heads x 8 bf16; q and o of 2 requests
    assert io == 2 * (8 * 2 * 2 * 8 + 2 * 2 * 4 * 8)


TINY = {"num_hidden_layers": 2, "hidden_size": 8, "head_dim": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 16, "vocab_size": 10}


def test_train_step_hand_count():
    ts = work("train_step")
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, three 8x16 MLP matrices
    assert ts.layer_matmul_params(TINY) == 64 + 32 + 32 + 64 + 384
    dense = 2 * 576 + 8 * 10  # the output head, not the embedding gather
    attn = 4 * 4 * 2 * (3 * 4 // 2)  # rows=1, seq=3: 6 visible pairs
    assert ts.flops(TINY, 1, 3) == 6 * dense * 3 + 3 * 2 * attn


def test_decode_step_hand_count():
    flops, io = work("decode_step").work(TINY, [3, 5])
    weights = 2 * 576 + 8 * 10
    assert flops == 2 * weights * 2 + 2 * 4 * 4 * 2 * 8
    # weights, 2 embedding rows, k and v read for 8 entries and written for 2
    assert io == 2 * (weights + 2 * 8 + 2 * 2 * 2 * 2 * (8 + 2))
