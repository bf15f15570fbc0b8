"""Fig. 4/5/6 analogue: attention speed, 3 implementations x sequence length.

Paper setting (A100): seq 512..16k with batch*seq = 16k tokens, hidden 2048,
head dim 64/128, causal and non-causal, fwd and fwd+bwd. CPU adaptation:
same batch*seq = const protocol with a reduced token budget; the *claims*
validated are relative (flash >= ref as seq grows; causal ~halves time in
packed mode), not A100 TFLOPs/s.

Derived column: TFLOPs/s using the paper's formula
    4 * seqlen^2 * head_dim * heads   ( / 2 if causal; * 2.5 for fwd+bwd ).
"""

from __future__ import annotations

import functools
from typing import Callable, List

import jax
import jax.numpy as jnp

from benchmarks.timing import interleaved_timeit, time_min
from repro.core.attention import AttentionConfig, attention
from repro.core.masks import MaskSpec
from repro.kernels.ops import flash_attention_pallas

TOKENS = 4096  # batch * seq held constant, like the paper's 16k
HEADS, HEAD_DIM = 4, 64
SEQS = (256, 512, 1024, 2048)


def _time(fn: Callable, *args, iters: int = 5) -> float:
    """Min-of-N wall time (shared helper; see benchmarks/timing.py).

    The previous single-warmup mean-of-3 was noise-dominated on a shared
    host and recorded ``ref`` forward-only at seq=512 as *slower* than
    forward+backward in BENCH_attn.json — a physical impossibility that
    forced a re-baseline of the whole trajectory once fixed.
    """
    return time_min(fn, *args, iters=iters)


def _flops(seq: int, batch: int, causal: bool, bwd: bool) -> float:
    f = 4.0 * seq * seq * HEAD_DIM * HEADS * batch
    if causal:
        f /= 2
    if bwd:
        f *= 3.5  # fwd (1) + bwd (2.5)
    return f


def _mk_qkv(key, seq: int, batch: int):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (batch, seq, HEADS, HEAD_DIM)
    return (
        jax.random.normal(kq, shape, jnp.float32),
        jax.random.normal(kk, shape, jnp.float32),
        jax.random.normal(kv, shape, jnp.float32),
    )


def _time_pair(
    csv: List[str], names, attn: Callable, q, k, v, seq: int, batch: int,
    causal: bool,
) -> None:
    """Time fwd and fwd+bwd of ``attn(q, k, v)``; append one CSV row each.

    names = (fwd_row_name, fwdbwd_row_name) -- everything left of the first
    comma in the emitted rows. The two are timed INTERLEAVED min-of-N
    (shared helper): they will be compared, so drift must hit both equally
    -- fwd > fwd+bwd in the output is a timing bug, not a measurement.
    """
    fwd = jax.jit(attn)
    loss = jax.jit(jax.grad(lambda q, k, v: attn(q, k, v).sum()))
    best = interleaved_timeit({"fwd": fwd, "fwdbwd": loss}, q, k, v)
    t_f, t_b = best["fwd"], best["fwdbwd"]
    csv.append(
        f"{names[0]},{t_f*1e6:.0f},{_flops(seq, batch, causal, False)/t_f/1e12:.4f} TFLOP/s"
        f";timing={best.provenance}"
    )
    csv.append(
        f"{names[1]},{t_b*1e6:.0f},{_flops(seq, batch, causal, True)/t_b/1e12:.4f} TFLOP/s"
        f";timing={best.provenance}"
    )


def run(csv: List[str]) -> None:
    key = jax.random.PRNGKey(0)
    for causal in (False, True):
        spec = MaskSpec(causal=causal)
        for seq in SEQS:
            batch = max(1, TOKENS // seq)
            q, k, v = _mk_qkv(jax.random.fold_in(key, seq), seq, batch)
            for impl in ("ref", "flash_xla", "flash_pallas"):
                if impl == "flash_pallas" and seq > 512:
                    continue  # interpret-mode python loop: keep it tractable
                cfg = AttentionConfig(
                    impl=impl, block_q=128, block_kv=128,
                    mode="packed" if causal else "dense",
                )
                tag = f"{impl}/causal={int(causal)}/seq={seq}"
                _time_pair(
                    csv, (f"fig5_fwd/{tag}", f"fig4_fwdbwd/{tag}"),
                    functools.partial(attention, spec=spec, cfg=cfg),
                    q, k, v, seq, batch, causal,
                )

    schedule_comparison(csv, key)
    bwd_comparison(csv, key)


def schedule_comparison(csv: List[str], key=None) -> None:
    """Compact-vs-dense Pallas tile schedule (FA2 Section 3.1 partitioning).

    Causal at a fixed small shape (interpret mode makes each grid step a
    Python-level kernel invocation, so the visited-step count is exactly
    what this measures): the compact schedule visits ~(t+1)/2t of the dense
    steps and must not regress on fwd or fwd+bwd. Also exposed as the
    ``sched_cmp`` benchmark module for the CI fast-tier smoke.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    seq, batch = 256, max(1, TOKENS // 256)
    spec = MaskSpec(causal=True)
    q, k, v = _mk_qkv(jax.random.fold_in(key, 7), seq, batch)
    for schedule in ("dense", "compact"):
        attn = functools.partial(
            flash_attention_pallas, spec=spec, block_q=64, block_kv=64,
            schedule=schedule,
        )
        tag = f"flash_pallas/schedule={schedule}/causal=1/seq={seq}"
        _time_pair(
            csv, (f"sched_cmp_fwd/{tag}", f"sched_cmp_fwdbwd/{tag}"),
            attn, q, k, v, seq, batch, True,
        )


def bwd_comparison(csv: List[str], key=None) -> None:
    """Fused one-pass vs split 3-launch Pallas backward (ISSUE 4).

    Causal seq >= 512 (the acceptance shape), timed at the KERNEL layer:
    one jit'd fwd+bwd over prepped (B*H, S, D) tensors per variant, so the
    row isolates exactly what the fused kernel changes (launches, (s, p)
    recompute, exp count, Q/dO streaming). The ``attention()``-layer grad
    is NOT used here on purpose: interpret mode lowers each grid step to an
    XLA while iteration that copies every carried array, and inside a full
    ``jax.grad`` those copies dominate and wash out the kernel delta on a
    small host. Fused must beat split -- asserted (interleaved min-of-N
    timing via the shared benchmarks/timing helper -- this function's
    original inline scheme is where the repo-wide discipline came from),
    not just reported. Also the ``bwd_cmp`` module for CI.
    """
    from repro.kernels import flash_bwd as FB
    from repro.kernels import flash_fwd as FF

    if key is None:
        key = jax.random.PRNGKey(0)
    seq, blk = 2048, 256
    batch = max(1, TOKENS // seq)
    BH = batch * HEADS
    spec = MaskSpec(causal=True)
    ks = jax.random.split(jax.random.fold_in(key, 11), 4)
    qh, kh, vh, do = (
        jax.random.normal(k_, (BH, seq, HEAD_DIM), jnp.float32) for k_ in ks
    )
    kw = dict(group=1, block_q=blk, block_kv=blk, kv_valid=seq)

    def make(bwd):
        def fn(qh, kh, vh, do):
            o, lse = FF.flash_fwd(qh, kh, vh, spec, **kw)
            if bwd == "fused":
                dk, dv, dq = FB.flash_bwd_fused(
                    qh, kh, vh, o, do, lse, spec, **kw
                )
            else:
                delta = FB.flash_bwd_delta(o, do, block_q=blk)
                lse_s = jnp.where(jnp.isneginf(lse), 0.0, lse)
                dk, dv = FB.flash_bwd_dkv(qh, kh, vh, do, lse_s, delta, spec, **kw)
                dq = FB.flash_bwd_dq(qh, kh, vh, do, lse_s, delta, spec, **kw)
            return dq, dk, dv

        return jax.jit(fn)

    fns = {bwd: make(bwd) for bwd in ("split", "fused")}
    # interleaved min-of-N (shared helper): robust to host contention
    best = interleaved_timeit(fns, qh, kh, vh, do, iters=5)
    for bwd in ("split", "fused"):
        tag = f"flash_pallas/bwd={bwd}/causal=1/seq={seq}"
        csv.append(
            f"bwd_cmp_fwdbwd/{tag},{best[bwd]*1e6:.0f},"
            f"{_flops(seq, batch, True, True)/best[bwd]/1e12:.4f} TFLOP/s"
            f";timing={best.provenance}"
        )
    assert best["fused"] < best["split"], (
        "fused backward must beat the split baseline", best,
    )
