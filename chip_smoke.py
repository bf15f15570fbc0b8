#!/usr/bin/env python3
"""Bring-up smoke test: the FlashAttention-2 kernels, training and serving
on one TPU chip, through the entry points a user calls.

    python chip_smoke.py                # one chip: kernels, train, serve
    python chip_smoke.py --four-chips   # four chips: ring context parallelism

Every phase runs in this one process (a chip belongs to one process at a
time) and checks its results against the repository's float32 references:

  kernels  Pallas forward + fused backward (causal and window 1024 at
           S=4096, causal at S=8192), the split backward at S=8192, the
           ring's per-rectangle backward, and contiguous and paged split-KV
           decode at qwen3-8b attention widths, against ``kernels/ref.py``;
           each compiled program must hold a Mosaic kernel
           (``tpu_custom_call``), never interpret mode.
  train    ``launch.train.train`` with the Pallas kernels for 4 steps at
           qwen3-8b layer widths (2 layers, vocabulary cut to an eighth);
           step 0's loss must match the XLA flash path's step 0.
  serve    ``PagedServingEngine`` built as ``launch/serve.py`` builds it, on
           qwen3-8b's widths and full vocabulary cut to 8 layers: 8 requests
           of 512-3,000 prompt tokens and 32 new tokens each. Its greedy
           tokens, and the prefill and first decode steps' logits of the
           serving path, must agree with ``lm.forward(impl="ref")`` in f32.
  ring     (``--four-chips`` only) ring attention fwd+grad at S=32k over a
           (1, 4) mesh against single-chip attention, then 3 ``train`` steps
           with ``model_axis=4, attn_sharding="ring"`` against one step on
           one chip.

Weights and data come from ``--seed``. Numbers printed are one unaveraged
run, not a benchmark. Any failed check raises, so the script exits
non-zero; without a TPU it exits 2 and prints no result. The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.core.attention import AttentionConfig  # noqa: E402
from repro.core.masks import MaskSpec  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.compat import resolve_interpret  # noqa: E402
from repro.kernels.ref import attention_reference  # noqa: E402
from repro.launch.train import TrainLoopConfig, train  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.obs import MetricsRegistry  # noqa: E402
from repro.serving.engine import PagedServingEngine, Request  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

# Kernel outputs and gradients, as max |kernel - reference| / max |reference|.
# q, k, v, o and the gradients are bf16 (8 significant bits, a relative step
# of 2^-8), and the kernels round p and dS to bf16 before their second
# matmuls; over ~4k accumulated terms of mixed sign that stays under 1% of
# the largest value, and 2% leaves twice that.
KERNEL_TOL = 2e-2
# Model logits, as max |system - reference| in units of the reference
# logits' RMS. The bf16 model rounds its hidden state in ~7 matmuls and 2
# residual adds per layer; that leaves about 1% relative error per logit,
# and the largest of ~10^6 such errors reaches about 5%. 10% leaves twice
# that. A greedy token must be within the same distance of the reference's
# best logit.
LOGIT_TOL = 0.1
# Step-0 loss of the Pallas path against the XLA flash path, in nats: the
# two differ only in bf16 rounding of the attention outputs, averaged over
# every token of the batch.
LOSS_TOL = 2e-2

QWEN = "qwen3-8b"
HQ, HK, HD = 32, 8, 128  # qwen3-8b attention widths


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


def rel_err(x, ref) -> float:
    x = np.asarray(x, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-30))


def compile_kernel(fn, *args):
    """jit + lower + compile; the program must hold a Mosaic kernel
    (``tpu_custom_call``), which proves it is not interpreted."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          "compiled program holds a Mosaic kernel (tpu_custom_call)")
    return compiled, secs


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _randn(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# (mask, sequence length, backward): the fused backward's dq blocks are
# revisited across its kv-major sweep, which first went wrong on the chip
# from S=8192; the split backward is the fused one's baseline.
KERNEL_CASES = (
    ("causal", 4096, "fused"),
    ("window1024", 4096, "fused"),
    ("causal", 8192, "fused"),
    ("causal", 8192, "split"),
)
MASKS = {"causal": MaskSpec(causal=True),
         "window1024": MaskSpec(causal=True, window=1024)}


def kernel_phase(seed: int, *, B=1, batch_decode=8, page=16) -> None:
    G = HQ // HK
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    for name, S, bwd in KERNEL_CASES:
        spec = MASKS[name]
        q = _randn(ks[0], (B, S, HQ, HD))
        k = _randn(ks[1], (B, S, HK, HD))
        v = _randn(ks[2], (B, S, HK, HD))
        do = _randn(ks[3], (B, S, HQ, HD))

        def fwd_bwd(q, k, v, do, spec=spec, bwd=bwd):
            o, vjp = jax.vjp(
                lambda q, k, v: ops.flash_attention_pallas(q, k, v, spec,
                                                           bwd=bwd),
                q, k, v)
            return (o, *vjp(do))

        compiled, secs = compile_kernel(fwd_bwd, q, k, v, do)
        out = compiled(q, k, v, do)
        jax.block_until_ready(out)

        # f32 reference one kv-head group at a time: the groups are
        # independent, and a whole (S, S) f32 score tensor per head would
        # not fit next to its gradient.
        @jax.jit
        def ref_group(q, k, v, do, spec=spec):
            f32 = lambda x: x.astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                o, vjp = jax.vjp(
                    lambda q, k, v: attention_reference(q, k, v, spec)[0],
                    f32(q), f32(k), f32(v))
                return (o, *vjp(f32(do)))

        errs = np.zeros(4)
        for h in range(HK):
            qs = slice(h * G, (h + 1) * G)
            ref = ref_group(q[:, :, qs], k[:, :, h:h + 1], v[:, :, h:h + 1],
                            do[:, :, qs])
            got = (out[0][:, :, qs], out[1][:, :, qs], out[2][:, :, h:h + 1],
                   out[3][:, :, h:h + 1])
            errs = np.maximum(errs, [rel_err(a, b) for a, b in zip(got, ref)])
        log(f"[kernels] flash fwd+bwd {name} S={S} bwd {bwd} heads "
            f"{HQ}/{HK}x{HD} bf16: "
            f"compile {secs:.2f}s, rel err o {errs[0]:.2e} dq {errs[1]:.2e} "
            f"dk {errs[2]:.2e} dv {errs[3]:.2e} (tol {KERNEL_TOL})")
        check(bool(np.all(np.isfinite(errs))) and errs.max() <= KERNEL_TOL,
              f"flash fwd+bwd {name} S={S} {bwd} within {KERNEL_TOL}")

        # the ring's per-rectangle backward: f32 (o, do) and an external lse
        def shard_bwd(q, k, v, do, spec=spec, bwd=bwd):
            o, lse = ops.flash_attention_pallas_with_lse(q, k, v, spec)
            return ops.flash_attention_pallas_shard_bwd(
                q, k, v, o.astype(jnp.float32), lse, do.astype(jnp.float32),
                spec, bwd=bwd, out_dtype=jnp.float32)

        compiled, secs = compile_kernel(shard_bwd, q, k, v, do)
        errs = [rel_err(a, b) for a, b in zip(compiled(q, k, v, do), out[1:])]
        log(f"[kernels] shard backward {name} vs the above: compile "
            f"{secs:.2f}s, rel err dq {errs[0]:.2e} dk {errs[1]:.2e} "
            f"dv {errs[2]:.2e} (tol {KERNEL_TOL})")
        check(max(errs) <= KERNEL_TOL,
              f"shard backward {name} S={S} {bwd} within {KERNEL_TOL}")

    # split-KV decode over a contiguous cache and over a shuffled page pool
    S = 4096
    Bd, n_pages = batch_decode, S // page
    qd = _randn(ks[4], (Bd, 1, HQ, HD))
    kc = _randn(ks[5], (Bd, S, HK, HD))
    vc = _randn(ks[6], (Bd, S, HK, HD))
    rng = np.random.default_rng(seed)
    lens_np = rng.integers(1, S + 1, size=Bd).astype(np.int32)
    lens_np[0], lens_np[-1] = S, 1
    lens = jnp.asarray(lens_np)
    table_np = (rng.permutation(Bd * n_pages) + 1).reshape(Bd, n_pages)
    table = jnp.asarray(table_np.astype(np.int32))

    def to_pages(c):  # (B, S, Hk, D) -> layer 1 of (2, Hk, 1 + B*n_pages, page, D)
        pages = c.reshape(Bd * n_pages, page, HK, HD).transpose(2, 0, 1, 3)
        pool = jnp.zeros((HK, 1 + Bd * n_pages, page, HD), c.dtype)
        pool = pool.at[:, table.reshape(-1)].set(pages)
        # the kernel reads layer 1 of the stack; NaN in layer 0 shows a
        # read of the wrong layer
        return jnp.stack([jnp.full_like(pool, jnp.nan), pool])

    kp, vp = to_pages(kc), to_pages(vc)
    with jax.default_matmul_precision("highest"):
        o_ref, lse_ref = jax.jit(lambda q, k, v, n: attention_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), MaskSpec(), kv_length=n))(qd, kc, vc, lens)
    for name, fn, args in (
        ("contiguous", lambda q, k, v, n: ops.flash_decode_pallas(q, k, v, n),
         (qd, kc, vc, lens)),
        (f"paged ps={page}",
         lambda q, k, v, n, t: ops.flash_decode_paged_pallas(q, k, v, n, t, 1),
         (qd, kp, vp, lens, table)),
    ):
        compiled, secs = compile_kernel(fn, *args)
        o, lse = compiled(*args)
        e_o, e_l = rel_err(o, o_ref), rel_err(lse, lse_ref)
        log(f"[kernels] decode {name} B={Bd} cache {S}: compile {secs:.2f}s, "
            f"rel err o {e_o:.2e} lse {e_l:.2e} (tol {KERNEL_TOL})")
        check(max(e_o, e_l) <= KERNEL_TOL, f"decode {name} within {KERNEL_TOL}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def train_config(layers=2, vocab_div=8):
    """qwen3-8b's published layer widths, cut to ``layers`` layers and the
    first 1/``vocab_div`` of its vocabulary rows."""
    cfg = registry.get(QWEN)
    return dataclasses.replace(
        cfg, name=f"{QWEN}-{layers}L-vocab1of{vocab_div}", num_layers=layers,
        vocab_size=cfg.vocab_size // vocab_div)


def run_train(cfg, seed, *, steps, seq, batch, attn_impl, **mesh):
    loop = TrainLoopConfig(
        steps=steps, seq_len=seq, batch_size=batch, attn_impl=attn_impl,
        max_restarts=0, log_every=1, seed=seed, **mesh)
    t0 = time.perf_counter()
    _, _, hist = train(cfg, loop)
    losses, times = hist["loss"], hist["step_time"]
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"{steps} finite losses from {attn_impl}: {losses}")
    steady = float(np.median(times[1:])) if len(times) > 1 else float("nan")
    log(f"[train] {cfg.name} {attn_impl} {mesh or ''} batch {batch}x{seq}: "
        f"losses {[round(x, 4) for x in losses]}, step 0 (compile + run) "
        f"{times[0]:.2f}s, later steps median {steady:.3f}s, wall "
        f"{time.perf_counter() - t0:.1f}s")
    return losses


def train_phase(seed: int, cfg, *, seq=4096, batch=2, steps=4) -> None:
    pallas = run_train(cfg, seed, steps=steps, seq=seq, batch=batch,
                       attn_impl="flash_pallas")
    xla = run_train(cfg, seed, steps=1, seq=seq, batch=batch,
                    attn_impl="flash_xla")
    diff = abs(pallas[0] - xla[0])
    log(f"[train] step-0 loss pallas {pallas[0]:.5f} vs flash_xla "
        f"{xla[0]:.5f}: |diff| {diff:.2e} (tol {LOSS_TOL})")
    check(diff <= LOSS_TOL, f"step-0 loss agrees within {LOSS_TOL}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_config(layers=8):
    cfg = registry.get(QWEN)
    return dataclasses.replace(cfg, name=f"{QWEN}-{layers}L", num_layers=layers)


@functools.partial(jax.jit, static_argnums=0)
def _ref_forward(cfg, params, ids, pos):
    with jax.default_matmul_precision("highest"):
        h, _, _ = lm.forward(cfg, params, ids, AttentionConfig(impl="ref"))
        return lm.logits_from_hidden(cfg, params, h[:, pos])[0]


def reference_logits(cfg, params, tokens, positions, pad_to):
    """f32 logits of ``lm.forward(impl="ref")`` at ``positions`` of one
    token sequence, right-padded to ``pad_to`` (causality keeps the padding
    out). Only the token rows used are upcast; every layer's bf16 weights
    are upcast inside that layer's matmuls, which take the f32 activations,
    so no f32 copy of the whole model is ever resident."""
    uniq, inv = np.unique(np.asarray(tokens), return_inverse=True)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, : len(tokens)] = inv
    rows = np.zeros((pad_to,), np.int32)  # a fixed shape: one compile
    rows[: len(uniq)] = uniq
    embed = dict(params["embed"])
    embed["tokens"] = params["embed"]["tokens"][jnp.asarray(rows)].astype(
        jnp.float32)
    f32cfg = dataclasses.replace(cfg, dtype="float32", remat=False)
    return _ref_forward(f32cfg, dict(params, embed=embed), jnp.asarray(ids),
                        jnp.asarray(positions))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _serve_prefill(cfg, params, toks, lens, cache_size):
    attn = AttentionConfig(impl="flash_pallas")
    h, caches, n = lm.prefill(cfg, params, toks, attn, cache_size, lens=lens)
    return lm.logits_from_hidden(cfg, params, h)[:, 0], caches, n


@functools.partial(jax.jit, static_argnums=0)
def _serve_decode(cfg, params, tok, caches, n):
    attn = AttentionConfig(impl="flash_pallas")
    logits, caches = lm.decode_step(cfg, params, tok, caches, n, attn)
    return logits[:, 0], caches


def serving_logits(cfg, params, prompt, feed, pad_to, cache_size):
    """Logits of the serving path for one request: the lens-masked bucketed
    prefill, then one decode step per token of ``feed``."""
    toks = np.zeros((1, pad_to), np.int32)
    toks[0, : len(prompt)] = prompt
    logits, caches, n = _serve_prefill(
        cfg, params, jnp.asarray(toks), jnp.asarray([len(prompt)], jnp.int32),
        cache_size)
    out = [logits[0]]
    for t in feed:
        logits, caches = _serve_decode(cfg, params, jnp.asarray([[t]], jnp.int32),
                                       caches, n)
        n = n + 1
        out.append(logits[0])
    return jnp.stack(out)


def serve_phase(seed: int, cfg, *, short=(449, 512), long=(2945, 3000),
                n_each=4, max_new=32, page=16, check_steps=4) -> None:
    params = lm.init_lm(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(*short, endpoint=True)) for _ in range(n_each)]
    lens += [int(rng.integers(*long, endpoint=True)) for _ in range(n_each)]
    requests = [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, size=n)
                        .tolist(), max_new_tokens=max_new)
                for i, n in enumerate(lens)]
    # as launch/serve.py builds it (--engine paged --attn flash_pallas)
    max_batch = len(requests)
    n_max = -(-(max(lens) + max_new + 1) // page)
    engine = PagedServingEngine(
        cfg, params, AttentionConfig(impl="flash_pallas"), max_batch=max_batch,
        num_pages=max_batch * n_max + 1, page_size=page,
        pages_per_seq_max=n_max, registry=MetricsRegistry())
    t0 = time.perf_counter()
    for req in requests:
        engine.submit(req)
    engine.tick()  # admits every request (prefill) + the first decode step
    t1 = time.perf_counter()
    finished = engine.run(max_ticks=10 * max_new)
    t2 = time.perf_counter()
    n_tok = sum(len(r.generated) for r in finished.values())
    log(f"[serve] {cfg.name} paged engine: {len(finished)} requests, prompts "
        f"{lens}, {n_tok} tokens in {engine.ticks} decode ticks; first tick "
        f"(admission prefill + decode, compiles included) {t1 - t0:.2f}s, "
        f"later ticks mean {(t2 - t1) / max(engine.ticks - 1, 1) * 1e3:.1f}ms; "
        f"decode_compiles {engine.decode_compiles}, admit_compiles "
        f"{engine.admit_compiles}")
    check(len(finished) == len(requests), "every request finished")
    check(all(len(r.generated) == max_new + 1 for r in finished.values()),
          f"each request generated {max_new + 1} tokens (prefill + decode)")
    check(engine.decode_compiles == 1, "decode_compiles == 1")
    del engine

    worst_tok, argmax_hits, n_checked, worst_logit = 0.0, 0, 0, 0.0
    for req in requests:
        P, gen = len(req.prompt), finished[req.rid].generated
        seq = req.prompt + gen[:-1]
        pad_to = -(-(P + max_new) // 64) * 64
        ref = reference_logits(cfg, params, seq, np.arange(P - 1, len(seq)),
                               pad_to)  # predicts gen[0], ..., gen[-1]
        ref = np.asarray(ref[:, : cfg.vocab_size])
        rms = float(np.sqrt(np.mean(ref ** 2)))
        chosen = ref[np.arange(len(gen)), gen]
        short_by = (ref.max(axis=1) - chosen) / rms
        worst_tok = max(worst_tok, float(short_by.max()))
        argmax_hits += int(np.sum(ref.argmax(axis=1) == np.asarray(gen)))
        n_checked += len(gen)
        if req.rid in (0, len(requests) - 1):  # one short, one long prompt
            bucket = -(-P // 64) * 64  # the engine's prompt bucket
            got = serving_logits(cfg, params, req.prompt, gen[:check_steps],
                                 bucket, bucket + 64)
            got = np.asarray(got[:, : cfg.vocab_size], np.float32)
            err = float(np.max(np.abs(got - ref[: check_steps + 1])) / rms)
            worst_logit = max(worst_logit, err)
            log(f"[serve] request {req.rid} (prompt {P}): prefill + "
                f"{check_steps} decode steps' logits vs f32 reference: max "
                f"|diff| {err:.3f} x rms {rms:.3f} (tol {LOGIT_TOL})")
    log(f"[serve] greedy tokens vs f32 reference: {argmax_hits}/{n_checked} "
        f"are its argmax; the worst is {worst_tok:.3f} x rms below the "
        f"reference's best logit (tol {LOGIT_TOL})")
    check(worst_logit <= LOGIT_TOL, f"serving logits within {LOGIT_TOL}")
    check(worst_tok <= LOGIT_TOL, f"greedy tokens within {LOGIT_TOL}")


# ---------------------------------------------------------------------------
# ring (four chips)
# ---------------------------------------------------------------------------


def ring_phase(seed: int, *, S=32768) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.ring_attention import ring_flash_attention
    from repro.launch.mesh import make_long_context_mesh

    n = len(jax.devices())
    mesh = make_long_context_mesh(1, n)
    spec = MaskSpec(causal=True)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, do = (_randn(k_, (1, S, HQ, HD)) for k_ in (ks[0], ks[3]))
    k, v = (_randn(k_, (1, S, HK, HD)) for k_ in (ks[1], ks[2]))

    def fwd_bwd(attn):
        def f(q, k, v, do):
            o, vjp = jax.vjp(attn, q, k, v)
            return (o, *vjp(do))
        return f

    single, s_secs = compile_kernel(
        fwd_bwd(lambda q, k, v: ops.flash_attention_pallas(q, k, v, spec)),
        q, k, v, do)
    want = single(q, k, v, do)
    seq_sharded = NamedSharding(mesh, P(None, "model"))
    args = [jax.device_put(x, seq_sharded) for x in (q, k, v, do)]
    ring, r_secs = compile_kernel(
        fwd_bwd(lambda q, k, v: ring_flash_attention(
            q, k, v, spec, mesh=mesh, impl="flash_pallas")),
        *args)
    got = ring(*args)
    errs = [rel_err(a, b) for a, b in zip(got, want)]
    log(f"[ring] fwd+bwd S={S} over {n} chips vs one chip: compile "
        f"{r_secs:.1f}s (one chip {s_secs:.1f}s), rel err o {errs[0]:.2e} "
        f"dq {errs[1]:.2e} dk {errs[2]:.2e} dv {errs[3]:.2e} "
        f"(tol {KERNEL_TOL})")
    check(max(errs) <= KERNEL_TOL, f"ring agrees with one chip within {KERNEL_TOL}")


def ring_train_phase(seed: int, cfg, *, seq=4096, batch=2, steps=3) -> None:
    n = len(jax.devices())
    one = run_train(cfg, seed, steps=1, seq=seq, batch=batch,
                    attn_impl="flash_pallas")
    ring = run_train(cfg, seed, steps=steps, seq=seq, batch=batch,
                     attn_impl="flash_pallas", model_axis=n,
                     attn_sharding="ring")
    diff = abs(ring[0] - one[0])
    log(f"[ring] step-0 loss ring over {n} chips {ring[0]:.5f} vs one chip "
        f"{one[0]:.5f}: |diff| {diff:.2e} (tol {LOSS_TOL})")
    check(diff <= LOSS_TOL, f"ring step-0 loss agrees within {LOSS_TOL}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only ring context parallelism over four chips "
                         "and what it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    check(resolve_interpret(None) is False, "Pallas kernels compile, not interpret")
    log(f"[device] {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.four_chips:
        ring_phase(args.seed)
        ring_train_phase(args.seed, train_config())
    else:
        kernel_phase(args.seed)
        train_phase(args.seed, train_config())
        serve_phase(args.seed, serve_config())
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
