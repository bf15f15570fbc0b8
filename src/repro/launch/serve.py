"""Serving driver: load (or init) a model and run the continuous-batching
engine over a file or synthetic stream of requests.

Usage:
  python -m repro.launch.serve --arch qwen3-8b --reduce --requests 8
  python -m repro.launch.serve --arch hymba-1.5b --reduce --ckpt-dir /ck
  python -m repro.launch.serve --arch qwen3-8b --reduce --engine paged \
      --num-pages 128 --page-size 16
  python -m repro.launch.serve --arch qwen3-8b --reduce --engine paged \
      --arrival-rate 1.0 --trace-out trace.json --metrics-out metrics.json

``--engine fixed`` (default) reserves a worst-case contiguous cache slice
per slot; ``--engine paged`` serves from a shared page pool with
block-table indirect flash decode (attention-only archs).

Observability (repro.obs): every run collects the unified metrics
registry (printed as the ``metrics`` block of the JSON summary, written
to ``--metrics-out``); ``--trace-out PATH`` additionally records the
request lifecycle (submit -> queue_wait -> prefill -> decode -> retire,
plus preempt/resume) and each tick's ``engine.*`` spans as
Chrome/Perfetto ``trace_event`` JSON -- load the file at
https://ui.perfetto.dev for a tick-by-tick timeline.
``--arrival-rate R`` replays a Poisson arrival process (R requests per
expected tick) instead of submitting everything upfront, so queue-wait
spans reflect admission pressure rather than a thundering herd.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.configs import registry
from repro.core.attention import AttentionConfig
from repro.models import lm
from repro.obs import MetricsRegistry, TraceRecorder, default_registry
from repro.serving.engine import PagedServingEngine, Request, ServingEngine
from repro.utils.compile_cache import enable_compile_cache


def _drive_poisson(engine, requests, rate: float, seed: int,
                   max_ticks: int) -> None:
    """Submit ``requests`` on a Poisson schedule (in engine ticks) while
    ticking; an idle engine fast-forwards to the next arrival."""
    rng = np.random.default_rng(seed)
    arrivals = []
    tick = 0
    for req in requests:
        tick += int(rng.poisson(1.0 / rate))
        arrivals.append((tick, req))
    it = iter(arrivals)
    pending = next(it, None)
    while engine.ticks < max_ticks:
        while pending is not None and pending[0] <= engine.ticks:
            engine.submit(pending[1])
            pending = next(it, None)
        idle = not engine.queue and not any(
            s is not None for s in engine.slots
        )
        if idle:
            if pending is None:
                break
            engine.submit(pending[1])  # fast-forward to the next arrival
            pending = next(it, None)
            continue
        engine.tick()


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache", type=int, default=256)
    ap.add_argument("--attn", default="flash_xla")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=("fixed", "paged"), default="fixed")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged: pool size; default matches the fixed "
                         "engine's HBM (max_batch * cache / page_size + 1)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages-per-seq", type=int, default=None,
                    help="paged: block-table width; default cache/page_size")
    ap.add_argument("--prefill-token-budget", type=int, default=None,
                    help="paged: most prompt tokens (width x bucket) one "
                         "prefill launch may hold; default unlimited")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrivals (requests per expected tick); "
                         "default submits every request upfront")
    ap.add_argument("--trace-out", default=None,
                    help="write the request-lifecycle Perfetto trace here")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final metrics snapshot (JSON) here")
    args = ap.parse_args()

    cfg = registry.get(args.arch)
    if args.reduce:
        cfg = registry.reduce_config(cfg)
    assert cfg.family != "encdec", "serve driver covers decoder-only families"
    params = lm.init_lm(cfg, jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        store = CheckpointStore(args.ckpt_dir)
        if store.latest_step() is not None:
            (params, _), meta = store.restore((params, None))
            print(f"[serve] restored step {meta.get('step')} from {args.ckpt_dir}")

    # Block sizes left at None so prefill resolves them per shape
    # (kernels/ops.default_block_sizes); the decode fans out over the
    # config's default split count.
    attn_cfg = AttentionConfig(impl=args.attn)
    obs_registry = MetricsRegistry()
    tracer = TraceRecorder(process=f"serve:{args.engine}") if args.trace_out else None
    if args.engine == "paged":
        num_pages = args.num_pages or (
            args.max_batch * args.cache // args.page_size + 1
        )
        n_max = args.pages_per_seq or max(1, args.cache // args.page_size)
        engine = PagedServingEngine(
            cfg, params, attn_cfg, max_batch=args.max_batch,
            num_pages=num_pages, page_size=args.page_size,
            pages_per_seq_max=n_max,
            prefill_token_budget=args.prefill_token_budget,
            registry=obs_registry, tracer=tracer,
        )
    else:
        engine = ServingEngine(cfg, params, attn_cfg, max_batch=args.max_batch,
                               cache_size=args.cache,
                               registry=obs_registry, tracer=tracer)
    rng = np.random.default_rng(args.seed)
    requests = [
        Request(rid=rid,
                prompt=rng.integers(1, min(cfg.vocab_size, 1000),
                                    size=int(rng.integers(2, 12))).tolist(),
                max_new_tokens=args.max_new)
        for rid in range(args.requests)
    ]

    t0 = time.perf_counter()
    if args.arrival_rate:
        _drive_poisson(engine, requests, args.arrival_rate, args.seed,
                       max_ticks=10_000)
    else:
        for req in requests:
            engine.submit(req)
        engine.run(max_ticks=10_000)
    dt = time.perf_counter() - t0
    finished = engine.finished
    toks = sum(len(r.generated) for r in finished.values())
    snap = engine.snapshot()
    # counters with no owner to hand them a registry (sharding, ring,
    # checkpoint) live on the process-wide default one; fold them in
    snap.update(default_registry().snapshot())
    summary = {
        "engine": args.engine, "requests": len(finished),
        "ticks": engine.ticks, "generated_tokens": toks,
        "tok_per_s": round(toks / dt, 1),
        "decode_compiles": engine.decode_compiles,
    }
    if args.engine == "paged":
        summary["preemptions"] = engine.preemptions
    print(json.dumps(summary))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
        print(f"[serve] wrote metrics snapshot to {args.metrics_out}")
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"[serve] wrote Perfetto trace ({len(tracer.events)} events) "
              f"to {args.trace_out}")
    for rid in sorted(finished)[:4]:
        print(f"  req {rid}: {finished[rid].generated}")


if __name__ == "__main__":
    main()
