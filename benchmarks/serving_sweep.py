"""Serving throughput: paged (block-table) vs fixed-slot continuous batching.

ISSUE 7 tentpole measurement. Both engines get the SAME Poisson arrival
trace of mixed-length requests (many short + one long) and the SAME HBM
budget for KV: the fixed engine spends it on ``max_batch`` worst-case
contiguous slices sized for the *longest* request, the paged engine on a
shared page pool -- so at this fragmented operating point the paged engine
runs ~4x the concurrent requests in the same memory. Rows:

  * ``serving_fixed`` / ``serving_paged``: tokens/sec over the measured
    drive (engines pre-warmed: jit compiles happen in a throwaway pass over
    the same trace, so rows time steady-state serving), p50/p95 per-token
    latency (a token's latency = its decode tick's wall time), mean
    slot/page utilization, tick and preemption counts.
  * ``serving_paged_vs_fixed``: the throughput ratio. ASSERTED > 1: paged
    must beat fixed at matched HBM, or the whole indirection is pointless.
  * ``serving_active_cells``: satellite (a) ledger -- KV cells *touched*
    per generated token. The fixed decode walks every slot's full
    ``cache_size`` whether the slot is live or not; the paged kernel's
    page-level ``pl.when`` skip touches only ``ceil(L/ps)`` live pages per
    live row (empty/finished slots touch ZERO pages). ASSERTED strictly
    smaller per token.

``REPRO_SERVING_SMOKE=1`` shrinks the trace/engines for the CI smoke step
(which also pins the zero-decode-recompile invariant). Records merge into
BENCH_serving.json via ``python -m benchmarks.run --json-serving``.
"""

from __future__ import annotations

import os
import time
from typing import List, Tuple

import jax
import numpy as np

from repro.configs import registry
from repro.core.attention import AttentionConfig
from repro.models import lm
from repro.serving.engine import PagedServingEngine, Request, ServingEngine

SMOKE = bool(int(os.environ.get("REPRO_SERVING_SMOKE", "0")))

# Matched HBM budget: fixed = BF slots x CACHE tokens; paged = the same
# token count as a pool (+1 null page), spent on more, mostly-short slots.
if SMOKE:
    BF, CACHE, PS, BP = 2, 64, 8, 4
    N_SHORT, SHORT_LEN, SHORT_NEW = 4, (2, 12), 4
    LONG_LEN, LONG_NEW = 30, 8
    RATE = 1.0
else:
    BF, CACHE, PS, BP = 2, 256, 16, 8
    N_SHORT, SHORT_LEN, SHORT_NEW = 12, (4, 24), 16
    LONG_LEN, LONG_NEW = 150, 32
    RATE = 2.0

NUM_PAGES = BF * CACHE // PS + 1
N_MAX = CACHE // PS  # paged per-seq capacity == the fixed slice


def _trace(seed: int) -> List[Tuple[int, dict]]:
    """Poisson arrivals (RATE requests per expected tick), mixed lengths:
    N_SHORT short prompts + ONE long one injected mid-trace -- the
    fragmented point where worst-case slot reservation hurts most."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(N_SHORT):
        L = int(rng.integers(*SHORT_LEN))
        reqs.append(dict(prompt=[int(t) for t in rng.integers(1, 100, L)],
                         max_new_tokens=SHORT_NEW))
    reqs.insert(N_SHORT // 2, dict(
        prompt=[int(t) for t in rng.integers(1, 100, LONG_LEN)],
        max_new_tokens=LONG_NEW))
    tick = 0
    trace = []
    for r in reqs:
        tick += int(rng.poisson(1.0 / RATE))
        trace.append((tick, r))
    return trace


def _drive(engine, trace, base_rid: int):
    """Run one trace to completion; returns per-tick (wall_s, tokens,
    live_cells, capacity_cells) samples. Arrival times are in engine ticks;
    an idle engine fast-forwards to the next arrival."""
    it = iter(trace)
    pending = next(it, None)
    rid = base_rid
    samples = []
    start = engine.ticks  # arrivals are relative: re-driving the trace on a
    # warmed engine replays the exact same admission pattern (same buckets,
    # same widths -> zero new jit traces in the measured pass)
    while True:
        while pending is not None and pending[0] + start <= engine.ticks:
            spec = pending[1]
            engine.submit(Request(rid=rid, prompt=list(spec["prompt"]),
                                  max_new_tokens=spec["max_new_tokens"]))
            rid += 1
            pending = next(it, None)
        idle = not engine.queue and not any(s is not None for s in engine.slots)
        if idle:
            if pending is None:
                break
            # fast-forward: submit the next arrival now
            spec = pending[1]
            engine.submit(Request(rid=rid, prompt=list(spec["prompt"]),
                                  max_new_tokens=spec["max_new_tokens"]))
            rid += 1
            pending = next(it, None)
            continue
        t0 = time.perf_counter()
        engine.tick()
        dt = time.perf_counter() - t0
        # Common engine interface (ISSUE 8 satellite): both engines expose
        # the cells their decode touches (paged: live pages only; fixed:
        # every slot's full slice) -- no isinstance special-casing.
        toks = sum(1 for l in np.asarray(engine.cache_len) if int(l) > 0)
        samples.append((dt, toks, engine.active_kv_cells(), engine.kv_capacity()))
    return samples


def _summarize(samples):
    total_s = sum(s[0] for s in samples)
    toks = sum(s[1] for s in samples)
    per_tok = [s[0] for s in samples for _ in range(s[1])]
    cells_per_tok = sum(s[2] for s in samples) / max(1, toks)
    occupancy = float(np.mean([s[2] / s[3] for s in samples if s[1]]))
    return dict(
        tok_per_s=toks / total_s if total_s else 0.0,
        us_per_tok=total_s / max(1, toks) * 1e6,
        p50_ms=float(np.percentile(per_tok, 50)) * 1e3,
        p95_ms=float(np.percentile(per_tok, 95)) * 1e3,
        ticks=len(samples),
        tokens=toks,
        cells_per_tok=cells_per_tok,
        occupancy=occupancy,
    )


def run(csv: List[str]) -> None:
    cfg = registry.reduce_config(registry.get("qwen3-8b"))
    params = lm.init_lm(cfg, jax.random.PRNGKey(0))
    attn = AttentionConfig(impl="flash_xla", block_q=64, block_kv=64,
                           decode_splits=2)
    trace = _trace(seed=7)
    n_req = len(trace)

    fixed = ServingEngine(cfg, params, attn, max_batch=BF, cache_size=CACHE,
                          prompt_pad=16)
    paged = PagedServingEngine(cfg, params, attn, max_batch=BP,
                               num_pages=NUM_PAGES, page_size=PS,
                               pages_per_seq_max=N_MAX, prompt_pad=16)
    # warmup pass: same trace, same shapes -> all jit traces built; the
    # measured pass below times steady-state serving only
    _drive(fixed, trace, base_rid=10_000)
    _drive(paged, trace, base_rid=20_000)
    fx = _summarize(_drive(fixed, trace, base_rid=0))
    pg = _summarize(_drive(paged, trace, base_rid=1_000))

    assert len(fixed.finished) == 2 * n_req and len(paged.finished) == 2 * n_req
    # decode_compiles is now COMMON interface (ISSUE 8): pin both engines
    assert paged.decode_compiles == 1, (
        f"paged decode recompiled: {paged.decode_compiles} traces"
    )
    assert fixed.decode_compiles == 1, (
        f"fixed decode recompiled: {fixed.decode_compiles} traces"
    )

    csv.append(
        f"serving_fixed/b{BF}_cache{CACHE},{fx['us_per_tok']:.1f},"
        f"tok_s={fx['tok_per_s']:.1f};p50_ms={fx['p50_ms']:.1f};"
        f"p95_ms={fx['p95_ms']:.1f};ticks={fx['ticks']};tokens={fx['tokens']};"
        f"slot_occupancy={fx['occupancy']:.3f};"
        f"decode_compiles={fixed.decode_compiles}"
    )
    csv.append(
        f"serving_paged/b{BP}_ps{PS}x{NUM_PAGES},{pg['us_per_tok']:.1f},"
        f"tok_s={pg['tok_per_s']:.1f};p50_ms={pg['p50_ms']:.1f};"
        f"p95_ms={pg['p95_ms']:.1f};ticks={pg['ticks']};tokens={pg['tokens']};"
        f"page_occupancy={pg['occupancy']:.3f};"
        f"preemptions={paged.preemptions};decode_compiles={paged.decode_compiles}"
    )

    speedup = pg["tok_per_s"] / fx["tok_per_s"]
    assert speedup > 1.0, (
        f"paged engine must beat fixed at matched HBM on the fragmented "
        f"trace: paged {pg['tok_per_s']:.1f} vs fixed {fx['tok_per_s']:.1f} "
        f"tok/s (x{speedup:.2f})"
    )
    csv.append(
        f"serving_paged_vs_fixed/matched_hbm_{BF * CACHE}tok,,"
        f"speedup=x{speedup:.2f};asserted=paged>fixed"
    )

    saving = pg["cells_per_tok"] / fx["cells_per_tok"]
    assert pg["cells_per_tok"] < fx["cells_per_tok"], (
        f"paged decode must touch fewer KV cells per token "
        f"(paged {pg['cells_per_tok']:.0f} vs fixed {fx['cells_per_tok']:.0f})"
    )
    csv.append(
        f"serving_active_cells/per_token_{BF * CACHE}tok,,"
        f"paged={pg['cells_per_tok']:.0f};fixed={fx['cells_per_tok']:.0f};"
        f"ratio={saving:.3f};asserted=paged<fixed"
    )
