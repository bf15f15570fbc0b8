#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell on the chip, in one process, to
find the highest rate the engine sustains (the knee).

    python3 bench/tools/sweep_serve.py --workload <name> --seed <n> \
        --seconds <s> --rates <r> [<r> ...] --out <file>

For each rate: the cell's traffic at that rate for its warm-up and then
``--seconds`` of window, a drain for the window's first tokens, and one
JSON line, read by the cell's own ``window_metrics``: the output tokens/s,
the time-to-first-token median and 90th percentile, the inter-token 95th
percentile, the queue at the window's start and end, and how many of the window's requests had no first token
``--drain`` seconds past it. A rate is sustained when the queue does not
grow through the window and every request due in it gets its first token.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--drain", type=float, default=20.0,
                    help="seconds past the window to wait for its requests")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    cell = harness.load_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    harness.require_accelerator(cell.chips)
    kind = harness.traffic_kind(cell)
    import devtrace
    import model

    cfg = model.program_config(cell.config)
    params = model.make_weights(cfg, harness.jax_key(args.seed))
    engine = kind.build_engine(cell, params)
    kind.warm(engine, cell)
    for i, rate in enumerate(args.rates):
        tr = dict(cell.traffic, rate_per_s=rate)
        client = kind.Client(engine, cfg.vocab_size, args.seed + i)
        client.next_rid = base = 10_000_000 * (i + 1)
        plans, n_warm, n_win = kind.schedule(tr, args.seconds)
        rids = list(range(base + n_warm, base + n_warm + n_win))
        t = time.perf_counter()
        client.drive(plans, t, t + tr["warmup_s"])
        q0 = len(engine.queue)
        with devtrace.Window(False) as win:
            client.drive(plans, t, t + tr["warmup_s"] + args.seconds)
        q1 = len(engine.queue)
        client.drive(plans, t, win.t1 + args.drain,
                     stop=lambda: all(client.token_times.get(r) for r in rids))
        seen = kind.window_metrics(client, rids, win.t0, win.t1,
                                   time.perf_counter())
        while engine.queue or any(s is not None for s in engine.slots):
            engine.tick()  # empty the engine for the next rate
        line = {
            "rate_per_s": rate, "window_s": win.seconds,
            "output_tokens_per_s": seen["delivered"] / win.seconds,
            "ttft_p50_ms": kind.nearest_rank(seen["ttft"], 0.5) * 1e3,
            "ttft_p90_ms": kind.nearest_rank(seen["ttft"], 0.9) * 1e3,
            "itl_p95_ms": kind.nearest_rank(seen["gaps"], 0.95) * 1e3,
            "queue_at_window_start": q0, "queue_at_window_end": q1,
            "no_first_token": seen["failed"],
            "due": n_win, "lateness_max_ms": max(client.lateness) * 1e3,
        }
        print(json.dumps(line), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
