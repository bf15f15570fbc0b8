#!/usr/bin/env python3
"""Run one cell traced, as ``bench/run.py --trace 1`` does, keeping the
program's own spans and the runtime's host lines in the record
(``program_trace``), and report what they show.

    python3 bench/tools/trace_program.py --workload <name> --seed <n> \
        --seconds <s> [--record-out <file>] [--fixture-out <file>]

Prints one JSON line last on stdout: ``correct``, the cell's end-to-end
metrics as the traced window measured them (``e2e``; ``bench/run.py``
leaves them out of a traced run's line), its per-layer metrics together
with the readers of the program's spans (the ``engine`` layer's
``prefill_p90_ms``, ``prefill_pad_share``, ``engine_host_ms_per_tick``),
``device``, the breakdown with each idle gap named by the innermost
``bench.*`` or program span over it, and how many runtime host lines
overlap a device-idle gap longer than ``program_trace.RUNTIME_GAP_S``;
those lines go to stderr. ``--fixture-out`` writes about a second of the
record, from the first tick of the window that admitted a request, as a
test fixture.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import devtrace  # noqa: E402
import harness  # noqa: E402
import program_trace  # noqa: E402

# by path, under a name of its own: ``run`` may name another module
run = harness.load_module(ROOT / "bench" / "run.py", "bench_run")  # Context

# the readers of the program's spans, with their units
READERS = {"prefill_p90_ms.serve": "ms", "prefill_pad_share.serve": "%",
           "engine_host_ms_per_tick.serve": "ms"}


def cut(rec: Dict[str, Any], t0: float, t1: float) -> Dict[str, Any]:
    """The events of ``rec`` that start in ``[t0, t1)``, with ``t0`` as the
    new window's start and ``t1 - t0`` its length."""
    inside = lambda a: t0 <= a < t1
    host = rec["host_record"]
    return {
        "window_s": t1 - t0,
        "host": [[n, a - t0, b - t0] for n, a, b in rec["host"] if inside(a)],
        "devices": {d: {k: [[n, a - t0, dur] for n, a, dur in v[k]
                            if inside(a)] for k in ("ops", "modules")}
                    for d, v in rec["devices"].items()},
        "program": [[n, a - t0, b - t0, args]
                    for n, a, b, args in rec["program"] if inside(a)],
        "runtime": [[th, n, a - t0, b - t0]
                    for th, n, a, b in rec["runtime"] if inside(a)],
        "host_record": dict(host, window_s=t1 - t0, ticks=[
            [a - t0, b - t0, lens] for a, b, lens in host.get("ticks", [])
            if inside(a)]),
    }


def fixture(rec: Dict[str, Any], seconds: float = 0.9) -> Dict[str, Any]:
    """About ``seconds`` of whole ``bench.tick`` spans, from 1 ms before the
    first one that holds an ``engine.admit`` span."""
    admits = program_trace.spans(rec, "repro.engine.admit")
    ticks = sorted(devtrace.host_spans(rec, "bench.tick"))
    first = next(i for i, (a, b) in enumerate(ticks)
                 if any(a <= s0 < b for s0, _, _ in admits))
    t0 = ticks[first][0] - 1e-3
    t1 = next((b for _, b in ticks[first:] if b - t0 >= seconds),
              ticks[-1][1])
    return cut(rec, t0, t1)


def main(argv=None, *, root: pathlib.Path = ROOT,
         require=harness.require_accelerator) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record-out", default=None,
                    help="write the whole record (JSON) here")
    ap.add_argument("--fixture-out", default=None,
                    help="write about a second of the record here")
    args = ap.parse_args(argv)
    cell = harness.load_cell(root, args.workload)
    harness.enable_compile_cache(root)
    try:
        devices = require(cell.chips)
    except harness.NoAccelerator as e:
        harness.log(f"bench: {e}")
        return 2

    # the window's trace is read through this name when the window closes
    devtrace.read_trace = program_trace.read_trace
    out = harness.traffic_kind(cell).run(
        cell, devices, seed=args.seed, seconds=args.seconds, trace=True,
        t_start=T_START)
    rec = dict(out["trace"], host_record=out["host"])
    device = dict(out["device"], busy_s=devtrace.busy_s(rec),
                  window_s=rec["window_s"])
    metrics = harness.read_layer_metrics(cell, rec, run.Context(root, device))
    for name, unit in READERS.items():
        value = harness.load_module(
            root / "bench" / "layer_metrics" / f"{name}.py").read(rec, None)
        if value is not None:
            metrics[name] = harness.metric_entry(value, unit)
    for thread, name, a, b in rec["runtime"]:
        harness.log(f"[runtime] {a:.6f}-{b:.6f} s {thread}: {name}")
    if args.record_out:
        with open(args.record_out, "w") as f:
            json.dump(rec, f)
    if args.fixture_out:
        with open(args.fixture_out, "w") as f:
            json.dump(fixture(rec), f)
    harness.print_checks(out["checks"])
    print(json.dumps({
        "correct": harness.checks_pass(out["checks"]), "e2e": out["e2e"],
        "metrics": metrics, "device": device,
        "breakdown": program_trace.breakdown(rec),
        "runtime_lines": len(rec["runtime"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
