#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, sets up (weights and state from
the seed, every shape warmed), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON line
last on stdout: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window), ``device``, and ``checks`` (each
compared number with its limit), which also end stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import harness  # noqa: E402


class Context:
    """What a per-layer metric's reader may use besides the record."""

    def __init__(self, root: pathlib.Path, device: dict):
        self.root = root
        self.device = device
        self.peak = harness.peaks(root, device["kind"])

    def work(self, family: str):
        return harness.work_family(self.root, family)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record-out", default=None,
                    help="also write the traced run's record (JSON) here, "
                         "and a description of the raw trace beside it")
    return ap.parse_args(argv)


def main(argv=None, *, root: pathlib.Path = ROOT,
         require=harness.require_accelerator, t_start: float = T_START) -> int:
    args = parse(argv)
    cell = harness.load_cell(root, args.workload)
    harness.enable_compile_cache(root)
    try:
        devices = require(cell.chips)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    kind = harness.traffic_kind(cell)
    if args.record_out:
        import devtrace

        devtrace.Window.describe_to = args.record_out + ".txt"
    out = kind.run(cell, devices, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_start=t_start)
    device = out["device"]
    breakdown = None
    if args.trace:
        rec = dict(out["trace"], host_record=out["host"])
        if args.record_out:
            with open(args.record_out, "w") as f:
                json.dump(rec, f)
        from devtrace import breakdown as make_breakdown, busy_s

        device = dict(device, busy_s=busy_s(rec), window_s=rec["window_s"])
        metrics = harness.read_layer_metrics(cell, rec, Context(root, device))
        breakdown = make_breakdown(rec)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: harness.metric_entry(v, units[k])
                   for k, v in out["e2e"].items() if k in units}
    correct = harness.checks_pass(out["checks"])
    harness.print_checks(out["checks"])
    print(harness.result_line(
        correct=correct, attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=device, checks=out["checks"],
        breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
