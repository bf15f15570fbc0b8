#!/usr/bin/env python3
"""Readings that a serving cell's limits are set from, on the chip, in one
process, for a traffic kind that compares more than one number
(``served_readings``, as ``serve_open_dsv2`` does).

    python3 bench/tools/calibrate_serve.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <n> ...] --out <file>

For each seed it runs the cell as ``run.py`` does and writes one JSON line:
the program's compared numbers, the end-to-end metrics, the failures and
the device's memory peak; for a seed also listed in ``--control-seeds``,
the control's numbers besides -- the reference in fp8, the precision below
the configuration's bf16, put in the program's place on the same finished
requests -- judged by the same comparison.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    cell = harness.load_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    devices = harness.require_accelerator(cell.chips)
    kind = harness.traffic_kind(cell)
    import model

    for seed in args.seeds:
        t0 = time.perf_counter()
        out = kind.run(cell, devices, seed=seed, seconds=args.seconds,
                       trace=False, t_start=t0)
        line = {"seed": seed,
                "program": {k: c["value"] for k, c in out["checks"].items()},
                "correct": harness.checks_pass(out["checks"]),
                "e2e": out["e2e"], "failed": out["failed"],
                "attempted": out["attempted"],
                "memory_peak_bytes": out["device"].get("memory_peak_bytes")}
        if seed in args.control_seeds:
            params = model.make_weights(model.program_config(cell.config),
                                        harness.jax_key(seed))
            line["control"] = kind.served_readings(params, cell, out["sample"],
                                                   quant="fp8")
            del params  # the next seed makes its own
        del out
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
