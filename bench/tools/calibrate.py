#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/tools/calibrate.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] [--control] [--fault half_batch] --out <file>

For each seed it runs the cell as ``run.py`` does (``--seconds`` of window;
training needs none) and writes one JSON line: the program's compared
numbers and, where asked, the control's -- the reference put in the
program's place in the nearest precision below the configuration's (fp8 for
bf16) -- and a planted fault's, each judged by the same comparison.

Training faults: ``half_batch`` (the reference on the first half of each
batch's rows, the mean taken over them). A state left unchanged reads 1 on
``change_gap`` by construction and needs no run.
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
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("half_batch",), default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    cell = harness.load_cell(ROOT, args.workload)
    harness.enable_compile_cache(ROOT)
    devices = harness.require_accelerator(cell.chips)
    kind = harness.traffic_kind(cell)
    import model
    from ref import qwen3 as ref

    for seed in args.seeds:
        t0 = time.perf_counter()
        out = kind.run(cell, devices, seed=seed, seconds=args.seconds,
                       trace=False, t_start=t0)
        line = {"seed": seed, "program": {k: c["value"] for k, c in
                                          out["checks"].items()},
                "e2e": out["e2e"], "failed": out["failed"],
                "attempted": out["attempted"]}
        if cell.traffic["kind"] == "train_closed":
            want = out["readings"]["reference"]
            line["losses"] = {"program": out["readings"]["program"]["losses"],
                              "reference": want["losses"]}
            batches = kind.build_batches(cell, seed)
            weights = lambda: kind._host_weights(cell, seed)
            dims = ref.Dims.from_config(cell.config)
            opt = ref.Adam(**cell.traffic["optimizer"])
            n = cell.traffic["check_steps"]
            if args.control:
                got = ref.train_readings(weights, batches, dims, opt,
                                         quant="fp8", steps=n)
                line["control"] = kind.compare(got, want)
                line["losses"]["control"] = got["losses"]
            if args.fault == "half_batch":
                got = ref.train_readings(weights, batches, dims, opt, steps=n,
                                         rows=cell.traffic["rows"] // 2)
                line["half_batch"] = kind.compare(got, want)
                line["losses"]["half_batch"] = got["losses"]
        elif args.control:
            params = model.make_weights(model.program_config(cell.config),
                                        harness.jax_key(seed))
            line["control"] = {"served_gap": kind.served_gaps(
                params, cell, out["sample"], quant="fp8")}
            del params  # the next seed makes its own
        del out
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
