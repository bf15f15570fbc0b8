#!/usr/bin/env python3
"""Spread of each end-to-end metric over sets of runs, and the bound it
suggests.

    python3 bench/tools/spread.py <runs.jsonl> [...]

Each line of a file is ``{"set": .., "seed": .., "rc": .., "line": <the
run's result line>}``. For each cell, metric and set it prints the median
and the spread -- the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median -- then
the wider set's spread and five times it, the bound the contract asks for
(never under 1%). ``setup_s`` of each set's first run, which may compile,
is printed apart and left out of its set.
"""

from __future__ import annotations

import collections
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths) -> int:
    runs = collections.defaultdict(lambda: collections.defaultdict(list))
    for path in paths:
        cell = path.rsplit("/", 1)[-1].rsplit(".jsonl", 1)[0]
        with open(path) as f:
            for raw in f:
                r = json.loads(raw)
                if r["line"] is None:
                    print(f"{cell} set {r['set']} seed {r['seed']}: no result "
                          f"(rc {r['rc']})")
                    continue
                runs[cell][r["set"]].append(r["line"])
    for cell, sets in runs.items():
        for set_name, lines in sets.items():
            bad = [l for l in lines if not l["correct"]]
            print(f"{cell} set {set_name}: {len(lines)} runs, "
                  f"{len(bad)} not correct, first setup_s "
                  f"{lines[0]['metrics']['setup_s']['value']:.3f}")
        for metric in lines[0]["metrics"]:
            widest = 0.0
            for set_name, lines in sets.items():
                vals = [l["metrics"][metric]["value"] for l in lines]
                if metric == "setup_s":
                    vals = vals[1:]
                s = spread(vals)
                widest = max(widest, s)
                print(f"  {metric} set {set_name}: median "
                      f"{statistics.median(vals)!r} spread {s:.5f} "
                      f"values {vals}")
            print(f"  {metric}: widest spread {widest:.5f}, five times "
                  f"{max(0.01, 5 * widest):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
