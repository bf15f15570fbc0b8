"""Benchmark harness: one module per paper table/figure.

  fig4_6_attn_speed   Fig. 4/5/6 -- attention speed, 3 impls x seq len
                      (+ compact-vs-dense Pallas tile-schedule comparison
                      + fused-vs-split backward comparison)
  sched_cmp           the schedule comparison alone (CI fast-tier smoke;
                      not in ALL -- fig4_6_attn_speed already includes it)
  bwd_cmp             the fused-vs-split backward comparison alone (CI
                      fast-tier smoke; not in ALL for the same reason)
  nonmatmul_census    Section 3.1 C1 -- FA1-vs-FA2 non-matmul FLOP census
                      (+ the backward exp census: one exp per visible tile
                      fused, two split -- asserted)
  table1_e2e          Table 1 -- end-to-end GPT training throughput
  roofline            deliverable (g) -- dry-run roofline table
  ring_accounting     context-parallel ring vs all-gather: per-mode comms
                      bytes, peak KV bytes, step/launch counts (static
                      ledger; no timing -- also in the CI fast smoke)
  occupancy_sweep     Fig. 5 analog -- forward partitioning (q-banded /
                      unbanded compact / dense) over a B x H x S grid:
                      grid-utilization ledger (asserted), kernel-layer
                      timing, banded exp census (also in the CI smoke)
  serving_sweep       ISSUE 7 -- paged vs fixed-slot continuous batching at
                      matched HBM on a Poisson mixed-length trace:
                      tokens/sec, p50/p95 per-token latency, utilization,
                      active-cell ledger (paged>fixed ASSERTED)

Prints ``name,us_per_call,derived`` CSV.

    python -m benchmarks.run [--json PATH] [--json-serving PATH]
                             [--prune-stale] [names]

``--json PATH`` additionally writes the rows as machine-readable records
``{"bench", "config", "us_per_call", "derived"}`` (the perf trajectory file
committed as BENCH_attn.json; CI runs a fast-tier smoke of it). An existing
file is MERGED, not clobbered: rows whose (bench, config) the current run
re-measured are replaced, everything else is kept — so the fast CI smoke
(sched_cmp + ring_accounting) never erases the fig4/fig5 trajectory.

``--json-serving PATH`` routes rows of the serving benches (bench name
starting with ``serving``) into their own trajectory file (committed as
BENCH_serving.json) with the same merge/dedupe/backup rules; with it set,
``--json`` receives only the non-serving rows.

Durability rules (the committed trajectory must survive bad runs):

  * a corrupt/truncated/mis-typed existing file never crashes the merge —
    it is backed up to ``PATH.bad`` with a warning and the run continues
    from an empty trajectory (losing the history to a crash in CI was the
    original failure mode);
  * kept + fresh rows are deduped by (bench, config), last write wins;
  * ``--prune-stale`` drops kept rows belonging to a *bench this run
    re-measured* whose (bench, config) was not emitted again — i.e. rows
    stranded by a config rename. Benches that did not run are never pruned.
  * every merged row (kept + fresh) passes a required-key schema check
    (``bench``/``config`` identity plus a units field: numeric
    ``us_per_call`` or non-empty ``derived``); nonconforming rows are
    warned about and tagged ``"schema": "nonconforming: ..."`` instead of
    silently mixing into the committed trajectory.
"""

from __future__ import annotations

import json
import os
import sys
import time

ALL = ("fig4_6_attn_speed", "nonmatmul_census", "table1_e2e", "roofline",
       "ring_accounting", "occupancy_sweep", "autotune_sweep",
       "serving_sweep")


def _records(csv_rows):
    """CSV rows ('bench/config...,us,derived') -> list of dict records."""
    records = []
    for row in csv_rows:
        name, _, rest = row.partition(",")
        us, _, derived = rest.partition(",")
        bench, _, config = name.partition("/")
        try:
            us_val = float(us)
        except ValueError:
            us_val = None
        records.append(
            dict(bench=bench, config=config, us_per_call=us_val, derived=derived)
        )
    return records


def _load_existing(json_path: str):
    """Tolerantly load the committed trajectory; never crash the merge.

    A corrupt/truncated file (a killed CI run mid-write) or a wrong-typed
    one is moved aside to ``PATH.bad`` with a warning and treated as empty,
    so one bad write can't take the merge step — and the whole committed
    history — down with it. Rows are deduped by (bench, config), keeping
    the last occurrence (the newest measurement of a key wins).
    """
    if not os.path.exists(json_path):
        return []
    try:
        with open(json_path) as f:
            rows = json.load(f)
        if not isinstance(rows, list) or not all(
            isinstance(r, dict) and "bench" in r and "config" in r for r in rows
        ):
            raise ValueError("trajectory must be a list of bench/config records")
    except (json.JSONDecodeError, ValueError, OSError) as e:
        backup = json_path + ".bad"
        os.replace(json_path, backup)
        print(f"# WARNING: existing {json_path} is invalid ({e}); backed it "
              f"up to {backup} and starting a fresh trajectory", file=sys.stderr)
        return []
    deduped = {}
    for r in rows:
        deduped[(r["bench"], r["config"])] = r
    if len(deduped) != len(rows):
        print(f"# deduped {len(rows) - len(deduped)} duplicate (bench, config) "
              f"rows in {json_path}", file=sys.stderr)
    return list(deduped.values())


# Required ledger-row schema, enforced at merge time: identity keys plus
# the units-bearing fields. Every bench module emits heterogeneous derived
# payloads, but a row missing its identity or carrying NO measurement at
# all (neither a us_per_call number nor a derived string) used to mix
# silently into the committed BENCH_*.json; now it is warned about and
# tagged so downstream readers can filter it.
REQUIRED_ROW_KEYS = ("bench", "config", "us_per_call", "derived")


def _check_schema(rows):
    """Warn-and-tag nonconforming ledger rows (never drop, never crash).

    A conforming row has all of ``REQUIRED_ROW_KEYS``, a non-empty
    ``bench`` name, and at least one units field filled in: a numeric
    ``us_per_call`` or a non-empty ``derived`` payload. Violations get a
    ``"schema": "nonconforming: <reason>"`` tag and a stderr warning.
    """
    bad = 0
    for r in rows:
        reason = None
        missing = [k for k in REQUIRED_ROW_KEYS if k not in r]
        if missing:
            reason = f"missing keys {missing}"
        elif not isinstance(r["bench"], str) or not r["bench"]:
            reason = "empty bench name"
        elif (not isinstance(r["us_per_call"], (int, float))
              and not (isinstance(r.get("derived"), str) and r["derived"])):
            reason = "no units field (neither us_per_call nor derived)"
        if reason is not None:
            r["schema"] = f"nonconforming: {reason}"
            bad += 1
        else:
            r.pop("schema", None)  # row was fixed since it was tagged
    if bad:
        print(f"# WARNING: {bad} ledger rows are nonconforming; tagged with "
              f"a 'schema' field instead of mixing silently", file=sys.stderr)
    return rows


def _merge_trajectory(json_path, records, prune_stale):
    """Merge fresh records into the committed trajectory at json_path.
    All rows (kept + fresh) pass the required-key schema check first."""
    fresh = {(r["bench"], r["config"]) for r in records}
    fresh_benches = {b for b, _ in fresh}
    kept = [r for r in _load_existing(json_path)
            if (r["bench"], r["config"]) not in fresh]
    if prune_stale:
        stale = [r for r in kept if r["bench"] in fresh_benches]
        if stale:
            print(f"# --prune-stale: dropping {len(stale)} stale rows of "
                  f"re-measured benches", file=sys.stderr)
        kept = [r for r in kept if r["bench"] not in fresh_benches]
    records = _check_schema(kept + records)
    with open(json_path, "w") as f:
        json.dump(records, f, indent=1)
    print(f"# wrote {json_path} ({len(records)} rows)", file=sys.stderr)


def main() -> None:
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = sys.argv[1:]
    json_path = None
    serving_path = None
    prune_stale = "--prune-stale" in args
    if prune_stale:
        args.remove("--prune-stale")
    for flag in ("--json", "--json-serving"):
        if flag in args:
            i = args.index(flag)
            if i + 1 >= len(args):
                sys.exit("usage: python -m benchmarks.run [--json PATH] "
                         "[--json-serving PATH] [--prune-stale] [names]")
            if flag == "--json":
                json_path = args[i + 1]
            else:
                serving_path = args[i + 1]
            args = args[:i] + args[i + 2:]
    names = args or list(ALL)
    csv = ["name,us_per_call,derived"]
    for name in names:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.perf_counter()
        before = len(csv)
        mod.run(csv)
        dt = time.perf_counter() - t0
        print(f"# {name}: {len(csv) - before} rows in {dt:.1f}s", file=sys.stderr)
    print("\n".join(csv))
    if json_path or serving_path:
        records = _records(csv[1:])
        if serving_path:
            serving = [r for r in records if r["bench"].startswith("serving")]
            records = [r for r in records if not r["bench"].startswith("serving")]
            _merge_trajectory(serving_path, serving, prune_stale)
        if json_path:
            _merge_trajectory(json_path, records, prune_stale)


if __name__ == "__main__":
    main()
