"""What every cell shares: finding a cell's files by name, the device check,
the compile cache, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix or per-layer metric sits in a file
of its own, found by name:

  bench/configs/<config>.json      model sizes, as run, and the deployment
  bench/traffic/<traffic>.json     traffic parameters; ``kind`` names the
  bench/traffic/<kind>.py          generator and driver that reads them
  bench/limits/<workload>.json     the limits ``correct`` is judged by
  bench/layer_metrics/<name>.py    one per-layer metric: ``read(rec, ctx)``
  bench/work/<family>.py           FLOPs and bytes one call requires

so a later cell, mix or metric is new files only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
from typing import Any, Dict, List, Optional


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    root: pathlib.Path
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def bench(self) -> pathlib.Path:
        return self.root / "bench"


def load_module(path: pathlib.Path, name: Optional[str] = None):
    """Import one file by path (metric names hold dots, so not by import)."""
    spec = importlib.util.spec_from_file_location(
        name or f"bench_plugin_{path.stem.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], workload: str,
             reported: Optional[set] = None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    """Find ``workload`` in ``<root>/BENCHMARK.json`` and load its files."""
    bench = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; have {sorted(by_name)}")
    w = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(root / "bench" / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(workload, root, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


def traffic_kind(cell: Cell):
    """The generator-and-driver module of the cell's traffic ``kind``."""
    return load_module(cell.bench / "traffic" / f"{cell.traffic['kind']}.py")


def work_family(root: pathlib.Path, family: str):
    return load_module(root / "bench" / "work" / f"{family}.py")


def peaks(root: pathlib.Path, device_kind: str) -> Dict[str, Any]:
    """The peak row of ``device_kind``; a kind not in the table is an error."""
    table = _read_json(root / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peak row for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


# ---------------------------------------------------------------- device


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache``, a fixed path, so
    only a cell's first run in a checkout compiles. The program's own
    ``utils.compile_cache`` reads the same variable."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_accelerator(chips: int):
    """The TPU devices the cell runs on; raises :class:`NoAccelerator`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"needs {chips} TPU chips, found {len(devices)}")
    return devices[:chips]


def device_info(devices) -> Dict[str, Any]:
    """``device`` of the result line: platform, kind, count, and the peak
    bytes in use on the fullest chip (read before any reference runs)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def seed_words(seed: int, stream: int = 0) -> List[int]:
    """Two 32-bit words from any whole ``seed`` (larger than 32 bits too)
    and a stream number, for numpy and JAX generators alike."""
    import numpy as np

    return [int(x) for x in
            np.random.SeedSequence([seed % (1 << 64), stream]).generate_state(2)]


def jax_key(seed: int, stream: int = 0):
    import jax
    import jax.numpy as jnp

    return jax.random.wrap_key_data(
        jnp.asarray(seed_words(seed, stream), jnp.uint32), impl="threefry2x32")


# ---------------------------------------------------------------- result


def judge(values: Dict[str, float], limits: Dict[str, Any]) -> Dict[str, Any]:
    """Each compared number beside its limit; a number is within its limit
    when finite and no larger. A number without a limit is an error."""
    checks = {}
    for name, value in values.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        checks[name] = {"value": value, "limit": limits[name]["limit"]}
    return checks


def checks_pass(checks: Dict[str, Any]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Any], device: Dict[str, Any],
                checks: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The contract's one JSON line; ``checks`` (each compared number with
    its limit) comes last."""
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def print_checks(checks: Dict[str, Any], file=None) -> None:
    file = file or sys.stderr
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=file)
    file.flush()


def log(msg: str) -> None:
    """Progress lines go to stderr; stdout ends with the result line."""
    print(msg, file=sys.stderr, flush=True)


def metric_entry(value: float, unit: str) -> Dict[str, Any]:
    return {"value": float(value), "unit": unit}


def read_layer_metrics(cell: Cell, rec: Dict[str, Any],
                       ctx: Any) -> Dict[str, Any]:
    """Each applicable per-layer metric's reader over the traced run's
    record; a reader that finds nothing returns None and is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(cell.bench / "layer_metrics" / f"{m['name']}.py")
        value = mod.read(rec, ctx)
        if value is not None:
            out[m["name"]] = metric_entry(value, m["unit"])
    return out
