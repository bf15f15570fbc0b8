"""The program's own spans, and the runtime's host lines in long device-idle
gaps, read from the profiler trace beside what ``devtrace.read_trace`` keeps.

The program's span recorder (``repro.obs.trace``) opens a ``jax.profiler``
annotation named ``repro.<span>`` for each scoped span, with the span's
numeric args as its stats. :func:`read_trace` returns devtrace's record with
two more keys, on the window's clock:

  "program": [[name, start_s, end_s, {arg: number}], ...]   (``repro.*``)
  "runtime": [[thread, name, start_s, end_s], ...]

``runtime`` holds the host events of any name that overlap a device-idle
gap longer than :data:`RUNTIME_GAP_S`. :func:`idle_by_span` names each idle
gap by the innermost ``bench.*`` or program span over it; on a record with
no ``program`` it reads exactly as ``devtrace.idle_by_host``.

``bench/run.py`` reads per-layer metrics from the record that
``devtrace.read_trace`` returns, which keeps ``bench.*`` host events only, so
the readers of program spans (the ``engine`` layer's) are run by
``tools/trace_program.py`` until ``devtrace.read_trace`` keeps them too.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, Iterable, List, Tuple

import devtrace

# bound here: a tool that reads every window through :func:`read_trace`
# puts it in place of ``devtrace.read_trace``
_read_bench_trace = devtrace.read_trace

PREFIX = "repro."
# the runtime's host lines are kept for idle gaps longer than this (seconds)
RUNTIME_GAP_S = 0.05


def _host_events(trace_dir: str):
    """(thread, event) of every host event in the newest trace file."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    pd = ProfileData.from_file(files[-1])
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    yield line.name, ev


def read_trace(trace_dir: str) -> Dict[str, Any]:
    """``devtrace.read_trace`` of ``trace_dir`` with ``program`` and
    ``runtime`` added (module docstring)."""
    rec = _read_bench_trace(trace_dir)
    events = list(_host_events(trace_dir))
    w0 = next(ev.start_ns for _, ev in events if ev.name == devtrace.WINDOW)
    s = lambda ns: (ns - w0) * 1e-9
    rec["program"] = [
        [ev.name, s(ev.start_ns), s(ev.start_ns + ev.duration_ns),
         {k: v for k, v in ev.stats if isinstance(v, (int, float))}]
        for _, ev in events if ev.name.startswith(PREFIX)]
    rec["runtime"] = runtime_lines(rec, (
        [thread, ev.name, s(ev.start_ns), s(ev.start_ns + ev.duration_ns)]
        for thread, ev in events if ev.name != devtrace.WINDOW))
    return rec


def runtime_lines(rec: Dict[str, Any], lines: Iterable[List[Any]]
                  ) -> List[List[Any]]:
    """The ``[thread, name, start_s, end_s]`` host lines that overlap a
    device-idle gap longer than :data:`RUNTIME_GAP_S`, by start."""
    gaps = long_gaps(rec)
    return sorted((ln for ln in lines
                   if any(ln[2] < g1 and ln[3] > g0 for g0, g1 in gaps)),
                  key=lambda ln: ln[2])


def idle_gaps(rec: Dict[str, Any], device: str) -> List[Tuple[float, float]]:
    """The device's idle intervals in the window, in order."""
    w = rec["window_s"]
    busy = devtrace.union(devtrace.clip(devtrace.op_intervals(rec, device),
                                        0.0, w))
    edges = [0.0] + [x for ab in busy for x in ab] + [w]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def long_gaps(rec: Dict[str, Any], longer: float = RUNTIME_GAP_S
              ) -> List[Tuple[float, float]]:
    """Idle gaps longer than ``longer`` seconds on any device."""
    return devtrace.union(g for d in rec["devices"]
                          for g in idle_gaps(rec, d) if g[1] - g[0] > longer)


def idle_by_span(rec: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """``devtrace.idle_by_host`` with the program's spans as candidates too:
    each gap goes to the innermost span over its midpoint."""
    spans = [[name, a, b] for name, a, b, _ in rec.get("program", [])]
    return devtrace.idle_by_host(dict(rec, host=rec["host"] + spans), n)


def breakdown(rec: Dict[str, Any]) -> Dict[str, Any]:
    return {"device_ops": devtrace.top_ops(rec), "idle_gaps": idle_by_span(rec)}


def spans(rec: Dict[str, Any], name: str) -> List[Tuple[float, float, dict]]:
    """``(start, end, args)`` of the program spans called ``name`` that
    started inside the window."""
    w = rec["window_s"]
    return [(a, b, args) for n, a, b, args in rec.get("program", [])
            if n == name and 0.0 <= a < w]


def idle_within(rec: Dict[str, Any], intervals: List[Tuple[float, ...]]
                ) -> List[float]:
    """Device-idle seconds inside each ``(start, end, ...)`` interval
    (clipped to the window), averaged over devices."""
    w = rec["window_s"]
    out = [0.0] * len(intervals)
    for d in rec["devices"]:
        gaps = idle_gaps(rec, d)
        ends = [b for _, b in gaps]
        for i, (a, b, *_) in enumerate(intervals):
            a, b = max(a, 0.0), min(b, w)
            j = bisect.bisect_right(ends, a)
            while j < len(gaps) and gaps[j][0] < b:
                out[i] += min(b, gaps[j][1]) - max(a, gaps[j][0])
                j += 1
    k = max(1, len(rec["devices"]))
    return [t / k for t in out]
