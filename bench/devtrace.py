"""The measured window, its profiler trace, and the reduction of that trace
to device busy time, idle gaps, kernel time and module time.

A traced run wraps its window in ``jax.profiler`` tracing and puts host
spans (``jax.profiler.TraceAnnotation``, all named ``bench.*``) around each
call it makes into the program. :func:`read_trace` turns the profiler's
``.xplane.pb`` into a plain record:

  {"window_s": float,
   "host": [[name, start_s, end_s], ...],
   "devices": {device: {"ops": [[name, start_s, dur_s], ...],
                        "modules": [[name, start_s, dur_s], ...]}}}

with every time in seconds from the start of the ``bench.window`` span.
Everything below works on that record, so it is checked on a small
recorded one (``bench/tests/fixtures``) without a chip.
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import tempfile
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
# control-flow ops whose device events enclose their bodies' own events
CONTAINERS = ("while", "conditional", "call")


def span(name: str):
    """A host span in the profiler trace (near free when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Window:
    """Host-clock window; with ``trace``, a profiler trace of it too.

    Set-up's objects are collected and frozen as the window opens, so that
    no Python collection inside it walks them; the window's own collections
    are counted and timed (:meth:`gc_summary`)."""

    # where to write :func:`describe` of the raw trace, for reading by hand
    describe_to: Optional[str] = None

    def __init__(self, trace: bool):
        self.trace = trace
        self.dir: Optional[str] = None
        self.t0 = self.t1 = 0.0
        self._span = None
        self.gc_counts = [0, 0, 0]
        self.gc_times: List[float] = []
        self._gc_start = 0.0

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        else:
            self.gc_counts[info["generation"]] += 1
            self.gc_times.append(time.perf_counter() - self._gc_start)

    def gc_summary(self) -> str:
        t = self.gc_times
        return (f"Python collections in the window by generation "
                f"{self.gc_counts}: {sum(t) * 1e3:.3f} ms in all, longest "
                f"{max(t, default=0.0) * 1e3:.3f} ms")

    def __enter__(self) -> "Window":
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._on_gc)
        if self.trace:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            # host spans are the benchmark's own annotations; tracing every
            # Python call would slow the host loop that the window times
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = span(WINDOW)
            self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def close(self) -> None:
        """End the window (idempotent). The profiler keeps running until
        :meth:`record`, so writing the trace out stalls nothing that the
        run still times; the record is cut to the window's span."""
        if self.t1:
            return
        self.t1 = time.perf_counter()
        if self._span is not None:
            self._span.__exit__(None, None, None)
        gc.callbacks.remove(self._on_gc)
        gc.unfreeze()

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def seconds(self) -> float:
        return (self.t1 or time.perf_counter()) - self.t0

    def record(self) -> Optional[Dict[str, Any]]:
        """The trace as a record (None without a trace); removes the files."""
        if self.dir is None:
            return None
        import jax

        try:
            jax.profiler.stop_trace()
            if self.describe_to:
                with open(self.describe_to, "w") as f:
                    f.write(describe(self.dir))
            return read_trace(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def op_name(text: str) -> str:
    """The HLO instruction's own name: ``fa2_fwd_compact.18`` of the event
    text ``%fa2_fwd_compact.18 = (...) custom-call(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")[:200]


def read_trace(trace_dir: str) -> Dict[str, Any]:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a record (above)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    host: List[Tuple[str, int, int]] = []
    devices: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"].extend((op_name(ev.name), ev.start_ns,
                                       ev.duration_ns) for ev in line.events)
                elif line.name == "XLA Modules":
                    dev["modules"].extend((ev.name, ev.start_ns, ev.duration_ns)
                                          for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events
                            if ev.name.startswith("bench."))
    win = [h for h in host if h[0] == WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = win[0][1], win[0][2]
    s = lambda ns: (ns - w0) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "host": [[n, s(a), s(b)] for n, a, b in host if n != WINDOW],
        "devices": {
            d: {"ops": [[n, s(a), dur * 1e-9] for n, a, dur in v["ops"]],
                "modules": [[n, s(a), dur * 1e-9] for n, a, dur in v["modules"]]}
            for d, v in devices.items()},
    }


def describe(trace_dir: str, n: int = 8) -> str:
    """Planes, lines and a few events with their stats: for looking at a
    trace by hand before writing a reader against it."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    pd = ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:n]:
                try:
                    stats = dict(ev.stats)
                except Exception as e:  # noqa: BLE001 - report and go on
                    stats = {"<error>": repr(e)}
                out.append(f"    {ev.name!r} start {ev.start_ns} dur "
                           f"{ev.duration_ns} {stats}"[:600])
    return "\n".join(out)


# ------------------------------------------------------------ reduction


def clip(intervals: Iterable[Tuple[float, float]], t0: float,
         t1: float) -> List[Tuple[float, float]]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_intervals(rec: Dict[str, Any], device: str) -> List[Tuple[float, float]]:
    return [(o[1], o[1] + o[2]) for o in rec["devices"][device]["ops"]]


def busy_s(rec: Dict[str, Any]) -> float:
    """Seconds of the window in which some op ran, averaged over devices."""
    w = rec["window_s"]
    devs = list(rec["devices"])
    if not devs:
        return 0.0
    total = 0.0
    for d in devs:
        total += sum(b - a for a, b in union(clip(op_intervals(rec, d), 0.0, w)))
    return total / len(devs)


def idle_share(rec: Dict[str, Any]) -> float:
    return 1.0 - busy_s(rec) / rec["window_s"]


def family_ops(rec: Dict[str, Any], prefix: str) -> List[Sequence[Any]]:
    """Ops of a kernel family that started inside the window, all devices."""
    w = rec["window_s"]
    return [o for d in rec["devices"].values() for o in d["ops"]
            if o[0].startswith(prefix) and 0.0 <= o[1] < w]


def module_events(rec: Dict[str, Any], fragment: str) -> List[Sequence[Any]]:
    w = rec["window_s"]
    return [m for d in rec["devices"].values() for m in d["modules"]
            if fragment in m[0] and 0.0 <= m[1] < w]


def host_spans(rec: Dict[str, Any], name: str) -> List[Tuple[float, float]]:
    return [(a, b) for n, a, b in rec["host"] if n == name]


def top_ops(rec: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """Device ops with the most time in the window, summed by name and
    averaged over devices. Control-flow ops are left out: their bodies' ops
    are listed themselves."""
    w = rec["window_s"]
    tot: Dict[str, float] = {}
    for d in rec["devices"].values():
        for name, start, dur in d["ops"]:
            a, b = max(start, 0.0), min(start + dur, w)
            if b > a and name.rsplit(".", 1)[0] not in CONTAINERS:
                tot[name] = tot.get(name, 0.0) + (b - a)
    k = max(1, len(rec["devices"]))
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_host(rec: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """Idle device time in the window, summed by the innermost host span
    covering each gap's midpoint (``host idle`` where none does)."""
    w = rec["window_s"]
    spans = sorted((b - a, name, a, b) for name, a, b in rec["host"])
    tot: Dict[str, float] = {}
    k = max(1, len(rec["devices"]))
    for d in rec["devices"]:
        busy = union(clip(op_intervals(rec, d), 0.0, w))
        edges = [0.0] + [x for ab in busy for x in ab] + [w]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            name = next((nm for _, nm, s0, s1 in spans if s0 <= mid <= s1),
                        "host idle")
            tot[name] = tot.get(name, 0.0) + (b - a) / k
    return [[name, t] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(rec: Dict[str, Any]) -> Dict[str, Any]:
    return {"device_ops": top_ops(rec), "idle_gaps": idle_by_host(rec)}
