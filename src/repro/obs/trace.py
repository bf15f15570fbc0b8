"""Span-based event log exported as Chrome/Perfetto ``trace_event`` JSON,
bridged onto the ``jax.profiler`` trace.

One :class:`TraceRecorder` per run collects events host-side (a plain
list of dicts) and serializes to the JSON Object Format the Perfetto UI /
``chrome://tracing`` load directly::

    {"traceEvents": [{"name", "ph", "ts", "pid", "tid", ...}, ...],
     "displayTimeUnit": "ms"}

Event vocabulary used by the repo (DESIGN.md §9.2 span taxonomy):

  * serving (pid ``serve``): per-request *tracks* (tid = request id)
    carry ``queue_wait`` -> ``prefill`` -> ``decode`` complete spans plus
    ``submit`` / ``retire`` / ``preempt`` / ``resume`` instants; the
    engine's own track (``serving.engine.ENGINE_TID``) carries the
    scoped ``engine.*`` spans of each tick (``engine.tick`` >
    ``engine.schedule`` / ``engine.admit`` / ``engine.decode`` >
    ``.dispatch`` / ``.wait`` / ``engine.bookkeep``), ``page_oom``
    instants and a ``resident`` counter series.
  * training (pid ``train``): per-step ``step`` spans with nested
    ``data`` / ``compute`` / ``checkpoint`` child spans on one track.

Timestamps are microseconds from the recorder's construction
(``time.perf_counter`` based -- monotonic, so spans always nest even
across NTP adjustments). Durations use ``X`` (complete) events recorded
at span *exit* with the entry timestamp carried along: emission order
never has to match nesting order, and a crashed span simply never emits
(the trace stays schema-valid).

A scoped :meth:`TraceRecorder.span` also opens a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` for its
lifetime, carrying the span's numeric args, so a running profiler session
records the program's spans on the device trace's clock (outside a
session the annotation costs next to nothing). Span args are single
numbers: the profiler keeps a number per arg, and an arg of another type
stays in the JSON only. Retroactive :meth:`~TraceRecorder.complete`
spans stay in the recorder, and so do spans opened while JAX traces a
function (``profile=False``): they describe a program's structure, not a
moment of its run. Neither side of the bridge adds a compile.
"""

from __future__ import annotations

import json
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional

import jax.profiler

__all__ = ["TraceRecorder", "set_default_recorder", "get_default_recorder"]

# name prefix of the recorder's spans on the jax.profiler trace
PROFILER_PREFIX = "repro."


class TraceRecorder:
    def __init__(self, process: str = "repro", pid: int = 1, clock=None):
        self.pid = pid
        self.events: List[dict] = []
        self._clock = clock or time.perf_counter
        self._t0 = self._clock()
        self._thread_names: Dict[int, str] = {}
        self.events.append({
            "name": "process_name", "ph": "M", "ts": 0, "pid": pid, "tid": 0,
            "args": {"name": process},
        })

    # ------------------------------------------------------------- clock
    def now_us(self) -> float:
        """Microseconds since recorder construction (event timebase), on a
        grid of 1/1024 us: sums and differences of such times are exact in
        floating point, so a span recorded as (ts, dur) that ends where the
        next one starts still nests (``ts + dur`` does not round past it)."""
        return round((self._clock() - self._t0) * 1e6 * 1024) / 1024

    # ------------------------------------------------------------ events
    def name_thread(self, tid: int, name: str) -> None:
        """Label a track (idempotent; Perfetto shows it as the row name)."""
        if self._thread_names.get(tid) == name:
            return
        self._thread_names[tid] = name
        self.events.append({
            "name": "thread_name", "ph": "M", "ts": 0, "pid": self.pid,
            "tid": tid, "args": {"name": name},
        })

    def complete(self, name: str, tid: int, ts_us: float, dur_us: float,
                 cat: str = "repro", args: Optional[dict] = None) -> None:
        """A finished span: ``X`` event with explicit start + duration."""
        ev = {
            "name": name, "ph": "X", "ts": ts_us, "dur": max(0.0, dur_us),
            "pid": self.pid, "tid": tid, "cat": cat,
        }
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, tid: int, cat: str = "repro",
                args: Optional[dict] = None) -> None:
        ev = {
            "name": name, "ph": "i", "ts": self.now_us(), "pid": self.pid,
            "tid": tid, "cat": cat, "s": "t",
        }
        if args:
            ev["args"] = args
        self.events.append(ev)

    def counter(self, name: str, values: Dict[str, float], tid: int = 0,
                cat: str = "repro") -> None:
        """A ``C`` sample: Perfetto renders these as stacked area tracks."""
        self.events.append({
            "name": name, "ph": "C", "ts": self.now_us(), "pid": self.pid,
            "tid": tid, "cat": cat,
            "args": {k: float(v) for k, v in values.items()},
        })

    @contextmanager
    def span(self, name: str, tid: int = 0, cat: str = "repro",
             args: Optional[dict] = None, profile: bool = True):
        """Context-managed span; emits one ``X`` event at exit.

        Yields the span's args dict: what the body writes into it is
        recorded at exit (args known only once the span's work is done).
        With ``profile`` the span is also a ``jax.profiler``
        annotation ``repro.<name>`` carrying its int and float args; pass
        ``profile=False`` for a span opened while JAX traces a function.
        """
        args = dict(args or {})
        t0 = self.now_us()
        with _annotation(name) if profile else nullcontext() as ann:
            try:
                yield args
            finally:
                numbers = {k: v for k, v in args.items()
                           if isinstance(v, (int, float))}
                if ann is not None and numbers:
                    ann.set_metadata(**numbers)
                self.complete(name, tid, t0, self.now_us() - t0, cat=cat,
                              args=args or None)

    # ------------------------------------------------------------ export
    def to_json(self) -> dict:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)


def _annotation(name: str):
    """The profiler annotation of a span (looked up on ``jax.profiler`` at
    each call, so a test can stand in for it)."""
    return jax.profiler.TraceAnnotation(PROFILER_PREFIX + name)


# ---------------------------------------------------------------------------
# Process-wide default recorder. Mirrors metrics.default_registry(): code
# that runs deep inside tracing with no recorder argument (the ring
# schedule's per-step spans) emits here when a run has installed one
# (launch/train.py --trace-out), and stays silent otherwise.
# ---------------------------------------------------------------------------

_default: Optional["TraceRecorder"] = None


def set_default_recorder(rec: Optional["TraceRecorder"]) -> None:
    """Install (or clear, with ``None``) the process-wide recorder."""
    global _default
    _default = rec


def get_default_recorder() -> Optional["TraceRecorder"]:
    return _default


def validate_trace(doc: dict) -> List[dict]:
    """Schema-check a trace document; returns the event list.

    Every event must carry ``ph``/``ts``/``pid`` (the fields the Perfetto
    JSON importer requires), ``X`` events a non-negative ``dur``, and on
    each (pid, tid) track the ``X`` spans must properly nest (equal-time
    zero-duration overlaps allowed). Raises ``ValueError`` on violation.
    Used by tests and the CI smoke -- an exporter regression fails fast
    instead of producing a trace the UI silently refuses.
    """
    if not isinstance(doc, dict) or not isinstance(doc.get("traceEvents"), list):
        raise ValueError("trace must be {'traceEvents': [...]}")
    events = doc["traceEvents"]
    for ev in events:
        for field in ("ph", "ts", "pid"):
            if field not in ev:
                raise ValueError(f"event missing {field!r}: {ev}")
        if ev["ph"] == "X" and not (isinstance(ev.get("dur"), (int, float))
                                    and ev["dur"] >= 0):
            raise ValueError(f"X event needs dur >= 0: {ev}")
    tracks: Dict[tuple, List[tuple]] = {}
    for ev in events:
        if ev["ph"] == "X":
            tracks.setdefault((ev["pid"], ev.get("tid", 0)), []).append(
                (float(ev["ts"]), float(ev["ts"]) + float(ev["dur"]), ev)
            )
    for key, spans in tracks.items():
        spans.sort(key=lambda s: (s[0], -s[1]))
        stack: List[tuple] = []
        for t0, t1, ev in spans:
            while stack and stack[-1][1] <= t0:
                stack.pop()
            if stack and t1 > stack[-1][1]:
                raise ValueError(
                    f"spans overlap without nesting on track {key}: "
                    f"{stack[-1][2].get('name')} vs {ev.get('name')}"
                )
            stack.append((t0, t1, ev))
    return events
