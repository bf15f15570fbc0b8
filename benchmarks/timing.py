"""Shared benchmark timing helper: interleaved min-of-N.

Thin re-export of :mod:`repro.utils.timing` so every benchmark module and
the library use the *same* timing discipline (the library side cannot
import ``benchmarks``; the benchmarks side should not fork the
implementation). See that module's docstring for why min-of-N and why
interleaved -- short version: the old mean-of-3 recorded a forward-only
row slower than forward+backward (a physical impossibility) and had to be
fixed before any timing could be trusted.
"""

from __future__ import annotations

from repro.utils.timing import (
    DEFAULT_ITERS,
    TimingResult,
    interleaved_timeit,
    time_min,
)

__all__ = ["DEFAULT_ITERS", "TimingResult", "interleaved_timeit", "time_min"]
