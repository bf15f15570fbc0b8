"""Interleaved min-of-N wall-clock timing.

The repo's single timing discipline, shared by the benchmark modules
(``benchmarks/timing.py`` re-exports this module) and the tests that time
kernels. Two rules, both load-bearing:

  * **min, not mean.** On a shared host every timing sample is the true
    cost plus non-negative noise (scheduler preemption, page faults, GC,
    turbo transitions). The minimum over N samples is the best estimator
    of the true cost; the mean is biased upward by exactly the noise we
    want to exclude. The original ``fig4_6_attn_speed._time`` used a
    mean-of-3 and recorded a forward-only row *slower* than the matching
    forward+backward row (BENCH_attn.json, ``ref/causal=0/seq=512``:
    438ms fwd vs 356ms fwd+bwd) -- a physical impossibility that made the
    whole trajectory untrustworthy.
  * **interleave competitors.** When two timings will be *compared*
    (fwd vs fwd+bwd, fused vs split), round-robin the
    candidates inside each iteration instead of timing them back-to-back
    in blocks. Slow drift (thermal, co-tenant load) then hits every
    candidate equally instead of biasing whichever ran during the bad
    window.

``jax.block_until_ready`` is applied to every call so asynchronous
dispatch never lets a timing stop before the work does.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Mapping

import jax

__all__ = ["TimingResult", "interleaved_timeit", "time_min"]

DEFAULT_ITERS = 5


class TimingResult(Dict[str, float]):
    """``{name: best_seconds}`` plus the discipline that produced it.

    ``iters`` (timed rounds per competitor) and ``warmup`` (untimed
    calls) ride along so BENCH ledger rows can be self-describing about
    their timing provenance -- ``provenance`` renders the canonical
    ``min_of_{iters}w{warmup}`` tag the benchmark modules append to their
    ``derived`` column. Plain-dict semantics are unchanged (drop-in for
    every existing caller).
    """

    def __init__(self, best: Dict[str, float], iters: int, warmup: int):
        super().__init__(best)
        self.iters = iters
        self.warmup = warmup

    @property
    def provenance(self) -> str:
        return f"min_of_{self.iters}w{self.warmup}"


def interleaved_timeit(
    fns: Mapping[str, Callable],
    *args,
    iters: int = DEFAULT_ITERS,
    warmup: int = 1,
) -> TimingResult:
    """Time competing callables interleaved; return best seconds per name.

    Every callable is invoked as ``fn(*args)``; ``warmup`` untimed calls
    each (compilation + first-touch) precede ``iters`` timed rounds. In
    each round the callables run round-robin in insertion order, and each
    keeps the minimum of its per-round samples. The returned mapping is a
    :class:`TimingResult`: a plain dict of best seconds that also carries
    the (iters, warmup) provenance for self-describing ledger rows.
    """
    iters, warmup = max(1, iters), max(1, warmup)
    items = list(fns.items())
    if not items:
        return TimingResult({}, iters, warmup)
    for _, fn in items:
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
    best = {name: float("inf") for name, _ in items}
    for _ in range(iters):
        for name, fn in items:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best[name] = min(best[name], time.perf_counter() - t0)
    return TimingResult(best, iters, warmup)


def time_min(fn: Callable, *args, iters: int = DEFAULT_ITERS, warmup: int = 1) -> float:
    """Min-of-N timing of a single callable (degenerate interleave)."""
    return interleaved_timeit({"fn": fn}, *args, iters=iters, warmup=warmup)["fn"]
