"""Open-loop serving traffic on the wall clock, driven through
``PagedServingEngine.submit`` / ``tick``.

Traffic file keys: the offered ``rate_per_s``; ``prompt`` and ``output``
lengths, each a lognormal ``median`` and ``sigma`` clipped to
``[min, max]``; the ``warmup_s`` of traffic that set-up runs so the slots
are in steady state when the window opens; the ``drain_s`` the run waits
at most past the window for the first tokens of requests due in it (one
with none by then has failed) and for ``check.requests`` finished requests,
the sample the reference reads.

Every seed gets the same schedule: each stretch's requests are
``round(rate * seconds)`` lengths and inter-arrival gaps taken at evenly
spaced quantiles of their distributions (the gaps scaled to fill the
stretch), in a fixed order that is the same for every seed. The seed draws
only the prompt tokens, which no length or time depends on (answers run to
their length, with no stop token).

Latency is the client's: a request's time to first token runs from when it
was due, not from when the loop submitted it, and a token counts as
delivered when the tick that made it returns. The engine is built as
``launch/serve.py`` builds it (``--engine paged``).
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import devtrace
import harness
import model
from ref import qwen3 as ref


# the schedule's order: one constant for every seed
ORDER_WORD = 2000


@dataclasses.dataclass
class Planned:
    due: float  # seconds from the schedule's start
    prompt_len: int
    output_len: int  # tokens delivered: the prefill's first, then decodes


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def exponential_gaps(n: int, total: float) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps at evenly spaced quantiles, scaled
    to sum to ``total``."""
    g = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return g * (total / g.sum())


def plan(traffic: Dict[str, Any], start: float, seconds: float,
         stream: int) -> List[Planned]:
    """One stretch of ``seconds`` of arrivals beginning at ``start``; its
    order is fixed by ``stream`` alone."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    p, o = traffic["prompt"], traffic["output"]
    rng = np.random.default_rng([ORDER_WORD, stream])
    prompts = rng.permutation(lognormal_quantiles(
        n, p["median"], p["sigma"], p["min"], p["max"]))
    outputs = rng.permutation(lognormal_quantiles(
        n, o["median"], o["sigma"], o["min"], o["max"]))
    due = start + np.cumsum(rng.permutation(exponential_gaps(n, seconds)))
    return [Planned(float(d), int(a), int(b))
            for d, a, b in zip(due, prompts, outputs)]


class Client:
    """Submits planned requests when due, ticks the engine, and records when
    each token reached the host."""

    def __init__(self, engine, vocab: int, seed: int):
        self.engine = engine
        self.vocab = vocab
        self.rng = np.random.default_rng(harness.seed_words(seed, 3))
        self.next_rid = 0
        self.due: Dict[int, float] = {}
        self.token_times: Dict[int, List[float]] = {}
        self.lateness: List[float] = []
        # per tick: (start, end, cache entries each decoding request read)
        self.ticks: List[tuple] = []

    def submit(self, item: Planned, t0: float, now: float) -> None:
        from repro.serving.engine import Request

        rid = self.next_rid
        self.next_rid += 1
        prompt = self.rng.integers(1, self.vocab, size=item.prompt_len).tolist()
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=item.output_len - 1)
        self.due[rid] = t0 + item.due
        self.token_times[rid] = []
        self.lateness.append(now - self.due[rid])
        with devtrace.span("bench.submit"):
            self.engine.submit(req)

    def busy(self) -> bool:
        e = self.engine
        return bool(e.queue) or any(s is not None for s in e.slots)

    def tick(self) -> None:
        e = self.engine
        live = [r for r in e.slots if r is not None] + list(e.queue)
        before = {r.rid: len(r.generated) for r in live}
        start = time.perf_counter()
        with devtrace.span("bench.tick"):
            e.tick()
        now = time.perf_counter()
        lens = []
        for r in live:
            new = len(r.generated) - before[r.rid]
            self.token_times[r.rid].extend([now] * new)
            if new:  # admitted or resident: it took part in the decode step
                lens.append(len(r.prompt) + len(r.generated) - 1)
        self.ticks.append((start, now, lens))

    def drive(self, schedule: List[Planned], t0: float, until: float,
              stop=None) -> None:
        """Run the open loop until ``until`` (perf_counter seconds), or
        earlier once ``stop()`` holds. ``schedule`` is consumed from the
        front as requests fall due."""
        while True:
            now = time.perf_counter()
            if now >= until or (stop is not None and stop()):
                return
            while schedule and t0 + schedule[0].due <= now:
                self.submit(schedule.pop(0), t0, now)
            if self.busy():
                self.tick()
            elif schedule:
                with devtrace.span("bench.wait"):
                    time.sleep(max(0.0, min(t0 + schedule[0].due - now,
                                            until - now)))
            else:
                with devtrace.span("bench.wait"):
                    time.sleep(max(0.0, min(0.01, until - now)))


def schedule(traffic: Dict[str, Any], seconds: float
             ) -> Tuple[List[Planned], int, int]:
    """Warm-up traffic, then the window's, then more through the drain; and
    how many requests the warm-up and the window hold."""
    warm_s = traffic["warmup_s"]
    plans = (plan(traffic, 0.0, warm_s, 0)
             + plan(traffic, warm_s, seconds, 1)
             + plan(traffic, warm_s + seconds, traffic["drain_s"], 2))
    n_warm = max(1, round(traffic["rate_per_s"] * warm_s))
    n_win = max(1, round(traffic["rate_per_s"] * seconds))
    return plans, n_warm, n_win


def window_metrics(client: Client, rids: List[int], t0: float, t1: float,
                   drain_end: float) -> Dict[str, Any]:
    """What the window's requests ``rids`` saw, on the host clock: the
    tokens delivered in ``[t0, t1]``; each request's time from due to first
    token (one with none by ``drain_end`` counts its wait until then, and
    as ``failed``); every gap between successive tokens of one request whose
    later token was delivered in the window."""
    ttft, failed = [], 0
    for rid in rids:
        ts = client.token_times.get(rid, [])
        failed += not ts
        ttft.append((ts[0] if ts else drain_end) - client.due[rid])
    times = client.token_times.values()
    return {
        "delivered": sum(1 for ts in times for t in ts if t0 <= t <= t1),
        "ttft": ttft,
        "gaps": [b - a for ts in times for a, b in zip(ts, ts[1:])
                 if t0 <= b <= t1],
        "failed": failed,
    }


def nearest_rank(values: List[float], q: float) -> float:
    """The ``q`` quantile by nearest rank (no interpolation)."""
    if not values:
        return float("nan")
    s = sorted(values)
    return float(s[max(0, math.ceil(q * len(s)) - 1)])


def build_engine(cell: harness.Cell, params, trace: bool = False):
    """The engine; with ``trace``, its own span recorder on (the
    ``queue_wait`` spans a per-layer metric reads)."""
    from repro.core.attention import AttentionConfig
    from repro.obs import MetricsRegistry, TraceRecorder
    from repro.serving.engine import PagedServingEngine

    cfg = model.program_config(cell.config)
    eng = cell.config["engine"]
    return PagedServingEngine(
        cfg, params, AttentionConfig(impl=cell.config["program"]["attn_impl"]),
        max_batch=eng["max_batch"], num_pages=eng["num_pages"],
        page_size=eng["page_size"], pages_per_seq_max=eng["pages_per_seq_max"],
        prompt_pad=eng["prompt_pad"], registry=MetricsRegistry(),
        tracer=TraceRecorder(process="serve") if trace else None)


def buckets(cell: harness.Cell) -> List[int]:
    """The prompt buckets the traffic can reach."""
    eng, p = cell.config["engine"], cell.traffic["prompt"]
    pad, cap = eng["prompt_pad"], eng["pages_per_seq_max"] * eng["page_size"]
    b = lambda n: min(max(-(-n // pad) * pad, pad), cap)
    return sorted({b(n) for n in range(p["min"], p["max"] + 1)})


def warm(engine, cell: harness.Cell) -> None:
    """Compile every (bucket, admission width) program the traffic can reach
    and the decode step, through the engine's own submit and tick."""
    from repro.serving.engine import Request

    rid = -1
    for b in buckets(cell):
        for w in cell.config["engine"]["admit_widths_warmed"]:
            for _ in range(w):
                engine.submit(Request(rid=rid, prompt=[1] * b, max_new_tokens=1))
                rid -= 1
            while engine.queue or any(s is not None for s in engine.slots):
                engine.tick()


# what ``served_gap`` reads when no request finished: nothing was shown correct
NO_SAMPLE = 1e9


def served_gaps(params, cell: harness.Cell, requests, quant: Optional[str] = None
                ) -> float:
    """Widest gap, in units of the reference logits' RMS over the request's
    served positions, by which a served token's reference logit lies below
    the reference's best. With ``quant``, the tokens compared are the ones
    the lower-precision reference puts first (the control)."""
    dims = ref.Dims.from_config(cell.config)
    p, o = cell.traffic["prompt"], cell.traffic["output"]
    pad_to = -(-(p["max"] + o["max"]) // 512) * 512
    if not requests:
        harness.log("[serve] no finished request to compare")
        return NO_SAMPLE
    worst = 0.0
    for req in requests:
        seq = np.asarray(req.prompt + req.generated, np.int32)
        first = len(req.prompt) - 1  # position that predicts generated[0]
        gap, _, ss = ref.position_logits(params, seq, pad_to, dims)
        if quant is not None:
            _, top_q, _ = ref.position_logits(params, seq, pad_to, dims,
                                              quant=quant)
            want = seq[1:].copy()
            want[first:] = top_q[first:]
            gap, _, _ = ref.position_logits(params, seq, pad_to, dims,
                                            want=want)
        rms = math.sqrt(float(np.sum(ss[first:])) / ((len(seq) - 1 - first)
                                                    * dims.vocab))
        worst = max(worst, float(np.max(gap[first:])) / rms)
    return worst


def run(cell: harness.Cell, devices, *, seed: int, seconds: float,
        trace: bool, t_start: float) -> Dict[str, Any]:
    tr = cell.traffic
    cfg = model.program_config(cell.config)
    params = model.make_weights(cfg, harness.jax_key(seed))
    engine = build_engine(cell, params, trace)
    warm(engine, cell)
    compiles0 = (engine.decode_compiles, engine.admit_compiles)
    client = Client(engine, cfg.vocab_size, seed)

    plans, n_warm, n_win = schedule(tr, seconds)
    warm_s = tr["warmup_s"]
    t_sched = time.perf_counter()
    client.drive(plans, t_sched, t_sched + warm_s)
    with devtrace.Window(trace) as win:
        client.drive(plans, t_sched, t_sched + warm_s + seconds)
    window_rids = list(range(n_warm, n_warm + n_win))
    k = tr["check"]["requests"]
    drained = lambda: (all(client.token_times.get(r) for r in window_rids)
                       and sum(rid >= 0 for rid in engine.finished) >= k)
    client.drive(plans, t_sched, win.t1 + tr["drain_s"], stop=drained)
    compiles = (engine.decode_compiles - compiles0[0],
                engine.admit_compiles - compiles0[1])
    device = harness.device_info(devices)

    t0, t1 = win.t0, win.t1
    seen = window_metrics(client, window_rids, t0, t1, time.perf_counter())
    failed = seen["failed"]
    lateness = client.lateness
    harness.log(
        f"[serve] generator lateness over {len(lateness)} submits: median "
        f"{nearest_rank(lateness, 0.5) * 1e3:.3f} ms, p99 "
        f"{nearest_rank(lateness, 0.99) * 1e3:.3f} ms, max "
        f"{max(lateness) * 1e3:.3f} ms")
    harness.log(
        f"[serve] window {win.seconds:.3f}s: {n_win} requests due, {failed} "
        f"without a first token at the drain limit, {seen['delivered']} "
        f"tokens delivered in {sum(t0 <= a < t1 for a, _, _ in client.ticks)}"
        f" ticks, compiles in window/drain (decode, admit) {compiles}, "
        f"preemptions {engine.preemptions}")
    c, eng = cell.config, cell.config["engine"]
    ticks = [lens for a, _, lens in client.ticks if t0 <= a < t1] or [[]]
    live = [sum(lens) for lens in ticks]
    pool = eng["num_pages"] * eng["page_size"]
    kv_token = 4 * c["num_hidden_layers"] * c["num_key_value_heads"] * c[
        "head_dim"]  # bf16 keys and values
    harness.log(
        f"[serve] live KV in the window: peak {max(live)} tokens "
        f"({max(live) * kv_token / 2**30:.3f} GiB, "
        f"{100 * max(live) / pool:.1f}% of the pool's {pool}), mean "
        f"{sum(live) / len(live):.0f}; requests in a tick: mean "
        f"{sum(map(len, ticks)) / len(ticks):.2f}, most "
        f"{max(map(len, ticks))} of {eng['max_batch']} slots")
    harness.log(f"[serve] {win.gc_summary()}")

    finished = [r for rid, r in sorted(engine.finished.items()) if rid >= 0]
    host = {"kind": "serve", "window_s": win.seconds, "chips": len(devices),
            "config": cell.config,
            "queue_wait_s": _queue_waits(engine, window_rids),
            "ticks": [[a - t0, b - t0, lens] for a, b, lens in client.ticks
                      if t0 <= a < t1]}
    rec = win.record()
    del engine, client
    gc.collect()

    sample = _sample(finished, k, seed)
    values = {"served_gap": served_gaps(params, cell, sample)}
    checks = harness.judge(values, cell.limits)
    e2e = {
        "serve_output_tokens_per_s": seen["delivered"] / win.seconds,
        "serve_ttft_p90_ms": nearest_rank(seen["ttft"], 0.9) * 1e3,
        "serve_itl_p95_ms": nearest_rank(seen["gaps"], 0.95) * 1e3,
        "setup_s": win.t0 - t_start,
    }
    return {"e2e": e2e, "attempted": n_win, "failed": failed,
            "checks": checks, "device": device, "trace": rec, "host": host,
            "sample": sample}


def _sample(finished, k: int, seed: int):
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.generated))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng(harness.seed_words(seed, 4))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _queue_waits(engine, rids) -> List[float]:
    """The engine's own ``queue_wait`` spans of the window's requests, in
    seconds (its recorder runs on the host's perf_counter clock)."""
    out = []
    for ev in engine.tracer.events if engine.tracer else ():
        if ev.get("name") == "queue_wait" and ev.get("tid") in rids:
            out.append(ev["dur"] * 1e-6)
    return out
