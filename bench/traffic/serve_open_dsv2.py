"""Open-loop serving traffic for a latent-attention MoE model (DeepSeek-V2),
driven through ``PagedServingEngine.submit`` / ``tick``.

The traffic file's keys, the schedule (one for every seed), the client,
the window's accounting and every end-to-end metric are ``serve_open``'s,
used from that file. What differs:

* the engine takes the configuration's ``engine.prefill_token_budget``,
  and set-up warms only the admission widths the traffic can reach: each
  warmed width of ``engine.admit_widths_warmed`` that the budget allows at
  each prompt bucket (``PagedServingEngine.admit_widths``);
* ``served_gap`` is read against ``ref/deepseek_v2.py``, and beside it
  ``served_gap_mean``, the mean of the same gaps over every served token;
* the host record also holds the routing counters of every prefill
  launch and decode tick the window saw, from the engine's own
  ``engine.admit`` / ``engine.decode`` spans (traced runs only):
  ``moe`` ``{"admit": [[start_s, tokens, experts_touched], ...],
  "decode": [[start_s, tokens, experts_touched], ...]}``, times from the
  window's start, tokens routed per MoE layer, experts touched summed
  over the MoE layers.

The configuration file is also checked against the program: every width
and routing constant of latent attention and of the experts.
"""

from __future__ import annotations

import gc
import math
import pathlib
import time
from typing import Any, Dict, List, Optional

import numpy as np

import devtrace
import harness
import model
from ref import deepseek_v2 as ref

serve_open = harness.load_module(pathlib.Path(__file__).with_name("serve_open.py"))

# what this kind shares with serve_open, unchanged
schedule = serve_open.schedule
Client = serve_open.Client
window_metrics = serve_open.window_metrics
nearest_rank = serve_open.nearest_rank
NO_SAMPLE = serve_open.NO_SAMPLE


def check_config(c: Dict[str, Any], cfg) -> None:
    """Refuse a file whose latent-attention or expert sizes, routing or
    rope scaling are not what the program runs."""
    m, e, y = cfg.mla, cfg.moe, cfg.rope_scaling
    s = c["rope_scaling"]
    pairs = {
        "kv_lora_rank": m.kv_lora_rank, "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
        "n_routed_experts": e.num_experts, "num_experts_per_tok": e.top_k,
        "moe_intermediate_size": e.d_expert, "n_shared_experts": e.num_shared,
        "norm_topk_prob": e.norm_topk, "routed_scaling_factor": e.routed_scale,
        "first_k_dense_replace": cfg.first_dense_layers,
        "rope_scaling.factor": y.factor,
        "rope_scaling.original_max_position_embeddings": y.original_max_position,
        "rope_scaling.beta_fast": y.beta_fast, "rope_scaling.beta_slow": y.beta_slow,
        "rope_scaling.mscale": y.mscale, "rope_scaling.mscale_all_dim": y.mscale_all_dim,
    }
    for key, have in pairs.items():
        want = s[key.split(".", 1)[1]] if key.startswith("rope_scaling.") else c[key]
        if want != have:
            raise ValueError(f"{key} is {want!r} in the file but the program "
                             f"runs {have!r}")
    if c["q_lora_rank"] is not None or c["scoring_func"] != "softmax" or (
            c["topk_method"] != "greedy"):
        raise ValueError("the program runs no query LoRA and a softmax, "
                         "greedy top-k router")


def build_engine(cell: harness.Cell, params, trace: bool = False):
    """The engine as ``launch/serve.py --engine paged`` builds it, with the
    configuration's prefill token budget; with ``trace``, its own span
    recorder on."""
    from repro.core.attention import AttentionConfig
    from repro.obs import MetricsRegistry, TraceRecorder
    from repro.serving.engine import PagedServingEngine

    cfg = model.program_config(cell.config)
    check_config(cell.config, cfg)
    eng = cell.config["engine"]
    return PagedServingEngine(
        cfg, params, AttentionConfig(impl=cell.config["program"]["attn_impl"]),
        max_batch=eng["max_batch"], num_pages=eng["num_pages"],
        page_size=eng["page_size"], pages_per_seq_max=eng["pages_per_seq_max"],
        prompt_pad=eng["prompt_pad"],
        prefill_token_budget=eng["prefill_token_budget"],
        registry=MetricsRegistry(),
        tracer=TraceRecorder(process="serve") if trace else None)


def warm_plan(engine, cell: harness.Cell) -> List[tuple]:
    """(bucket, width) of every admission program the traffic can reach."""
    warmed = cell.config["engine"]["admit_widths_warmed"]
    return [(b, w) for b in serve_open.buckets(cell)
            for w in engine.admit_widths(b) if w in warmed]


def warm(engine, cell: harness.Cell) -> None:
    """Compile every reachable (bucket, admission width) program and the
    decode step, through the engine's own submit and tick."""
    from repro.serving.engine import Request

    rid = -1
    for b, w in warm_plan(engine, cell):
        for _ in range(w):
            engine.submit(Request(rid=rid, prompt=[1] * b, max_new_tokens=1))
            rid -= 1
        while engine.queue or any(s is not None for s in engine.slots):
            engine.tick()


def served_readings(params, cell: harness.Cell, requests,
                    quant: Optional[str] = None) -> Dict[str, float]:
    """How far the served tokens lie below the reference's greedy choice,
    each gap in units of the reference logits' RMS over its request's
    served positions: ``served_gap``, the widest gap (``serve_open``'s
    number against this model's reference), and ``served_gap_mean``, the
    mean gap over every served position of the sample. With ``quant``, the
    tokens compared are the ones the lower-precision reference puts first
    (the control)."""
    dims = ref.Dims.from_config(cell.config)
    p, o = cell.traffic["prompt"], cell.traffic["output"]
    pad_to = -(-(p["max"] + o["max"]) // 512) * 512
    if not requests:
        harness.log("[serve] no finished request to compare")
        return {"served_gap": NO_SAMPLE, "served_gap_mean": NO_SAMPLE}
    worst, total, off, n = 0.0, 0.0, 0, 0
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
        served = gap[first:] / rms
        worst = max(worst, float(np.max(served)))
        total += float(np.sum(served))
        off += int(np.count_nonzero(served))
        n += served.size
    harness.log(f"[serve] {'control ' if quant else ''}served tokens other "
                f"than the reference's best: {off} of {n}")
    return {"served_gap": worst, "served_gap_mean": total / n}


def served_gaps(params, cell: harness.Cell, requests,
                quant: Optional[str] = None) -> float:
    """``served_gap`` alone (what ``tools/calibrate.py`` reads)."""
    return served_readings(params, cell, requests, quant)["served_gap"]


def routing(engine, t0: float, t1: float) -> Dict[str, list]:
    """The window's ``engine.admit`` and ``engine.decode`` spans as
    [start from t0 (s), tokens routed per MoE layer, experts touched]."""
    out: Dict[str, list] = {"admit": [], "decode": []}
    tr = engine.tracer
    if tr is None:
        return out
    for ev in tr.events:
        kind = {"engine.admit": "admit", "engine.decode": "decode"}.get(
            ev.get("name"))
        args = ev.get("args", {})
        if kind is None or "experts_touched" not in args:
            continue
        start = tr._t0 + ev["ts"] * 1e-6
        if t0 <= start < t1:
            tokens = args["tokens"] if kind == "admit" else args["live"]
            out[kind].append([start - t0, int(tokens),
                              int(args["experts_touched"])])
    return out


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
    token_bytes = 2 * c["num_hidden_layers"] * cfg.mla.cache_dim
    harness.log(
        f"[serve] live latent cache in the window: peak {max(live)} tokens "
        f"({max(live) * token_bytes / 2**30:.3f} GiB, "
        f"{100 * max(live) / pool:.1f}% of the pool's {pool}), mean "
        f"{sum(live) / len(live):.0f}; requests in a tick: mean "
        f"{sum(map(len, ticks)) / len(ticks):.2f}, most "
        f"{max(map(len, ticks))} of {eng['max_batch']} slots")
    snap = engine.snapshot()
    harness.log(
        f"[serve] routing over the run: "
        f"{snap['serving/moe_experts_touched']:.0f} expert visits in "
        f"{snap['serving/moe_expert_load_max/count']:.0f} layer launches")
    harness.log(f"[serve] {win.gc_summary()}")

    finished = [r for rid, r in sorted(engine.finished.items()) if rid >= 0]
    host = {"kind": "serve", "window_s": win.seconds, "chips": len(devices),
            "config": cell.config,
            "queue_wait_s": serve_open._queue_waits(engine, window_rids),
            "ticks": [[a - t0, b - t0, lens] for a, b, lens in client.ticks
                      if t0 <= a < t1],
            "moe": routing(engine, t0, t1)}
    rec = win.record()
    del engine, client
    gc.collect()

    sample = serve_open._sample(finished, k, seed)
    values = served_readings(params, cell, sample)
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
