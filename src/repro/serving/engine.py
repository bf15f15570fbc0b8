"""Continuous-batching serving engines.

Two engines share the Request/tick/retire lifecycle:

  * :class:`ServingEngine` -- the fixed-slot baseline: ``max_batch``
    contiguous cache slices of ``cache_size`` tokens each, reserved for a
    request's worst case whether it uses them or not. Kept as the
    benchmark baseline (benchmarks/serving_sweep.py measures it against
    the paged engine at a matched HBM budget).
  * :class:`PagedServingEngine` -- vLLM-style paged KV: HBM is a pool of
    fixed-size pages (serving/kv_pool.py), each resident sequence holds
    exactly ``ceil((L+1)/page_size)`` of them via an int32 block table,
    and the decode kernel reads pages through the table
    (kernels/flash_decode.flash_decode_paged_kernel). Throughput becomes
    a function of tokens *resident*, not slots *reserved*.

Both engines decode every tick with ONE jitted step whose shapes are
engine-geometry-static, so requests join/leave with zero recompiles
(pinned by compile-count tests).
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.attention import AttentionConfig
from repro.kernels.flash_decode import paged_decode_work
from repro.launch.steps import (
    build_paged_admit_step,
    build_paged_serve_step,
    build_prefill_step,
    build_serve_step,
)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import TraceRecorder
from repro.serving.kv_pool import KVPagePool


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def feed(self) -> List[int]:
        """Tokens whose KV must be (re)built at admission: the prompt plus
        anything already generated -- nonempty ``generated`` means the
        request was preempted mid-flight and is resuming (greedy decoding
        makes the continuation deterministic, so resume == never-paused;
        tests/test_paged.py pins it)."""
        return self.prompt + self.generated


# (B, ...) leaf ranks; a latent cache is one (B, S, 1, W) latent "head"
_CACHE_BASE_NDIM = {"k": 4, "v": 4, "latent": 4, "h": 3, "conv": 3}

# Fixed buckets for the admission-size histogram (prompt pad buckets are
# prompt_pad multiples clamped to capacity; pow2 bounds cover both engines)
ADMIT_BUCKETS = (16.0, 32.0, 64.0, 128.0, 256.0, 512.0, 1024.0)
QUEUE_WAIT_S_BUCKETS = (0.001, 0.01, 0.1, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0, 60.0)
# tokens on the busiest expert of one MoE layer in one launch
EXPERT_LOAD_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 256.0, 1024.0, 4096.0)
# The engine's own trace track; request tracks use tid = rid, so it takes a
# tid no request id reaches (the largest int32, as trace viewers read tids).
ENGINE_TID = 2**31 - 1


class _EngineTelemetry:
    """Shared observability surface of both serving engines.

    Everything is host-side (obs/metrics, obs/trace): it runs *around*
    the jitted steps and never enters a trace, so enabling telemetry adds
    zero compiles and leaves the step shapes untouched
    (tests/test_obs.py pins ``decode_compiles == 1`` with it on).

    Registry schema (``snapshot()``; always on):

      counters   serving/{tokens, admissions, retirements, ticks,
                 prefill_tokens (real feed tokens prefilled),
                 prefill_launched_tokens (width x bucket per launch)}
      gauge_fns  serving/{active_slots, slot_utilization, queue_depth,
                 kv_cells_active, kv_cells_capacity, token_occupancy}
                 (+ kv_pool/* and serving/{preemptions, page_oom,
                 pool_in_place_calls, pool_copied_calls} paged)
      histograms serving/admit_bucket (admitted pad bucket, tokens),
                 serving/queue_wait_s (submit -> admission, seconds)

    With a tracer, each tick is a tree of scoped spans on the engine
    track, each also a ``repro.engine.*`` profiler annotation
    (DESIGN.md §9.2): ``engine.tick`` {live, queued} holds
    ``engine.schedule`` {picked}, one ``engine.admit`` {n, width,
    bucket, tokens, launched} per prefill launch, ``engine.decode``
    {live; paged on the Pallas kernel also kv_pages, kv_blocks,
    kv_blocks_launched} with ``.dispatch`` and ``.wait`` children, and
    ``engine.bookkeep`` {retired}. Without one, no span is opened.

    The paged engine's steps consume the pool they are given (donated):
    ``serving/pool_in_place_calls`` counts the calls that did so in place
    (every leaf of the pool passed in deleted after the call) and
    ``serving/pool_copied_calls`` the others, and ``engine.decode`` and
    ``engine.admit`` carry ``pool_in_place`` (1 or 0).

    A paged MoE model's steps also return their routing counters
    (models/moe.STATS), fetched with the tokens: ``engine.admit`` and
    ``engine.decode`` then carry ``experts_touched`` (summed over the MoE
    layers) and ``expert_load_max`` (the most over them), and the registry
    counts ``serving/moe_experts_touched`` (counter) and
    ``serving/moe_expert_load_max`` (histogram, one observation per layer).
    """

    def _obs_init(self, registry: Optional[MetricsRegistry],
                  tracer: Optional[TraceRecorder]):
        self.obs = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer
        if tracer is not None:
            tracer.name_thread(ENGINE_TID, "engine")
        self._c_tokens = self.obs.counter("serving/tokens")
        self._c_admissions = self.obs.counter("serving/admissions")
        self._c_retirements = self.obs.counter("serving/retirements")
        self._c_ticks = self.obs.counter("serving/ticks")
        self._h_bucket = self.obs.histogram("serving/admit_bucket", ADMIT_BUCKETS)
        self._c_prefill_tokens = self.obs.counter("serving/prefill_tokens")
        self._c_prefill_launched = self.obs.counter(
            "serving/prefill_launched_tokens"
        )
        self._h_wait = self.obs.histogram(
            "serving/queue_wait_s", QUEUE_WAIT_S_BUCKETS
        )
        self.obs.gauge_fn(
            "serving/active_slots",
            lambda: sum(s is not None for s in self.slots),
        )
        self.obs.gauge_fn(
            "serving/slot_utilization",
            lambda: sum(s is not None for s in self.slots) / self.B,
        )
        self.obs.gauge_fn("serving/queue_depth", lambda: len(self.queue))
        self.obs.gauge_fn("serving/kv_cells_active", self.active_kv_cells)
        self.obs.gauge_fn("serving/kv_cells_capacity", self.kv_capacity)
        self.obs.gauge_fn(
            "serving/token_occupancy",
            lambda: self.resident_tokens() / max(1, self.kv_capacity()),
        )
        self._submit_s: Dict[int, float] = {}  # rid -> perf_counter at (re)submit
        self._submit_ts: Dict[int, float] = {}  # rid -> trace us at submit
        self._decode_t0: Dict[int, float] = {}  # rid -> decode-span start us
        self._preempted_rids: set = set()  # resumes owe a 'resume' instant

    def snapshot(self) -> Dict[str, float]:
        """Flat metrics snapshot (obs/metrics schema); both engines."""
        return self.obs.snapshot()

    @property
    def decode_compiles(self) -> int:
        return self._step._cache_size()

    # ------------------------------------------------------------ spans
    def _span(self, name: str, **args):
        """A scoped span on the engine track (yields its args dict, which
        the body may add to); a no-op context without a tracer."""
        if self.tracer is None:
            return nullcontext({})
        return self.tracer.span(name, tid=ENGINE_TID, args=args)

    def _admit_span(self, n: int, width: int, bucket: int, tokens: int):
        """One prefill launch of ``n`` requests padded to ``width`` rows x
        ``bucket`` tokens, holding ``tokens`` real feed tokens: counted,
        and its ``engine.admit`` span."""
        launched = width * bucket
        self._c_prefill_tokens.inc(tokens)
        self._c_prefill_launched.inc(launched)
        return self._span("engine.admit", n=n, width=width, bucket=bucket,
                          tokens=tokens, launched=launched)

    def _decode(self, live: int, next_token, *tables, **work):
        """The decode call, then its tokens on the host: the
        ``engine.decode`` span (``live`` and ``work`` as args) with
        ``.dispatch`` / ``.wait`` children. Returns the device tokens and
        their host copy."""
        with self._span("engine.decode", live=live, **work) as span:
            with self._span("engine.decode.dispatch"):
                pool = self.caches
                tok, self.caches, *stats = self._step(
                    self.params, next_token, pool, *tables
                )
                self._note_pool(span, pool)
            with self._span("engine.decode.wait"):
                tok_host, *stats = jax.device_get((tok, *stats))
            self._note_routing(span, *stats)
            return tok, tok_host

    def _note_pool(self, span: dict, pool):
        """A step took the caches ``pool``: nothing to count here; the
        paged engine, whose steps consume the pool, counts whether they
        did so in place."""

    def _note_routing(self, span: dict, stats=None):
        """Routing counters of one launch (rows of models/moe.STATS, one
        per MoE layer) into the registry and the launch's span."""
        if stats is None or not len(stats):
            return
        touched = int(stats[:, 1].sum())
        self._c_touched.inc(touched)
        for load in stats[:, 2]:
            self._h_load.observe(float(load))
        span["experts_touched"] = touched
        span["expert_load_max"] = int(stats[:, 2].max())

    # --------------------------------------------------- lifecycle hooks
    def _note_submit(self, req: Request, *, resumed: bool = False):
        self._submit_s[req.rid] = time.perf_counter()
        if self.tracer:
            self.tracer.name_thread(req.rid, f"req {req.rid}")
            self._submit_ts[req.rid] = self.tracer.now_us()
            if not resumed:
                self.tracer.instant(
                    "submit", tid=req.rid,
                    args={"rid": req.rid, "prompt_len": len(req.prompt)},
                )

    def _note_picked(self, req: Request):
        """A request left the queue for a slot: its queue wait."""
        self._h_wait.observe(time.perf_counter() - self._submit_s.pop(req.rid))

    def _note_admission(self, req: Request, bucket: int,
                        t_pref0: float, t_pref1: float):
        """One request admitted: counters + the rid track's queue_wait /
        prefill spans ([submit, admit) and [admit, prefill-done))."""
        self._c_admissions.inc()
        self._h_bucket.observe(bucket)
        if self.tracer:
            sub = self._submit_ts.pop(req.rid, t_pref0)
            self.tracer.complete("queue_wait", req.rid, sub, t_pref0 - sub)
            self.tracer.complete(
                "prefill", req.rid, t_pref0, t_pref1 - t_pref0,
                args={"bucket": bucket, "feed_len": len(req.feed)},
            )
            if req.rid in self._preempted_rids:
                self._preempted_rids.discard(req.rid)
                self.tracer.instant("resume", tid=req.rid, args={"rid": req.rid})
            self._decode_t0[req.rid] = t_pref1

    def _note_leave(self, req: Request, *, preempted: bool):
        """Request left its slot (retire or preempt): close its decode
        span; a preempt emits the matching instant (resume pairs with it
        at re-admission -- tests assert both carry the same rid)."""
        if not preempted:
            self._c_retirements.inc()
        if self.tracer:
            now = self.tracer.now_us()
            t0 = self._decode_t0.pop(req.rid, now)
            self.tracer.complete(
                "decode", req.rid, t0, now - t0,
                args={"generated": len(req.generated), "preempted": preempted},
            )
            self.tracer.instant(
                "preempt" if preempted else "retire", tid=req.rid,
                args={"rid": req.rid},
            )

    def _note_decode_tick(self, live: int):
        self.ticks += 1
        self._c_ticks.inc()
        self._c_tokens.inc(live)
        if self.tracer:
            self.tracer.counter(
                "resident", {"slots": live, "tokens": self.resident_tokens()}
            )

    def _now_us(self) -> float:
        return self.tracer.now_us() if self.tracer else 0.0


def _batch_axis(path, leaf) -> int:
    """Batch axis of a cache leaf: scan-stacked leaves carry leading group
    dims, so batch sits at ndim - base_rank (k/v: (B,S,H,D); h/conv: (B,..))."""
    name = None
    for p in reversed(path):
        if hasattr(p, "key"):
            name = str(p.key)
            break
    base = _CACHE_BASE_NDIM.get(name, leaf.ndim)
    return leaf.ndim - base


def _tree_slot_write(batched, single, slot: int):
    """Write a (batch=1, ...) cache tree into batch position ``slot``."""

    def one(path, buf, new):
        ax = _batch_axis(path, buf)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), slot, axis=ax
        )

    return jax.tree_util.tree_map_with_path(one, batched, single)


class ServingEngine(_EngineTelemetry):
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        attn_cfg: AttentionConfig,
        *,
        max_batch: int = 4,
        cache_size: int = 512,
        prompt_pad: int = 64,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[TraceRecorder] = None,
    ):
        assert cfg.family != "encdec", "engine serves decoder-only families"
        self.cfg = cfg
        self.params = params
        self.attn = attn_cfg
        self.B = max_batch
        self.cache_size = cache_size
        self.prompt_pad = prompt_pad
        # Prompt-length bucketing needs the lens-masked prefill, which is
        # attention-only (an SSM's recurrent state would consume padding).
        self._bucket = prompt_pad > 1 and cfg.ssm is None
        self._prefill = jax.jit(build_prefill_step(cfg, attn_cfg, cache_size))
        self._step = jax.jit(build_serve_step(cfg, attn_cfg))
        from repro.configs.registry import cache_specs

        spec = cache_specs(cfg, max_batch, cache_size)
        self.caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        self.cache_len = jnp.zeros((max_batch,), jnp.int32)
        self.next_token = jnp.zeros((max_batch, 1), jnp.int32)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []
        self.finished: Dict[int, Request] = {}
        self.ticks = 0
        self._obs_init(registry, tracer)

    # ----------------------------------------------------------- metrics
    def resident_tokens(self) -> int:
        return int(np.asarray(self.cache_len).sum())

    def active_kv_cells(self) -> int:
        """KV cells the decode step touches: every slot's full slice,
        live or not (the cost the paged engine's page skip removes)."""
        return self.B * self.cache_size

    def kv_capacity(self) -> int:
        return self.B * self.cache_size

    # ------------------------------------------------------------- admin
    def submit(self, req: Request):
        self._note_submit(req)
        self.queue.append(req)

    def _admit(self, slot: int, req: Request):
        """Bucketed (B=1) prefill into ``slot``.

        Prompts are right-padded to the next multiple of ``prompt_pad`` so
        the jitted prefill compiles once per *bucket*, not once per prompt
        length; ``lens`` tells the prefill where the real tokens end (the
        hidden is read at the last real position, causality keeps padding
        out of every real row's attention, and the padded cache tail sits
        beyond ``cache_len`` so decode never sees it — the first generated
        token simply overwrites it).
        """
        self._note_picked(req)
        L = len(req.prompt)
        pad_to = -(-L // self.prompt_pad) * self.prompt_pad if self._bucket else L
        pad_to = min(pad_to, self.cache_size - 1)
        assert L <= pad_to, f"prompt ({L}) exceeds cache capacity {self.cache_size}"
        with self._admit_span(1, 1, pad_to, L):
            prompt_arr = np.zeros((1, pad_to), np.int32)
            prompt_arr[0, :L] = req.prompt
            batch = {"inputs": jnp.asarray(prompt_arr)}
            if self._bucket:
                batch["lens"] = jnp.asarray([L], jnp.int32)
            t_pref0 = self._now_us()
            tok, cache1, lens = self._prefill(self.params, batch)
            true_len, first = int(lens[0]), int(tok[0, 0])
            t_pref1 = self._now_us()
        self._note_admission(req, pad_to, t_pref0, t_pref1)
        self.caches = _tree_slot_write(self.caches, cache1, slot)
        self.cache_len = self.cache_len.at[slot].set(true_len)
        self.next_token = self.next_token.at[slot].set(first)
        req.generated.append(first)
        self.slots[slot] = req

    def _retire(self, slot: int):
        req = self.slots[slot]
        if req is not None:
            req.done = True
            self.finished[req.rid] = req
            self._note_leave(req, preempted=False)
        self.slots[slot] = None
        self.cache_len = self.cache_len.at[slot].set(0)

    # -------------------------------------------------------------- tick
    def tick(self):
        """Admit from queue, run one decode step, retire finished."""
        live = sum(s is not None for s in self.slots)
        with self._span("engine.tick", live=live, queued=len(self.queue)):
            for slot in range(self.B):
                if self.slots[slot] is None and self.queue:
                    self._admit(slot, self.queue.pop(0))
            live = sum(s is not None for s in self.slots)
            if not live:
                return
            tok, tok_host = self._decode(live, self.next_token, self.cache_len)
            self.cache_len = self.cache_len + jnp.asarray(
                [1 if s is not None else 0 for s in self.slots], jnp.int32
            )
            self.next_token = tok
            self._note_decode_tick(live)
            with self._span("engine.bookkeep") as span:
                retired = 0
                for slot, req in enumerate(self.slots):
                    if req is None:
                        continue
                    t = int(tok_host[slot, 0])
                    req.generated.append(t)
                    if (req.eos_id is not None and t == req.eos_id) or len(
                        req.generated
                    ) >= req.max_new_tokens + 1 or int(self.cache_len[slot]) >= self.cache_size - 1:
                        self._retire(slot)
                        retired += 1
                span["retired"] = retired

    def run(self, max_ticks: int = 1000) -> Dict[int, Request]:
        while (self.queue or any(s is not None for s in self.slots)) and self.ticks < max_ticks:
            self.tick()
        return self.finished


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class PagedServingEngine(_EngineTelemetry):
    """Continuous batching over a paged KV pool.

    HBM holds ``num_pages`` physical pages of ``page_size`` tokens per
    layer (``registry.paged_cache_specs``); a resident request owns
    ``len // page_size + 1`` of them (one page of write headroom) through
    its row of the int32 block table. Admission allocates, growth extends
    one page at a time, retirement frees -- so a request's HBM footprint
    tracks its *actual* length, and the engine admits by free *pages*, not
    free worst-case slots.

    Static shapes / compiles:
      * decode: ONE jitted step, shapes fixed by
        (max_batch, pages_per_seq_max, page_size). Zero recompiles on
        join/leave/preempt (``decode_compiles`` stays 1; pinned by test).
      * admission: one jitted batched prefill per (prompt bucket,
        pow2 admission width) pair -- all same-bucket queued prompts
        admitted in a single call, scattered into their pages on device.

    OOM policy (DESIGN.md): admission is strict FIFO and reserves one
    growth page per already-resident request; if decode-time growth still
    finds the pool empty, the *youngest* resident request is preempted --
    its pages freed, the request requeued at the queue FRONT with its
    generated tokens kept, so re-admission re-prefills prompt+generated
    and greedy decoding resumes exactly where it left off.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        attn_cfg: AttentionConfig,
        *,
        max_batch: int = 4,
        num_pages: int = 64,
        page_size: int = 16,
        pages_per_seq_max: int = 16,
        prompt_pad: int = 64,
        prefill_token_budget: Optional[int] = None,
        registry: Optional[MetricsRegistry] = None,
        tracer: Optional[TraceRecorder] = None,
    ):
        self.cfg = cfg
        self.params = params
        self.attn = attn_cfg
        self.B = max_batch
        self.prefill_token_budget = prefill_token_budget
        self.ps = page_size
        self.n_max = pages_per_seq_max
        self.prompt_pad = prompt_pad
        self.pool = KVPagePool(num_pages, page_size)
        from repro.configs.registry import paged_cache_specs

        spec = paged_cache_specs(cfg, num_pages, page_size)  # asserts attn-only
        # The engine holds the only pool; each step consumes it (donated)
        # and returns the updated one, which replaces it (DESIGN.md
        # Section 5.3): a pool held across a call is a deleted array.
        self.caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        self._step = jax.jit(build_paged_serve_step(cfg, attn_cfg),
                             donate_argnums=(2,))
        self._admit = jax.jit(build_paged_admit_step(cfg, attn_cfg, page_size),
                              donate_argnums=(2,))
        # Host-side scheduler state, pushed to device every tick.
        self.table = np.zeros((max_batch, pages_per_seq_max), np.int32)
        self.cache_len = np.zeros((max_batch,), np.int32)
        self.next_token = np.zeros((max_batch, 1), np.int32)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []
        self.finished: Dict[int, Request] = {}
        self.ticks = 0
        self.preemptions = 0
        self._seq = 0  # admission order, for preempt-youngest
        self._slot_seq = np.zeros((max_batch,), np.int64)
        self._obs_init(registry, tracer)
        if cfg.moe is not None:
            self._c_touched = self.obs.counter("serving/moe_experts_touched")
            self._h_load = self.obs.histogram("serving/moe_expert_load_max",
                                              EXPERT_LOAD_BUCKETS)
        self.pool.register_metrics(self.obs)
        self._c_page_oom = self.obs.counter("serving/page_oom")
        self._c_pool_in_place = self.obs.counter("serving/pool_in_place_calls")
        self._c_pool_copied = self.obs.counter("serving/pool_copied_calls")
        self.obs.gauge_fn("serving/preemptions", lambda: float(self.preemptions))
        # fraction of *allocated* page cells holding real KV
        self.obs.gauge_fn(
            "serving/page_fill",
            lambda: self.resident_tokens()
            / max(1, self.pool.used_pages * self.ps),
        )

    # ----------------------------------------------------------- metrics
    def _note_pool(self, span: dict, pool):
        """Whether the step that took ``pool`` updated it in place: the
        donation was honoured when every leaf is deleted after the call."""
        in_place = all(a.is_deleted() for a in jax.tree.leaves(pool))
        (self._c_pool_in_place if in_place else self._c_pool_copied).inc()
        span["pool_in_place"] = int(in_place)

    @property
    def admit_compiles(self) -> int:
        return self._admit._cache_size()

    def resident_tokens(self) -> int:
        return int(self.cache_len.sum())

    def active_kv_cells(self) -> int:
        """KV cells the decode step touches: live rows' allocated pages
        only -- the kernel copies nothing else."""
        return int(sum(-(-int(l) // self.ps) * self.ps
                       for l in self.cache_len if int(l) > 0))

    def kv_capacity(self) -> int:
        return self.pool.usable_pages * self.ps

    # ------------------------------------------------------------- admin
    def _need_pages(self, tokens: int) -> int:
        # +1: headroom so the next decode write always has a page.
        return tokens // self.ps + 1

    def submit(self, req: Request):
        worst = len(req.prompt) + req.max_new_tokens
        assert worst <= self.n_max * self.ps - 1, (
            f"request {req.rid}: prompt+max_new ({worst}) exceeds per-seq "
            f"capacity {self.n_max * self.ps - 1}"
        )
        assert self._need_pages(len(req.prompt)) <= self.pool.usable_pages, (
            f"request {req.rid}: prompt alone overflows the pool"
        )
        self._note_submit(req)
        self.queue.append(req)

    def _bucket(self, L: int) -> int:
        pad = -(-L // self.prompt_pad) * self.prompt_pad
        return min(max(pad, self.prompt_pad), self.n_max * self.ps)

    def admit_widths(self, bucket: int) -> List[int]:
        """The admission widths a group of ``bucket``-token prompts can
        launch at: powers of two up to ``max_batch`` whose launch stays
        within ``prefill_token_budget`` (one row always can)."""
        cap = self.B
        if self.prefill_token_budget is not None:
            cap = min(cap, max(1, self.prefill_token_budget // bucket))
        return [1 << i for i in range(cap.bit_length()) if 1 << i <= cap]

    def _admit_tick(self):
        """Strict-FIFO admission, then one batched prefill per bucket, a
        bucket's group split into launches within the token budget."""
        with self._span("engine.schedule") as span:
            picks = self._pick()
            span["picked"] = len(picks)
        # Group by bucket; one batched admit call per bucket and width cap.
        by_bucket: Dict[int, List[Tuple[int, Request, List[int]]]] = {}
        for pick in picks:
            by_bucket.setdefault(self._bucket(len(pick[1].feed)), []).append(pick)
        launches = []
        for pad_to, group in sorted(by_bucket.items()):
            cap = self.admit_widths(pad_to)[-1]
            launches += [(pad_to, group[i: i + cap])
                         for i in range(0, len(group), cap)]
        for pad_to, group in launches:
            W = min(_next_pow2(len(group)), self.B)
            tokens = sum(len(req.feed) for _, req, _ in group)
            with self._admit_span(len(group), W, pad_to, tokens) as span:
                npb = -(-pad_to // self.ps)
                inputs = np.zeros((W, pad_to), np.int32)
                lens = np.ones((W,), np.int32)  # dummy rows: 1 token, null dest
                dest = np.zeros((W, npb), np.int32)
                for i, (slot, req, pages) in enumerate(group):
                    feed = req.feed
                    inputs[i, : len(feed)] = feed
                    lens[i] = len(feed)
                    n_dest = min(-(-len(feed) // self.ps), npb)
                    dest[i, :n_dest] = pages[:n_dest]
                t_pref0 = self._now_us()
                pool = self.caches
                tok, lens_total, self.caches, *stats = self._admit(
                    self.params,
                    {"inputs": jnp.asarray(inputs), "lens": jnp.asarray(lens)},
                    pool,
                    jnp.asarray(dest),
                )
                self._note_pool(span, pool)
                tok_host, *stats = jax.device_get((tok, *stats))
                t_pref1 = self._now_us()
                self._note_routing(span, *stats)
            for i, (slot, req, pages) in enumerate(group):
                self._note_admission(req, pad_to, t_pref0, t_pref1)
                self.table[slot] = 0
                self.table[slot, : len(pages)] = pages
                self.cache_len[slot] = int(lens_total[i])
                t = int(tok_host[i, 0])
                req.generated.append(t)
                self.next_token[slot, 0] = t
                self.slots[slot] = req
                self._slot_seq[slot] = self._seq
                self._seq += 1

    def _pick(self) -> List[Tuple[int, Request, List[int]]]:
        """Strict-FIFO pick of (slot, request, pages) with pages allocated.

        A request is admitted only if, after taking its pages, the pool
        still holds one reserve page per resident request (including
        requests picked earlier this tick) -- decode growth must not be
        starved by admission. The first request that does not fit blocks
        the rest (FIFO fairness: no small-prompt overtaking).
        """
        free_slots = [i for i, s in enumerate(self.slots) if s is None]
        reserve = sum(s is not None for s in self.slots)
        picks: List[Tuple[int, Request, List[int]]] = []
        while self.queue and free_slots:
            req = self.queue[0]
            need = self._need_pages(len(req.feed))
            if len(req.feed) > self._bucket(len(req.feed)):
                # resumed request grew past the largest bucket: it cannot
                # re-prefill; drop to finished as-is
                self.queue.pop(0)
                req.done = True
                self.finished[req.rid] = req
                continue
            if self.pool.free_pages - need < reserve:
                break
            pages = self.pool.alloc(req.rid, need)
            if pages is None:
                break
            self.queue.pop(0)
            self._note_picked(req)
            picks.append((free_slots.pop(0), req, pages))
            reserve += 1
        return picks

    def _clear_slot(self, slot: int):
        self.slots[slot] = None
        self.table[slot] = 0
        self.cache_len[slot] = 0
        self.next_token[slot, 0] = 0

    def _retire(self, slot: int):
        req = self.slots[slot]
        assert req is not None
        self.pool.free(req.rid)
        req.done = True
        self.finished[req.rid] = req
        self._note_leave(req, preempted=False)
        self._clear_slot(slot)

    def _preempt_youngest(self) -> bool:
        """Free the most recently admitted request's pages and requeue it
        at the queue FRONT (it keeps FIFO priority and its generated
        tokens; Request.feed makes re-admission a deterministic resume)."""
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if len(active) <= 1:
            return False  # never preempt the last runner: no progress
        victim = max(active, key=lambda i: self._slot_seq[i])
        req = self.slots[victim]
        self.pool.free(req.rid)
        self._note_leave(req, preempted=True)
        self._preempted_rids.add(req.rid)
        self._note_submit(req, resumed=True)
        self.queue.insert(0, req)
        self._clear_slot(victim)
        self.preemptions += 1
        return True

    def _grow(self):
        """Ensure every resident request owns a page for its next write;
        extend from the pool, preempting the youngest on exhaustion.
        Oldest-first so preemption cost lands on the least-progressed."""
        order = sorted(
            (i for i, s in enumerate(self.slots) if s is not None),
            key=lambda i: self._slot_seq[i],
        )
        for slot in order:
            req = self.slots[slot]
            if req is None:  # preempted by an earlier iteration
                continue
            while self._need_pages(int(self.cache_len[slot])) > len(
                self.pool.pages_of(req.rid)
            ):
                page = self.pool.extend(req.rid)
                if page is None:
                    self._c_page_oom.inc()
                    if self.tracer:
                        self.tracer.instant(
                            "page_oom", tid=ENGINE_TID, args={"rid": req.rid}
                        )
                    if not self._preempt_youngest():
                        raise RuntimeError(
                            "page pool exhausted with a single resident "
                            "request; pool too small for this workload"
                        )
                    if self.slots[slot] is None:
                        break  # we preempted ourselves
                    continue
                self.table[slot, len(self.pool.pages_of(req.rid)) - 1] = page

    def _decode_work(self) -> Dict[str, int]:
        """What this tick's paged decode kernel does in one layer without a
        window (``flash_decode.paged_decode_work``: ``kv_pages``,
        ``kv_blocks``, ``kv_blocks_launched``), counted from the lengths the
        step hands it; nothing without a tracer or off the Pallas kernel."""
        if self.tracer is None or self.attn.impl != "flash_pallas":
            return {}
        # the step attends over the new token too; empty slots over nothing
        lens = np.where(self.cache_len > 0, self.cache_len + 1, 0)
        return paged_decode_work(lens, self.n_max, self.ps,
                                 self.attn.decode_splits)

    # -------------------------------------------------------------- tick
    def tick(self):
        live = sum(s is not None for s in self.slots)
        with self._span("engine.tick", live=live, queued=len(self.queue)):
            self._admit_tick()
            live = sum(s is not None for s in self.slots)
            if not live:
                return
            _, tok_host = self._decode(
                live,
                jnp.asarray(self.next_token),
                jnp.asarray(self.table),
                jnp.asarray(self.cache_len),
                **self._decode_work(),
            )
            self._note_decode_tick(live)
            with self._span("engine.bookkeep") as span:
                retired = 0
                for slot, req in enumerate(self.slots):
                    if req is None:
                        continue
                    self.cache_len[slot] += 1
                    t = int(tok_host[slot, 0])
                    req.generated.append(t)
                    self.next_token[slot, 0] = t
                    if (
                        (req.eos_id is not None and t == req.eos_id)
                        or len(req.generated) >= req.max_new_tokens + 1
                        or int(self.cache_len[slot]) >= self.n_max * self.ps - 1
                    ):
                        self._retire(slot)
                        retired += 1
                self._grow()
                span["retired"] = retired

    def run(self, max_ticks: int = 10000) -> Dict[int, Request]:
        while (self.queue or any(s is not None for s in self.slots)) and self.ticks < max_ticks:
            self.tick()
        return self.finished
