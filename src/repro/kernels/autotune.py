"""Empirical autotuner for the Pallas kernel knob space.

FlashAttention-2 (paper Sec 3.2 / Sec 4) gets its last 20-30% of FLOPs
utilization from picking the right work partitioning per shape, tuned
empirically per (head dim, causal, seq) -- not from algorithm changes.
This module replaces the repo's hand heuristics with measurement for the
five interacting forward/backward knobs

    block_q, block_kv, schedule, bwd, num_q_bands, kv_splits

plus the split-KV decode's ``num_splits``:

  * **Sweep** (``run_sweep`` / the CLI): measure candidate knob settings
    per (shape, dtype, mask family) with the interleaved min-of-N timer
    (``repro.utils.timing`` -- the same fixed discipline the benchmarks
    use; the old mean-of-3 timer recorded fwd slower than fwd+bwd and
    could not rank knobs). Candidates always include the existing
    heuristic's choice, so a winner is never worse than the heuristic
    *as measured*.
  * **Cache**: winners persist to a committed JSON cache
    (``src/repro/kernels/tuned.json``), keyed like the BENCH_attn.json
    configs: ``impl/causal=<0|1>/seq=<S>/heads=<H>/hd=<D>/dtype=<dt>``.
    An entry stores only the knobs the sweep fixed; omitted knobs defer
    to the heuristic at resolution time.
  * **Resolution**: ``kernels/ops.resolve_pallas_knobs`` consults
    :func:`lookup` whenever a ``PallasFlashConfig`` knob is ``None``.
    Precedence is explicit arg > tuned cache > heuristic
    (``default_block_sizes`` / ``default_forward_partitions`` /
    ``_resolve_bwd``). Lookup is exact-key first, then nearest-shape:
    same impl/causal/head-dim/dtype, nearest seq within a 2x radius
    (preferring a heads match) -- knob landscapes are smooth in seq but
    cliff-shaped in head dim, so head dim never relaxes. Mask families
    beyond plain causal/full (windows, sinks) skip the cache entirely.
  * **Escape hatches**: ``use_tuned=False`` on the config, or env
    ``REPRO_TUNED_CACHE=0`` globally; ``REPRO_TUNED_CACHE_PATH`` points
    resolution at an alternate cache file (tests and CI use this).

The committed cache is honest only for the environment that produced it
(the ``backend`` field records it; this repo's CI measures CPU interpret
mode, where step count dominates). :func:`lookup` therefore answers only
when the ``backend`` field's platform (the part before ``/``) is the one
running: on a TPU a ``cpu/interpret`` cache is ignored and every knob
comes from the heuristics until a sweep on the chip writes its own
cache. ``--check`` guards staleness: it re-sweeps the smoke shapes and
fails if the committed knobs measure more than ``--tol`` slower than a
fresh winner.

CLI::

    python -m repro.kernels.autotune [--out PATH] [--smoke] [--check]
        [--iters N] [--tol F] [--shapes seq:heads:hd:causal:batch[:dtype],...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import warnings
from typing import Dict, List, Optional, Tuple

__all__ = [
    "DEFAULT_PATH",
    "ENV_DISABLE",
    "ENV_PATH",
    "cache_enabled",
    "cache_key",
    "clear_cache",
    "load_cache",
    "lookup",
    "new_doc",
    "parse_key",
    "resolve_decode_splits",
    "run_sweep",
    "save_cache",
    "sweep_attention_shape",
    "sweep_decode_shape",
    "sweep_paged_decode_shape",
    "validate_doc",
]

DEFAULT_PATH = os.path.join(os.path.dirname(__file__), "tuned.json")
ENV_DISABLE = "REPRO_TUNED_CACHE"       # "0" disables the cache globally
ENV_PATH = "REPRO_TUNED_CACHE_PATH"     # alternate cache file

SCHEMA_VERSION = 1
# Knobs an entry may pin, per key family (impl prefix). Entries storing
# other keys (or illegal values) fail validate_doc.
ATTN_KNOBS = {
    "block_q": int, "block_kv": int, "schedule": str, "bwd": str,
    "num_q_bands": int, "kv_splits": int,
}
DECODE_KNOBS = {"num_splits": int}
# Provenance fields entries may carry alongside knobs (ignored at lookup).
PROVENANCE = ("us_fwd", "us_fwdbwd", "batch", "iters")
# Nearest-shape fallback never reaches past this seq ratio.
NEAREST_SEQ_RADIUS = 2.0


# ---------------------------------------------------------------------------
# Cache file: key format, schema, load/save
# ---------------------------------------------------------------------------


def cache_key(impl: str, causal: bool, seq: int, heads: int, head_dim: int,
              dtype) -> str:
    """BENCH_attn.json-style config key for one tuned entry."""
    import jax.numpy as jnp

    dt = str(jnp.dtype(dtype))
    return (
        f"{impl}/causal={int(bool(causal))}/seq={int(seq)}"
        f"/heads={int(heads)}/hd={int(head_dim)}/dtype={dt}"
    )


def parse_key(key: str) -> Dict[str, object]:
    """Inverse of :func:`cache_key`; raises ValueError on malformed keys."""
    impl, _, rest = key.partition("/")
    fields = {}
    for part in rest.split("/"):
        name, eq, val = part.partition("=")
        if not impl or not eq or name in fields:
            raise ValueError(f"malformed tuned-cache key: {key!r}")
        fields[name] = val
    if set(fields) != {"causal", "seq", "heads", "hd", "dtype"}:
        raise ValueError(f"malformed tuned-cache key: {key!r}")
    return dict(
        impl=impl, causal=bool(int(fields["causal"])), seq=int(fields["seq"]),
        heads=int(fields["heads"]), head_dim=int(fields["hd"]),
        dtype=fields["dtype"],
    )


def new_doc(backend: str, entries: Optional[dict] = None) -> dict:
    return {"version": SCHEMA_VERSION, "backend": backend,
            "entries": dict(entries or {})}


def _knob_spec(impl: str) -> Dict[str, type]:
    # Paged decode entries key as "flash_decode_paged<page_size>": the page
    # size changes the kernel's DMA granularity, so geometries tuned at one
    # page size never answer lookups for another.
    return DECODE_KNOBS if impl.startswith("flash_decode") else ATTN_KNOBS


def validate_doc(doc: object) -> dict:
    """Schema-check a cache document; returns it, raises ValueError if bad."""
    if not isinstance(doc, dict):
        raise ValueError("tuned cache must be a JSON object")
    if doc.get("version") != SCHEMA_VERSION:
        raise ValueError(f"tuned cache version must be {SCHEMA_VERSION}, "
                         f"got {doc.get('version')!r}")
    if not isinstance(doc.get("backend"), str):
        raise ValueError("tuned cache needs a string 'backend' field")
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        raise ValueError("tuned cache needs an 'entries' object")
    for key, entry in entries.items():
        meta = parse_key(key)  # raises on malformed keys
        if not isinstance(entry, dict):
            raise ValueError(f"entry {key!r} must be an object")
        spec = _knob_spec(meta["impl"])
        for name, val in entry.items():
            if name in PROVENANCE:
                continue
            if name not in spec:
                raise ValueError(f"entry {key!r}: unknown knob {name!r}")
            if not isinstance(val, spec[name]) or isinstance(val, bool):
                raise ValueError(f"entry {key!r}: knob {name} has bad value "
                                 f"{val!r}")
        if entry.get("schedule") not in (None, "compact", "dense"):
            raise ValueError(f"entry {key!r}: bad schedule")
        if entry.get("bwd") not in (None, "fused", "split"):
            raise ValueError(f"entry {key!r}: bad bwd")
        for name in ("block_q", "block_kv", "num_q_bands", "kv_splits",
                     "num_splits"):
            v = entry.get(name)
            if v is not None and v < 1:
                raise ValueError(f"entry {key!r}: {name} must be >= 1")
    return doc


_LOAD_CACHE: Dict[str, Tuple[Optional[int], dict]] = {}


def _cache_path(path: Optional[str] = None) -> str:
    return path or os.environ.get(ENV_PATH) or DEFAULT_PATH


def clear_cache() -> None:
    """Drop the in-process load cache (tests that swap cache files)."""
    _LOAD_CACHE.clear()


def load_cache(path: Optional[str] = None) -> dict:
    """Load + validate the tuned cache; {} entries when absent or invalid.

    Tolerant by design: a missing, unreadable, or schema-invalid file
    disables tuning (with a warning) rather than breaking attention calls
    -- strict validation belongs to ``--check`` / CI, not the hot path.
    Results are memoized per (path, mtime).
    """
    path = _cache_path(path)
    try:
        mtime: Optional[int] = os.stat(path).st_mtime_ns
    except OSError:
        mtime = None
    hit = _LOAD_CACHE.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    doc = new_doc(backend="empty")
    if mtime is not None:
        try:
            with open(path) as f:
                doc = validate_doc(json.load(f))
        except (OSError, ValueError) as e:
            warnings.warn(
                f"ignoring invalid tuned cache {path}: {e}", stacklevel=2
            )
            doc = new_doc(backend="empty")
    _LOAD_CACHE[path] = (mtime, doc)
    return doc


def save_cache(doc: dict, path: Optional[str] = None) -> str:
    path = _cache_path(path)
    validate_doc(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    clear_cache()
    return path


def cache_enabled(use_tuned: Optional[bool] = None) -> bool:
    """Config knob (tri-state) + env escape hatch -> concrete bool."""
    if use_tuned is not None:
        return use_tuned
    return os.environ.get(ENV_DISABLE, "1") != "0"


def lookup(impl: str, causal: bool, seq: int, heads: int, head_dim: int,
           dtype, *, path: Optional[str] = None) -> Dict[str, object]:
    """Tuned knobs for a shape; {} when no (near-enough) entry exists.

    Exact key first; otherwise the nearest entry with the same
    impl/causal/head-dim/dtype whose seq is within NEAREST_SEQ_RADIUS
    (2x), ranked by (heads mismatch, |log2 seq ratio|). Null-valued knobs
    and provenance fields are stripped so callers can treat the result as
    "knobs this entry pins". A cache measured on another platform than the
    running one answers nothing (see the module docstring).
    """
    import math

    import jax
    import jax.numpy as jnp

    doc = load_cache(path)
    if doc["backend"].split("/")[0] != jax.default_backend():
        return {}
    entries = doc["entries"]
    key = cache_key(impl, causal, seq, heads, head_dim, dtype)
    entry = entries.get(key)
    if entry is None:
        dt = str(jnp.dtype(dtype))
        best_rank = None
        for k, e in entries.items():
            m = parse_key(k)
            if (m["impl"] != impl or m["causal"] != bool(causal)
                    or m["head_dim"] != head_dim or m["dtype"] != dt):
                continue
            dist = abs(math.log2(m["seq"] / seq)) if seq else float("inf")
            if dist > math.log2(NEAREST_SEQ_RADIUS):
                continue
            rank = (m["heads"] != heads, dist)
            if best_rank is None or rank < best_rank:
                best_rank, entry = rank, e
    if entry is None:
        return {}
    spec = _knob_spec(impl)
    return {k: v for k, v in entry.items() if k in spec and v is not None}


def resolve_decode_splits(seq: int, heads: int, head_dim: int, dtype, *,
                          page_size: Optional[int] = None,
                          use_tuned: Optional[bool] = None,
                          default: int = 8) -> int:
    """Tuned ``num_splits`` for split-KV decode against a seq-long cache.

    ``page_size`` switches to the paged-decode key family
    (``flash_decode_paged<ps>``, ``seq`` = the *logical* capacity
    ``n_pages * page_size``) so the serving engine's page-indirect step
    consults its own tuned entries rather than the contiguous cache's."""
    from repro.obs.metrics import count_knob

    impl = ("flash_decode" if page_size is None
            else f"flash_decode_paged{int(page_size)}")
    if not cache_enabled(use_tuned):
        count_knob(impl, "heuristic")
        return default
    tuned = lookup(impl, True, seq, heads, head_dim, dtype)
    count_knob(impl, "tuned" if "num_splits" in tuned else "heuristic")
    return int(tuned.get("num_splits", default))


# ---------------------------------------------------------------------------
# Sweep harness
# ---------------------------------------------------------------------------


def _attention_candidates(seq: int, heads: int, head_dim: int, batch: int,
                          causal: bool) -> List[Dict[str, object]]:
    """Concrete five-knob candidate set for one shape (heuristic included).

    Kept deliberately small -- interpret mode pays Python per grid step, so
    the sweep prunes block sizes that would explode the step count
    (anything under seq/8) and only toggles the knobs that can matter:
    dense-vs-compact once (at default blocks), partitions on-vs-off.
    The backward knob is staged separately (see sweep_attention_shape).
    """
    from repro.kernels.ops import (
        default_block_sizes,
        default_forward_partitions,
    )

    def _round8(x):
        return (x + 7) // 8 * 8

    bq_def, bk_def = default_block_sizes(seq, seq, head_dim)
    pairs = {(bq_def, bk_def)}
    for b in (64, 128, 256, 512):
        if b <= _round8(seq) and b * 8 >= seq:
            pairs.add((b, b))
    cands: List[Dict[str, object]] = []
    seen = set()

    def _add(bq, bk, schedule, nb, ks):
        knobs = dict(block_q=bq, block_kv=bk, schedule=schedule,
                     num_q_bands=nb, kv_splits=ks)
        sig = tuple(sorted(knobs.items()))
        if sig not in seen:
            seen.add(sig)
            cands.append(knobs)

    for bq, bk in sorted(pairs):
        t_q, t_kv = -(-seq // bq), -(-seq // bk)
        nb_auto, ks_auto = default_forward_partitions(
            batch * heads, max(1, t_q), max(1, t_kv)
        )
        _add(bq, bk, "compact", nb_auto, ks_auto)  # the heuristic's pick
        if (nb_auto, ks_auto) != (1, 1):
            _add(bq, bk, "compact", 1, 1)
    _add(bq_def, bk_def, "dense", 1, 1)
    return cands


def _fmt_knobs(knobs: Dict[str, object]) -> str:
    return ";".join(f"{k}={v}" for k, v in sorted(knobs.items()))


def sweep_attention_shape(
    *, seq: int, heads: int, head_dim: int, causal: bool, batch: int,
    dtype="float32", iters: int = 3, interpret: Optional[bool] = None,
    log=None,
) -> Tuple[str, Dict[str, object]]:
    """Measure the knob space for one attention shape -> (key, entry).

    Two stages keep the candidate count linear instead of multiplicative:
    stage A sweeps the forward knobs (blocks x schedule x partitions) on
    forward wall time; stage B fixes the stage-A winner and sweeps the
    backward knob on forward+backward wall time. Every knob in the
    returned entry is concrete (the resolution layer's precedence then
    reads: explicit > this entry > heuristic).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.masks import MaskSpec
    from repro.kernels.ops import flash_attention_pallas
    from repro.utils.timing import interleaved_timeit

    spec = MaskSpec(causal=causal)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, seq, heads, head_dim)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(kq, shape, jnp.float32).astype(dt)
    k = jax.random.normal(kk, shape, jnp.float32).astype(dt)
    v = jax.random.normal(kv, shape, jnp.float32).astype(dt)

    def _fwd(knobs):
        return jax.jit(lambda q, k, v: flash_attention_pallas(
            q, k, v, spec, interpret=interpret, use_tuned=False, **knobs
        ))

    cands = _attention_candidates(seq, heads, head_dim, batch, causal)
    fwd_fns = {_fmt_knobs(kn): _fwd(kn) for kn in cands}
    fwd_best = interleaved_timeit(fwd_fns, q, k, v, iters=iters)
    by_sig = {_fmt_knobs(kn): kn for kn in cands}
    win_sig = min(fwd_best, key=fwd_best.get)
    winner = dict(by_sig[win_sig])
    if log:
        for sig in sorted(fwd_best, key=fwd_best.get):
            log(f"  fwd {fwd_best[sig]*1e6:10.0f}us  {sig}")

    def _fwdbwd(bwd):
        return jax.jit(jax.grad(lambda q, k, v: flash_attention_pallas(
            q, k, v, spec, interpret=interpret, use_tuned=False,
            bwd=bwd, **winner
        ).astype(jnp.float32).sum()))

    bwd_best = interleaved_timeit(
        {bwd: _fwdbwd(bwd) for bwd in ("fused", "split")}, q, k, v,
        iters=iters,
    )
    winner["bwd"] = min(bwd_best, key=bwd_best.get)
    if log:
        for name, t in sorted(bwd_best.items(), key=lambda kv: kv[1]):
            log(f"  fwd+bwd {t*1e6:10.0f}us  bwd={name}")
    entry = dict(winner)
    entry["us_fwd"] = round(fwd_best[win_sig] * 1e6, 1)
    entry["us_fwdbwd"] = round(bwd_best[winner["bwd"]] * 1e6, 1)
    entry["batch"] = batch
    entry["iters"] = iters
    return cache_key("flash_pallas", causal, seq, heads, head_dim, dt), entry


def sweep_decode_shape(
    *, seq: int, heads: int, head_dim: int, batch: int = 4, dtype="float32",
    iters: int = 3, interpret: Optional[bool] = None, log=None,
) -> Tuple[str, Dict[str, object]]:
    """Measure split-KV decode ``num_splits`` for one cache size."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import flash_decode_pallas
    from repro.utils.timing import interleaved_timeit

    dt = jnp.dtype(dtype)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (batch, 1, heads, head_dim), jnp.float32).astype(dt)
    kc = jax.random.normal(kk, (batch, seq, heads, head_dim), jnp.float32).astype(dt)
    vc = jax.random.normal(kv, (batch, seq, heads, head_dim), jnp.float32).astype(dt)
    lens = jnp.full((batch,), seq, jnp.int32)

    def _fn(ns):
        return jax.jit(lambda q, kc, vc, lens: flash_decode_pallas(
            q, kc, vc, lens, num_splits=ns, interpret=interpret
        )[0])

    splits = sorted({ns for ns in (1, 4, 8, 16) if ns <= max(1, seq // 8)})
    best = interleaved_timeit(
        {str(ns): _fn(ns) for ns in splits}, q, kc, vc, lens, iters=iters
    )
    win = min(best, key=best.get)
    if log:
        for name, t in sorted(best.items(), key=lambda kv: kv[1]):
            log(f"  decode {t*1e6:10.0f}us  num_splits={name}")
    entry = dict(num_splits=int(win), us_fwd=round(best[win] * 1e6, 1),
                 batch=batch, iters=iters)
    return cache_key("flash_decode", True, seq, heads, head_dim, dt), entry


def _paged_fixture(seq, heads, head_dim, batch, page_size, dt):
    """Random paged-decode operands at full logical occupancy, with the
    physical pages deliberately shuffled (the serving steady state). The
    planes are a stack of one layer, read at layer 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_pages = seq // page_size
    P = batch * n_pages + 1  # + the reserved null page 0
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (batch, 1, heads, head_dim), jnp.float32).astype(dt)
    kp = jax.random.normal(kk, (1, heads, P, page_size, head_dim), jnp.float32).astype(dt)
    vp = jax.random.normal(kv, (1, heads, P, page_size, head_dim), jnp.float32).astype(dt)
    perm = np.random.default_rng(0).permutation(P - 1) + 1
    tbl = jnp.asarray(perm.reshape(batch, n_pages), jnp.int32)
    lens = jnp.full((batch,), seq, jnp.int32)
    return q, kp, vp, lens, tbl


def sweep_paged_decode_shape(
    *, seq: int, heads: int, head_dim: int, page_size: int, batch: int = 4,
    dtype="float32", iters: int = 3, interpret: Optional[bool] = None,
    log=None,
) -> Tuple[str, Dict[str, object]]:
    """Measure page-indirect decode ``num_splits`` for one logical capacity
    (``seq = n_pages * page_size``) at one page size -- the serving path's
    geometry (kernels/flash_decode.flash_decode_paged_kernel)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import flash_decode_paged_pallas
    from repro.utils.timing import interleaved_timeit

    dt = jnp.dtype(dtype)
    assert seq % page_size == 0, "logical capacity must be page-aligned"
    n_pages = seq // page_size
    q, kp, vp, lens, tbl = _paged_fixture(seq, heads, head_dim, batch,
                                          page_size, dt)

    def _fn(ns):
        return jax.jit(lambda q, kp, vp, lens, tbl: flash_decode_paged_pallas(
            q, kp, vp, lens, tbl, num_splits=ns, interpret=interpret
        )[0])

    splits = sorted({ns for ns in (1, 2, 4, 8, 16) if ns <= n_pages})
    best = interleaved_timeit(
        {str(ns): _fn(ns) for ns in splits}, q, kp, vp, lens, tbl, iters=iters
    )
    win = min(best, key=best.get)
    if log:
        for name, t in sorted(best.items(), key=lambda kv: kv[1]):
            log(f"  paged_decode {t*1e6:10.0f}us  num_splits={name}")
    entry = dict(num_splits=int(win), us_fwd=round(best[win] * 1e6, 1),
                 batch=batch, iters=iters)
    return cache_key(f"flash_decode_paged{page_size}", True, seq, heads,
                     head_dim, dt), entry


# The BENCH_attn.json benchmark shapes (fig4_6 protocol: batch*seq = 4096
# tokens, 4 heads, head dim 64; flash_pallas rows run seq <= 512, the
# bwd_cmp/kernel-layer rows run causal seq 1024/2048) plus the decode
# serving shapes. Each is (kind, seq, heads, head_dim, causal, batch) with
# optional trailing fields: an int is the page_size for
# kind == "paged_decode" (seq is then the logical capacity
# n_pages * page_size), a str is the dtype (default float32).
BENCH_SHAPES: Tuple[Tuple, ...] = (
    ("attn", 256, 4, 64, False, 16),
    ("attn", 256, 4, 64, True, 16),
    ("attn", 512, 4, 64, False, 8),
    ("attn", 512, 4, 64, True, 8),
    ("attn", 1024, 4, 64, True, 4),
    ("attn", 2048, 4, 64, True, 2),
    # ISSUE 9: ring-shard geometries. The ring's rectangle kernels resolve
    # knobs at the per-chunk seq (S / 2P) in the run's compute dtype;
    # bf16 is what long-context training keeps KV in on the wire, and the
    # ring's off-diagonal rectangles are *non*-causal.
    ("attn", 512, 4, 64, True, 8, "bfloat16"),
    ("attn", 512, 4, 64, False, 8, "bfloat16"),
    ("attn", 1024, 4, 64, True, 4, "bfloat16"),
    ("decode", 512, 4, 64, True, 4),
    ("paged_decode", 512, 4, 64, True, 4, 64),
)

# Tiny shapes for the CI interpret-mode smoke sweep (seconds, not minutes).
SMOKE_SHAPES: Tuple[Tuple, ...] = (
    ("attn", 128, 2, 32, True, 2),
    ("attn", 128, 2, 32, False, 2),
    ("attn", 128, 2, 32, True, 2, "bfloat16"),
    ("decode", 128, 2, 32, True, 2),
    ("paged_decode", 128, 2, 32, True, 2, 32),
)


def _shape_extras(extras) -> Tuple[Optional[int], str]:
    """Optional trailing shape-tuple fields -> (page_size, dtype).

    Order-free by type: an int is a page size, a str is a dtype name."""
    page, dtype = None, "float32"
    for x in extras:
        if isinstance(x, str):
            dtype = x
        else:
            page = int(x)
    return page, dtype


def _sweep_one(kind_shape, iters, log):
    kind, seq, heads, hd, causal, batch = kind_shape[:6]
    page, dtype = _shape_extras(kind_shape[6:])
    if log:
        log(f"sweep {kind} seq={seq} heads={heads} hd={hd} "
            f"causal={int(causal)} batch={batch} dtype={dtype}"
            + (f" page={page}" if page else ""))
    if kind == "paged_decode":
        return sweep_paged_decode_shape(seq=seq, heads=heads, head_dim=hd,
                                        page_size=page, batch=batch,
                                        dtype=dtype, iters=iters, log=log)
    if kind == "decode":
        return sweep_decode_shape(seq=seq, heads=heads, head_dim=hd,
                                  batch=batch, dtype=dtype, iters=iters,
                                  log=log)
    return sweep_attention_shape(seq=seq, heads=heads, head_dim=hd,
                                 causal=causal, batch=batch, dtype=dtype,
                                 iters=iters, log=log)


def run_sweep(shapes, *, iters: int = 3, backend: Optional[str] = None,
              base: Optional[dict] = None, log=None) -> dict:
    """Sweep ``shapes`` and merge winners into a (copy of) ``base`` doc."""
    import jax

    backend = backend or f"{jax.default_backend()}/interpret"
    doc = new_doc(backend, (base or {}).get("entries"))
    for kind_shape in shapes:
        key, entry = _sweep_one(kind_shape, iters, log)
        doc["entries"][key] = entry
    return doc


def check_cache(shapes, *, path: Optional[str] = None, iters: int = 3,
                tol: float = 0.25, log=print) -> List[str]:
    """Drift check: committed knobs must keep up with a fresh sweep.

    For each shape: the committed cache must hold the exact key, and the
    committed knobs must time within ``tol`` (fractional) of a freshly
    swept winner's knobs in a HEAD-TO-HEAD interleaved run -- the two
    candidates share one timing block, so host drift between "sweep now"
    and "committed then" cannot fail the check (comparing times from
    different timing blocks is the exact bug class this module's timer
    exists to kill). Knob-identity is deliberately not required: near-tied
    candidates may swap places between runs without the cache being
    meaningfully stale. Returns a list of failure strings (empty = pass).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.masks import MaskSpec
    from repro.kernels.ops import (
        flash_attention_pallas,
        flash_decode_paged_pallas,
        flash_decode_pallas,
    )
    from repro.utils.timing import interleaved_timeit

    path = _cache_path(path)
    with open(path) as f:  # strict here, unlike load_cache
        doc = validate_doc(json.load(f))
    failures: List[str] = []
    for kind_shape in shapes:
        kind, seq, heads, hd, causal, batch = kind_shape[:6]
        page, dtype = _shape_extras(kind_shape[6:])
        dt = jnp.dtype(dtype)
        impl = ("flash_pallas" if kind == "attn"
                else f"flash_decode_paged{page}" if kind == "paged_decode"
                else "flash_decode")
        key = cache_key(impl, causal, seq, heads, hd, dt)
        committed = doc["entries"].get(key)
        if committed is None:
            failures.append(f"missing committed entry for {key}")
            continue
        fresh_key, fresh = _sweep_one(kind_shape, iters, log)
        assert fresh_key == key
        knob_names = _knob_spec(impl)
        knobs = {k: v for k, v in committed.items()
                 if k in knob_names and v is not None}
        fresh_knobs = {k: v for k, v in fresh.items()
                       if k in knob_names and v is not None}
        if kind == "paged_decode":
            args = _paged_fixture(seq, heads, hd, batch, page, dt)

            def _mk(kn):
                return jax.jit(
                    lambda q, kp, vp, lens, tbl: flash_decode_paged_pallas(
                        q, kp, vp, lens, tbl, **kn)[0])
        elif kind == "decode":
            kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
            q = jax.random.normal(kq, (batch, 1, heads, hd), jnp.float32).astype(dt)
            kc = jax.random.normal(kk, (batch, seq, heads, hd), jnp.float32).astype(dt)
            vc = jax.random.normal(kv, (batch, seq, heads, hd), jnp.float32).astype(dt)
            args = (q, kc, vc, jnp.full((batch,), seq, jnp.int32))

            def _mk(kn):
                return jax.jit(lambda q, kc, vc, lens: flash_decode_pallas(
                    q, kc, vc, lens, **kn)[0])
        else:
            spec = MaskSpec(causal=causal)
            ks = jax.random.split(jax.random.PRNGKey(0), 3)
            args = tuple(jax.random.normal(k_, (batch, seq, heads, hd),
                                           jnp.float32).astype(dt)
                         for k_ in ks)
            # fwd-time check; bwd is staged separately in the sweep
            knobs.pop("bwd", None)
            fresh_knobs.pop("bwd", None)

            def _mk(kn):
                return jax.jit(lambda q, k, v: flash_attention_pallas(
                    q, k, v, spec, use_tuned=False, **kn))

        if knobs == fresh_knobs:
            log(f"check {key}: committed knobs == fresh winner -> ok")
            continue
        best = interleaved_timeit(
            {"committed": _mk(knobs), "fresh": _mk(fresh_knobs)},
            *args, iters=iters,
        )
        t, t_fresh = best["committed"], best["fresh"]
        verdict = "ok" if t <= t_fresh * (1 + tol) else "STALE"
        log(f"check {key}: committed {t*1e6:.0f}us vs fresh winner "
            f"{t_fresh*1e6:.0f}us -> {verdict}")
        if verdict != "ok":
            failures.append(
                f"{key}: committed knobs measure {t*1e6:.0f}us, fresh winner "
                f"{t_fresh*1e6:.0f}us (> {tol:.0%} slower -- re-run "
                f"`python -m repro.kernels.autotune` and commit tuned.json)"
            )
    return failures


def _parse_shapes(text: str):
    shapes = []
    for part in text.split(","):
        fields = part.split(":")
        dtype = None
        if fields and not fields[-1].lstrip("-").isdigit():
            dtype = fields.pop()
        seq, heads, hd, causal, batch = (int(x) for x in fields)
        shape = ("attn", seq, heads, hd, bool(causal), batch)
        shapes.append(shape + ((dtype,) if dtype else ()))
    return shapes


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default=None,
                   help=f"cache file to write (default {DEFAULT_PATH})")
    p.add_argument("--smoke", action="store_true",
                   help="sweep only the tiny CI smoke shapes")
    p.add_argument("--check", action="store_true",
                   help="don't write: verify the committed cache against a "
                        "fresh sweep of the selected shapes")
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--tol", type=float, default=0.25)
    p.add_argument("--shapes", default=None,
                   help="seq:heads:hd:causal:batch[:dtype][,...] "
                        "(attention shapes; dtype defaults to float32)")
    args = p.parse_args(argv)
    shapes = (_parse_shapes(args.shapes) if args.shapes
              else SMOKE_SHAPES if args.smoke
              else BENCH_SHAPES + SMOKE_SHAPES)
    log = lambda *a: print(*a, file=sys.stderr)  # noqa: E731
    if args.check:
        failures = check_cache(shapes, path=args.out, iters=args.iters,
                               tol=args.tol, log=log)
        for fail in failures:
            log(f"FAIL: {fail}")
        log(f"--check: {len(shapes) - len(failures)}/{len(shapes)} shapes ok")
        return 1 if failures else 0
    base = load_cache(args.out)
    doc = run_sweep(shapes, iters=args.iters, base=base, log=log)
    path = save_cache(doc, args.out)
    log(f"wrote {path} ({len(doc['entries'])} entries)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
