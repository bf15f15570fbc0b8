"""Production trainer driver: data pipeline -> sharded train step ->
supervised checkpoint/restart -> telemetry. The end-to-end entry point
(examples/train_gpt.py is a thin wrapper).

The loop runs *under* training.fault_tolerance.run_with_restarts:
  * restore_fn owns a whole incarnation -- it (re-)enters the mesh
    context, re-jits the step, restores the latest durable checkpoint
    onto the *current* mesh (per-shard elastic restore via
    distributed/params.tree_shardings) and reseats the packed-data
    stream position; a step failure replays from there,
  * per-shard async atomic saves on a Young/Daly cadence fed the
    worker's *actual* write duration (store.drain_write_stats),
  * a SIGTERM/SIGINT grace handler (the preemption notice): finish the
    in-flight step, drain the async writer, write a final checkpoint,
    exit cleanly,
  * --fault-plan injects deterministic faults (training/fault_injection)
    for end-to-end recovery drills,
  * StepMonitor straggler telemetry + NaN step-skip inside apply_updates.

Usage:
  python -m repro.launch.train --arch qwen3-8b --reduce --steps 100
  python -m repro.launch.train --preset gpt-100m --steps 300 --seq 512
  python -m repro.launch.train --preset gpt-20m --ckpt-dir /tmp/ckpt \\
      --fault-plan raise@5,corrupt@8   # recovery drill
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import signal
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import CheckpointStore, _flatten
from repro.configs import registry
from repro.configs.base import ModelConfig
from repro.core.attention import AttentionConfig
from repro.data.pipeline import DataConfig, make_source
from repro.launch.steps import build_train_step
from repro.models import lm
from repro.training.fault_injection import FaultPlan
from repro.training.fault_tolerance import CheckpointCadence, run_with_restarts
from repro.training.optimizer import AdamWConfig, init_opt_state
from repro.utils import flops as F
from repro.utils.compile_cache import enable_compile_cache

PRESETS: Dict[str, ModelConfig] = {
    # ~verifiable-on-CPU GPT-style models (paper Table 1 scale ladder)
    "gpt-20m": ModelConfig(
        name="gpt-20m", family="dense", num_layers=4, d_model=256,
        num_heads=4, num_kv_heads=4, head_dim=64, d_ff=1024,
        vocab_size=8192, vocab_pad_to=256, dtype="float32", remat=False,
    ),
    "gpt-100m": ModelConfig(
        name="gpt-100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=12, head_dim=64, d_ff=3072,
        vocab_size=32768, vocab_pad_to=256, dtype="float32", remat=False,
    ),
}


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    seq_len: int = 512
    batch_size: int = 8
    microbatches: int = 1
    ckpt_dir: Optional[str] = None
    # ckpt_every is a FLOOR on checkpoint spacing (a minimum number of
    # steps between saves); above it the Young/Daly interval computed
    # from mtbf_seconds and the observed write cost decides when to
    # actually save. Small mtbf_seconds => save at every floor boundary
    # (what the deterministic kill-and-resume tests use).
    ckpt_every: int = 50
    mtbf_seconds: float = 3600.0
    max_restarts: int = 3
    # Deterministic fault injection: a FaultPlan or a plan spec string
    # ("raise@5,corrupt@8" -- training/fault_injection.py grammar).
    fault_plan: Optional[Any] = None
    history_out: Optional[str] = None
    attn_impl: str = "flash_xla"
    log_every: int = 10
    seed: int = 0
    packed: bool = False  # varlen sequence packing (segment-masked attention)
    # Mesh: model_axis > 1 (or data_axis > 1) builds a (data, model) host
    # mesh and installs sharding rules for the run. data_axis = 0 derives
    # the data axis as devices / model_axis; > 0 pins it (the 2D
    # data x ring composition -- batch over 'data', ring context
    # parallelism over 'model' inside each data group). attn_sharding
    # overrides the arch default: 'heads' | 'sequence' (all-gather
    # context parallel) | 'ring' (KV-sharded context parallel --
    # distributed/ring_attention.py).
    model_axis: int = 1
    data_axis: int = 0
    attn_sharding: Optional[str] = None
    # Observability (repro.obs): metrics always collect into `registry`
    # (or a fresh one); trace_out records step -> data/compute/checkpoint
    # spans as Perfetto JSON. Both host-side: zero extra compiles.
    trace_out: Optional[str] = None
    metrics_out: Optional[str] = None
    registry: Optional[Any] = None


def resolve_model(arch: Optional[str], preset: Optional[str], reduce: bool) -> ModelConfig:
    if preset:
        return PRESETS[preset]
    assert arch, "--arch or --preset required"
    cfg = registry.get(arch)
    return registry.reduce_config(cfg) if reduce else cfg


def _mesh_context(cfg: ModelConfig, loop: TrainLoopConfig):
    """The sharding context for the run: a (data, model) host mesh +
    lm_rules when model_axis > 1 (or data_axis pinned > 1), else a no-op.
    Entered around tracing AND execution so `constrain` / the
    ring-attention route see the rules. ``--data-axis N --model-axis M``
    composes batch/FSDP data parallelism with ring/sequence context
    parallelism on the same (N, M) mesh."""
    if loop.model_axis <= 1 and loop.data_axis <= 1:
        return contextlib.nullcontext()
    from repro.distributed.sharding import lm_rules, use_rules
    from repro.launch.mesh import make_host_mesh, make_long_context_mesh

    if loop.data_axis > 0:
        mesh = make_long_context_mesh(loop.data_axis, loop.model_axis)
    else:
        mesh = make_host_mesh(model_axis=loop.model_axis)
    rules = lm_rules(cfg, model_axis=loop.model_axis,
                     data_axis=mesh.shape["data"],
                     batch_size=loop.batch_size)
    stack = contextlib.ExitStack()
    stack.enter_context(mesh)
    stack.enter_context(use_rules(mesh, rules))
    print(f"[train] mesh {dict(mesh.shape)} attn_sharding={cfg.attn_sharding}")
    return stack


def train(cfg: ModelConfig, loop: TrainLoopConfig, opt_cfg: Optional[AdamWConfig] = None):
    """Run the loop; returns (params, opt_state, history dict).

    The mesh context is NOT entered here: the supervisor's restore_fn
    enters (and on restart re-enters) _mesh_context per incarnation, so
    a restore genuinely re-forms the mesh."""
    if loop.attn_sharding is not None:
        if loop.model_axis <= 1:
            raise ValueError(
                f"--attn-sharding {loop.attn_sharding} needs --model-axis > 1 "
                "(no mesh is built otherwise, so the flag would do nothing)"
            )
        # Applied to THE cfg (not a rules-local copy) so everything
        # cfg-derived downstream (flops accounting, rules) sees the mode.
        cfg = dataclasses.replace(cfg, attn_sharding=loop.attn_sharding)
    return _train(cfg, loop, opt_cfg)


class _GraceHandler:
    """SIGTERM/SIGINT -> graceful stop flag (the preemption notice).

    First signal sets the flag: the loop finishes the in-flight step,
    drains the async writer, writes a final checkpoint and exits
    cleanly. A second signal escalates (KeyboardInterrupt). Installing
    outside the main thread (tests calling train() from a worker) is a
    silent no-op -- the flag just never fires.
    """

    def __init__(self):
        self.flag = False
        self._prev: Dict[int, Any] = {}

    def _on(self, signum, frame):
        if self.flag:
            raise KeyboardInterrupt(f"second signal {signum}: hard stop")
        self.flag = True
        print(f"[train] caught {signal.Signals(signum).name}: finishing step, "
              "draining async save, writing final checkpoint", flush=True)

    def install(self) -> "_GraceHandler":
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev[sig] = signal.signal(sig, self._on)
            except ValueError:  # not the main thread
                pass
        return self

    def uninstall(self) -> None:
        for sig, prev in self._prev.items():
            signal.signal(sig, prev)
        self._prev = {}


def _current_sharding_fn(template):
    """Elastic-restore placement for the *current* mesh: leaf key ->
    NamedSharding from distributed/params.tree_shardings under the active
    rules, or None outside a mesh context (plain device_put)."""
    from repro.distributed import sharding as dist_sharding

    state = dist_sharding.current()
    if state is None:
        return None, None
    from repro.distributed.params import tree_shardings

    mesh, rules = state
    shardings = tree_shardings(template, mesh, rules)
    table = dict(_flatten(shardings))
    return (lambda key, spec: table.get(key)), shardings


def _train(cfg: ModelConfig, loop: TrainLoopConfig, opt_cfg: Optional[AdamWConfig] = None):
    opt_cfg = opt_cfg or AdamWConfig(total_steps=loop.steps)
    # Block sizes left at None so training picks up the shape-aware
    # heuristics per shape instead of a hardcoded 256.
    attn_cfg = AttentionConfig(impl=loop.attn_impl, mode="auto")
    data = make_source(DataConfig(
        batch_size=loop.batch_size, seq_len=loop.seq_len,
        vocab_size=cfg.vocab_size, seed=loop.seed,
        source="packed" if loop.packed else "synthetic",
    ))
    fault_plan = loop.fault_plan
    if isinstance(fault_plan, str):
        fault_plan = FaultPlan.parse(fault_plan, seed=loop.seed)

    cadence = CheckpointCadence(loop.mtbf_seconds, min_interval_steps=loop.ckpt_every)
    n_params, _ = F.param_count(cfg)

    # Telemetry (repro.obs): registry + MFU meter always on (host-side
    # arithmetic around the jitted step -- the jaxpr is pinned identical
    # with/without them by tests/test_obs.py); span tracing when asked.
    from repro.obs import MetricsRegistry, TraceRecorder, TrainEfficiency

    obs = loop.registry if loop.registry is not None else MetricsRegistry()
    eff = TrainEfficiency(cfg, loop.batch_size, loop.seq_len, obs)
    c_stragglers = obs.counter("train/stragglers")
    c_ckpts = obs.counter("train/checkpoints")
    c_preempt = obs.counter("train/preemptions")
    obs.counter("train/restarts")  # pre-register: snapshot carries 0
    g_loss = obs.gauge("train/loss")
    tracer = TraceRecorder(process="train") if loop.trace_out else None
    if tracer is not None:
        # Ring attention + the checkpoint store emit spans into the
        # process default recorder (obs.trace); install this run's
        # recorder so they land in the same --trace-out file.
        from repro.obs import set_default_recorder

        set_default_recorder(tracer)

    store = CheckpointStore(loop.ckpt_dir, registry=obs,
                            fault_plan=fault_plan) if loop.ckpt_dir else None

    loss_by_step: Dict[int, float] = {}
    time_by_step: Dict[int, float] = {}
    history = {"loss": [], "step_time": [], "stragglers": 0,
               "restored_at": 0, "restarts": 0, "preempted": False,
               "registry": obs}
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"{loop.steps} steps x {loop.batch_size}x{loop.seq_len} tokens, attn={loop.attn_impl}")

    # --- incarnation: everything a restart must rebuild --------------------
    # restore_fn owns it: close the old mesh context, re-enter
    # _mesh_context (re-forming the mesh), re-jit the step, restore the
    # latest durable checkpoint onto the *current* mesh, reseat the data
    # stream. The same path serves cold start, in-process replay after a
    # step failure, and the elastic relaunch after a preemption.
    inc: Dict[str, Any] = {"ctx": None, "step_fn": None, "restores": 0}

    def _close_incarnation():
        if inc["ctx"] is not None:
            inc["ctx"].__exit__(None, None, None)
            inc["ctx"] = None

    def restore_fn():
        if store is not None:
            # Drain the in-flight async write before listing steps: the
            # worker renames + GCs concurrently, and a half-written .tmp
            # must never race the restore scan. A *failed* write was
            # already surfaced (warning + ckpt/async_failures); it must
            # not abort the restart itself.
            try:
                store.wait()
            except RuntimeError:
                pass
        _close_incarnation()
        inc["ctx"] = _mesh_context(cfg, loop)
        inc["ctx"].__enter__()
        # Donated: the step's new (params, opt_state) reuse the old ones'
        # buffers instead of holding both copies (about 14 B per param of
        # state) at once. Nothing reads a state after its step: a save
        # snapshots to host before returning, and a replay restores.
        inc["step_fn"] = jax.jit(build_train_step(
            cfg, attn_cfg, opt_cfg, microbatches=loop.microbatches, ce_chunk=512,
        ), donate_argnums=(0, 1))
        params = lm.init_lm(cfg, jax.random.PRNGKey(loop.seed))
        opt_state = init_opt_state(params)
        sharding_fn, shardings = _current_sharding_fn((params, opt_state))
        if shardings is not None:
            # Place the fresh init per the rules so every save (including
            # one before the first step output) is per-shard.
            params, opt_state = jax.tree.map(
                jax.device_put, (params, opt_state), shardings)
        start_step = 0
        if store is not None and store.steps():
            try:
                (params, opt_state), meta = store.restore(
                    (params, opt_state), sharding_fn=sharding_fn)
                start_step = meta["step"]
                data.restore(meta["data"])
                print(f"[train] restored step {start_step} from {loop.ckpt_dir}")
            except FileNotFoundError as e:
                import warnings

                warnings.warn(
                    f"every checkpoint in {loop.ckpt_dir} failed validation "
                    f"({e}); starting FRESH from step 0")
        if inc["restores"] == 0:
            history["restored_at"] = start_step
        inc["restores"] += 1
        return start_step, (params, opt_state)

    def step_body(step, state):
        params, opt_state = state
        if fault_plan is not None:
            fault_plan.fire_step(step)
        t_step0 = tracer.now_us() if tracer else 0.0
        t_data0 = time.perf_counter()
        out = data.batch(step)
        if not isinstance(out, dict):
            out = {"inputs": out[0], "targets": out[1]}
        batch = {k: jnp.asarray(v) for k, v in out.items()}
        t_data = time.perf_counter() - t_data0
        t_c0 = time.perf_counter()
        params, opt_state, metrics = inc["step_fn"](params, opt_state, batch)
        loss = float(metrics["loss"])
        t_compute = time.perf_counter() - t_c0
        loss_by_step[step] = loss
        time_by_step[step] = t_compute
        eff.step(t_compute)
        g_loss.set(loss)
        if tracer:
            tracer.complete("data", 0, t_step0, t_data * 1e6)
            tracer.complete("compute", 0, t_step0 + t_data * 1e6,
                            t_compute * 1e6, args={"loss": loss, "step": step})
            tracer.complete("step", 0, t_step0, tracer.now_us() - t_step0,
                            args={"step": step})
        if step % loop.log_every == 0 or step == loop.steps - 1:
            snap = obs.snapshot()
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):7.3f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"{snap['train/tokens_per_s']:8.0f} tok/s "
                  f"mfu {snap['train/mfu']:.4f}", flush=True)
        if store is not None:
            # Young/Daly write cost = the worker's actual wall duration
            # (the blocking save() call only measures the snapshot).
            for _s, dt in store.drain_write_stats():
                cadence.observe_write(dt)
        return params, opt_state

    def save_fn(step, state):
        if store is None:
            return
        t_ckpt0 = time.perf_counter()
        t_ckpt0_us = tracer.now_us() if tracer else 0.0
        data_state = dict(data.state())
        data_state["step"] = step
        store.save(step, state,
                   meta={"step": step, "data": data_state,
                         "config": cfg.name}, async_=True)
        c_ckpts.inc()
        if tracer:
            # the *blocking* portion only: local-shard snapshot + handoff
            tracer.complete("checkpoint", 0, t_ckpt0_us,
                            (time.perf_counter() - t_ckpt0) * 1e6,
                            args={"step": step})

    grace = _GraceHandler().install()
    try:
        (params, opt_state), restarts, telem = run_with_restarts(
            step_body, restore_fn, save_fn,
            total_steps=loop.steps, cadence=cadence,
            max_restarts=loop.max_restarts,
            should_stop=lambda: grace.flag, registry=obs,
        )
    finally:
        grace.uninstall()
        if store is not None:
            store.wait()  # drain the in-flight async save
        _close_incarnation()
    for _s, dt in store.drain_write_stats() if store is not None else ():
        cadence.observe_write(dt)
    if telem["preempted"]:
        c_preempt.inc()
        print(f"[train] preempted: drained async writer; final checkpoint at "
              f"step {telem['last_step']}", flush=True)

    done = sorted(loss_by_step)
    history["loss"] = [loss_by_step[s] for s in done]
    history["step_time"] = [time_by_step[s] for s in done]
    history["steps"] = done
    history["restarts"] = restarts
    history["preempted"] = telem["preempted"]
    history["stragglers"] = len(telem["stragglers"])
    for _ in telem["stragglers"]:
        c_stragglers.inc()
    if loop.history_out:
        with open(loop.history_out, "w") as f:
            json.dump({"loss": history["loss"], "steps": done,
                       "restored_at": history["restored_at"],
                       "restarts": restarts,
                       "preempted": history["preempted"]}, f)
        print(f"[train] wrote loss history to {loop.history_out}")
    if loop.metrics_out:
        from repro.obs import default_registry

        snap = obs.snapshot()
        snap.update(default_registry().snapshot())  # ring/sharding/ckpt
        with open(loop.metrics_out, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
        print(f"[train] wrote metrics snapshot to {loop.metrics_out}")
    if tracer is not None:
        from repro.obs import set_default_recorder

        set_default_recorder(None)
        tracer.save(loop.trace_out)
        print(f"[train] wrote Perfetto trace ({len(tracer.events)} events) "
              f"to {loop.trace_out}")
    return params, opt_state, history


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="assigned architecture id")
    ap.add_argument("--preset", default=None, choices=sorted(PRESETS))
    ap.add_argument("--reduce", action="store_true", help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--attn", default="flash_xla", choices=("ref", "flash_xla", "flash_pallas"))
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="FLOOR on checkpoint spacing in steps; above it "
                         "the Young/Daly interval (from --mtbf and the "
                         "observed async write cost) decides when to save")
    ap.add_argument("--mtbf", type=float, default=3600.0,
                    help="assumed mean time between failures (seconds) for "
                         "the Young/Daly checkpoint interval; tiny values "
                         "pin saves to every --ckpt-every boundary")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="in-process supervisor restarts before giving up")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault injection, e.g. "
                         "'raise@5,corrupt@8' (kinds: raise, sigterm, "
                         "sigkill, abort, torn, trunc, drop, corrupt)")
    ap.add_argument("--history-out", default=None,
                    help="write the per-step loss history + restore "
                         "telemetry (JSON) here -- what the "
                         "kill-and-resume continuity checks diff")
    ap.add_argument("--packed", action="store_true",
                    help="varlen sequence packing (segment-masked attention)")
    ap.add_argument("--model-axis", type=int, default=1,
                    help="model-axis width of the (data, model) host mesh")
    ap.add_argument("--data-axis", type=int, default=0,
                    help="data-axis width of the (data, model) mesh; 0 "
                         "derives it as devices / model-axis. Composes "
                         "batch/FSDP parallelism with the ring: "
                         "--data-axis 2 --model-axis 4 runs two 4-wide "
                         "rings side by side on 8 devices")
    ap.add_argument("--attn-sharding", default=None,
                    choices=("heads", "sequence", "ring"),
                    help="override the arch's attention sharding strategy")
    ap.add_argument("--trace-out", default=None,
                    help="write step/data/compute/checkpoint spans as "
                         "Perfetto trace_event JSON here")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final metrics snapshot (JSON) here")
    args = ap.parse_args()

    cfg = resolve_model(args.arch, args.preset, args.reduce)
    loop = TrainLoopConfig(
        steps=args.steps, seq_len=args.seq, batch_size=args.batch,
        microbatches=args.microbatches, attn_impl=args.attn, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, mtbf_seconds=args.mtbf,
        max_restarts=args.max_restarts, fault_plan=args.fault_plan,
        history_out=args.history_out,
        packed=args.packed, model_axis=args.model_axis,
        data_axis=args.data_axis, attn_sharding=args.attn_sharding,
        trace_out=args.trace_out, metrics_out=args.metrics_out,
    )
    _, _, history = train(cfg, loop)
    first = np.mean(history["loss"][:5]) if history["loss"] else float("nan")
    last = np.mean(history["loss"][-5:]) if history["loss"] else float("nan")
    snap = history["registry"].snapshot()
    print(json.dumps({"first5_loss": round(float(first), 4),
                      "last5_loss": round(float(last), 4),
                      "median_step_s": round(float(np.median(history['step_time'])), 4),
                      "stragglers": history["stragglers"],
                      "mfu": snap.get("train/mfu"),
                      "tokens_per_s": round(snap.get("train/tokens_per_s", 0.0), 1)}))


if __name__ == "__main__":
    main()
