"""Closed-loop training job: one program steps as fast as it can over
pre-built batches; the next step starts when the last one's loss is read.

Traffic file keys: ``rows`` x ``seq_len`` tokens per step, the synthetic
stream's ``noise`` share, how many distinct ``batches`` to pre-build, the
``check_steps`` that set-up drives and the reference follows, and the job's
``optimizer``.

Set-up builds one object -- the step jitted with donation under the
program's mesh context, as ``launch.train.train`` does, with its state made
from the seed -- drives it through ``check_steps`` steps on distinct rows
with the window's own call, reads what ``correct`` compares, and hands the
same object to the window. Once the window has closed and the device's peak
memory is read, the program's state is freed and the plain f32 reference
follows the same steps.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List, Tuple

import numpy as np

import devtrace
import harness
import model
from ref import qwen3 as ref


def synthetic_batch(seed: int, step: int, rows: int, seq: int, vocab: int,
                    noise: float) -> Tuple[np.ndarray, np.ndarray]:
    """One batch of the order-1 synthetic stream: each next token is a fixed
    random permutation of the last one, or with probability ``noise`` a
    uniform jump. Copied from ``repro.data.pipeline.SyntheticLM.batch`` so the
    yardstick does not move with the program; seeded from any whole seed."""
    perm = np.random.default_rng(harness.seed_words(seed, 1)).permutation(vocab)
    rng = np.random.default_rng(harness.seed_words(seed, 1000 + step))
    toks = np.empty((rows, seq + 1), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    jump = rng.random((rows, seq)) < noise
    jumps = rng.integers(0, vocab, size=(rows, seq))
    for t in range(1, seq + 1):
        toks[:, t] = np.where(jump[:, t - 1], jumps[:, t - 1],
                              perm[toks[:, t - 1]])
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    """Worst leaf of |norm(program) - norm(reference)|, over the larger of
    that leaf's reference norm and the median leaf's. Leaves whose reference
    gradient is under a thousandth of the median's are left out by the
    caller (they move under Adam by round-off alone)."""
    med = float(np.median(list(want.values())))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in want)


def moving_leaves(grad0: Dict[str, float]) -> List[str]:
    med = float(np.median(list(grad0.values())))
    return [k for k, v in grad0.items() if v >= 1e-3 * med]


def compare(prog: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """The numbers ``correct`` compares: the first step's loss gap (nats),
    and the worst leaf's gap of the first gradient's and of the three
    steps' change norms.

    Only the first step's loss: the program keeps its weights in bf16 (an
    f32 master copy beside them), and a warm-up step of about 3e-6 is below
    bf16's resolution of most weights, so its loss after an update departs
    from the f32 reference's by about 0.06 nats on every seed while its
    gradients and master weights agree to 1e-4 -- more than the fp8
    control departs. The later steps are still read: through the gradient
    and the master weights' change."""
    keep = moving_leaves(want["grad0"])
    pick = lambda d: {k: d[k] for k in keep}
    return {
        "loss_gap": abs(prog["losses"][0] - want["losses"][0]),
        "grad_gap": worst_leaf_gap(pick(prog["grad0"]), pick(want["grad0"])),
        "change_gap": worst_leaf_gap(pick(prog["change"]), pick(want["change"])),
    }


class Job:
    """The program's train step and its state, as ``launch.train`` builds
    them, driven from the seed."""

    def __init__(self, cell: harness.Cell, seed: int):
        import jax
        from repro.core.attention import AttentionConfig
        from repro.launch import steps
        from repro.launch import train as train_mod
        from repro.training.optimizer import AdamWConfig, init_opt_state

        tr, mesh = cell.traffic, cell.config.get("mesh", {})
        self.cell, self.seed = cell, seed
        self.cfg = model.program_config(cell.config)
        self.rows, self.seq = tr["rows"], tr["seq_len"]
        loop = train_mod.TrainLoopConfig(
            seq_len=self.seq, batch_size=self.rows,
            model_axis=mesh.get("model_axis", 1),
            attn_sharding=mesh.get("attn_sharding"))
        if loop.attn_sharding is not None:
            import dataclasses

            self.cfg = dataclasses.replace(self.cfg,
                                           attn_sharding=loop.attn_sharding)
        self.ctx = train_mod._mesh_context(self.cfg, loop)
        self.ctx.__enter__()
        attn = AttentionConfig(impl=cell.config["program"]["attn_impl"],
                               mode="auto")
        self.opt_cfg = AdamWConfig(**tr["optimizer"])
        self.step_fn = jax.jit(
            steps.build_train_step(self.cfg, attn, self.opt_cfg),
            donate_argnums=(0, 1))
        self.key = harness.jax_key(seed)
        self.params = model.make_weights(self.cfg, self.key)
        self.opt = init_opt_state(self.params)
        sharding_fn, shardings = train_mod._current_sharding_fn(
            (self.params, self.opt))
        if shardings is not None:
            self.params, self.opt = jax.tree.map(
                jax.device_put, (self.params, self.opt), shardings)

    def step(self, batch):
        self.params, self.opt, metrics = self.step_fn(self.params, self.opt,
                                                      batch)
        return metrics

    def close(self):
        self.params = self.opt = None
        self.ctx.__exit__(None, None, None)


def program_readings(job: Job, batches, steps: int) -> Dict[str, Any]:
    """Drive the first ``steps`` steps; read each loss, the first gradient
    as the optimizer got it (from its first moment after one step) and the
    parameters' change after ``steps`` (from the f32 master copy)."""
    import jax
    import jax.numpy as jnp

    b1, clip_at = job.opt_cfg.b1, job.opt_cfg.grad_clip
    losses, grad0 = [], None
    for s in range(steps):
        with devtrace.span("bench.step"):
            m = job.step(batches[s])
        with devtrace.span("bench.loss_sync"):
            losses.append(float(m["loss"]))
        if s == 0:
            gnorm = float(m["grad_norm"])
            clip = min(1.0, clip_at / (gnorm + 1e-9))
            grad0 = {k: v / (1 - b1) / clip
                     for k, v in ref.leaf_norms(job.opt.mu).items()}
    start = model.make_weights(job.cfg, job.key)
    change = ref.leaf_norms(jax.tree.map(
        lambda m, p: m - p.astype(jnp.float32), job.opt.master, start))
    del start
    return {"losses": losses, "grad0": grad0, "change": change}


def build_batches(cell: harness.Cell, seed: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    tr = cell.traffic
    return [synthetic_batch(seed, i, tr["rows"], tr["seq_len"],
                            cell.config["vocab_size"], tr["noise"])
            for i in range(tr["batches"])]


def run(cell: harness.Cell, devices, *, seed: int, seconds: float,
        trace: bool, t_start: float) -> Dict[str, Any]:
    import jax

    tr = cell.traffic
    n_check = tr["check_steps"]
    batches_np = build_batches(cell, seed)
    job = Job(cell, seed)
    batches = [{"inputs": jax.device_put(i), "targets": jax.device_put(t)}
               for i, t in batches_np]
    prog = program_readings(job, batches, n_check)
    tokens_per_step = job.rows * job.seq

    n, failed, k = 0, 0, n_check
    # each step's loss read: wall and main-thread CPU seconds, and its start
    # in the window
    syncs = []
    with devtrace.Window(trace) as win:
        while True:
            with devtrace.span("bench.data"):
                batch = batches[k % len(batches)]
            with devtrace.span("bench.step"):
                m = job.step(batch)
            t, c = time.perf_counter(), time.thread_time()
            with devtrace.span("bench.loss_sync"):
                loss = float(m["loss"])
            syncs.append((time.perf_counter() - t, time.thread_time() - c,
                          t - win.t0))
            n, k = n + 1, k + 1
            failed += not math.isfinite(loss)
            if time.perf_counter() - win.t0 >= seconds:
                break
    device = harness.device_info(devices)
    rec = win.record()
    job.close()
    del batches, job
    gc.collect()
    harness.log(f"[train] {n} steps of {tokens_per_step} tokens in "
                f"{win.seconds:.3f}s; losses {prog['losses']}")
    walls = [w for w, _, _ in syncs]
    med, (worst, worst_cpu, worst_at) = float(np.median(walls)), max(syncs)
    late = [w - med for w in walls if w > med + 0.02]
    harness.log(f"[train] loss read per step: median {med * 1e3:.3f} ms, "
                f"longest {worst * 1e3:.3f} ms at {worst_at:.3f} s into the "
                f"window, {win.t0 - t_start + worst_at:.3f} s after start "
                f"(main thread on the CPU {worst_cpu * 1e3:.3f} ms of it); "
                f"{len(late)} steps over the median by 20 ms or more, "
                f"{sum(late):.3f} s in all")
    harness.log(f"[train] {win.gc_summary()}")

    want = ref.train_readings(
        lambda: _host_weights(cell, seed), batches_np,
        ref.Dims.from_config(cell.config), ref.Adam(**tr["optimizer"]),
        steps=n_check)
    values = compare(prog, want)
    checks = harness.judge(values, cell.limits)
    return {
        "e2e": {"train_tokens_per_s": n * tokens_per_step / win.seconds,
                "setup_s": win.t0 - t_start},
        "attempted": n, "failed": failed, "checks": checks, "device": device,
        "trace": rec, "readings": {"program": prog, "reference": want},
        "host": {"kind": "train", "steps": n, "window_s": win.seconds,
                 "rows": tr["rows"], "seq": tr["seq_len"],
                 "loss_read_s": walls,
                 "chips": len(devices), "config": cell.config},
    }


def _host_weights(cell: harness.Cell, seed: int):
    """The same weights the program started from, made again from the seed
    for the reference (the program's copy was donated to its step)."""
    return model.make_weights(model.program_config(cell.config),
                              harness.jax_key(seed))
