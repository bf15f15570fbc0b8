"""AdamW with fp32 master weights and sharded moments (pure JAX).

Mixed-precision contract (DESIGN.md Section 8): model params are compute-
dtype (bf16 on TPU); the optimizer keeps fp32 master copies + moments. The
gradient all-reduce happens in compute dtype (bf16 -- 2x less pod-link
traffic, the "gradient compression" the brief asks for) and is accumulated
into fp32 masters here. Every optimizer-state leaf inherits the parameter's
sharding (handed out by distributed/sharding rules), so with FSDP rules the
optimizer state is fully sharded (ZeRO-3-equivalent).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


class OptState(NamedTuple):
    step: jnp.ndarray  # () int32
    master: Any  # fp32 master params
    mu: Any
    nu: Any


def init_opt_state(params) -> OptState:
    # A copy even where params are already f32: the trainer donates params
    # and state to its step, and one buffer cannot be donated twice.
    f32 = lambda t: jax.tree.map(
        lambda x: jnp.array(x, dtype=jnp.float32, copy=True), t)
    zeros = lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), t)
    return OptState(jnp.zeros((), jnp.int32), f32(params), zeros(params), zeros(params))


def lr_schedule(cfg: AdamWConfig, step: jnp.ndarray) -> jnp.ndarray:
    """Linear warmup + cosine decay to min_lr_ratio."""
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    t = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0
    )
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def _no_decay(path: str) -> bool:
    p = path.lower()
    return any(t in p for t in ("norm", "bias", "scale", "a_log", "dt_bias", "meta", "'d'"))


def apply_updates(
    cfg: AdamWConfig, state: OptState, grads, param_dtype=jnp.bfloat16,
    skip_update: Optional[jnp.ndarray] = None,
) -> Tuple[Any, OptState, dict]:
    """grads in compute dtype -> (new_params (compute dtype), new_state, metrics).

    skip_update: optional () bool -- when True (e.g. non-finite grads, see
    fault_tolerance.py), the step is a no-op except for the step counter.
    """
    gnorm = global_norm(grads)
    finite = jnp.isfinite(gnorm)
    if skip_update is None:
        skip = ~finite
    else:
        skip = skip_update | ~finite
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    lr = lr_schedule(cfg, state.step)
    b1, b2 = cfg.b1, cfg.b2
    step1 = state.step + 1
    bc1 = 1 - b1 ** step1.astype(jnp.float32)
    bc2 = 1 - b2 ** step1.astype(jnp.float32)

    paths_grads = jax.tree_util.tree_flatten_with_path(grads)
    paths = ["/".join(str(k) for k in path) for path, _ in paths_grads[0]]
    flat_g = [g for _, g in paths_grads[0]]
    flat_m, tdef = jax.tree_util.tree_flatten(state.master)
    flat_mu = jax.tree_util.tree_flatten(state.mu)[0]
    flat_nu = jax.tree_util.tree_flatten(state.nu)[0]

    new_m, new_mu, new_nu, new_p = [], [], [], []
    for path, g, m, mu, nu in zip(paths, flat_g, flat_m, flat_mu, flat_nu):
        gf = g.astype(jnp.float32) * clip
        gf = jnp.where(skip, 0.0, gf)
        mu2 = b1 * mu + (1 - b1) * gf
        nu2 = b2 * nu + (1 - b2) * gf * gf
        upd = (mu2 / bc1) / (jnp.sqrt(nu2 / bc2) + cfg.eps)
        if cfg.weight_decay and not _no_decay(path):
            upd = upd + cfg.weight_decay * m
        m2 = m - lr * jnp.where(skip, 0.0, upd)
        mu2 = jnp.where(skip, mu, mu2)
        nu2 = jnp.where(skip, nu, nu2)
        new_m.append(m2)
        new_mu.append(mu2)
        new_nu.append(nu2)
        new_p.append(m2.astype(param_dtype))

    unf = lambda leaves: jax.tree_util.tree_unflatten(tdef, leaves)
    new_state = OptState(step1, unf(new_m), unf(new_mu), unf(new_nu))
    metrics = {"grad_norm": gnorm, "lr": lr, "skipped": skip.astype(jnp.float32)}
    return unf(new_p), new_state, metrics
