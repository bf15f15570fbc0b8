"""Production mesh builders.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the `pod`
axis carries only the once-per-step gradient all-reduce (it crosses the
slow pod-to-pod links), `data` is FSDP + batch, `model` is tensor/context
parallelism within a pod's fast ICI.

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS *before* first jax use).

Every mesh has ``Auto`` axes: the model code places arrays with sharding
constraints and lets the partitioner propagate them. ``jax.make_mesh``
defaults to ``Explicit`` axes, under which a gather from a sharded
embedding table already fails to trace.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples on CPU).

    Multi-device on a CPU host needs the devices *before* first jax use:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the CI
    multidevice job and tests/test_ring.py run this way).
    """
    n = len(jax.devices())
    if n % model_axis != 0 or n < model_axis:
        raise ValueError(
            f"model_axis={model_axis} does not fit the {n} visible devices "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    data = n // model_axis
    return _mesh((data, model_axis), ("data", "model"))


def make_long_context_mesh(data: int = 1, model: int = None):
    """2D (data x ring) mesh for long-context runs: ring context
    parallelism over ``model`` *inside each of* ``data`` data-parallel /
    FSDP groups. The default (data=1, model=all devices) is the
    single-group layout where one long sequence is the whole workload
    (examples/long_context.py, ring benchmarks); ``train.py --data-axis
    N --model-axis M`` builds the composed mesh so the trainer scales
    past one model-axis group."""
    n = len(jax.devices())
    if model is None:
        if data <= 0 or n % data != 0:
            raise ValueError(
                f"data={data} does not divide the {n} visible devices "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
            )
        model = n // data
    if data * model != n:
        raise ValueError(
            f"mesh (data={data}) x (model={model}) != {n} visible devices"
        )
    return _mesh((data, model), ("data", "model"))
