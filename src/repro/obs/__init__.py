"""Unified host-side telemetry: metrics registry, lifecycle tracing, MFU.

Three pillars (ISSUE 8), all host-side Python around the jitted steps --
attaching any of them is guaranteed not to add compiles or perturb traced
shapes (pinned by tests/test_obs.py):

  * :mod:`repro.obs.metrics` -- counters / gauges / fixed-bucket
    histograms behind one registry with a flat-dict ``snapshot()``
    schema. Both serving engines, the KV page pool, the kernel-knob
    resolution path and the train loop register into it.
  * :mod:`repro.obs.trace`   -- span-based request-lifecycle, engine-tick
    and train-step event log exported as Chrome/Perfetto ``trace_event``
    JSON (``--trace-out`` on launch/serve.py and launch/train.py); its
    scoped spans are also ``jax.profiler`` annotations (``repro.*``).
  * :mod:`repro.obs.mfu`     -- analytic model-FLOPs (utils/flops) +
    the visible-tile census folded into live achieved-vs-model FLOPs,
    tokens/s and MFU gauges for training (the paper's Table 1 metric as
    a counter rather than a one-off benchmark).
"""

from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    count_knob,
    default_registry,
    reset_default_registry,
)
from repro.obs.mfu import (  # noqa: F401
    TrainEfficiency,
    peak_flops,
)
from repro.obs.trace import (  # noqa: F401
    TraceRecorder,
    get_default_recorder,
    set_default_recorder,
    validate_trace,
)
