"""Backend shim for the Pallas kernels.

``resolve_interpret`` is the single place where ``interpret=None`` (the
default everywhere: ops.py, AttentionConfig, kernel entry points) becomes a
concrete bool: interpret off on real TPUs, on everywhere else. Callers that
pass an explicit bool keep full control (tests, benchmarks).
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> 'not on a TPU'; an explicit bool passes through unchanged."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
