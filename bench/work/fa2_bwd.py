"""Work one FlashAttention backward call requires, from its shapes.

FLOPs count the backward's five matmuls over the visible (query, key)
pairs: S = Q K^T (the forward's scores, which FlashAttention does not keep),
dV = P^T dO, dP = dO V^T, dQ = dS K and dK = dS^T Q -- 2.5 times the
forward's, the count FlashAttention-2 reports against. Bytes: Q, K, V, O,
dO and the log-sum-exp (f32) in, dQ, dK, dV out, each once.
"""

import pathlib
import importlib.util

_spec = importlib.util.spec_from_file_location(
    "bench_work_fa2_fwd", pathlib.Path(__file__).with_name("fa2_fwd.py"))
_fwd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fwd)


def work(*, batch: int, s_q: int, s_kv: int, heads: int, kv_heads: int,
         head_dim: int, causal: bool = True, dtype_bytes: int = 2):
    """-> (flops, bytes) of one call."""
    pairs = batch * _fwd.visible_pairs(s_q, s_kv, causal)
    flops = 10 * heads * head_dim * pairs
    io = dtype_bytes * batch * head_dim * (4 * s_q * heads + 4 * s_kv * kv_heads)
    return flops, io + 4 * batch * heads * s_q
