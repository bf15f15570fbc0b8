"""Work one FlashAttention forward call requires, from its shapes.

FLOPs count the two matmuls (Q K^T and P V, 2 FLOPs a multiply-add) over
the (query, key) pairs the mask leaves visible -- for a causal mask exactly
the lower triangle with its diagonal, never the tiles a kernel launches.
Bytes are what must cross HBM at least once: Q, K, V in, O and the
per-row log-sum-exp (f32) out. Any kernel doing this call, fused, split or
a later one, is measured against the same numbers.
"""


def visible_pairs(s_q: int, s_kv: int, causal: bool) -> int:
    """Visible (query, key) pairs of one head; a causal mask aligns the
    last query with the last key."""
    if not causal:
        return s_q * s_kv
    return s_q * (s_kv - s_q) + s_q * (s_q + 1) // 2


def work(*, batch: int, s_q: int, s_kv: int, heads: int, kv_heads: int,
         head_dim: int, causal: bool = True, dtype_bytes: int = 2):
    """-> (flops, bytes) of one call."""
    pairs = batch * visible_pairs(s_q, s_kv, causal)
    flops = 4 * heads * head_dim * pairs
    io = dtype_bytes * batch * head_dim * (2 * s_q * heads + 2 * s_kv * kv_heads)
    return flops, io + 4 * batch * heads * s_q
