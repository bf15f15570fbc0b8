"""Work one split-KV decode attention call requires: one new query token
per decoding request against its cached keys and values.

``lens`` are the cache entries each request reads (its new token's
included). FLOPs: Q K^T and P V over those entries, 2 FLOPs a
multiply-add. Bytes: every cached key and value read once, the query in
and the output out; pages, splits and their merge add nothing required.
"""


def work(*, lens, heads: int, kv_heads: int, head_dim: int,
         dtype_bytes: int = 2):
    """-> (flops, bytes) of one call (one layer)."""
    total = sum(int(n) for n in lens)
    flops = 4 * heads * head_dim * total
    io = dtype_bytes * head_dim * (2 * kv_heads * total + 2 * heads * len(lens))
    return flops, io
