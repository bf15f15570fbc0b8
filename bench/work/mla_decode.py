"""Work one latent-attention decode call requires (absorbed form): one new
latent query per decoding request, for every head, against the request's
cached latent tokens.

``lens`` are the cache entries each request reads (its new token's
included). A cached token is ``kv_lora_rank + qk_rope_head_dim`` wide (the
key); its first ``kv_lora_rank`` lanes are the value. FLOPs: per head and
entry, the score over the key's width and the value's accumulation over
its own, 2 FLOPs a multiply-add. Bytes: every cached latent token read once
(the value is the same bytes), each request's latent queries in and outputs
out; pages, lane padding, splits and their merge add nothing required.
"""


def work(*, lens, heads: int, kv_lora_rank: int, rope_dim: int,
         dtype_bytes: int = 2):
    """-> (flops, bytes) of one call (one layer)."""
    total = sum(int(n) for n in lens)
    width = kv_lora_rank + rope_dim
    flops = 2 * heads * (width + kv_lora_rank) * total
    io = dtype_bytes * (width * total
                        + heads * (width + kv_lora_rank) * len(lens))
    return flops, io
