"""Work one decode step requires: one token for each decoding request.

FLOPs: 2 per matmul parameter per token (every layer's attention and MLP
weights and the output head over the vocabulary) plus each layer's
attention over the cache entries read. Bytes: every matmul weight read
once per step, each request's embedding row, and every layer's keys and
values read for the cached entries and written for the new token.
"""

import pathlib
import importlib.util

_spec = importlib.util.spec_from_file_location(
    "bench_work_train_step", pathlib.Path(__file__).with_name("train_step.py"))
_train = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_train)


def work(c, lens, dtype_bytes: int = 2):
    """-> (flops, bytes) of one step over requests reading ``lens``."""
    L, d, hd = c["num_hidden_layers"], c["hidden_size"], c["head_dim"]
    hq, hk = c["num_attention_heads"], c["num_key_value_heads"]
    n, total = len(lens), sum(int(x) for x in lens)
    weights = L * _train.layer_matmul_params(c) + d * c["vocab_size"]
    flops = 2 * weights * n + L * 4 * hq * hd * total
    io = dtype_bytes * (weights + n * d + L * 2 * hk * hd * (total + n))
    return flops, io
