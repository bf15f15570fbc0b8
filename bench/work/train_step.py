"""Model FLOPs one training step requires, from the configuration and the
batch: 6 per token for every matmul parameter outside the embedding (the
attention and MLP weights of each layer, and the output head over the
configuration's vocabulary), plus the causal-visible attention matmuls at
three times their forward count (forward and the backward's two). The
embedding gather, the norms and any recomputation under remat are not
counted, so no implementation can read more work than the step requires.
"""

import pathlib
import importlib.util

_spec = importlib.util.spec_from_file_location(
    "bench_work_fa2_fwd", pathlib.Path(__file__).with_name("fa2_fwd.py"))
_fwd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_fwd)


def layer_matmul_params(c) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * c["intermediate_size"]


def attention_fwd_flops(c, rows: int, seq: int) -> int:
    return _fwd.work(batch=rows, s_q=seq, s_kv=seq,
                     heads=c["num_attention_heads"],
                     kv_heads=c["num_key_value_heads"],
                     head_dim=c["head_dim"], causal=True)[0]


def flops(c, rows: int, seq: int) -> int:
    """Model FLOPs of one step of ``rows`` x ``seq`` tokens."""
    L = c["num_hidden_layers"]
    dense = L * layer_matmul_params(c) + c["hidden_size"] * c["vocab_size"]
    return 6 * dense * rows * seq + 3 * L * attention_fwd_flops(c, rows, seq)
