"""Work the routed experts of one MoE layer require for one launch.

Each routed token copy (``top_k`` per token) runs its expert's SwiGLU:
three matmuls of ``d x d_expert``, 2 FLOPs a multiply-add. Bytes: the
weights of every expert touched (gate, up and down) read once; an expert
no token chose is neither read nor computed. The tokens' own activations
are left out: at these widths they never bound the least time.
"""


def work(*, tokens: int, experts_touched: int, d: int, d_expert: int,
         top_k: int, dtype_bytes: int = 2):
    """-> (flops, bytes) of ``tokens`` routed tokens through one or more
    MoE layers that touched ``experts_touched`` experts in all."""
    flops = 2 * 3 * d * d_expert * top_k * tokens
    io = dtype_bytes * 3 * d * d_expert * experts_touched
    return flops, io
