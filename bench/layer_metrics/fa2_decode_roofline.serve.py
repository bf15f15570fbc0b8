"""Share of its roofline that the split-KV decode family (``fa2_decode*``
device events) reaches over the traced window: per decode tick and layer,
the least time of the required attention (the larger of its FLOPs over
peak and the cache bytes it must read over HBM bandwidth,
``work/fa2_decode.py``), summed, over the family's summed device time."""

import devtrace

FAMILY = "fa2_decode"


def read(rec, ctx):
    h = rec["host_record"]
    if h.get("kind") != "serve":
        return None
    ops = devtrace.family_ops(rec, FAMILY)
    busy = sum(o[2] for o in ops)
    ticks = [t for t in h["ticks"] if t[2]]
    if not ops or not ticks or busy <= 0:
        return None
    c = h["config"]
    work = ctx.work(FAMILY).work
    least = 0.0
    for _, _, lens in ticks:
        flops, io = work(lens=lens, heads=c["num_attention_heads"],
                         kv_heads=c["num_key_value_heads"],
                         head_dim=c["head_dim"])
        least += max(flops / ctx.peak["flops_per_s"],
                     io / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least * c["num_hidden_layers"] / busy
