"""Share of its roofline that the FlashAttention forward family
(``fa2_fwd*`` device events, every launch, recomputation under remat
included) reaches in the traced window: the least time the steps' required
forward work could take (one forward per layer per step, the larger of its
FLOPs over peak and bytes over HBM bandwidth) over the family's summed
device time."""

import devtrace

FAMILY = "fa2_fwd"


def read(rec, ctx):
    h = rec["host_record"]
    if h.get("kind") != "train":
        return None
    ops = devtrace.family_ops(rec, FAMILY)
    steps = [s for s in devtrace.host_spans(rec, "bench.step")
             if 0.0 <= s[0] < rec["window_s"]]
    busy = sum(o[2] for o in ops)
    if not ops or not steps or busy <= 0:
        return None
    c = h["config"]
    flops, io = ctx.work(FAMILY).work(
        batch=h["rows"], s_q=h["seq"], s_kv=h["seq"],
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], causal=True)
    least = max(flops / ctx.peak["flops_per_s"], io / ctx.peak["hbm_bytes_per_s"])
    calls = len(steps) * c["num_hidden_layers"] * h["chips"]
    return 100.0 * least * calls / busy
