"""Share of its roofline that the routed experts' grouped matmuls
(``moe_gmm*`` device events: gate, up and down of every MoE layer, in
prefill and decode) reach over the traced window: per prefill launch and
decode tick the window saw (the host record's ``moe`` routing counters:
tokens routed per layer, experts touched over the layers), the least time
of the required expert work (the larger of its FLOPs over peak and the
touched experts' weight bytes over HBM bandwidth, ``work/moe_ffn.py``),
summed, over the family's summed device time."""

import devtrace

FAMILY = "moe_gmm"


def read(rec, ctx):
    h = rec["host_record"]
    moe = h.get("moe") if h.get("kind") == "serve" else None
    if not moe:
        return None
    ops = devtrace.family_ops(rec, FAMILY)
    busy = sum(o[2] for o in ops)
    calls = moe.get("admit", []) + moe.get("decode", [])
    if not ops or not calls or busy <= 0:
        return None
    c = h["config"]
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    work = ctx.work("moe_ffn").work
    least = 0.0
    for _, tokens, touched in calls:
        flops, io = work(tokens=tokens * layers, experts_touched=touched,
                         d=c["hidden_size"], d_expert=c["moe_intermediate_size"],
                         top_k=c["num_experts_per_tok"])
        least += max(flops / ctx.peak["flops_per_s"],
                     io / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / busy
