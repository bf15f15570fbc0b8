"""Share of its roofline that the latent-attention paged decode
(``mla_decode_paged*`` device events) reaches over the traced window: per
decode tick and layer, the least time of the required attention (the
larger of its FLOPs over peak and the latent bytes it must read over HBM
bandwidth, ``work/mla_decode.py``), summed, over the family's summed device
time. Nothing to read in a cell whose model has no latent attention."""

import devtrace

FAMILY = "mla_decode_paged"


def read(rec, ctx):
    h = rec["host_record"]
    if h.get("kind") != "serve" or "kv_lora_rank" not in h["config"]:
        return None
    ops = devtrace.family_ops(rec, FAMILY)
    busy = sum(o[2] for o in ops)
    ticks = [t for t in h["ticks"] if t[2]]
    if not ops or not ticks or busy <= 0:
        return None
    c = h["config"]
    work = ctx.work("mla_decode").work
    least = 0.0
    for _, _, lens in ticks:
        flops, io = work(lens=lens, heads=c["num_attention_heads"],
                         kv_lora_rank=c["kv_lora_rank"],
                         rope_dim=c["qk_rope_head_dim"])
        least += max(flops / ctx.peak["flops_per_s"],
                     io / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least * c["num_hidden_layers"] / busy
