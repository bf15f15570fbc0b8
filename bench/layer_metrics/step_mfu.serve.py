"""The decode step's share of its roofline over the traced window: for
each decode tick, the least time its required work could take (the larger
of its FLOPs over peak and its bytes -- every weight, the live cache read,
the new entries written -- over HBM bandwidth, ``work/decode_step.py``),
summed, over the summed device time of the decode step's program. Named
``mfu`` because it is the whole step's share of the chip's peak."""

import devtrace

MODULE = "paged_serve_step"


def read(rec, ctx):
    h = rec["host_record"]
    if h.get("kind") != "serve":
        return None
    mods = devtrace.module_events(rec, MODULE)
    busy = sum(m[2] for m in mods)
    ticks = [t for t in h["ticks"] if t[2]]
    if not mods or not ticks or busy <= 0:
        return None
    step = ctx.work("decode_step")
    least = 0.0
    for _, _, lens in ticks:
        flops, io = step.work(h["config"], lens)
        least += max(flops / ctx.peak["flops_per_s"],
                     io / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / busy
