"""Model FLOPs utilization of the training steps in the traced window: the
FLOPs the steps require (``work/train_step.py``: no recompute, no
embedding gather, causal-visible attention) over window seconds x chips x
the chip's peak."""


def read(rec, ctx):
    h = rec["host_record"]
    if h.get("kind") != "train" or not h["steps"]:
        return None
    flops = ctx.work("train_step").flops(h["config"], h["rows"], h["seq"])
    return 100.0 * flops * h["steps"] / (
        h["window_s"] * h["chips"] * ctx.peak["flops_per_s"])
