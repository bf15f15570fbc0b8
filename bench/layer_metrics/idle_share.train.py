"""Share of the traced window in which no operation ran on the device (one
minus the union of device op intervals over the window), in training."""

import devtrace


def read(rec, ctx):
    if rec["host_record"].get("kind") != "train" or not rec["devices"]:
        return None
    return 100.0 * devtrace.idle_share(rec)
