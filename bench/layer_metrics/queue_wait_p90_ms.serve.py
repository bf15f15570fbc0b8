"""90th percentile (nearest rank) of the engine's own ``queue_wait`` spans
(submit to admission) of the requests due in the window, in ms. The
engine's span recorder runs on the host clock the harness uses."""

import math


def read(rec, ctx):
    h = rec["host_record"]
    waits = h.get("queue_wait_s") if h.get("kind") == "serve" else None
    if not waits:
        return None
    s = sorted(waits)
    return 1e3 * s[max(0, math.ceil(0.9 * len(s)) - 1)]
