"""90th percentile (nearest rank), over the requests admitted in the traced
window, of their prefill launch's time in ms: the engine's
``repro.engine.admit`` span (host inputs built, the admission program run,
its tokens on the host), counted once for each request it admitted."""

import math

import program_trace


def read(rec, ctx):
    admits = program_trace.spans(rec, "repro.engine.admit")
    if not admits:
        return None
    s = sorted(b - a for a, b, args in admits for _ in range(int(args["n"])))
    return 1e3 * s[max(0, math.ceil(0.9 * len(s)) - 1)]
