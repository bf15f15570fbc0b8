"""Device-idle time inside the engine's ticks: the idle ms within the
traced window's ``repro.engine.tick`` spans over the number of those
spans, the host time of a tick that the device waits through."""

import program_trace


def read(rec, ctx):
    ticks = program_trace.spans(rec, "repro.engine.tick")
    if not ticks or not rec["devices"]:
        return None
    return 1e3 * sum(program_trace.idle_within(rec, ticks)) / len(ticks)
