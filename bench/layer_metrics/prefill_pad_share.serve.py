"""Share of the prefill work launched in the traced window that was
padding: one minus the real feed tokens over the launched tokens (rows x
prompt bucket) of the engine's ``repro.engine.admit`` spans, in %."""

import program_trace


def read(rec, ctx):
    admits = program_trace.spans(rec, "repro.engine.admit")
    launched = sum(args["launched"] for _, _, args in admits)
    if launched <= 0:
        return None
    tokens = sum(args["tokens"] for _, _, args in admits)
    return 100.0 * (1.0 - tokens / launched)
