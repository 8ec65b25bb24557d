"""step.ffn_ms: device milliseconds per traced step of the dense FFN and
the shared experts (scope ``ffn``): the union of its operations'
intervals, averaged over the chips (``program_trace.metrics``, by the
compiled step's layer map)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "step.ffn_ms")
