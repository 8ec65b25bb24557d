"""step.attention_ms: device milliseconds per traced step of the attention
layer (scope ``attention``: MLA or MHA, forward, backward and
recompute): the union of its operations' intervals, averaged over the
chips (``program_trace.metrics``, by the compiled step's layer map)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "step.attention_ms")
