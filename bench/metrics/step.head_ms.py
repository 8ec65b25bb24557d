"""step.head_ms: device milliseconds per traced step of embedding, final
norm, unembedding and the loss (scope ``head``): the union of its
operations' intervals, averaged over the chips
(``program_trace.metrics``, by the compiled step's layer map)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "step.head_ms")
