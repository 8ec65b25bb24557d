"""step.optimizer_ms: device milliseconds per traced step of the optimizer
(scope ``optimizer``: AdamW with the global norm and the clip): the
union of its operations' intervals, averaged over the chips
(``program_trace.metrics``, by the compiled step's layer map)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "step.optimizer_ms")
