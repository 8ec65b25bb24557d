"""step.other_ms: device milliseconds per traced step of operations outside
every scope (norms, residual adds, casts, copies); with the six scoped
layers it partitions ``step.device_ms``: the union of its operations'
intervals, averaged over the chips (``program_trace.metrics``, by the
compiled step's layer map)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "step.other_ms")
