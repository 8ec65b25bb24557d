"""step.moe_experts_ms: device milliseconds per traced step of the routed
experts' FFN (scope ``moe_experts``): the union of its operations'
intervals, averaged over the chips (``program_trace.metrics``, by the
compiled step's layer map)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "step.moe_experts_ms")
