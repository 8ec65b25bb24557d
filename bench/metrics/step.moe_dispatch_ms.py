"""step.moe_dispatch_ms: device milliseconds per traced step of expert
dispatch (scope ``moe_dispatch``: router, binning into the capacity bins
and back, the exchange where there is one): the union of its operations'
intervals, averaged over the chips (``program_trace.metrics``, by the
compiled step's layer map)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "step.moe_dispatch_ms")
