"""moe.dropped_share: routed units that found their expert's capacity
full, over all routed units of the traced steps, in %: the program's
``dropped_units`` counter of each traced step, read after the run
(``program_trace.metrics``)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "moe.dropped_share")
