"""device.idle_wait_ms: device idle milliseconds per traced step while
the innermost host span open is a wait on a finished step (the program's
``train.sync`` or the harness's ``bench.block_loss``): the gap between
one step program and the next (``program_trace.metrics``)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "device.idle_wait_ms")
