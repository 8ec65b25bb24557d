"""device.idle_input_ms: device idle milliseconds per traced step while
the innermost host span open is one of the program's input spans
(``input.*``: ``next_batch``, the engine's advance, decode, assembly)
(``program_trace.metrics``)."""

import program_trace


def read(ctx):
    return program_trace.read(ctx, "device.idle_input_ms")
