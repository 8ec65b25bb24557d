"""step.device_ms: device-busy milliseconds per traced step (the union of
the intervals in which an operation ran), averaged over the chips. Only
the train step runs on the device inside the window."""


def read(ctx):
    red = ctx["trace"]
    busy = sum(red.busy_s(d) for d in red.devices) / len(red.devices)
    return busy / ctx["steps"] * 1e3
