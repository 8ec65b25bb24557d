"""device.idle_share: share of the traced window in which no operation
ran on the device, in %; over several chips, the largest."""


def read(ctx):
    red = ctx["trace"]
    return max(red.idle_share(d) for d in red.devices) * 100.0
