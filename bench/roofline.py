"""A kernel's share of its roofline, for ``metrics/<kernel>_roofline.py``.

The least time the chip could take for the kernel's calls in the traced
window is the larger of its operations over the peak rate and its bytes
over the peak HBM bandwidth (``peaks.json``); the share is that time over
the kernel's device time on chip 0, in %. The operations and bytes of one
call come from the kernel's shapes, computed by the metric's own file;
``calls`` is how many calls the traced steps make."""


def share(ctx, pattern: str, flops_per_call: float, bytes_per_call: float,
          calls: int, rate: str = "bf16_flops"):
    red = ctx["trace"]
    t, _ = red.op_s(red.devices[0], pattern)
    if t <= 0:
        return None
    peaks = ctx["peaks"]
    least = max(flops_per_call * calls / peaks[rate],
                bytes_per_call * calls / peaks["hbm_bytes_per_s"])
    return least / t * 100.0
