"""step.mfu: model FLOPs of the traced steps (``flops/<model type>.py``)
over the traced window's length times the chips times each chip's peak
bf16 FLOP/s (``peaks.json``), in %: the whole step's share of the chips'
peak, host gaps included."""


def read(ctx):
    red = ctx["trace"]
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return ctx["step_flops"] * ctx["steps"] / (red.window_s * peak) * 100.0
