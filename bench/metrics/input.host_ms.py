"""input.host_ms: mean host time per traced step in the harness span
``bench.next_batch``: the pipeline's ``next_batch``, which advances the
shuffle engine and assembles the batch while earlier steps run."""


def read(ctx):
    red = ctx["trace"]
    w0, w1 = red.window
    spans = [b - a for name, a, b in red.host
             if name == "bench.next_batch" and a >= w0 and b <= w1]
    if not spans:
        return None
    return sum(spans) / len(spans) * 1e-6
