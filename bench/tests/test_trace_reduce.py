"""The reduction from a profile to per-layer numbers: interval arithmetic
on a made-up trace, and the whole reduction on a trace recorded on a
TPU v5 lite chip (``data/trace.xplane.pb.gz``: eight steps of the
deepseek cell, with the harness's spans)."""

import pathlib

import pytest

from trace_reduce import Reduced, length, minus, union

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_interval_arithmetic():
    assert union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert length([(0, 2), (1, 3), (5, 7)]) == 5
    assert minus([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert minus([(0, 4)], []) == 4


def made_up():
    ops = {"/device:TPU:0": [("fusion.1", 10, 30), ("all-to-all.2", 25, 45),
                             ("fusion.3", 60, 80)],
           "/device:TPU:1": [("fusion.1", 10, 90)]}
    host = [("bench.window", 0, 100), ("bench.next_batch", 40, 70),
            ("bench.dispatch", 0, 10)]
    return Reduced((0, 100), ops, host)


def test_busy_idle_and_collectives():
    red = made_up()
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s("/device:TPU:0") == pytest.approx(55e-9)
    assert red.idle_share("/device:TPU:0") == pytest.approx(0.45)
    total, exposed = red.op_s("/device:TPU:0", "all-to-all")
    assert total == pytest.approx(20e-9)
    assert exposed == pytest.approx(15e-9)
    assert red.gaps("/device:TPU:0") == [(0, 10), (45, 60), (80, 100)]


def test_idle_gaps_named_by_host_span():
    red = made_up()
    gaps = red.idle_gaps(2)
    assert gaps[0] == ["none", pytest.approx(20e-9)]
    assert gaps[1] == ["bench.next_batch", pytest.approx(15e-9)]
    assert red.top_ops(1)[0][0] == "fusion.1"


def test_recorded_trace():
    """Eight traced steps of ``deepseek-v2-lite-2l.shuffle-fed`` on one TPU
    v5 lite chip: the harness's spans bracket device work on one clock,
    and the reduction gives the numbers that run printed."""
    import gzip

    import jax

    from trace_reduce import reduce_profile

    raw = gzip.decompress((DATA / "trace.xplane.pb.gz").read_bytes())
    red = reduce_profile(jax.profiler.ProfileData.from_serialized_xspace(raw))
    assert red.devices == ["/device:TPU:0"]
    dev = red.devices[0]
    assert 0 < red.busy_s(dev) < red.window_s
    assert 0 < red.idle_share(dev) < 1
    names = {n for n, a, b in red.host}
    assert {"bench.window", "bench.next_batch", "bench.dispatch",
            "bench.block_loss"} <= names
    w0, w1 = red.window
    inside = [n for n, a, b in red.host
              if n == "bench.block_loss" and w0 <= a and b <= w1]
    assert len(inside) == 8          # the traced steps
    assert red.window_s == pytest.approx(1.773808352)
    assert red.busy_s(dev) == pytest.approx(1.733702706)
    ops = red.top_ops(10)
    assert len(ops) == 10 and all(t > 0 for _, t in ops)
    assert all(t > 0 for _, t in red.idle_gaps(3))
