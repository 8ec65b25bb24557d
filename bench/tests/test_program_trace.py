"""The program's layers, spans and counter read from a trace
(``program_trace.py``): on a made-up trace, where the layer times
partition the busy time and the idle split sums to the gaps, and on a
trace recorded on a TPU v5 lite chip by ``record_trace.py``
(``data/program_trace.*``: eight steps of the deepseek cell with the
program's scopes and spans, the step's layer map and dropped units); and
the readers of ``metrics/`` over what ``run.read_trace`` keeps of it."""

import gzip
import json
import pathlib

import pytest

from program_trace import (LAYERS, idle_by_span, instruction, layer_s,
                           metrics, program_spans)
from trace_reduce import Reduced, reduce_profile

DEV = "/device:TPU:0"
DATA = pathlib.Path(__file__).resolve().parent / "data"


def made_up():
    """Two steps of 100 ns. In each the host dispatches the step (0-65),
    blocks on it (5-65), reads its loss (65-70) and runs the input
    (70-99); the device runs attention (10-30), the optimizer (30-50) and
    an operation outside every layer (55-60)."""
    ops, host, spans = [], [("bench.window", 0, 200)], []
    for t in (0, 100):
        ops += [(f"fusion.{t + 1} bf16[4]", t + 10, t + 30),
                (f"fusion.{t + 2} f32[8]", t + 30, t + 50),
                (f"copy.{t + 3} f32[8]", t + 55, t + 60)]
        host += [("bench.block_loss", t + 5, t + 65),
                 ("bench.next_batch", t + 65, t + 100)]
        spans += [("train.dispatch", t + 0, t + 65),
                  ("train.sync", t + 65, t + 70),
                  ("input.next_batch", t + 70, t + 99),
                  ("input.advance", t + 72, t + 90),
                  ("input.drain", t + 90, t + 96),
                  ("input.assemble", t + 96, t + 98)]
    layer_of = {"fusion.1": "attention", "fusion.101": "attention",
                "fusion.2": "optimizer", "fusion.102": "optimizer"}
    return Reduced((0, 200), {DEV: ops}, host), spans, layer_of


def test_layer_times_partition_the_busy_time():
    red, _, layer_of = made_up()
    got = layer_s(red, DEV, layer_of)
    assert set(got) == set(LAYERS)
    assert got["attention"] == pytest.approx(40e-9)
    assert got["optimizer"] == pytest.approx(40e-9)
    assert got["other"] == pytest.approx(10e-9)
    assert sum(got.values()) == pytest.approx(red.busy_s(DEV))


def test_idle_split_sums_to_the_gaps():
    red, spans, _ = made_up()
    idle = idle_by_span(red, spans)
    gaps = sum(b - a for a, b in red.gaps(DEV)) * 1e-9
    assert sum(idle.values()) == pytest.approx(gaps)
    # per step: the device waits 0-5 on the dispatch, 5-10, 50-55 and
    # 60-65 on the step, 65-70 on the loss read, 70-99 on the input, and
    # 99-100 on the harness
    assert idle["train.dispatch"] == pytest.approx(2 * 5e-9)
    assert idle["bench.block_loss"] == pytest.approx(2 * 15e-9)
    assert idle["train.sync"] == pytest.approx(2 * 5e-9)
    assert idle["input.next_batch"] == pytest.approx(2 * 3e-9)
    assert idle["input.advance"] == pytest.approx(2 * 18e-9)
    assert idle["input.drain"] == pytest.approx(2 * 6e-9)
    assert idle["input.assemble"] == pytest.approx(2 * 2e-9)
    assert idle["bench.next_batch"] == pytest.approx(2 * 1e-9)


def test_metrics_per_step():
    red, spans, layer_of = made_up()
    got = metrics(red, spans, layer_of, steps=2, dropped=[3, 5],
                  routed_units=100)
    busy_ms = red.busy_s(DEV) / 2 * 1e3
    assert sum(got[f"step.{k}_ms"] for k in LAYERS) == pytest.approx(busy_ms)
    assert got["step.attention_ms"] == pytest.approx(20e-6)
    assert got["input.engine_ms"] == pytest.approx(18e-6)
    assert got["input.decode_ms"] == pytest.approx(6e-6)
    assert got["input.assemble_ms"] == pytest.approx(2e-6)
    assert got["device.idle_wait_ms"] == pytest.approx((15 + 5) * 1e-6)
    assert got["device.idle_input_ms"] == pytest.approx(
        (3 + 18 + 6 + 2) * 1e-6)
    assert got["moe.dropped_share"] == pytest.approx(4.0)


@pytest.fixture(scope="module")
def recorded():
    import jax

    raw = gzip.decompress((DATA / "program_trace.xplane.pb.gz").read_bytes())
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    layer_of = json.loads((DATA / "program_trace.layers.json").read_text())
    counter = json.loads((DATA / "program_trace.json").read_text())
    return reduce_profile(data), program_spans(data), layer_of, counter


def test_recorded_trace_layers(recorded):
    red, spans, layer_of, counter = recorded
    got = metrics(red, spans, layer_of, 8, counter["dropped_units"],
                  counter["routed_units_per_step"])
    device_ms = red.busy_s(DEV) / 8 * 1e3
    layers = {k: got[f"step.{k}_ms"] for k in LAYERS}
    assert sum(layers.values()) == pytest.approx(device_ms, rel=5e-3)
    assert layers["other"] <= 0.1 * device_ms
    # AdamW on the three routed-expert weights; the scatter into and the
    # gathers from the capacity bins
    for name, _, _ in red.ops[DEV]:
        shape = name.partition(" ")[2]
        if name.startswith("fusion.") and shape in (
                "f32[1,64,1408,2048]", "f32[1,64,2048,1408]"):
            assert layer_of[instruction(name)] == "optimizer", name
        if name.startswith("fusion.") and shape in (
                "bf16[61440,2048]", "bf16[61441,2048]"):
            assert layer_of[instruction(name)] == "moe_dispatch", name
    assert layers["optimizer"] >= 21 and layers["moe_dispatch"] >= 18
    assert layers == pytest.approx({
        "attention": 45.78329075, "moe_dispatch": 55.94696525,
        "moe_experts": 30.592178625, "ffn": 28.458278125,
        "head": 18.72618675, "optimizer": 29.129483875,
        "other": 8.912655875}, rel=1e-9)


def test_recorded_trace_input_and_idle(recorded):
    red, spans, layer_of, counter = recorded
    got = metrics(red, spans, layer_of, 8, counter["dropped_units"],
                  counter["routed_units_per_step"])
    w0, w1 = red.window
    host = [b - a for n, a, b in red.host
            if n == "bench.next_batch" and w0 <= a and b <= w1]
    input_host_ms = sum(host) / len(host) * 1e-6
    assert (got["input.engine_ms"] + got["input.decode_ms"]
            + got["input.assemble_ms"]) <= input_host_ms
    idle_ms = (red.window_s - red.busy_s(DEV)) / 8 * 1e3
    assert got["device.idle_input_ms"] + got["device.idle_wait_ms"] <= idle_ms
    assert {k: got[k] for k in (
        "input.engine_ms", "input.decode_ms", "input.assemble_ms",
        "device.idle_input_ms", "device.idle_wait_ms", "moe.dropped_share")
    } == pytest.approx({
        "input.engine_ms": 0.5821975, "input.decode_ms": 0.01457625,
        "input.assemble_ms": 0.202664, "device.idle_input_ms": 0.815793625,
        "device.idle_wait_ms": 2.840629375,
        "moe.dropped_share": 53.576151529947914}, rel=1e-9)


# --- the readers of metrics/ over what a traced run keeps ----------------------

#: the per-layer metrics read from the program's layers, spans and counter
READERS = ["step.attention_ms", "step.moe_dispatch_ms", "step.moe_experts_ms",
           "step.ffn_ms", "step.head_ms", "step.optimizer_ms",
           "step.other_ms", "device.idle_input_ms", "device.idle_wait_ms",
           "moe.dropped_share"]
@pytest.fixture(scope="module")
def traced(recorded, tmp_path_factory):
    """``run.read_trace`` over the recorded profile, counter and layer map
    (in place of the HLO text, which the recording does not keep): its
    metrics and the readers' ``ctx``."""
    import types

    import numpy as np

    import program_trace
    import run
    from cell import load_cell, peaks_of

    _, _, layer_of, counter = recorded
    trace_dir = tmp_path_factory.mktemp("trace")
    (trace_dir / "run.xplane.pb").write_bytes(
        gzip.decompress((DATA / "program_trace.xplane.pb.gz").read_bytes()))
    cell = load_cell("deepseek-v2-lite-2l.shuffle-fed")
    step = types.SimpleNamespace(
        traced=(5, 12), dropped=[np.int32(v) for v in counter["dropped_units"]])
    keep = {}
    plain_dir, plain_map = run.TRACE_DIR, program_trace.layer_of_ops
    run.TRACE_DIR = trace_dir
    program_trace.layer_of_ops = lambda hlo: layer_of
    try:
        got, extra = run.read_trace(cell, step, peaks_of("TPU v5 lite"), 1,
                                    "", counter["routed_units_per_step"],
                                    keep)
    finally:
        run.TRACE_DIR, program_trace.layer_of_ops = plain_dir, plain_map
    assert not trace_dir.exists()
    return got, keep["ctx"], extra


def test_readers_give_what_metrics_gives(recorded, traced):
    red, spans, layer_of, counter = recorded
    want = metrics(red, spans, layer_of, 8, counter["dropped_units"],
                   counter["routed_units_per_step"])
    got, ctx, _ = traced
    assert ctx["dropped"] == counter["dropped_units"]
    assert set(READERS) <= set(got)
    assert {k: got[k] for k in READERS} == {k: want[k] for k in READERS}
    layers = sum(got[f"step.{k}_ms"] for k in LAYERS)
    assert layers == pytest.approx(got["step.device_ms"], rel=5e-3)


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_its_key(traced, name):
    from cell import metric_reader

    _, ctx, _ = traced
    read = metric_reader(name)
    assert read(ctx) == ctx["program"][name]
    assert read({k: v for k, v in ctx.items() if k != "program"}) is None
    lacking = {k: v for k, v in ctx["program"].items() if k != name}
    assert read(dict(ctx, program=lacking)) is None


def test_no_dropped_share_without_counter_or_routed_units(recorded):
    red, spans, layer_of, counter = recorded
    for dropped, routed in ((None, counter["routed_units_per_step"]),
                            (counter["dropped_units"], None)):
        got = metrics(red, spans, layer_of, 8, dropped, routed)
        assert "moe.dropped_share" not in got
        assert set(READERS) - set(got) == {"moe.dropped_share"}


def test_every_per_layer_metric_has_a_reader():
    from cell import BENCH, load_cell, metric_reader

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert set(READERS) <= set(names)
    for name in names:
        assert (BENCH / "metrics" / f"{name}.py").is_file(), name
        assert callable(metric_reader(name))
    # the program's layers are read in every cell, present and to come
    for w in bench["workloads"]:
        per_layer = {m["name"] for m in load_cell(w["name"]).per_layer}
        assert set(READERS) <= per_layer, w["name"]


def test_layer_map_finds_the_programs_scopes(deepseek_tiny):
    """The yardstick's layer map (``program_trace.layer_of_ops``) knows the
    program's scope names and, on a compiled train step of the tiny
    deepseek type, finds each of them and ``other``."""
    import jax

    import program_trace
    import run
    from repro.models import init_params, lm
    from repro.obs.scopes import LAYERS as SCOPE_NAMES
    from repro.training import adamw_init, make_train_step
    from stream import step_batch

    assert set(program_trace.SCOPES) == set(SCOPE_NAMES)
    model_cfg, tcfg, _, _ = run.program_config(deepseek_tiny)
    params = init_params(lm.param_defs(model_cfg), jax.random.key(1))
    t = deepseek_tiny.traffic
    batch = step_batch(1, 0, model_cfg.vocab_size, t["batch"], t["seq_len"])
    hlo = jax.jit(make_train_step(model_cfg, tcfg)).lower(
        params, adamw_init(params), batch).compile().as_text()
    assert set(program_trace.layer_of_ops(hlo).values()) == set(LAYERS)
