"""The training path's names in a profiler trace (``repro.obs.scopes``)
and the dropped-units counter:

* ``layer_of_ops`` on a compiled tiny MoE train step finds every layer
  and puts the AdamW update in ``optimizer``;
* the input pipeline's host spans are recorded once per call, nested in
  ``input.next_batch``, under ``jax.profiler.trace`` on the CPU;
* a router that sends every unit to one expert drops ``U - cap`` of
  them, the count numpy gives for the same routing, and the count
  reaches the train step's metrics.
"""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import AsyncShuffleEngine, BlobShuffleConfig, EngineConfig
from repro.core.stores import SimulatedS3
from repro.models import lm
from repro.models.common import init_params
from repro.obs.scopes import LAYERS, OTHER, SPANS, layer_of_ops, scope, span
from repro.shuffle.api import dense_moe_ffn
from repro.shuffle.dispatch import _cap
from repro.train_input import ShuffleFedInput, TokenStreamConfig
from repro.training import (TrainConfig, adamw_init, make_loss_fn,
                            make_train_step)

ARCH = "deepseek-v2-lite-16b"      # MLA, routed and shared experts, a
#                                    dense first block


@pytest.fixture(scope="module")
def tiny_step():
    """(config, params, opt state, batch, compiled step's HLO text)."""
    cfg = get_config(ARCH, smoke=True)
    params = init_params(lm.param_defs(cfg), jax.random.key(0))
    opt = adamw_init(params)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    step = jax.jit(make_train_step(cfg, TrainConfig()))
    hlo = step.lower(params, opt, batch).compile().as_text()
    return cfg, params, opt, batch, hlo


def test_layer_of_ops_finds_every_layer(tiny_step):
    *_, hlo = tiny_step
    layer_of = layer_of_ops(hlo)
    found = set(layer_of.values())
    assert set(LAYERS) <= found and found <= set(LAYERS) | {OTHER}


def test_adamw_update_is_in_optimizer(tiny_step):
    """Every new parameter, moment and the step count (the outputs ahead
    of the metrics in the entry's root tuple) comes from the optimizer."""
    _, params, *_, hlo = tiny_step
    layer_of = layer_of_ops(hlo)
    entry = hlo[hlo.index("\nENTRY"):]
    root = next(line for line in entry.splitlines()
                if line.strip().startswith("ROOT"))
    outs = re.findall(r"%([\w.\-]+)", root.split(" tuple(", 1)[1])
    n = len(jax.tree.leaves(params))
    assert len(outs) == 3 * n + 1 + 5       # params, count, m, v; metrics
    assert {layer_of[o] for o in outs[:3 * n + 1]} == {"optimizer"}


def test_layer_of_ops_reads_metadata_and_called_computations():
    hlo = "\n".join([
        "%fused_computation (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        "  %a = f32[4]{0} exp(%p), "
        'metadata={op_name="jit(f)/moe_experts/exp"}',
        '  ROOT %b = f32[4]{0} log(%a), '
        'metadata={op_name="jit(f)/transpose(jvp(attention))/log"}',
        "  %c = f32[4]{0} sine(%p), "
        'metadata={op_name="jit(f)/moe_dispatch/moe_experts/sin"}',
        "}",
        "",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        "  %x = f32[4]{0} parameter(0), metadata={op_name=\"params['ffn']\"}",
        "  ROOT %fusion = f32[4]{0} fusion(%x), kind=kLoop, "
        "calls=%fused_computation",
        "}",
    ])
    layer_of = layer_of_ops(hlo)
    assert layer_of["a"] == "moe_experts"
    assert layer_of["b"] == "attention"        # inside a transform's name
    assert layer_of["c"] == "moe_experts"      # the last layer in the path
    assert layer_of["fusion"] == "moe_experts"  # the majority it calls
    assert layer_of["x"] == OTHER               # an argument path
    assert layer_of["p"] == OTHER


def test_names_are_declared():
    with pytest.raises(ValueError):
        scope("attn")
    with pytest.raises(ValueError):
        span("input.decode")
    assert len(set(SPANS)) == len(SPANS)


# -- host spans ------------------------------------------------------------


def _spans(trace_dir):
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in SPANS]


@pytest.mark.parametrize("with_mesh", [False, True])
def test_input_spans_once_per_call_inside_next_batch(tmp_path, with_mesh):
    from repro.launch import make_test_mesh

    cfg = get_config(ARCH, smoke=True)
    stream = TokenStreamConfig(vocab_size=cfg.vocab_size, batch=4,
                               seq_len=16, seed=3)
    engine = AsyncShuffleEngine(
        BlobShuffleConfig(batch_bytes=4 * 68, max_interval_s=0.02,
                          num_partitions=5, num_az=3),
        EngineConfig(commit_interval_s=0.05), n_instances=2,
        store=SimulatedS3(seed=1), seed=2, exactly_once=True)
    mesh = make_test_mesh(devices=1) if with_mesh else None
    pipe = ShuffleFedInput(engine, stream, steps=6, step_interval_s=0.05,
                           mesh=mesh, model_cfg=cfg)
    pipe.submit()
    calls = 4
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(calls):
            pipe.next_batch()
    spans = _spans(tmp_path)
    count = {n: sum(1 for s in spans if s[0] == n) for n in SPANS}
    assert count["input.next_batch"] == calls
    assert count["input.assemble"] == calls
    assert count["input.device_put"] == (calls if with_mesh else 0)
    # one drain per engine advance, and the engine advanced
    assert count["input.advance"] == count["input.drain"] >= 1
    outer = [s for s in spans if s[0] == "input.next_batch"]
    for name, a, b in spans:
        if name != "input.next_batch":
            assert any(c <= a and b <= d for _, c, d in outer), name


# -- dropped units ---------------------------------------------------------


def _numpy_dropped(x, w_router, top_k, cap):
    """Units past capacity for the router's top-k experts by softmax
    probability, ties to the lower expert (as ``lax.top_k``)."""
    logits = np.asarray(x, np.float32) @ np.asarray(w_router, np.float32)
    probs = np.exp(logits - logits.max(axis=1, keepdims=True))
    experts = np.argsort(-probs, axis=1, kind="stable")[:, :top_k]
    counts = np.bincount(experts.ravel(), minlength=w_router.shape[1])
    return int(np.maximum(counts - cap, 0).sum())


@pytest.mark.parametrize("top_k", [1, 2])
def test_one_expert_router_drops_all_but_capacity(top_k):
    T, d, E, de = 64, 16, 8, 8
    ks = jax.random.split(jax.random.key(0), 4)
    x = jnp.abs(jax.random.normal(ks[0], (T, d))) + 0.1
    # positive inputs, one column far ahead of the rest: expert 0 first
    w_router = jax.random.normal(ks[1], (d, E)) * 0.1
    w_router = w_router.at[:, 0].set(10.0)
    we = jax.random.normal(ks[2], (E, d, de)) * 0.1
    wd = jax.random.normal(ks[3], (E, de, d)) * 0.1
    _, _, load, dropped = dense_moe_ffn(x, w_router, we, we, wd,
                                        top_k=top_k, capacity_factor=1.25)
    U = T * top_k
    cap = _cap(U / E, 1.25)
    assert int(load[0]) == T
    assert int(dropped) == _numpy_dropped(x, w_router, top_k, cap)
    if top_k == 1:
        assert int(dropped) == U - cap


def test_dropped_units_reach_the_step_metrics(tiny_step):
    """The step reports the units its forward dropped, summed over the
    MoE layers."""
    cfg, params, opt, batch, _ = tiny_step
    moe_cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.5))
    _, aux = make_loss_fn(moe_cfg, TrainConfig())(params, batch)
    _, _, metrics = jax.jit(make_train_step(moe_cfg, TrainConfig()))(
        params, opt, batch)
    assert metrics["dropped_units"].dtype == jnp.int32
    assert int(metrics["dropped_units"]) == int(aux["dropped_units"]) > 0
