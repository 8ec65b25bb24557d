"""The plain reference against the program at test size on the CPU: the
same initial parameters from the seed, and the same float32 loss when the
program computes in float32 at full matmul precision."""

import dataclasses

import numpy as np
import pytest

from conftest import tiny_cell


@pytest.mark.parametrize("config", ["deepseek-v2-tiny", "qwen2-moe-tiny"])
def test_reference_matches_program_in_float32(config):
    import jax
    import jax.numpy as jnp

    import run
    from reference.common import (Numerics, block_loss, init_params,
                                  model_from, route, router_inputs)
    from repro.models import init_params as program_init, lm
    from repro.training.train_step import make_loss_fn
    from stream import step_batch

    cell = tiny_cell(config, "tiny-shuffle-fed")
    hf, t = cell.config, cell.traffic
    model_cfg, tcfg, _, _ = run.program_config(cell)
    model_cfg = dataclasses.replace(model_cfg, compute_dtype=jnp.float32)
    seed = 2147483911
    batch = step_batch(seed, 0, hf["vocab_size"], t["batch"], t["seq_len"])

    mine = init_params(cell.module("reference").layout(hf), seed)
    theirs = program_init(lm.param_defs(model_cfg), jax.random.key(seed))
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)

    with jax.default_matmul_precision("highest"):
        want = float(make_loss_fn(model_cfg, tcfg)(theirs, batch)[1]["loss"])
    m = model_from(cell.module("reference"), hf, t["capacity_factor"], 1)
    num = Numerics()
    routes, loads = [], []
    for _ in range(m.n_moe):
        sel, keep, load = route(m.moe, np.asarray(router_inputs(
            m, num, mine, batch["tokens"], routes)))
        routes.append((sel, keep))
        loads.append(load)
    _, ce = block_loss(m, num, mine, batch["tokens"], batch["labels"],
                       routes, loads, t["batch"])
    assert float(ce) == pytest.approx(want, rel=2e-6)


def test_yarn_is_plain_rope_at_factor_one_and_scales_at_the_published():
    import json
    import math

    from cell import BENCH
    from reference.common import rope_freqs
    from reference.deepseek_v2 import yarn

    hf = json.loads((BENCH / "configs" / "deepseek-v2-lite-2l.json")
                    .read_text())
    plain = rope_freqs(64, 10000)
    inv, rotated, softmax = yarn(hf)
    assert hf["rope_scaling"]["factor"] == 1
    np.testing.assert_array_equal(inv, plain)
    assert (rotated, softmax) == (1.0, 1.0)

    published = hf["reduced"]["rope_scaling"][0]
    inv, rotated, softmax = yarn(dict(hf, rope_scaling=published))
    # dims 0..9 rotate more than beta_fast times over 4096 positions and
    # keep their frequency; dims from 23 on rotate under beta_slow times
    # and are interpolated by the factor 40
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(inv[11:23] < plain[11:23])
    assert rotated == 1.0
    assert softmax == pytest.approx((0.1 * 0.707 * math.log(40) + 1) ** 2)
