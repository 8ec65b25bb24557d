"""The plain reference at test size on the CPU: against the program (the
same initial parameters from the seed, and the same float32 loss when the
program computes in float32 at full matmul precision); against its own
recorded steps, bit for bit; and the MoE rule a model type supplies
(router, router state, experts held) on a test-only type that uses only
that interface (``sigmoid_moe.py``), against loops over tokens."""

import dataclasses
import json

import numpy as np
import pytest

from conftest import tiny_cell
from reference.common import ROUTER_STATE


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


# --- the MoE rule a model type supplies ---------------------------------------

def digest(tree):
    """{leaf: [sha256 of its float32 bytes, first 16 hex digits, norm]}."""
    import hashlib

    return {k: [hashlib.sha256(np.ascontiguousarray(
        np.asarray(v, np.float32)).tobytes()).hexdigest()[:16],
        float(np.linalg.norm(v))] for k, v in tree.items()}


@pytest.mark.parametrize("config", ["deepseek-v2-tiny", "qwen2-moe-tiny"])
def test_reference_steps_are_bit_identical_to_the_recorded(config):
    """The two existing types through the router interface give the
    losses, first gradient and change recorded before it, bit for bit."""
    import jax

    import run
    from conftest import DATA

    want = json.loads((DATA / "reference_parity.json").read_text())
    cell = tiny_cell(config, "tiny-shuffle-fed")
    got = run.reference_steps(cell, want["seed"], 1, jax.devices()[0])
    assert [float(v).hex() for v in got.losses] == want[config]["losses"]
    assert got.state_leaves == frozenset()
    assert digest(got.grads) == want[config]["grads"]
    assert digest(got.change) == want[config]["change"]


SIGMOID = {
    "model_type": "sigmoid_moe", "hidden_size": 16,
    "num_attention_heads": 2, "vocab_size": 32, "num_hidden_layers": 2,
    "n_routed_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 8, "shared_intermediate_size": 12,
    "routed_scaling_factor": 2.5, "bias_update_rate": 0.01,
    "aux_loss_alpha": 0.01, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "experts_held": None,
}


def sigmoid_model(capacity_factor=1.0, **changes):
    import sigmoid_moe
    from reference.common import model_from

    return model_from(sigmoid_moe, dict(SIGMOID, **changes),
                      capacity_factor, 1)


def loop_route(logits, bias, k, scale, cap):
    """Sigmoid routing one token at a time: the ``k`` highest biased
    scores (lower expert first on ties), kept while the expert has fewer
    than ``cap`` earlier units, weighted by the unbiased scores over their
    sum times ``scale``; and each expert's load."""
    T, E = logits.shape
    sel = np.zeros((T, k), np.int32)
    keep = np.zeros((T, k), bool)
    w = np.zeros((T, k), np.float32)
    taken = [0] * E
    for t in range(T):
        s = [np.float32(1) / (np.float32(1) + np.exp(-logits[t, e]))
             for e in range(E)]
        chosen = []
        for _ in range(k):
            best = None
            for e in range(E):
                if e not in chosen and (best is None or s[e] + bias[e]
                                        > s[best] + bias[best]):
                    best = e
            chosen.append(best)
        total = sum(s[e] for e in chosen)
        for j, e in enumerate(chosen):
            sel[t, j] = e
            keep[t, j] = taken[e] < cap
            taken[e] += 1
            w[t, j] = s[e] / total * scale
    return sel, keep, w, np.array(taken, np.float32)


def loop_bias(bias, load, rate):
    mean = sum(load) / len(load)
    return np.array([b + rate * (1.0 if v < mean else -1.0 if v > mean
                                 else 0.0) for b, v in zip(bias, load)],
                    np.float32)


def test_sigmoid_type_routes_and_combines_as_a_loop():
    """Route, combine weights and the held experts' output of the test
    type against a loop over tokens, with a selection bias in the state
    and a capacity that drops units."""
    import jax
    import jax.numpy as jnp

    from reference.common import capacity, init_params, make_einsum, \
        moe_ffn, route, Numerics

    m = sigmoid_model(capacity_factor=0.75, experts_held=[2, 6])
    T, E, k = 40, 8, 3
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(T, E)).astype(np.float32)
    bias = (rng.normal(size=E) * 0.3).astype(np.float32)
    sel, keep, load = route(m.moe, logits, bias)
    cap = capacity(T * k, E, 0.75)
    want_sel, want_keep, want_w, want_load = loop_route(
        logits, bias, k, SIGMOID["routed_scaling_factor"], cap)
    np.testing.assert_array_equal(sel, want_sel)
    np.testing.assert_array_equal(keep, want_keep)
    np.testing.assert_array_equal(load, want_load)
    assert 0 < keep.sum() < keep.size
    _, combine, _ = m.moe.router(np, logits, bias)
    np.testing.assert_allclose(combine(sel), want_w, rtol=1e-6)

    params = init_params(m.layout, 11)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["ffn"])
    x = jnp.asarray(rng.normal(size=(T, 16)).astype(np.float32))
    router_logits = np.asarray(x @ p["router"])
    sel, keep, _ = route(m.moe, router_logits, bias)
    _, _, w, _ = loop_route(router_logits, bias, k,
                            SIGMOID["routed_scaling_factor"], cap)
    y, _ = moe_ffn(make_einsum(Numerics()), m.moe, p, x, sel, keep)
    xs = np.asarray(x, np.float64)
    want = np.zeros((T, 16))
    for t in range(T):
        for j in range(k):
            e = sel[t, j]
            if keep[t, j] and 2 <= e < 6:
                g = xs[t] @ np.asarray(p["we_gate"][e - 2], np.float64)
                u = xs[t] @ np.asarray(p["we_up"][e - 2], np.float64)
                h = g / (1 + np.exp(-g)) * u
                want[t] += w[t, j] * (h @ np.asarray(p["we_down"][e - 2],
                                                     np.float64))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-6)


def test_sigmoid_type_state_follows_the_loads_and_is_not_trained():
    """Over two steps the bias is moved by the sign rule of each step's
    loads and by nothing else (no AdamW, no weight decay), takes no
    gradient, and the first step's loads are the loop's."""
    from reference.common import Numerics, Opt, capacity, init_params, \
        router_inputs, train_steps
    from stream import step_batch

    m = sigmoid_model(capacity_factor=1.25)
    opt = Opt(learning_rate=3e-3, beta1=0.9, beta2=0.95, eps=1e-8,
              weight_decay=0.1, grad_clip=1.0, warmup_steps=0,
              total_steps=10, min_lr_frac=0.1)
    seed = 2147483931
    batches = [step_batch(seed, i, 32, 2, 16) for i in range(2)]
    got = train_steps(m, opt, seed, batches, trees=True)
    name = "blocks/ffn/router_state"
    assert got.state_leaves == frozenset({name})
    assert name not in got.grads
    assert got.grad_norms[got.leaf_names.index(name)] == 0.0

    params = init_params(m.layout, seed)
    logits = np.asarray(router_inputs(m, Numerics(), params,
                                      batches[0]["tokens"], []))
    *_, load0 = loop_route(logits, np.zeros(8, np.float32), 3,
                           SIGMOID["routed_scaling_factor"],
                           capacity(32 * 3, 8, 1.25))
    np.testing.assert_array_equal(got.loads[0][0], load0)

    rate = SIGMOID["bias_update_rate"]
    for layer in range(2):
        bias = np.zeros(8, np.float32)
        for step in range(2):
            bias = loop_bias(bias, got.loads[step][layer], rate)
        np.testing.assert_array_equal(got.change[name][layer], bias)
    assert np.abs(got.change[name]).max() > 0


def test_four_holders_add_up_to_the_uncut_layer():
    """Four holders of a quarter of the experts each, with the shared
    expert counted once, give the uncut layer's output, and through the
    head its loss, to float32 rounding; each holder's load-balance loss
    covers every routed expert and equals the uncut one."""
    import jax
    import jax.numpy as jnp

    from reference.common import (Numerics, aux_loss, init_params,
                                  make_einsum, moe_ffn, rms_norm, route,
                                  swiglu)

    es = make_einsum(Numerics())
    full = sigmoid_model(capacity_factor=1.0)
    params = init_params(full.layout, 5)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["ffn"])
    rng = np.random.default_rng(7)
    T = 64
    x = jnp.asarray(rng.normal(size=(T, 16)).astype(np.float32))
    bias = (rng.normal(size=8) * 0.2).astype(np.float32)
    p[ROUTER_STATE] = jnp.asarray(bias)
    sel, keep, load = route(full.moe, np.asarray(x @ p["router"]), bias)
    assert not keep.all()

    def shared(z):
        s = p["shared"]
        return swiglu(es, z, s["w_gate"], s["w_up"], s["w_down"])
    y_full, probs = moe_ffn(es, full.moe, p, x, sel, keep)
    aux_full = aux_loss(full.moe, jnp.sum(probs, 0), load, T)
    y_parts = 0.0
    for lo in (0, 2, 4, 6):
        part = sigmoid_model(capacity_factor=1.0, experts_held=[lo, lo + 2])
        ph = dict(p, **{w: p[w][lo:lo + 2]
                        for w in ("we_gate", "we_up", "we_down")})
        y, probs_h = moe_ffn(es, part.moe, ph, x, sel, keep)
        y_parts = y_parts + y
        assert float(aux_loss(part.moe, jnp.sum(probs_h, 0), load, T)) \
            == float(aux_full)
    y_full = x + y_full + shared(x)
    y_parts = x + y_parts + shared(x)
    np.testing.assert_allclose(np.asarray(y_parts), np.asarray(y_full),
                               rtol=1e-5, atol=1e-6)

    labels = jnp.asarray(rng.integers(0, 32, size=T))

    def loss(h):
        logits = es("td,dv->tv", rms_norm(h, params["final_norm"], 1e-6),
                    params["embed"]["unembed"])
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return float(jnp.mean(lse - jnp.take_along_axis(
            logits, labels[:, None], -1)[:, 0]))
    assert loss(y_parts) == pytest.approx(loss(y_full), rel=1e-6)
