"""Per-architecture smoke tests: reduced config of the same family, one
forward / train / decode step on CPU, asserting shapes + no NaNs."""


import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import lm
from repro.models.common import applicable_shapes, init_params
from repro.training import OptConfig, TrainConfig, adamw_init, make_train_step


def make_batch(cfg, B, S, key, labels=False):
    ks = jax.random.split(key, 3)
    mm = cfg.multimodal
    if mm is not None and mm.kind == "audio":
        batch = {"frames": jax.random.normal(
            ks[0], (B, S, cfg.d_model), jnp.bfloat16)}
    elif mm is not None and mm.kind == "vision":
        P = mm.num_patches
        batch = {"tokens": jax.random.randint(
            ks[0], (B, S - P), 0, cfg.vocab_size),
            "patches": jax.random.normal(
                ks[1], (B, P, cfg.d_model), jnp.bfloat16)}
    else:
        batch = {"tokens": jax.random.randint(
            ks[0], (B, S), 0, cfg.vocab_size)}
    if labels:
        batch["labels"] = jax.random.randint(ks[2], (B, S), 0,
                                             cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_smoke(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(lm.param_defs(cfg), jax.random.key(0))
    B, S = 2, 32
    batch = make_batch(cfg, B, S, jax.random.key(1))
    logits, aux, dropped = lm.forward(cfg, params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not jnp.isnan(logits).any()
    assert not jnp.isnan(aux)
    assert int(dropped) >= 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(lm.param_defs(cfg), jax.random.key(0))
    opt = adamw_init(params)
    tcfg = TrainConfig(opt=OptConfig(learning_rate=1e-3), microbatches=1,
                       remat="full")
    step = make_train_step(cfg, tcfg)
    B, S = 2, 32
    batch = make_batch(cfg, B, S, jax.random.key(1), labels=True)
    params2, opt2, metrics = jax.jit(step)(params, opt, batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    assert metrics["grad_norm"] > 0
    # parameters actually moved
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if get_config(a).has_decode])
def test_decode_smoke(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(lm.param_defs(cfg), jax.random.key(0))
    B, MAXS = 2, 16
    cache = jax.tree.map(
        jnp.zeros_like,
        init_params(lm.cache_defs(cfg, B, MAXS), jax.random.key(1)))
    toks = jnp.ones((B, 1), jnp.int32)
    for t in range(3):
        logits, cache = lm.decode_step(
            cfg, params, cache, {"tokens": toks, "pos": jnp.int32(t)})
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert not jnp.isnan(logits).any()


def test_encoder_has_no_decode_shapes():
    cfg = get_config("hubert-xlarge")
    names = {s.name for s in applicable_shapes(cfg)}
    assert names == {"train_4k", "prefill_32k"}


def test_full_attention_archs_skip_long():
    for arch in ("starcoder2-3b", "gemma-2b", "qwen2-72b",
                 "deepseek-v2-lite-16b", "llava-next-34b"):
        names = {s.name for s in applicable_shapes(get_config(arch))}
        assert "long_500k" not in names


def test_sub_quadratic_archs_run_long():
    for arch in ("mamba2-130m", "zamba2-2.7b"):
        names = {s.name for s in applicable_shapes(get_config(arch))}
        assert "long_500k" in names


def test_exact_assigned_configs():
    """The full configs match the assignment table exactly."""
    c = get_config("qwen2-72b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff,
            c.vocab_size) == (80, 8192, 64, 8, 29568, 152064)
    c = get_config("deepseek-v2-lite-16b")
    assert (c.moe.num_experts, c.moe.top_k, c.moe.num_shared) == (64, 6, 2)
    assert c.mla.kv_lora_rank == 512
    c = get_config("gemma-2b")
    assert (c.num_kv_heads, c.resolved_head_dim, c.vocab_size) == \
        (1, 256, 256000)
    c = get_config("zamba2-2.7b")
    assert (c.num_layers, c.ssm.d_state) == (54, 64)
    c = get_config("qwen2-moe-a2.7b")
    assert (c.moe.num_experts, c.moe.top_k, c.moe.num_shared) == (60, 4, 4)
    c = get_config("hubert-xlarge")
    assert c.kind == "encoder" and c.vocab_size == 504
