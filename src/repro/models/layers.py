"""Basic layers: norms, MLPs, embeddings — pure functions over param dicts."""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.common import ArraySpec, ModelConfig
from repro.obs.scopes import scope


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with f32 statistics but the large elementwise product in the
    input dtype — keeps activations (and their cotangents) bf16, which is
    what lets GSPMD move bf16 instead of f32 across the mesh (§Perf)."""
    dtype = x.dtype
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(dtype)
    w = (1.0 + weight.astype(jnp.float32)).astype(dtype)
    return x * inv * w


def norm_defs(d: int, *, stacked: int = 0) -> ArraySpec:
    shape = (stacked, d) if stacked else (d,)
    axes = ("layers", "embed") if stacked else ("embed",)
    return ArraySpec(shape, jnp.float32, axes, init="zeros")


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain GELU)
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: int, *, stacked: int = 0) -> dict:
    d = cfg.d_model
    L = (stacked,) if stacked else ()
    la = ("layers",) if stacked else ()
    pd = cfg.param_dtype
    if cfg.mlp in ("swiglu", "geglu"):
        return {
            "w_gate": ArraySpec(L + (d, d_ff), pd, la + ("embed", "mlp")),
            "w_up": ArraySpec(L + (d, d_ff), pd, la + ("embed", "mlp")),
            "w_down": ArraySpec(L + (d_ff, d), pd, la + ("mlp", "embed")),
        }
    return {  # plain gelu MLP (hubert-style encoder FFN)
        "w_up": ArraySpec(L + (d, d_ff), pd, la + ("embed", "mlp")),
        "b_up": ArraySpec(L + (d_ff,), pd, la + ("mlp",), init="zeros"),
        "w_down": ArraySpec(L + (d_ff, d), pd, la + ("mlp", "embed")),
        "b_down": ArraySpec(L + (d,), pd, la + ("embed",), init="zeros"),
    }


@scope("ffn")
def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    cd = cfg.compute_dtype
    x = x.astype(cd)
    if cfg.mlp in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp == "swiglu" else (
            lambda v: jax.nn.gelu(v, approximate=True))
        g = act(x @ p["w_gate"].astype(cd))
        u = x @ p["w_up"].astype(cd)
        return (g * u) @ p["w_down"].astype(cd)
    h = jax.nn.gelu(x @ p["w_up"].astype(cd) + p["b_up"].astype(cd))
    return h @ p["w_down"].astype(cd) + p["b_down"].astype(cd)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig) -> dict:
    out = {"tok": ArraySpec((cfg.vocab_size, cfg.d_model), cfg.param_dtype,
                            ("vocab", "embed"), init="small")}
    if not cfg.tie_embeddings:
        out["unembed"] = ArraySpec((cfg.d_model, cfg.vocab_size),
                                   cfg.param_dtype, ("embed", "vocab"))
    return out


def embed_apply(cfg: ModelConfig, p: dict, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.compute_dtype)
    return x


def unembed_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    cd = cfg.compute_dtype
    if cfg.tie_embeddings:
        return x.astype(cd) @ p["tok"].astype(cd).T
    return x.astype(cd) @ p["unembed"].astype(cd)
