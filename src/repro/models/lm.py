"""Model assembly: decoder / encoder / SSM / hybrid LMs with scan-over-layers.

Parameters are stacked along a leading ``layers`` axis so the HLO stays O(1)
in depth (essential for 80-layer dry-runs and 1000-node compile times).

Public surface:
  * ``param_defs(cfg)``                         — ArraySpec tree
  * ``forward(cfg, params, batch, ...)``        — logits + aux (train/prefill)
  * ``cache_defs(cfg, batch, max_seq)``         — decode cache ArraySpec tree
  * ``decode_step(cfg, params, cache, batch)``  — one-token serve step
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.common import ArraySpec, ModelConfig
from repro.models.flash import NO_HINTS, ShardHints
from repro.obs.scopes import scope
from repro.shuffle.api import ShuffleConfig

DENSE = ShuffleConfig(mode="dense")


# ---------------------------------------------------------------------------
# Block definitions
# ---------------------------------------------------------------------------

def _attn_defs(cfg: ModelConfig, *, stacked: int = 0) -> dict:
    if cfg.mla is not None:
        return MLA.mla_defs(cfg, stacked=stacked)
    return A.attention_defs(cfg, stacked=stacked)


def _moe_layers(cfg: ModelConfig) -> int:
    if cfg.moe is None:
        return 0
    return cfg.num_layers - cfg.moe.first_dense_layers


def block_defs(cfg: ModelConfig, *, stacked: int, ffn: str) -> dict:
    """One transformer block (attention + FFN). ffn: mlp | moe | dense_moe."""
    out = {"ln1": L.norm_defs(cfg.d_model, stacked=stacked),
           "attn": _attn_defs(cfg, stacked=stacked),
           "ln2": L.norm_defs(cfg.d_model, stacked=stacked)}
    if ffn == "moe":
        out["ffn"] = MOE.moe_defs(cfg, stacked=stacked)
    elif ffn == "dense_moe":  # leading dense layers of a MoE model
        out["ffn"] = L.mlp_defs(cfg, cfg.moe.dense_d_ff, stacked=stacked)
    else:
        out["ffn"] = L.mlp_defs(cfg, cfg.d_ff, stacked=stacked)
    return out


def ssm_block_defs(cfg: ModelConfig, *, stacked: int) -> dict:
    return {"ln": L.norm_defs(cfg.d_model, stacked=stacked),
            "mamba": SSM.mamba2_defs(cfg, stacked=stacked)}


def param_defs(cfg: ModelConfig) -> dict:
    defs: Dict[str, Any] = {"embed": L.embed_defs(cfg)}
    if cfg.kind in ("decoder", "encoder"):
        if cfg.moe is not None and cfg.moe.first_dense_layers:
            defs["dense_blocks"] = block_defs(
                cfg, stacked=cfg.moe.first_dense_layers, ffn="dense_moe")
            defs["blocks"] = block_defs(
                cfg, stacked=_moe_layers(cfg), ffn="moe")
        else:
            defs["blocks"] = block_defs(
                cfg, stacked=cfg.num_layers,
                ffn="moe" if cfg.moe is not None else "mlp")
    elif cfg.kind == "ssm":
        defs["blocks"] = ssm_block_defs(cfg, stacked=cfg.num_layers)
    elif cfg.kind == "hybrid":
        h = cfg.hybrid
        n_inv = cfg.num_layers // h.shared_block_every
        defs["blocks"] = ssm_block_defs(cfg, stacked=cfg.num_layers)
        defs["shared_block"] = block_defs(cfg, stacked=0, ffn="mlp")
        concat_dim = 2 * cfg.d_model if h.concat_embed else cfg.d_model
        defs["shared_in"] = ArraySpec(
            (n_inv, concat_dim, cfg.d_model), cfg.param_dtype,
            ("stack", "embed", None))
    else:
        raise ValueError(cfg.kind)
    defs["final_norm"] = L.norm_defs(cfg.d_model)
    return defs


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

@scope("head")
def _embed_inputs(cfg: ModelConfig, params, batch) -> jax.Array:
    """Token / multimodal / stub-frontend embedding. Returns (B, S, d)."""
    if cfg.multimodal is not None and cfg.multimodal.kind == "audio":
        # hubert: precomputed frame embeddings from the stub frontend
        x = batch["frames"].astype(cfg.compute_dtype)
        S = x.shape[1]
        pos = _sinusoidal(S, cfg.d_model, x.dtype)
        return x + pos[None]
    tok = L.embed_apply(cfg, params["embed"], batch["tokens"])
    if cfg.multimodal is not None and cfg.multimodal.kind == "vision":
        patches = batch["patches"].astype(cfg.compute_dtype)
        return jnp.concatenate([patches, tok], axis=1)
    return tok


def _sinusoidal(S: int, d: int, dtype) -> jax.Array:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    out = jnp.zeros((S, d), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(ang))
    out = out.at[:, 1::2].set(jnp.cos(ang[:, : (d // 2)]))
    return out.astype(dtype)


@scope("attention")
def _attn_apply(cfg, p, x, positions, hints=NO_HINTS):
    if cfg.mla is not None:
        return MLA.mla_apply(cfg, p, x, positions=positions, hints=hints)
    return A.attention_apply(cfg, p, x, positions=positions, hints=hints)


def _block_apply(cfg, p, x, positions, *, moe: bool, mesh, shuffle,
                 hints=NO_HINTS):
    """Pre-LN transformer block. Returns (x, aux, units dropped to expert
    capacity)."""
    h = _attn_apply(cfg, p["attn"],
                    L.rms_norm(x, p["ln1"], cfg.norm_eps), positions,
                    hints=hints)
    x = x + h
    z = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if moe:
        y, aux, diag = MOE.moe_apply(cfg, p["ffn"], z, shuffle=shuffle,
                                     mesh=mesh)
        dropped = diag["dropped"]
    else:
        y, aux = L.mlp_apply(cfg, p["ffn"], z), jnp.zeros((), jnp.float32)
        dropped = jnp.zeros((), jnp.int32)
    return x + y, aux, dropped


def _ssm_block_apply(cfg, p, x):
    return x + SSM.mamba2_apply(cfg, p["mamba"],
                                L.rms_norm(x, p["ln"], cfg.norm_eps))


def _squeeze0(tree):
    return jax.tree.map(lambda a: a[0], tree)


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)  # "full": save only layer boundaries


def forward(cfg: ModelConfig, params, batch, *, mesh=None,
            shuffle: ShuffleConfig = DENSE, remat: str = "none",
            hints: ShardHints = NO_HINTS
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits (B, S, V), aux_loss, units
    dropped to expert capacity summed over the MoE layers).

    ``hints.residual`` shards the residual stream at every block boundary
    (sequence parallelism — shards the remat-saved activations over the
    "model" axis); ``hints.qblocks`` shards flash-attention q blocks
    (context parallelism for archs whose heads don't divide the TP axis).
    """
    c = hints.res
    x = c(_embed_inputs(cfg, params, batch))
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    aux_total = jnp.zeros((), jnp.float32)
    dropped_total = jnp.zeros((), jnp.int32)

    if cfg.kind in ("decoder", "encoder"):
        if "dense_blocks" in params:
            def dense_body(x, p):
                x, aux, _ = _block_apply(cfg, p, x, positions, moe=False,
                                         mesh=mesh, shuffle=shuffle,
                                         hints=hints)
                return c(x), aux
            if cfg.moe.first_dense_layers == 1:
                # size-1 scans trigger degenerate GSPMD reshards — inline
                x, aux = _remat(dense_body, remat)(
                    x, _squeeze0(params["dense_blocks"]))
                aux_total += aux
            else:
                x, auxs = jax.lax.scan(_remat(dense_body, remat), x,
                                       params["dense_blocks"])
                aux_total += jnp.sum(auxs)

        moe = cfg.moe is not None

        def body(x, p):
            x, aux, dropped = _block_apply(cfg, p, x, positions, moe=moe,
                                           mesh=mesh, shuffle=shuffle,
                                           hints=hints)
            return c(x), (aux, dropped)
        x, (auxs, dropped) = jax.lax.scan(_remat(body, remat), x,
                                          params["blocks"])
        aux_total += jnp.sum(auxs)
        dropped_total += jnp.sum(dropped)

    elif cfg.kind == "ssm":
        def body(x, p):
            return c(_ssm_block_apply(cfg, p, x)), None
        x, _ = jax.lax.scan(_remat(body, remat), x, params["blocks"])

    elif cfg.kind == "hybrid":
        h = cfg.hybrid
        k = h.shared_block_every
        n_inv = cfg.num_layers // k
        x0 = x  # initial embedding, re-fed to every shared-block call
        blocks = jax.tree.map(
            lambda a: a.reshape((n_inv, k) + a.shape[1:]), params["blocks"])

        def group_body(x, xs):
            p_group, w_in = xs

            def inner(x, p):
                return _ssm_block_apply(cfg, p, x), None
            x, _ = jax.lax.scan(inner, x, p_group)
            inp = jnp.concatenate([x, x0], axis=-1) if h.concat_embed else x
            z = inp.astype(cfg.compute_dtype) @ w_in.astype(cfg.compute_dtype)
            y, _, _ = _block_apply(cfg, params["shared_block"], z,
                                   positions, moe=False, mesh=mesh,
                                   shuffle=shuffle, hints=hints)
            return c(x + y - z), None  # residual contribution of shared block

        x, _ = jax.lax.scan(_remat(group_body, remat), x,
                            (blocks, params["shared_in"]))

    with scope("head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed_apply(cfg, params["embed"], x)
    return logits, aux_total, dropped_total


# ---------------------------------------------------------------------------
# Decode (one token with a cache)
# ---------------------------------------------------------------------------

def cache_defs(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    """Decode-cache ArraySpec tree (stacked per layer like the params)."""
    if cfg.kind == "decoder":
        n_dense = cfg.moe.first_dense_layers if cfg.moe else 0
        mk = (MLA.mla_cache_defs if cfg.mla is not None
              else A.attention_cache_defs)
        out = {"blocks": mk(cfg, batch, max_seq,
                            stacked=cfg.num_layers - n_dense)}
        if n_dense:
            out["dense_blocks"] = mk(cfg, batch, max_seq, stacked=n_dense)
        return out
    if cfg.kind == "ssm":
        return {"blocks": SSM.mamba2_cache_defs(
            cfg, batch, stacked=cfg.num_layers)}
    if cfg.kind == "hybrid":
        n_inv = cfg.num_layers // cfg.hybrid.shared_block_every
        return {"blocks": SSM.mamba2_cache_defs(
                    cfg, batch, stacked=cfg.num_layers),
                "shared": A.attention_cache_defs(
                    cfg, batch, max_seq, stacked=n_inv)}
    raise ValueError(f"{cfg.kind} has no decode step")


def _attn_decode(cfg, p, x, cache, pos):
    if cfg.mla is not None:
        return MLA.mla_decode(cfg, p, x, cache, pos)
    return A.attention_decode(cfg, p, x, cache, pos)


def _block_decode(cfg, p, x, cache, pos, *, moe, mesh, shuffle):
    h, new_cache = _attn_decode(cfg, p["attn"],
                                L.rms_norm(x, p["ln1"], cfg.norm_eps),
                                cache, pos)
    x = x + h
    z = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if moe:
        y, _, _ = MOE.moe_apply(cfg, p["ffn"], z, shuffle=shuffle, mesh=mesh)
    else:
        y = L.mlp_apply(cfg, p["ffn"], z)
    return x + y, new_cache


def decode_step(cfg: ModelConfig, params, cache, batch, *, mesh=None,
                shuffle: ShuffleConfig = DENSE):
    """One-token decode. batch: {"tokens": (B, 1), "pos": scalar int32}.

    Returns (logits (B, 1, V), new_cache).
    """
    pos = batch["pos"]
    x = L.embed_apply(cfg, params["embed"], batch["tokens"])

    if cfg.kind == "decoder":
        if "dense_blocks" in params:
            def dense_body(x, xs):
                p, c = xs
                x, nc = _block_decode(cfg, p, x, c, pos, moe=False,
                                      mesh=mesh, shuffle=shuffle)
                return x, nc
            if cfg.moe.first_dense_layers == 1:
                x, nc1 = dense_body(x, (_squeeze0(params["dense_blocks"]),
                                        _squeeze0(cache["dense_blocks"])))
                ncache_d = jax.tree.map(lambda a: a[None], nc1)
            else:
                x, ncache_d = jax.lax.scan(
                    dense_body, x, (params["dense_blocks"],
                                    cache["dense_blocks"]))
        moe = cfg.moe is not None

        def body(x, xs):
            p, c = xs
            x, nc = _block_decode(cfg, p, x, c, pos, moe=moe, mesh=mesh,
                                  shuffle=shuffle)
            return x, nc
        x, ncache = jax.lax.scan(body, x, (params["blocks"],
                                           cache["blocks"]))
        new_cache = {"blocks": ncache}
        if "dense_blocks" in params:
            new_cache["dense_blocks"] = ncache_d

    elif cfg.kind == "ssm":
        def body(x, xs):
            p, c = xs
            h, nc = SSM.mamba2_decode(
                cfg, p["mamba"], L.rms_norm(x, p["ln"], cfg.norm_eps), c, pos)
            return x + h, nc
        x, ncache = jax.lax.scan(body, x, (params["blocks"],
                                           cache["blocks"]))
        new_cache = {"blocks": ncache}

    elif cfg.kind == "hybrid":
        h = cfg.hybrid
        k = h.shared_block_every
        n_inv = cfg.num_layers // k
        x0 = x
        blocks = jax.tree.map(
            lambda a: a.reshape((n_inv, k) + a.shape[1:]), params["blocks"])
        caches = jax.tree.map(
            lambda a: a.reshape((n_inv, k) + a.shape[1:]), cache["blocks"])

        def group_body(x, xs):
            p_group, c_group, w_in, attn_c = xs

            def inner(x, pc):
                p, c = pc
                y, nc = SSM.mamba2_decode(
                    cfg, p["mamba"], L.rms_norm(x, p["ln"], cfg.norm_eps),
                    c, pos)
                return x + y, nc
            x, nc_group = jax.lax.scan(inner, x, (p_group, c_group))
            inp = jnp.concatenate([x, x0], axis=-1) if h.concat_embed else x
            z = inp.astype(cfg.compute_dtype) @ w_in.astype(cfg.compute_dtype)
            sb = params["shared_block"]
            y, n_attn_c = _block_decode(cfg, sb, z, attn_c, pos, moe=False,
                                        mesh=mesh, shuffle=shuffle)
            return x + y - z, (nc_group, n_attn_c)

        x, (nc, n_shared) = jax.lax.scan(
            group_body, x, (blocks, caches, params["shared_in"],
                            cache["shared"]))
        new_cache = {
            "blocks": jax.tree.map(
                lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), nc),
            "shared": n_shared,
        }

    else:
        raise ValueError(f"{cfg.kind} has no decode step")

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed_apply(cfg, params["embed"], x)
    return logits, new_cache
