"""MoE FFN layer: shared experts (always-on, local — the "local cache"
analogue: never shuffled) + routed experts dispatched via repro.shuffle."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ArraySpec, ModelConfig
from repro.obs.scopes import scope
from repro.shuffle.api import ShuffleConfig, dense_moe_ffn, ep_moe_ffn


def moe_defs(cfg: ModelConfig, *, stacked: int = 0) -> dict:
    m = cfg.moe
    d, de = cfg.d_model, m.d_expert
    E = m.num_experts
    L = (stacked,) if stacked else ()
    la = ("layers",) if stacked else ()
    pd = cfg.param_dtype
    out = {
        "router": ArraySpec(L + (d, E), jnp.float32, la + ("embed", None),
                            init="small"),
        "we_gate": ArraySpec(L + (E, d, de), pd,
                             la + ("experts", "embed", "expert_mlp")),
        "we_up": ArraySpec(L + (E, d, de), pd,
                           la + ("experts", "embed", "expert_mlp")),
        "we_down": ArraySpec(L + (E, de, d), pd,
                             la + ("experts", "expert_mlp", "embed")),
    }
    if m.num_shared:
        ds = m.num_shared * de  # shared experts fused into one wide SwiGLU
        # hidden dim replicated (logical axis None): model-sharding it
        # conflicts with the sequence-sharded residual stream and makes
        # GSPMD fully re-replicate f32 activations in the backward (§Perf)
        out["shared"] = {
            "w_gate": ArraySpec(L + (d, ds), pd, la + ("embed", None)),
            "w_up": ArraySpec(L + (d, ds), pd, la + ("embed", None)),
            "w_down": ArraySpec(L + (ds, d), pd, la + (None, "embed")),
        }
    return out


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array, *,
              shuffle: ShuffleConfig, mesh=None
              ) -> Tuple[jax.Array, jax.Array, dict]:
    """x: (B, S, d). Returns (y, aux_loss, diagnostics dict)."""
    m = cfg.moe
    cd = cfg.compute_dtype
    B, S, d = x.shape
    xt = x.reshape(B * S, d)

    if shuffle.mode == "dense" or mesh is None:
        y, aux, load, dropped = dense_moe_ffn(
            xt, p["router"], p["we_gate"], p["we_up"], p["we_down"],
            top_k=m.top_k, capacity_factor=m.capacity_factor,
            norm_topk=shuffle.norm_topk, compute_dtype=cd)
        diag = {"expert_load": load, "dropped": dropped,
                "dcn_bytes": jnp.zeros((), jnp.float32)}
    else:
        # pad token count to the token-axes product
        from repro.shuffle.api import mesh_axis_size
        shuf = shuffle.resolve(mesh if not shuffle.use_context_mesh
                               else None)
        devs = 1
        for a in shuf.token_axes:
            devs *= mesh_axis_size(
                mesh if not shuffle.use_context_mesh else None, a)
        T = B * S
        pad = (-T) % devs
        if pad:
            xt = jnp.pad(xt, ((0, pad), (0, 0)))
        mask = (jnp.arange(T + pad) < T).astype(jnp.float32)
        y, aux, dg = ep_moe_ffn(
            xt, p["router"], p["we_gate"], p["we_up"], p["we_down"],
            top_k=m.top_k, cfg=shuf, mesh=mesh, compute_dtype=cd,
            token_mask=mask)
        y = y[:T]
        diag = {"expert_load": dg.expert_load, "dropped": dg.dropped,
                "dcn_bytes": dg.dcn_bytes}

    y = y.reshape(B, S, d)
    if m.num_shared:
        with scope("ffn"):
            sp = p["shared"]
            xs = x.astype(cd)
            g = jax.nn.silu(xs @ sp["w_gate"].astype(cd))
            u = xs @ sp["w_up"].astype(cd)
            y = y + (g * u) @ sp["w_down"].astype(cd)
    return y.astype(x.dtype), aux * m.aux_loss_coef, diag
