"""DeepSeek-V2 (model_type ``deepseek_v2``) for the plain reference.

Multi-head latent attention without query compression (``q_lora_rank``
null, as in DeepSeek-V2-Lite): queries from one projection, keys and
values expanded from a normalised ``kv_lora_rank`` latent, a decoupled
rotary part of ``qk_rope_head_dim`` shared by all heads, softmax scale
1/sqrt(qk_nope_head_dim + qk_rope_head_dim), both under the YaRN
``rope_scaling`` where one is given (``yarn``). The first
``first_k_dense_replace`` blocks have a dense SwiGLU of
``intermediate_size``; the rest route to ``n_routed_experts`` experts of
``moe_intermediate_size`` and add ``n_shared_experts`` shared ones, fused
into one SwiGLU of n_shared_experts x moe_intermediate_size.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from reference.common import (Leaf, Model, causal_attention, rms_norm, rope,
                              rope_freqs, softmax_router)


def _attn_layout(hf, n):
    d, H = hf["hidden_size"], hf["num_attention_heads"]
    r, nope = hf["kv_lora_rank"], hf["qk_nope_head_dim"]
    rp, vd = hf["qk_rope_head_dim"], hf["v_head_dim"]
    return {
        "kv_norm": Leaf((n, r), "zeros"),
        "w_dkv": Leaf((n, d, r + rp), "normal", d),
        "w_uk": Leaf((n, r, H, nope), "normal", r),
        "w_uv": Leaf((n, r, H, vd), "normal", r),
        "wo": Leaf((n, H, vd, d), "normal", H),
        "wq": Leaf((n, d, H, nope + rp), "normal", d),
    }


def layout(hf):
    d, V = hf["hidden_size"], hf["vocab_size"]
    n_dense = hf["first_k_dense_replace"]
    n_moe = hf["num_hidden_layers"] - n_dense
    E, de = hf["n_routed_experts"], hf["moe_intermediate_size"]
    ds = hf["n_shared_experts"] * de
    dff = hf["intermediate_size"]

    def block(n, ffn):
        return {"attn": _attn_layout(hf, n), "ffn": ffn,
                "ln1": Leaf((n, d), "zeros"), "ln2": Leaf((n, d), "zeros")}
    out = {
        "embed": {"tok": Leaf((V, d), "small"),
                  "unembed": Leaf((d, V), "normal", d)},
        "final_norm": Leaf((d,), "zeros"),
        "blocks": block(n_moe, {
            "router": Leaf((n_moe, d, E), "small"),
            "shared": {"w_down": Leaf((n_moe, ds, d), "normal", ds),
                       "w_gate": Leaf((n_moe, d, ds), "normal", d),
                       "w_up": Leaf((n_moe, d, ds), "normal", d)},
            "we_down": Leaf((n_moe, E, de, d), "normal", de),
            "we_gate": Leaf((n_moe, E, d, de), "normal", d),
            "we_up": Leaf((n_moe, E, d, de), "normal", d)}),
    }
    if n_dense:
        out["dense_blocks"] = block(n_dense, {
            "w_down": Leaf((n_dense, dff, d), "normal", dff),
            "w_gate": Leaf((n_dense, d, dff), "normal", d),
            "w_up": Leaf((n_dense, d, dff), "normal", d)})
    return out


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(hf):
    """(inverse frequencies of the rotary part, factor on its rotated
    values, factor on the softmax scale) under DeepSeek-V2's YaRN
    ``rope_scaling`` (``DeepseekV2YarnRotaryEmbedding`` and the attention's
    ``softmax_scale`` of the published modeling_deepseek.py): frequencies
    below the ``beta_slow``..``beta_fast`` band are divided by ``factor``,
    with a linear ramp across it. With no ``rope_scaling``, or a ``factor``
    of 1, this is plain RoPE: (theta^(-2i/d), 1, 1)."""
    d, theta = hf["qk_rope_head_dim"], hf["rope_theta"]
    rs = hf.get("rope_scaling")
    if not rs or rs["factor"] <= 1:
        return rope_freqs(d, theta), 1.0, 1.0
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r} is not yarn")
    f, base_len = rs["factor"], rs["original_max_position_embeddings"]

    def band(rotations):
        return d * math.log(base_len / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(band(rs["beta_fast"])), 0)
    high = min(math.ceil(band(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    extra = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inv = extra / f * ramp + extra * (1.0 - ramp)
    all_dim = rs.get("mscale_all_dim", 0)
    rotated = _mscale(f, rs.get("mscale", 1)) / _mscale(f, all_dim)
    softmax = _mscale(f, all_dim) ** 2 if all_dim else 1.0
    return inv.astype(np.float32), rotated, softmax


def moe_settings(hf) -> dict:
    """Softmax routing, greedy top-k (``topk_method`` greedy), the chosen
    probabilities normalised where ``norm_topk_prob``, scale 1
    (DeepSeek-V2-Lite's ``routed_scaling_factor``); every expert held."""
    return {"num_experts": hf["n_routed_experts"],
            "top_k": hf["num_experts_per_tok"],
            "router": softmax_router(hf["norm_topk_prob"]),
            "aux_coef": hf["aux_loss_alpha"]}


def model(hf, moe) -> Model:
    r, nope = hf["kv_lora_rank"], hf["qk_nope_head_dim"]
    H, eps, theta = hf["num_attention_heads"], hf["rms_norm_eps"], \
        hf["rope_theta"]
    inv, rotated, softmax = yarn(hf)

    def attn(es, p, x):
        q = es("bsd,dhe->bshe", x, p["wq"])
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], theta, inv) * rotated],
            -1) * softmax
        dkv = es("bsd,de->bse", x, p["w_dkv"])
        c = rms_norm(dkv[..., :r], p["kv_norm"], eps)
        k_rope = rope(dkv[..., None, r:], theta, inv) * rotated
        k = jnp.concatenate(
            [es("bsr,rhe->bshe", c, p["w_uk"]),
             jnp.broadcast_to(k_rope, k_rope.shape[:2] + (H,)
                              + k_rope.shape[3:])], -1)
        v = es("bsr,rhe->bshe", c, p["w_uv"])
        return es("bshe,hed->bsd", causal_attention(es, q, k, v), p["wo"])

    n_dense = hf["first_k_dense_replace"]
    return Model(layout=layout(hf), attn=attn, moe=moe,
                 first_dense=n_dense,
                 n_moe=hf["num_hidden_layers"] - n_dense, eps=eps)
