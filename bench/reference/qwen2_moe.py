"""Qwen2-MoE (model_type ``qwen2_moe``) for the plain reference.

Multi-head attention with biases on the query, key and value
projections, rotary embeddings on the whole head, softmax scale
1/sqrt(head_dim). Every block routes to ``num_experts`` experts of
``moe_intermediate_size`` and adds a shared SwiGLU of
``shared_expert_intermediate_size``, ungated, as the program runs it
(the published model gates it by a sigmoid; the configuration's file
lists that departure).
"""

from __future__ import annotations

from reference.common import (Leaf, Model, causal_attention, rope,
                              softmax_router)


def layout(hf):
    d, V = hf["hidden_size"], hf["vocab_size"]
    H, KVH = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = d // H
    n = hf["num_hidden_layers"]
    E, de = hf["num_experts"], hf["moe_intermediate_size"]
    ds = hf["shared_expert_intermediate_size"]
    return {
        "embed": {"tok": Leaf((V, d), "small"),
                  "unembed": Leaf((d, V), "normal", d)},
        "final_norm": Leaf((d,), "zeros"),
        "blocks": {
            "attn": {"bk": Leaf((n, KVH, hd), "zeros"),
                     "bq": Leaf((n, H, hd), "zeros"),
                     "bv": Leaf((n, KVH, hd), "zeros"),
                     "wk": Leaf((n, d, KVH, hd), "normal", d),
                     "wo": Leaf((n, H, hd, d), "normal", H),
                     "wq": Leaf((n, d, H, hd), "normal", d),
                     "wv": Leaf((n, d, KVH, hd), "normal", d)},
            "ffn": {"router": Leaf((n, d, E), "small"),
                    "shared": {"w_down": Leaf((n, ds, d), "normal", ds),
                               "w_gate": Leaf((n, d, ds), "normal", d),
                               "w_up": Leaf((n, d, ds), "normal", d)},
                    "we_down": Leaf((n, E, de, d), "normal", de),
                    "we_gate": Leaf((n, E, d, de), "normal", d),
                    "we_up": Leaf((n, E, d, de), "normal", d)},
            "ln1": Leaf((n, d), "zeros"), "ln2": Leaf((n, d), "zeros")},
    }


def moe_settings(hf) -> dict:
    """Softmax routing, greedy top-k, the chosen probabilities normalised
    where ``norm_topk_prob``, scale 1; every expert held."""
    return {"num_experts": hf["num_experts"],
            "top_k": hf["num_experts_per_tok"],
            "router": softmax_router(hf["norm_topk_prob"]),
            "aux_coef": hf["router_aux_loss_coef"]}


def model(hf, moe) -> Model:
    H, KVH = hf["num_attention_heads"], hf["num_key_value_heads"]
    theta = hf["rope_theta"]

    def attn(es, p, x):
        q = rope(es("bsd,dhe->bshe", x, p["wq"]) + p["bq"], theta)
        k = rope(es("bsd,dhe->bshe", x, p["wk"]) + p["bk"], theta)
        v = es("bsd,dhe->bshe", x, p["wv"]) + p["bv"]
        if KVH != H:
            k = k.repeat(H // KVH, axis=2)
            v = v.repeat(H // KVH, axis=2)
        return es("bshe,hed->bsd", causal_attention(es, q, k, v), p["wo"])

    return Model(layout=layout(hf), attn=attn, moe=moe, first_dense=0,
                 n_moe=hf["num_hidden_layers"], eps=hf["rms_norm_eps"])
