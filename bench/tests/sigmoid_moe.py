"""A test-sized model type that uses only the reference's MoE interface
(``reference.common.MoESpec``) and edits nothing there: sigmoid scoring
with a selection bias kept as router state and moved after each step by
the sign of each expert's load against the mean (DeepSeek-V3's
auxiliary-loss-free balancing), the chosen experts' sigmoid scores
normalised and scaled by ``routed_scaling_factor``, a load-balance loss
over the normalised scores, and a share of the routed experts held
(``experts_held``: [start, stop), or null for all).

Blocks: multi-head attention with rotary embeddings, then the routed
experts beside one shared SwiGLU expert.
"""

from __future__ import annotations

import numpy as np

from reference.common import (ROUTER_STATE, Leaf, Model, causal_attention,
                              rope)


def held(hf):
    return tuple(hf["experts_held"] or (0, hf["n_routed_experts"]))


def layout(hf):
    d, V, H = hf["hidden_size"], hf["vocab_size"], hf["num_attention_heads"]
    hd = d // H
    n = hf["num_hidden_layers"]
    E, de = hf["n_routed_experts"], hf["moe_intermediate_size"]
    lo, hi = held(hf)
    ds = hf["shared_intermediate_size"]
    return {
        "embed": {"tok": Leaf((V, d), "small"),
                  "unembed": Leaf((d, V), "normal", d)},
        "final_norm": Leaf((d,), "zeros"),
        "blocks": {
            "attn": {"wk": Leaf((n, d, H, hd), "normal", d),
                     "wo": Leaf((n, H, hd, d), "normal", H),
                     "wq": Leaf((n, d, H, hd), "normal", d),
                     "wv": Leaf((n, d, H, hd), "normal", d)},
            "ffn": {"router": Leaf((n, d, E), "small"),
                    ROUTER_STATE: Leaf((n, E), "state"),
                    "shared": {"w_down": Leaf((n, ds, d), "normal", ds),
                               "w_gate": Leaf((n, d, ds), "normal", d),
                               "w_up": Leaf((n, d, ds), "normal", d)},
                    "we_down": Leaf((n, hi - lo, de, d), "normal", de),
                    "we_gate": Leaf((n, hi - lo, d, de), "normal", d),
                    "we_up": Leaf((n, hi - lo, d, de), "normal", d)},
            "ln1": Leaf((n, d), "zeros"), "ln2": Leaf((n, d), "zeros")},
    }


def sigmoid_router(scale: float):
    def router(xp, logits, bias):
        s = 1.0 / (1.0 + xp.exp(-logits))

        def combine(sel):
            w = xp.take_along_axis(s, sel, axis=-1)
            return w / xp.sum(w, -1, keepdims=True) * scale
        return s + bias, combine, s / xp.sum(s, -1, keepdims=True)
    return router


def bias_update(rate: float):
    def update(bias, load):
        return (bias + np.float32(rate) * np.sign(load.mean() - load)
                ).astype(np.float32)
    return update


def moe_settings(hf) -> dict:
    return {"num_experts": hf["n_routed_experts"],
            "top_k": hf["num_experts_per_tok"],
            "router": sigmoid_router(hf["routed_scaling_factor"]),
            "aux_coef": hf["aux_loss_alpha"],
            "update": bias_update(hf["bias_update_rate"]),
            "held": held(hf)}


def model(hf, moe) -> Model:
    theta = hf["rope_theta"]

    def attn(es, p, x):
        q = rope(es("bsd,dhe->bshe", x, p["wq"]), theta)
        k = rope(es("bsd,dhe->bshe", x, p["wk"]), theta)
        v = es("bsd,dhe->bshe", x, p["wv"])
        return es("bshe,hed->bsd", causal_attention(es, q, k, v), p["wo"])

    return Model(layout=layout(hf), attn=attn, moe=moe, first_dense=0,
                 n_moe=hf["num_hidden_layers"], eps=hf["rms_norm_eps"])
