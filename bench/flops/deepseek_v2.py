"""Model FLOPs of one ``deepseek_v2`` train step.

Forward multiply-adds per token, times 2 FLOPs each, times 3 for the
forward and backward passes: the attention projections, the dense SwiGLU
of the first blocks, the router, the ``top_k`` routed experts and the
shared experts of the MoE blocks, and the LM head; plus the attention
score and value products, causal, so S*S/2 of them per sequence and head.
Not counted: recomputation under remat, capacity padding, the embedding
lookup."""

from __future__ import annotations


def step_flops(hf: dict, batch: int, seq: int) -> float:
    d, H, V = hf["hidden_size"], hf["num_attention_heads"], hf["vocab_size"]
    r, nope = hf["kv_lora_rank"], hf["qk_nope_head_dim"]
    rp, vd = hf["qk_rope_head_dim"], hf["v_head_dim"]
    n_dense = hf["first_k_dense_replace"]
    n_moe = hf["num_hidden_layers"] - n_dense
    E, k = hf["n_routed_experts"], hf["num_experts_per_tok"]
    de = hf["moe_intermediate_size"]
    ds = hf["n_shared_experts"] * de
    proj = d * H * (nope + rp) + d * (r + rp) + r * H * (nope + vd) \
        + H * vd * d
    core = H * (nope + rp + vd) * seq / 2
    dense = 3 * d * hf["intermediate_size"]
    moe = d * E + k * 3 * d * de + 3 * d * ds
    per_token = (hf["num_hidden_layers"] * (proj + core) + n_dense * dense
                 + n_moe * moe + d * V)
    return 3 * 2 * per_token * batch * seq
