"""Model FLOPs of one ``qwen2_moe`` train step.

Forward multiply-adds per token, times 2 FLOPs each, times 3 for the
forward and backward passes: the q/k/v/o projections, the router, the
``top_k`` routed experts, the shared expert and the LM head; plus the
attention score and value products, causal, so S*S/2 of them per
sequence and head. Not counted: recomputation under remat, capacity
padding, biases, the embedding lookup."""

from __future__ import annotations


def step_flops(hf: dict, batch: int, seq: int) -> float:
    d, H, V = hf["hidden_size"], hf["num_attention_heads"], hf["vocab_size"]
    KVH = hf["num_key_value_heads"]
    hd = d // H
    E, k = hf["num_experts"], hf["num_experts_per_tok"]
    de = hf["moe_intermediate_size"]
    ds = hf["shared_expert_intermediate_size"]
    proj = 2 * d * H * hd + 2 * d * KVH * hd
    core = H * 2 * hd * seq / 2
    moe = d * E + k * 3 * d * de + 3 * d * ds
    per_token = hf["num_hidden_layers"] * (proj + core + moe) + d * V
    return 3 * 2 * per_token * batch * seq
