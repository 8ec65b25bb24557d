"""A ``deepseek_v2`` configuration file as the program's ``ModelConfig``."""

from __future__ import annotations


def model_config(hf: dict, capacity_factor: float):
    from repro.models.common import MLAConfig, MoEConfig, ModelConfig

    from cell import BenchError

    if (hf.get("rope_scaling") or {}).get("factor", 1) > 1:
        # models/rope.py has plain RoPE only: YaRN at factor 1
        raise BenchError("the program has no YaRN rope_scaling; it runs "
                         "the configuration only at a factor of 1")
    return ModelConfig(
        name=hf["name"], kind="decoder",
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        rope_theta=float(hf["rope_theta"]), norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        moe=MoEConfig(num_experts=hf["n_routed_experts"],
                      top_k=hf["num_experts_per_tok"],
                      d_expert=hf["moe_intermediate_size"],
                      num_shared=hf["n_shared_experts"],
                      first_dense_layers=hf["first_k_dense_replace"],
                      dense_d_ff=hf["intermediate_size"],
                      aux_loss_coef=hf["aux_loss_alpha"],
                      capacity_factor=capacity_factor),
        mla=MLAConfig(kv_lora_rank=hf["kv_lora_rank"], q_lora_rank=0,
                      qk_nope_head_dim=hf["qk_nope_head_dim"],
                      qk_rope_head_dim=hf["qk_rope_head_dim"],
                      v_head_dim=hf["v_head_dim"]),
        source=hf["source"])
