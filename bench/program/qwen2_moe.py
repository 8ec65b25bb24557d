"""A ``qwen2_moe`` configuration file as the program's ``ModelConfig``."""

from __future__ import annotations


def model_config(hf: dict, capacity_factor: float):
    from repro.models.common import MoEConfig, ModelConfig

    de = hf["moe_intermediate_size"]
    n_shared, rest = divmod(hf["shared_expert_intermediate_size"], de)
    if rest:
        raise ValueError("the program fuses shared experts of the routed "
                         "width: shared width must be a multiple of it")
    return ModelConfig(
        name=hf["name"], kind="decoder",
        num_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        qkv_bias=True, rope_theta=float(hf["rope_theta"]),
        norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        moe=MoEConfig(num_experts=hf["num_experts"],
                      top_k=hf["num_experts_per_tok"], d_expert=de,
                      num_shared=n_shared, aux_loss_coef=hf["router_aux_loss_coef"],
                      capacity_factor=capacity_factor),
        source=hf["source"])
