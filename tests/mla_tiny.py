"""The latent-attention family at a size a CPU holds, for `test_mla_lm.py` and
the share test of `test_routed_lm.py`: every mechanism of the published model
(q and keys/values through low ranks with one shared rotary key, YaRN past its
original positions, a leading dense SwiGLU layer, routed layers behind a
group-limited sigmoid router with a correction bias beside a shared expert, a
second head) in the source's own keys."""

import fedml_tpu
from chipbench.planes.sft_mla import model_args

CFG = {
    "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 48, "moe_intermediate_size": 24,
    "n_shared_experts": 1, "n_routed_experts": 4, "num_experts_per_tok": 4,
    "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "hidden_act": "silu",
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_nextn_predict_layers": 1, "vocab_size": 211, "rms_norm_eps": 1e-6,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "rope_type": "yarn"},
    "published": {"n_routed_experts": 32}, "experts_first_held": 4,
    "mtp_loss_weight": 0.3, "initializer_range": 0.2, "router_bias_std": 0.05,
    "weights_stored": "float32"}
T = 32


def module(cfg=CFG):
    return fedml_tpu.model.create(fedml_tpu.Config(**model_args(cfg)),
                                  cfg["vocab_size"]).module
