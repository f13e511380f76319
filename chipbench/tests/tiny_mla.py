"""The latent-attention family's cell at a size a CPU test can hold: every
mechanism of the real one (q and keys/values through low ranks with one shared
rotary key, YaRN past its original positions, a leading dense SwiGLU layer,
two routed layers behind a group-limited sigmoid router with a correction
bias beside a shared expert, a second head; 4 of 32 experts held; the frozen
matrices stored in bfloat16), driven through ``runner.run_cell`` as
``tiny.py`` drives GPT-2's."""

import copy
import os

from chipbench.harness import runner
from chipbench.harness.record import now

import tiny

CONFIG = {
    "name": "tiny_mla", "reference": "gigachat3",
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
    "weights_stored": "bfloat16"}

#: the program computes in float32 here but for the experts' products, whose
#: operands it rounds to bfloat16 on every backend, over matrices stored in
#: bfloat16 on both sides: the precision below it is fp8.  Limits from
#: readings on five seeds at this size: above the sound runs' largest
#: (first_grad_gap 8.8e-4, picks 0, mtp_loss_gap 3.5e-5, probe_change_gap
#: 1.7e-3, change_norm_gap 5.1e-3), below the fp8 reference's smallest
#: (first_grad_gap 0.23, picks 0.35)
SFT = {"plane": "sft_mla",
       "traffic": {"use_lora": True, "seq_len": 32, "batch_size": 1,
                   "steps_per_call": 3, "cycle": 16},
       "trace": {"start_s": 0.0, "seconds": 0.3},
       "limits": {"first_grad_gap": 4e-2,
                  "probe_change_gap": 2e-2, "mtp_loss_gap": 5e-4,
                  "loss_gap": 1e-3, "change_norm_gap": 0.1,
                  "picks_disagree_share": 0.08,
                  "state_leaves_not_float32": 0}}


def run(cell, metric_names, seed, seconds, tmp_path, trace=False,
        config=CONFIG):
    metrics = [{"name": n, "unit": "x"} for n in metric_names]
    return runner.run_cell(copy.deepcopy(cell), config, metrics, seed,
                           seconds, trace, tiny.DEVICE, now(),
                           os.fspath(tmp_path))
