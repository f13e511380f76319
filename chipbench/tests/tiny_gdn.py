"""The cell of the family with delta-rule layers at a size a CPU test can
hold: every mechanism of the real one (three gated delta-rule layers to one
gated softmax layer with q and k normed by head and a quarter of a head
rotated, norm scales centred on zero, two value heads a key head in the rule,
a causal convolution of four taps, softmax routing of 4 picks among 16 experts
of which 4 are held, a sigmoid-gated shared expert, the frozen matrices
stored in bfloat16), driven through ``runner.run_cell`` as ``tiny.py`` drives
GPT-2's."""

import copy
import os

from chipbench.harness import runner
from chipbench.harness.record import now

import tiny

CONFIG = {
    "name": "tiny_gdn", "reference": "qwen3_next",
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "full_attention_interval": 4, "num_hidden_layers": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 24,
    "shared_expert_intermediate_size": 24, "num_experts": 4,
    "num_experts_per_tok": 4, "hidden_act": "silu", "vocab_size": 211,
    "rms_norm_eps": 1e-6, "published": {"num_experts": 16},
    "experts_first_held": 4, "initializer_range": 0.2,
    "weights_stored": "bfloat16"}

#: the program computes in float32 here but for the experts' products, whose
#: operands it rounds to bfloat16 on every backend, over matrices stored in
#: bfloat16 on both sides: the precision below it is fp8.  Limits from
#: readings at this size, with the scan's kernels interpreted (bfloat16
#: operands, as on the chip) and without (the test file's docstring has them)
SFT = {"plane": "sft_gdn",
       "traffic": {"use_lora": True, "seq_len": 32, "batch_size": 1,
                   "steps_per_call": 3, "cycle": 16},
       "trace": {"start_s": 0.0, "seconds": 0.3},
       "limits": {"first_grad_gap": 8e-2, "first_loss_gap": 3e-3,
                  "probe_change_gap": 2e-2, "loss_gap": 2e-3,
                  "change_norm_gap": 0.1, "picks_disagree_share": 0.15,
                  "state_leaves_not_float32": 0}}


def run(cell, metric_names, seed, seconds, tmp_path, trace=False,
        config=CONFIG):
    metrics = [{"name": n, "unit": "x"} for n in metric_names]
    return runner.run_cell(copy.deepcopy(cell), config, metrics, seed,
                           seconds, trace, tiny.DEVICE, now(),
                           os.fspath(tmp_path))
