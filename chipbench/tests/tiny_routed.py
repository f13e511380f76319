"""The routed family's cell at a size a CPU test can hold: every mechanism of
the real one (a NoPE full layer to three RoPE window layers, twice; grouped
heads; 16 experts of which 4 are held, top-3; a window shorter than the
sequence), driven through ``runner.run_cell`` as ``tiny.py`` drives GPT-2's."""

import copy
import os

from chipbench.harness import runner
from chipbench.harness.record import now

import tiny

CONFIG = {
    "name": "tiny_routed", "reference": "smallthinker",
    "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 8, "vocab_size": 211,
    "moe_ffn_hidden_size": 24, "moe_num_primary_experts": 4,
    "moe_num_active_primary_experts": 3, "experts_first_held": 8,
    "published": {"moe_num_primary_experts": 16},
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "sliding_window_size": 12,
    "rope_layout": [0, 1, 1, 1] * 3, "sliding_window_layout": [0, 1, 1, 1] * 3,
    "initializer_range": 0.2}

#: the program computes in float32 here but for the experts' products, whose
#: operands it rounds to bfloat16 on every backend, so the precision below it
#: is fp8.  Limits from readings on five seeds at this size: above the sound
#: runs' largest (first_loss_gap 1.5e-4, first_grad_gap 0.0143, picks 0.012),
#: below the fp8 reference's smallest (1.0e-3, 0.0876, 0.18); the others far
#: above the sound runs' (a state left unchanged reads 1)
SFT = {"plane": "sft_routed",
       "traffic": {"use_lora": True, "seq_len": 32, "batch_size": 1,
                   "steps_per_call": 3, "cycle": 16},
       "trace": {"start_s": 0.0, "seconds": 0.3},
       "limits": {"first_loss_gap": 5e-4, "first_grad_gap": 4e-2,
                  "probe_change_gap": 2e-2, "loss_gap": 1e-3,
                  "change_norm_gap": 0.1, "picks_disagree_share": 0.08,
                  "state_leaves_not_float32": 0}}


def run(cell, metric_names, seed, seconds, tmp_path, trace=False):
    metrics = [{"name": n, "unit": "x"} for n in metric_names]
    return runner.run_cell(copy.deepcopy(cell), CONFIG, metrics, seed,
                           seconds, trace, tiny.DEVICE, now(),
                           os.fspath(tmp_path))
