"""The LFM2 serving cell at a size a CPU test can hold: every mechanism of the
real one (a dense short-convolution layer, then routed layers: grouped-head
attention with q and k normed by head and rotated, short convolutions of three
taps, sigmoid routing with a bias of 2 picks among 8 experts all held, a tied
head), driven through ``runner.run_cell`` as ``tiny.py`` drives GPT-2's."""

import copy
import os

from chipbench.harness import runner
from chipbench.harness.record import now

import tiny

CONFIG = {
    "name": "tiny_lfm2", "reference": "lfm2", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "intermediate_size": 48, "moe_intermediate_size": 24,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "held_layers": [0, 2, 3, 4], "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_eps": 1e-5, "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000}, "vocab_size": 211,
    "n_positions": 96, "initializer_range": 0.1}

#: the program computes in bfloat16 here as on the chip (bfloat16 weights,
#: its experts' products on bfloat16 operands); the limit lies between the
#: sound runs' readings and the controls' (the test file's docstring has
#: them).  Prompts of 3-8 tokens are fed through decode from position 0, the
#: longer ones prefilled; 4 slots, so every slot is reused
SERVE = {"plane": "serve_lfm2",
         "traffic": {"max_batch": 4,
                     "arrivals": {"process": "exponential_gaps",
                                  "rate_qps": 20.0},
                     "prompt_tokens": [[3, 8, 1], [9, 20, 1], [21, 40, 1]],
                     "output_tokens": [[2, 6, 1], [7, 12, 1]],
                     "max_total_tokens": 64, "drain_seconds": 60},
         "trace": {"start_s": -1.0, "seconds": 1.0},
         "limits": {"served_logit_gap": 0.7,
                    "served_logit_gap_mean": 0.02}}


def run(cell, metric_names, seed, seconds, tmp_path, trace=False,
        config=CONFIG):
    metrics = [{"name": n, "unit": "x"} for n in metric_names]
    return runner.run_cell(copy.deepcopy(cell), config, metrics, seed,
                           seconds, trace, tiny.DEVICE, now(),
                           os.fspath(tmp_path))
