"""Cells of a size a CPU test can hold, driven through the same entry as a
real run (``runner.run_cell``), which prints no result line."""

import copy
import os

from chipbench.harness import runner
from chipbench.harness.record import now

CONFIG = {"name": "tiny", "reference": "gpt2", "vocab_size": 211,
          "n_positions": 96, "n_embd": 32, "n_layer": 2, "n_head": 2,
          "layer_norm_epsilon": 1e-5, "initializer_range": 0.02}

#: limits far above float32 rounding (the program and the reference are both
#: float32 here) and far below what bfloat16 or fp8 does at this size
SFT = {"plane": "sft",
       "traffic": {"use_lora": True, "seq_len": 32, "batch_size": 2,
                   "steps_per_call": 3},
       "trace": {"start_s": 0.0, "seconds": 0.3},
       "limits": {"first_loss_gap": 1e-4, "first_grad_gap": 1e-3,
                  "probe_change_gap": 1e-3, "loss_gap": 1e-4,
                  "change_norm_gap": 2e-3, "state_leaves_not_float32": 0}}

SERVE = {"plane": "serve",
         "traffic": {"max_batch": 4,
                     "arrivals": {"process": "exponential_gaps",
                                  "rate_qps": 20.0},
                     "prompt_tokens": [[9, 20, 1], [21, 40, 1]],
                     "output_tokens": [[2, 6, 1], [7, 12, 1]],
                     "max_total_tokens": 64, "drain_seconds": 60},
         "trace": {"start_s": -1.0, "seconds": 1.0},
         "limits": {"served_logit_gap": 1e-4}}

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
          "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                    "hbm_bytes": 1e10}}


def run(cell, metric_names, seed, seconds, tmp_path, trace=False):
    metrics = [{"name": n, "unit": "x"} for n in metric_names]
    return runner.run_cell(copy.deepcopy(cell), CONFIG, metrics, seed,
                           seconds, trace, DEVICE, now(),
                           os.fspath(tmp_path))
