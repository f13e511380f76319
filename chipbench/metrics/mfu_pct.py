"""Model FLOP/s utilization of the training window: the operations a trained
token requires (``chipbench/kernels/gpt2_train.py``: no recomputation, the
frozen base's weight gradient not counted under LoRA) times tokens per second,
over the chip's bf16 peak."""

from chipbench.kernels import gpt2_train


def read(run):
    t = run.cell["traffic"]
    calls = run.rec.spans_named("chipbench.train_call")
    if not calls:
        return None
    rank = run.plane.tcfg.lora_rank if t["use_lora"] else 0
    per_token = gpt2_train.flops_per_token(run.config, t["seq_len"], rank)
    rate = sum(c["tokens"] for c in calls) / run.rec.window_s()
    return 100.0 * per_token * rate / (
        run.peaks["bf16_flops_per_s"] * run.device["count"])
