"""Model FLOP/s utilization of the training window of the family with
delta-rule layers: the operations a trained token requires (``chipbench/kernels/
qwen3next_train.py``: LoRA's 4N over the softmax and the delta-rule layers'
matrices, attention over the keys before a query at heads of 256, the scan at
chunks of 64, the shared expert, the routed experts by the picks the program
counted, the head; no recomputation) times tokens per second, over the chip's
bf16 peak."""

from chipbench.kernels import qwen3next_train
from chipbench.metrics.moe_picks_held_pct import picks


def read(run):
    t, got = run.cell["traffic"], picks()
    calls = run.rec.spans_named("chipbench.train_call")
    if not calls or got is None:
        return None
    landed = got[1] / got[0] * run.config["num_experts_per_tok"]
    parts = qwen3next_train.flops_per_token(
        run.config, t["seq_len"], run.plane.tcfg.lora_rank, landed)
    total = sum(parts.values())
    run.rec.say("qnext_sft_mfu", flops_per_token=total, shares={
        k: round(v / total, 4) for k, v in parts.items()})
    rate = sum(c["tokens"] for c in calls) / run.rec.window_s()
    return 100.0 * total * rate / (
        run.peaks["bf16_flops_per_s"] * run.device["count"])
