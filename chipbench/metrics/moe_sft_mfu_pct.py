"""Model FLOP/s utilization of the routed family's training window: the
operations a trained token requires (``chipbench/kernels/
smallthinker_train.py``: LoRA's 4N, window-limited attention, the experts by
the picks the program counted, no recomputation) times tokens per second,
over the chip's bf16 peak."""

from chipbench.kernels import smallthinker_train
from chipbench.metrics.moe_picks_held_pct import picks


def read(run):
    t, got = run.cell["traffic"], picks()
    calls = run.rec.spans_named("chipbench.train_call")
    if not calls or got is None:
        return None
    landed = got[1] / got[0] * run.config["moe_num_active_primary_experts"]
    parts = smallthinker_train.flops_per_token(
        run.config, t["seq_len"], run.plane.tcfg.lora_rank, landed)
    total = sum(parts.values())
    run.rec.say("moe_sft_mfu", flops_per_token=total, shares={
        k: round(v / total, 4) for k, v in parts.items()})
    rate = sum(c["tokens"] for c in calls) / run.rec.window_s()
    return 100.0 * total * rate / (
        run.peaks["bf16_flops_per_s"] * run.device["count"])
