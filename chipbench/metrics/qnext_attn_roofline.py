"""The flash-attention forward kernel's share of its roofline at 16/2 heads of
256: the least time for one execution in one softmax layer (``chipbench/
kernels/qwen3next_train.py``: the pairs a query may see, float32 q, k, v and
output as the trainer passes them) times the kernel's executions, over their
device time."""

from chipbench.kernels import qwen3next_train
from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps
from chipbench.metrics.window_attn_ms_per_step import KERNEL


def read(run):
    got = kernel_ns_and_steps(run, KERNEL)
    if got is None:
        return None
    total_ns, n_events, _ = got
    t = run.cell["traffic"]
    least = qwen3next_train.attention_least_seconds(
        run.config, t["batch_size"], t["seq_len"], 4, run.peaks)
    run.rec.say("qnext_attn_roofline", bound=least["bound"],
                least_us_per_execution=least["seconds"] * 1e6,
                measured_us_per_execution=total_ns / n_events / 1e3,
                kernel_events=n_events)
    return 100.0 * least["seconds"] * n_events / (total_ns / 1e9)
