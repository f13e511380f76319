"""The flash-attention forward kernel's share of its roofline at heads of 192
(``nope + rope`` for q and k, ``v`` for the values): the least time for one
execution in every block (``chipbench/kernels/gigachat_train.py``: the pairs a
query may see, float32 q, k, v and output as the trainer passes them) over
the kernel's device time for as many executions."""

from chipbench.kernels import gigachat_train
from chipbench.metrics.window_attn_ms_per_step import KERNEL
from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps


def read(run):
    got = kernel_ns_and_steps(run, KERNEL)
    if got is None:
        return None
    total_ns, n_events, _ = got
    t = run.cell["traffic"]
    least = gigachat_train.attention_least_seconds(
        run.config, t["batch_size"], t["seq_len"], 4, run.peaks)
    sweeps = n_events / least["blocks"]
    run.rec.say("giga_attn_roofline", bound=least["bound"],
                least_us_per_sweep=least["seconds"] * 1e6,
                measured_us_per_sweep=total_ns / sweeps / 1e3,
                kernel_events=n_events)
    return 100.0 * least["seconds"] * sweeps / (total_ns / 1e9)
