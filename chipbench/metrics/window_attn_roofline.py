"""The flash-attention forward kernel's share of its roofline over the full
and the window layers of the routed family: the least time for one execution
in every layer (``chipbench/kernels/smallthinker_train.py``: only the pairs
a query may see, float32 q, k, v and output as the trainer passes them) over
the kernel's device time for as many executions."""

from chipbench.kernels import smallthinker_train
from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps
from chipbench.metrics.window_attn_ms_per_step import KERNEL


def read(run):
    got = kernel_ns_and_steps(run, KERNEL)
    if got is None:
        return None
    total_ns, n_events, _ = got
    cfg, t = run.config, run.cell["traffic"]
    least = smallthinker_train.attention_least_seconds(
        cfg, t["batch_size"], t["seq_len"], 4, run.peaks)
    sweeps = n_events / int(cfg["num_hidden_layers"])
    run.rec.say("window_attn_roofline", bound=least["bound"],
                least_us_per_sweep=least["seconds"] * 1e6,
                measured_us_per_sweep=total_ns / sweeps / 1e3,
                kernel_events=n_events)
    return 100.0 * least["seconds"] * sweeps / (total_ns / 1e9)
