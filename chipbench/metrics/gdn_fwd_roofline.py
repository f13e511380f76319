"""The scan's forward kernel's share of its roofline: the least time for one
execution over one layer (``chipbench/kernels/qwen3next_train.py``: the
chunked form's operations at chunks of 64 whatever chunk the kernel takes;
float32 q, k, v, ``g``, ``beta`` read and o written once) times the kernel's
executions, over their device time."""

from chipbench.kernels import qwen3next_train
from chipbench.metrics.gdn_fwd_ms_per_step import KERNEL
from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps


def read(run, kernel=KERNEL, backward=False, line="gdn_fwd_roofline"):
    got = kernel_ns_and_steps(run, kernel)
    if got is None:
        return None
    total_ns, n_events, _ = got
    t = run.cell["traffic"]
    least = qwen3next_train.scan_least_seconds(
        run.config, t["batch_size"], t["seq_len"], backward, run.peaks)
    run.rec.say(line, bound=least["bound"],
                least_us_per_execution=least["seconds"] * 1e6,
                measured_us_per_execution=total_ns / n_events / 1e3,
                kernel_events=n_events)
    return 100.0 * least["seconds"] * n_events / (total_ns / 1e9)
