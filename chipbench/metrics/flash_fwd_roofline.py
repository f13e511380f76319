"""The flash-attention forward kernel's share of its roofline: the least time
the chip could take for one call (``chipbench/kernels/flash_attention.py``:
causal half counted once; the larger of operations over the bf16 peak and
bytes over the HBM peak) over the kernel's mean device time per call."""

from chipbench.kernels import flash_attention
from chipbench.metrics.flash_fwd_ms_per_step import kernel_ns_and_steps


def read(run):
    got = kernel_ns_and_steps(run)
    if got is None:
        return None
    total_ns, n_calls, _ = got
    cfg, t = run.config, run.cell["traffic"]
    heads = cfg["n_head"]
    least = flash_attention.least_seconds(
        t["batch_size"], heads, t["seq_len"], cfg["n_embd"] // heads,
        4, run.peaks)           # the trainer's q, k, v are float32
    run.rec.say("flash_fwd_roofline", bound=least["bound"],
                least_us=least["seconds"] * 1e6,
                measured_us=total_ns / n_calls / 1e3, kernel_calls=n_calls)
    return 100.0 * least["seconds"] / (total_ns / n_calls / 1e9)
