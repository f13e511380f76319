"""Device milliseconds the scan's forward kernel takes per optimizer step:
the ``gdn_fwd`` kernel's events (``ops/delta_rule.py``: the chunked gated delta
rule, one execution a delta-rule layer forward and one more when its block is
made again) inside the ``train()`` calls the trace holds whole, over those
calls' steps.  Nothing on a program without the kernel."""

from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps

KERNEL = r"^%?gdn_fwd"


def read(run):
    got = kernel_ns_and_steps(run, KERNEL)
    return None if got is None else got[0] / got[2] / 1e6
