"""Device milliseconds the scan's backward kernel takes per optimizer step:
the ``gdn_bwd`` kernel's events (one execution a delta-rule layer) inside the
``train()`` calls the trace holds whole, over those calls' steps.  Nothing on
a program without the kernel."""

from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps

KERNEL = r"^%?gdn_bwd"


def read(run):
    got = kernel_ns_and_steps(run, KERNEL)
    return None if got is None else got[0] / got[2] / 1e6
