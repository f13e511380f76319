"""Device milliseconds the routed layers' combine takes per optimizer step:
the summed durations of the ``moe_sum_picks`` kernel's events (over a
token's picks that landed, its rows times their weights; two executions a
routed layer a step, the forward's and the backward's sum of the rows'
gradients: what the rematerialised forward would sum nothing reads) inside
the ``train()`` calls the trace holds whole, over those calls' steps.
Nothing where the program has no such kernel."""

from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps

#: the combine's kernel of ``ops/routed_experts.py`` by its own name
KERNEL = r"^%?moe_sum_picks"


def read(run):
    got = kernel_ns_and_steps(run, KERNEL)
    return None if got is None else got[0] / got[2] / 1e6
