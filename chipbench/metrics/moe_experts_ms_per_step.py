"""Device milliseconds the expert products take per optimizer step: the
summed durations of the ``moe_experts`` / ``moe_experts_t`` kernels' events
inside the ``train()`` calls the trace holds whole, over those calls'
steps."""

from chipbench.harness import xplane

#: the Pallas kernels of ``ops/routed_experts.py`` in the device trace
KERNEL = r"^%?moe_experts"


def kernel_ns_and_steps(run, pattern=KERNEL):
    """(summed ns, events, steps) of a kernel over the whole calls; nothing
    without a trace, whole calls or such events."""
    if run.trace is None:
        return None
    calls = xplane.host_spans(run.trace, "chipbench.train_call")
    ops = xplane.matching(xplane.first_device(run.trace), pattern)
    if not calls or not ops:
        return None
    inside = [ev for c in calls for ev in xplane.within(ops, c)]
    steps = len(calls) * run.cell["traffic"]["steps_per_call"]
    return sum(ev.dur for ev in inside), len(inside), steps


def read(run):
    got = kernel_ns_and_steps(run)
    return None if got is None else got[0] / got[2] / 1e6
