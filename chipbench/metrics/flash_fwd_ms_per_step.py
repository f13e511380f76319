"""Device milliseconds the flash-attention forward kernel takes per optimizer
step: the summed durations of its events inside the ``train()`` calls the
trace holds whole, over those calls' steps."""

from chipbench.harness import xplane

#: the Pallas kernel of ``ops/pallas_attention.py`` in the device trace.  It
#: has no name of its own there (``jvp__.252 = ... custom-call(...),
#: custom_call_target="tpu_custom_call"``); it is the epoch program's only
#: Mosaic kernel, the attention backward being plain jnp
KERNEL = r"custom_call_target=\"tpu_custom_call\""


def kernel_ns_and_steps(run):
    if run.trace is None:
        return None
    calls = xplane.host_spans(run.trace, "chipbench.train_call")
    ops = xplane.matching(xplane.first_device(run.trace), KERNEL)
    if not calls or not ops:
        return None
    inside = [ev for c in calls for ev in xplane.within(ops, c)]
    steps = len(calls) * run.cell["traffic"]["steps_per_call"]
    return sum(ev.dur for ev in inside), len(inside), steps


def read(run):
    got = kernel_ns_and_steps(run)
    return None if got is None else got[0] / got[2] / 1e6
