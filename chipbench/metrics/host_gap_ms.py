"""Milliseconds of a ``train()`` call in which no operation ran on the
device (packing, copies in, a fresh optimizer state, the loss fetched back):
the call's length less the device's busy time inside it, median over the calls
the trace holds whole."""

from chipbench.harness import xplane
from chipbench.harness.stats import median


def read(run):
    if run.trace is None:
        return None
    ops = xplane.first_device(run.trace)
    calls = xplane.host_spans(run.trace, "chipbench.train_call")
    v = median((c.dur - xplane.busy_within_ns(ops, c)) for c in calls)
    return None if v is None else v / 1e6
