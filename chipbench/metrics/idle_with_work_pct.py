"""Per cent of the traced window in which the device stood idle with work
offered: all its idle time (the window less its busy time) that does not lie
under a ``fedml.serve.empty`` span (``idle_empty_pct``): the host in the
device's way.  The two add up to the cell's idle share."""

from chipbench.metrics.idle_empty_pct import idle_split_ns


def read(run):
    got = idle_split_ns(run)
    return None if got is None else 100.0 * got[1] / got[2]
