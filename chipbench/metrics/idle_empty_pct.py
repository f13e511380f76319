"""Per cent of the traced window in which the device stood idle because no
work was offered: the part of the first device's gaps of 5 us and more
(``xplane.gaps``) that lies under a ``fedml.serve.empty`` span, the engine
thread's wait for a request with no request in flight, over the traced
window's length.  Nothing where the program opens no such span."""

from chipbench.harness import xplane

EMPTY = "fedml.serve.empty"


def _overlap_ns(a, b):
    """The length two sorted lists of disjoint intervals share."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]), 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_split_ns(run):
    """(idle ns under an `EMPTY` span, all other idle ns, the traced
    window's ns); None without a trace, device operations or such spans."""
    if run.trace is None or run.rec.traced is None:
        return None
    waits = xplane.union((h.start, h.end) for h in run.trace.host
                         if h.name == EMPTY)
    if not waits:
        return None
    ops = xplane.first_device(run.trace)
    if not ops:
        return None
    empty = _overlap_ns([g for g in xplane.gaps(ops)
                         if g[1] - g[0] >= xplane.SHORT_GAP_NS], waits)
    window = (run.rec.traced["t1"] - run.rec.traced["t0"]) * 1e9
    return empty, window - xplane.busy_ns(ops) - empty, window


def read(run):
    got = idle_split_ns(run)
    return None if got is None else 100.0 * got[0] / got[2]
