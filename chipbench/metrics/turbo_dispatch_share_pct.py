"""Share of the engine's dispatches that ran short: ``fedml.serve.dispatch.k<k>``
spans with ``k`` under the engine's ``tokens_per_dispatch`` (the dispatch that
follows an admission), of all dispatch spans in the trace."""

from chipbench.metrics.loop_host_ms_per_dispatch import DISPATCH, dispatches


def read(run):
    found = dispatches(run)
    if not found:
        return None
    full = int(run.plane.tokens_per_dispatch)
    short = sum(int(h.name[len(DISPATCH):]) < full for h in found)
    return 100.0 * short / len(found)
