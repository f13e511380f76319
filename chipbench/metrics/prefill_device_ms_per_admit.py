"""Device milliseconds an admission costs before the dispatch behind it can
start: the summed lengths of the ``jit_prefill`` and ``jit_scatter_cache_row``
executions (``XLA Modules``) over the number of admission prefills the engine
made in the trace (its ``fedml.serve.prefill.t<bucket>`` spans)."""

from chipbench.harness import xplane

PROGRAMS = r"^jit_(prefill|scatter_cache_row)\b"
PREFILL = "fedml.serve.prefill.t"


def read(run):
    if run.trace is None:
        return None
    admits = sum(h.name.startswith(PREFILL) for h in run.trace.host)
    if not admits:
        return None
    ran = xplane.matching(xplane.first_device_modules(run.trace), PROGRAMS)
    return sum(m.dur for m in ran) / admits / 1e6
