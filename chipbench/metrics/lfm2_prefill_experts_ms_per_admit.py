"""Device milliseconds an admission prefill of the LFM2 stage spends on its
routed layers: the scopes ``fedml.router`` and ``fedml.experts.*`` inside the
``jit_prefill`` executions, over the admission prefills the engine made in
the trace (its ``fedml.serve.prefill.t<bucket>`` spans)."""

from chipbench.harness import scopes
from chipbench.metrics.lfm2_experts_ms_per_token import under
from chipbench.metrics.prefill_device_ms_per_admit import PREFILL

WANTED = ("fedml.router", "fedml.experts")


def read(run):
    if run.trace is None:
        return None
    admits = sum(h.name.startswith(PREFILL) for h in run.trace.host)
    by = admits and scopes.time_by_scope(run.trace, scopes.of_run(run),
                                         r"^jit_prefill\b")
    if not by:
        return None
    return under(by, WANTED) / admits / 1e6
