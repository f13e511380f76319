"""Host milliseconds ``LLMTrainer.train()`` spends making a fresh optimizer
state (``self.tx.init``, the program's ``fedml.sft.opt_init`` span), summed
over a call and the median over the ``fedml.sft.train`` calls the trace holds
whole."""

from chipbench.harness import xplane
from chipbench.harness.stats import median

TRAIN = "fedml.sft.train"


def whole_calls(run):
    """The ``train()`` calls the trace holds from start to end: nothing where
    the run was not traced or the program opens no such span."""
    return [] if run.trace is None else xplane.host_spans(run.trace, TRAIN)


def span_ms_per_call(run, name):
    """A span's length summed over each whole call, median over calls; a
    span whose call the trace cuts is left out."""
    calls = whole_calls(run)
    spans = xplane.host_spans(run.trace, name) if calls else []
    if not spans:
        return None
    return median(sum(s.dur for s in xplane.within(spans, c))
                  for c in calls) / 1e6


def read(run):
    return span_ms_per_call(run, "fedml.sft.opt_init")
