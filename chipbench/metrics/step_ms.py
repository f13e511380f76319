"""Host-clock milliseconds per optimizer step: each ``train()`` call of the
window divided by its steps, the median over calls."""

from chipbench.harness.stats import median


def read(run):
    calls = run.rec.spans_named("chipbench.train_call")
    v = median((c["t1"] - c["t0"]) / c["steps"] for c in calls)
    return None if v is None else v * 1e3
