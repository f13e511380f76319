"""Tokens trained per second: every token of every ``train()`` call of the
window, over the whole window (packing, copies and the loss fetch included)."""


def read(run):
    calls = run.rec.spans_named("chipbench.train_call")
    return sum(c["tokens"] for c in calls) / run.rec.window_s()
