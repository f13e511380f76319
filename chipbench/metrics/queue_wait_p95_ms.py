"""95th percentile of the engine's own queue wait (submit to admission into a
batch slot), read from each finished request's record."""

from chipbench.harness.stats import percentile


def read(run):
    v = percentile([r["queue_wait_s"] for r in run.plane.done
                    if "queue_wait_s" in r], 95)
    return None if v is None else v * 1e3
