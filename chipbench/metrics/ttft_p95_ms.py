"""95th percentile, over every request due in the window, of first token
minus due time; a request that failed has no first token and counts as never."""

from chipbench.harness.stats import percentile


def read(run):
    v = percentile([r.get("ttft_s", float("inf")) for r in run.plane.done], 95)
    return None if v is None else v * 1e3
