"""95th percentile of how late the load generator sent a request (actual
submit minus due time): a starved generator must not read as a fast server."""

from chipbench.harness.stats import percentile


def read(run):
    v = percentile([r["late_s"] for r in run.plane.done], 95)
    return None if v is None else v * 1e3
