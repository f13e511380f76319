"""Median time to first token from the due time: the steadier statistic
beside the 95th percentile."""

from chipbench.harness.stats import median


def read(run):
    v = median(r["ttft_s"] for r in run.plane.done if "ttft_s" in r)
    return None if v is None else v * 1e3
