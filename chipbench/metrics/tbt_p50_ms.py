"""Median over requests of the mean gap between output tokens."""

from chipbench.harness.stats import median


def read(run):
    v = median(r["tbt_s"] for r in run.plane.done if "tbt_s" in r)
    return None if v is None else v * 1e3
