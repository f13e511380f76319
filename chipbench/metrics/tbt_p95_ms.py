"""95th percentile over requests of a request's mean gap between output
tokens, (last - first token time) / (n - 1), from the client's side."""

from chipbench.harness.stats import percentile


def read(run):
    v = percentile([r.get("tbt_s", float("inf")) for r in run.plane.done
                    if r["max_new"] >= 2], 95)
    return None if v is None else v * 1e3
