"""95th percentile of the engine's admission prefill (admission to prefill
done), read from each finished request's record."""

from chipbench.harness.stats import percentile


def read(run):
    v = percentile([r["prefill_s"] for r in run.plane.done
                    if "prefill_s" in r], 95)
    return None if v is None else v * 1e3
