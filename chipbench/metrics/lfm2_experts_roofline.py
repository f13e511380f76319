"""The expert kernels' share of their roofline at decode in the LFM2 stage:
the least time of a token step's expert products
(``chipbench/kernels/lfm2_decode.py``: the touched experts' bfloat16 matrices
fetched once, or the picks' rows multiplied, whichever is longer; both as the
program counted them, means over the run's token steps), over the device
time of the ``moe_experts`` kernels' events inside the full dispatch's
executions, a token step."""

from chipbench.harness import xplane
from chipbench.kernels import lfm2_decode
from chipbench.metrics.lfm2_decode_roofline import (full_dispatches,
                                                    per_token_step)
from chipbench.metrics.moe_experts_ms_per_step import KERNEL


def read(run):
    got, mean = full_dispatches(run), per_token_step(run)
    if got is None or mean is None:
        return None
    ran, k = got
    ops = xplane.matching(xplane.first_device(run.trace), KERNEL)
    inside = [ev for m in ran for ev in xplane.within(ops, m)]
    if not inside:
        return None
    least = lfm2_decode.experts_least_seconds(
        run.config, mean["touched"], mean["picks"], 2, run.peaks)
    measured = sum(ev.dur for ev in inside) / (len(ran) * k) / 1e9
    run.rec.say("lfm2_experts_roofline", bound=least["bound"],
                least_us=least["seconds"] * 1e6, measured_us=measured * 1e6,
                kernel_events=len(inside))
    return 100.0 * least["seconds"] / measured
