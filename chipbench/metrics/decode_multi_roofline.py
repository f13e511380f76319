"""A full ``decode_multi`` dispatch against the memory roofline of the bytes
it cannot avoid: every weight read once per token step
(``chipbench/kernels/gpt2_decode.py``; the live cache is left out, so the
share is understated), over the HBM peak, over the dispatch's median device
time."""

from chipbench.harness.stats import median
from chipbench.kernels import gpt2_decode
from chipbench.metrics.decode_device_ms import variants_ns


def read(run):
    found = variants_ns(run)
    if not found:
        return None
    ns = median(found[0])
    least = (run.plane.tokens_per_dispatch * gpt2_decode.weight_bytes(run.config, 2)
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
