"""Device milliseconds of the short ``decode_multi`` dispatch the engine runs
right after an admission (``ADMIT_TURBO_K`` tokens for every slot), the median
over the trace: every admitted request stalls the whole batch for this long.
See ``decode_device_ms`` for how the two variants are told apart."""

from chipbench.harness.stats import median
from chipbench.metrics.decode_device_ms import variants_ns


def read(run):
    found = variants_ns(run)
    return median(found[-1]) / 1e6 if len(found) > 1 else None
