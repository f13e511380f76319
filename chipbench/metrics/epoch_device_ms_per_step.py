"""Device milliseconds of the epoch program (``jit_sft_epoch`` on the ``XLA
Modules`` line) per optimizer step: each execution inside a whole
``fedml.sft.train`` call over the call's steps, the median."""

from chipbench.harness.stats import median
from chipbench.metrics.small_programs_per_call import epochs_and_others


def read(run):
    steps = run.cell["traffic"].get("steps_per_call")
    if not steps:
        return None
    v = median(m.dur / steps for epochs, _ in epochs_and_others(run)
               for m in epochs)
    return None if v is None else v / 1e6
