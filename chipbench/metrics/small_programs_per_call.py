"""Programs a ``train()`` call runs on the device beside the epoch program:
``XLA Modules`` executions that start inside a whole ``fedml.sft.train`` span
and are not ``jit_sft_epoch`` (the fresh optimizer state is made an array a
program), median over calls."""

import re

from chipbench.harness import xplane
from chipbench.harness.stats import median
from chipbench.metrics.opt_init_ms_per_call import whole_calls

EPOCH = re.compile(r"^jit_sft_epoch\b")


def epochs_and_others(run):
    """For each whole call, the epoch program's executions and the other
    programs' that start inside it."""
    modules = [] if run.trace is None else \
        xplane.first_device_modules(run.trace)
    out = []
    for c in whole_calls(run):
        inside = [m for m in modules if c.start <= m.start < c.end]
        out.append(([m for m in inside if EPOCH.search(m.name)],
                    [m for m in inside if not EPOCH.search(m.name)]))
    return out


def read(run):
    return median(len(others) for _, others in epochs_and_others(run))
