"""Device milliseconds of one ``decode_multi`` execution at the engine's full
dispatch length, the median over the trace.

The engine runs two compiled variants of ``decode_multi``: the full one
(``tokens_per_dispatch`` tokens for every slot) and the short one that follows
an admission (``ADMIT_TURBO_K`` tokens).  The trace names them alike but for a
hash, and which of them runs more often depends on the traffic.  So they are
told apart by the work inside them: every token step yields logits of shape
``[max_batch, vocab]``, and the variant whose executions hold more operations
over that shape is the one that makes more tokens.  The other is
``admit_dispatch_device_ms``."""

import bisect
import collections
import re

from chipbench.harness import xplane
from chipbench.harness.stats import median

PROGRAM = r"decode_multi"


def variants_ns(run):
    """Durations of each variant's executions, the variant with the most
    token steps to an execution first."""
    if run.trace is None:
        return []
    logits = re.compile(r"\[%d,%d\]" % (int(run.cell["traffic"]["max_batch"]),
                                        int(run.config["vocab_size"])))
    starts = sorted(ev.start for ev in xplane.first_device(run.trace)
                    if logits.search(ev.name))
    by_name = collections.defaultdict(lambda: ([], []))
    for ev in xplane.matching(xplane.first_device_modules(run.trace), PROGRAM):
        steps, durs = by_name[ev.name]
        steps.append(bisect.bisect_right(starts, ev.end)
                     - bisect.bisect_left(starts, ev.start))
        durs.append(ev.dur)
    found = sorted(by_name.values(), key=lambda v: -median(v[0]))
    counts = [median(steps) for steps, _ in found]
    if len(set(counts)) < len(counts):      # cannot tell them apart
        return []
    return [durs for _, durs in found]


def read(run):
    found = variants_ns(run)
    return median(found[0]) / 1e6 if found else None
