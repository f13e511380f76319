"""What a Poisson stream of the cell's rate offers, with two things taken out
that would make the offered work itself differ from seed to seed by several
percent (which a check would read as noise of the system): the number of
arrivals in the window is fixed at ``round(rate * seconds)``, and their
inter-arrival gaps are the quantiles of Exp(rate) instead of a sample of it.
The seed draws the order, freely, so short gaps do come in runs as they do in
a sampled stream.  This is *not* a sampled Poisson process, and no cell's
reason may call it one.

    "arrivals": {"process": "exponential_gaps", "rate_qps": 6.0}
"""

from typing import Dict

import numpy as np


def due_times(arrivals: Dict, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds): gaps that are the (i + 1/2)/n quantiles of
    Exp(rate), in an order drawn from ``rng``."""
    rate = float(arrivals["rate_qps"])
    n = int(round(rate * seconds))
    u = (np.arange(n) + 0.5) / n
    due = np.cumsum((-np.log1p(-u) / rate)[rng.permutation(n)])
    if due.size and due[-1] >= seconds:
        due = due * (seconds * (1.0 - 1e-6) / due[-1])
    return due
