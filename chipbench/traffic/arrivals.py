"""Open-loop arrivals and request sizes for a serving cell.

An arrival process is a module of its own beside this one, found by the name
in the cell's file (``"arrivals": {"process": "<name>", ...}``); it has one
function, ``due_times(arrivals, seconds, rng)``.  Request sizes are one fixed
set for every seed, in an order drawn from the seed, freely: sizes and gaps
fall as they fall, so long answers do sometimes come together.
"""

import importlib
from typing import Dict, List, Sequence

import numpy as np


def due_times(arrivals: Dict, seconds: float, rng) -> np.ndarray:
    kind = arrivals["process"]
    try:
        process = importlib.import_module("chipbench.traffic." + kind)
    except ImportError:
        raise ValueError(f"unknown arrival process {kind!r}: no "
                         f"chipbench/traffic/{kind}.py") from None
    return process.due_times(arrivals, seconds, rng)


def from_bins(bins: Sequence[Sequence[float]], n: int) -> np.ndarray:
    """``n`` whole-number sizes from ``[[low, high, weight], ...]``: each bin
    gets its share of ``n`` (largest remainders first) and its sizes are
    spread evenly over ``low..high``.  No seed: the set is fixed."""
    w = np.asarray([b[2] for b in bins], float)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts))[:n - counts.sum()]:
        counts[i] += 1
    out = [np.round(np.linspace(lo, hi, c + 2)[1:-1]).astype(int)
           for (lo, hi, _), c in zip(bins, counts) if c]
    return np.concatenate(out) if out else np.zeros((0,), int)


def requests(traffic: Dict, seconds: float, seed: int, vocab: int) -> List[Dict]:
    """The requests due in a window of ``seconds``: ``due_s``, ``prompt``
    (token ids) and ``max_new``.  Prompt and answer lengths are paired by a
    fixed shuffle, so every seed offers the same pairs; the seed draws their
    order, the order of the gaps and the prompts' tokens."""
    rng = np.random.default_rng([int(seed), 0xa77])
    due = due_times(traffic["arrivals"], seconds, rng)
    n = len(due)
    fixed = np.random.default_rng(0)
    prompts = fixed.permutation(from_bins(traffic["prompt_tokens"], n))
    outputs = fixed.permutation(from_bins(traffic["output_tokens"], n))
    outputs = np.minimum(outputs, int(traffic["max_total_tokens"]) - prompts)
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(0, vocab, int(prompts[j])).astype(np.int32),
             "max_new": int(outputs[j])}
            for i, j in enumerate(rng.permutation(n))]
