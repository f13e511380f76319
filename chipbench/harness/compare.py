"""The comparison that decides ``correct``: every number compared is held to
a limit of its own, and each is printed beside it."""

import statistics
from typing import Dict, List


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float]) -> float:
    """Over the leaves of a tree of norms: the gap between the program's norm
    and the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))[:4]}")
    floor = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
               for k in want)


def against_limits(values: Dict[str, float],
                   limits: Dict[str, float]) -> List[Dict]:
    """One row per number compared: its value, its limit, whether it held.
    A number with no limit in the cell's file is an error, not a pass."""
    rows = []
    for name, value in values.items():
        if name not in limits:
            raise KeyError(f"the cell's file gives no limit for {name!r}")
        limit = float(limits[name])
        ok = bool(value == value and value <= limit)     # NaN never holds
        rows.append({"name": name, "value": float(value), "limit": limit,
                     "ok": ok})
    return rows
