"""The spread a bound is set from: for each metric of two sets of result
lines, each set's median and the distance between its quartiles as a share of
its median (``statistics.quantiles(values, n=4)``), and the wider of the two.

    python3 chipbench/tools/spread.py set1.jsonl set2.jsonl
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.harness.stats import iqr_share  # noqa: E402


def main(paths) -> None:
    sets = []
    for path in paths:
        with open(path) as f:
            sets.append([json.loads(line) for line in f
                         if line.startswith("{")])
    for i, runs in enumerate(sets):
        print(f"set {i + 1}: {len(runs)} runs, correct "
              f"{sum(r['correct'] for r in runs)}, failed "
              f"{sum(r['failed'] for r in runs)} of "
              f"{sum(r['attempted'] for r in runs)}, peak "
              f"{max(r['device']['memory_peak_bytes'] for r in runs)}")
    for name in sets[0][0]["metrics"]:
        rows = []
        for runs in sets:
            v = [r["metrics"][name]["value"] for r in runs
                 if name in r["metrics"]]
            m = statistics.median(v)
            # the driver's test of tightness leaves out each set's run
            # farthest from the median
            near = sorted(v, key=lambda x: abs(x - m))[:-1]
            rows.append((m, iqr_share(v), iqr_share(near), v))
        widest = max(r[1] for r in rows)
        print(f"{name}: " + "; ".join(
            f"median {m:.6g} spread {100 * s:.2f}% ({100 * t:.2f}% without "
            f"its farthest run)" for m, s, t, _ in rows)
            + f"; widest {100 * widest:.2f}% -> five times {500 * widest:.1f}%")
        for *_, v in rows:
            print("    " + " ".join(f"{x:.6g}" for x in v))


if __name__ == "__main__":
    main(sys.argv[1:])
