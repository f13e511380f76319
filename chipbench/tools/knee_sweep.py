"""Find the highest rate a serving cell sustains: one engine, one window per
rate at the cell's own mix, lowest rate first.

    python3 chipbench/tools/knee_sweep.py --workload serve.chat_steady \
        --rates 4,8,12,16,20 --seconds 20

A rate is sustained while the tail of time-to-first-token stays flat and the
requests left unfinished when the window closes do not pile up.  The cell's
rate is then written, as a number, into its file: the largest share of the
knee at which the slots do not fill in some runs and not in others.  Run once, by hand, on the chip; not part of a benchmark run.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    opts = ap.parse_args()

    from chipbench.harness import runner
    from chipbench.harness.record import Record, now
    from chipbench.harness.stats import median, percentile

    found = runner.open_cell(opts.workload)
    cell, config = copy.deepcopy(found["cell"]), found["config"]
    ref, plane_mod = found["reference"], found["plane"]
    rec = Record()
    plane = plane_mod.Plane(cell, config, ref, opts.seed, rec)
    plane.setup()
    for i, rate in enumerate(float(r) for r in opts.rates.split(",")):
        cell["traffic"]["arrivals"] = {"process": "exponential_gaps",
                                       "rate_qps": rate}
        plane.seed = opts.seed + i
        rec.samples.clear()
        plane.window(opts.seconds)
        t_close = now()
        unfinished = sum(s.future is not None and not s.future.done()
                         for s in plane.sent)
        plane.drain()
        rows = plane.done
        ok = [r for r in rows if r["ok"]]
        tokens = sum(r["n"] for r in ok)
        occ = rec.samples.get("occupancy", [])
        p = lambda key, q: (lambda v: None if v is None else v * 1e3)(
            percentile([r.get(key, float("inf")) for r in rows], q))
        print("SWEEP " + json.dumps({
            "rate_qps": rate, "sent": len(rows), "failed": plane.failed,
            "unfinished_at_close": unfinished,
            "drain_s": now() - t_close,
            "ttft_p50_ms": median(r["ttft_s"] for r in ok) * 1e3,
            "ttft_p95_ms": p("ttft_s", 95), "tbt_p95_ms": p("tbt_s", 95),
            "queue_wait_p95_ms": p("queue_wait_s", 95),
            "late_p95_ms": p("late_s", 95),
            "occupancy_mean": sum(occ) / max(len(occ), 1),
            "occupancy_last_quarter": (sum(occ[-len(occ) // 4:])
                                       / max(len(occ[-len(occ) // 4:]), 1)),
            # what it completed, over the window and the drain it needed
            "completed_tokens_per_s": tokens / (
                opts.seconds + now() - t_close)}), flush=True)
    plane.finish()


if __name__ == "__main__":
    main()
