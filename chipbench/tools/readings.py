"""Read, on the chip and at the cell's own size, the numbers a cell's limits
are set from: over a list of seeds, what sound runs of the program give
against the plain reference, and what the control (the reference computed in
the nearest precision below the one the configuration states) gives.

    python3 chipbench/tools/readings.py --workload sft.lora_1k \
        --seeds 11,12,13 --seconds 12

One process for all seeds (set-up is most of a run).  Training cells need no
window: set-up's first call is the call compared.  Serving cells run a short
window at the cell's own load.  Not part of a benchmark run.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: the control of each plane: the precision below the one its configuration
#: states; and, where the program today computes above the stated precision,
#: the reference computed in the stated one, as a second sound reading
CONTROLS = {"sft": ("bfloat16", "fp8"), "serve": ("bfloat16", "fp8")}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--leaves", help="directory for per-leaf norms (sft)")
    ap.add_argument("--controls-only", action="store_true",
                    help="sft: the controls' probe against the reference's, "
                         "no program")
    opts = ap.parse_args()

    from chipbench.harness import runner
    from chipbench.harness.record import Record

    found = runner.open_cell(opts.workload)
    cell, config = found["cell"], found["config"]
    ref, plane_mod = found["reference"], found["plane"]
    for seed in (int(s) for s in opts.seeds.split(",")):
        rec = Record()
        plane = plane_mod.Plane(cell, config, ref, seed, rec)
        row = {"workload": opts.workload, "seed": seed}
        if opts.controls_only:
            want = plane.reference_reading("float32", follow=False)
            for mode in CONTROLS["sft"]:
                row[mode] = plane.gaps(
                    plane.reference_reading(mode, follow=False), want)
            print("READING " + json.dumps(row), flush=True)
            continue
        plane.setup()
        if cell["plane"] == "sft":
            plane.finish()
            want = plane.reference_reading("float32")
            row["program"] = {**plane.gaps(plane.first, want),
                              **plane.after_sixteen(plane.first, want)}
            row["loss"] = {"program": plane.first["loss"],
                           "reference": want["loss"]}
            leaves = {"reference": want, "program": plane.first}
            for mode in CONTROLS["sft"]:
                got = plane.reference_reading(mode)
                leaves[mode] = got
                row[mode] = {**plane.gaps(got, want),
                             **plane.after_sixteen(got, want)}
            if opts.leaves:
                with open(os.path.join(opts.leaves, f"sft.leaves.{seed}.json"),
                          "w") as f:
                    json.dump(leaves, f)
        else:
            plane.window(opts.seconds)
            plane.finish()
            sample = plane.finished()
            row["failed"] = plane.failed
            ttft = sorted(r["ttft_s"] for r in plane.done if r["ok"])
            row["ttft_p95_ms"] = 1e3 * ttft[int(0.95 * len(ttft))]
            row["program"] = plane.gaps_on(sample, "float32")
            for mode in CONTROLS["serve"]:
                row[mode] = plane.gaps_on(sample, mode)
        print("READING " + json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
