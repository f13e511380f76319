"""Several windows of one cell in one process, to see where a slow run's time
went: one set-up, then ``--windows`` windows of ``--seconds`` each; the
plane's own ``CHIPBENCH`` lines say what each unit of work took.  No check, no
result line.  Training cells only (a serving window ends with its engine).

    python3 chipbench/tools/windows.py --workload sft.lora_1k --windows 8 --seconds 51
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=3)
    opts = ap.parse_args()

    from chipbench.harness import runner
    from chipbench.harness.record import Record

    found = runner.open_cell(opts.workload)
    cell, config = found["cell"], found["config"]
    ref, plane_mod = found["reference"], found["plane"]
    rec = Record()
    plane = plane_mod.Plane(cell, config, ref, opts.seed, rec)
    plane.setup()
    for _ in range(opts.windows):
        rec.spans.clear()
        plane.window(opts.seconds)


if __name__ == "__main__":
    main()
