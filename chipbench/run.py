"""Run one cell of the benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the chips the cell asks for.  Refuses (non-zero exit, no result
line) unless JAX's devices are that many TPUs of a kind listed in
``chipbench/peaks.json``.  The last line of standard output is the result;
lines before it that start with ``CHIPBENCH`` say what was compared and seen.
With ``--trace 1`` the per-layer metrics are reported instead of the
end-to-end ones, from a run in which the profiler was on for part of the
window.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args()

    from chipbench.harness import runner

    found = runner.open_cell(opts.workload)
    dev, cache_dir = found["device"], found["cache_dir"]
    out_dir = os.path.join(ROOT, "chipbench", "out", opts.workload)
    os.makedirs(out_dir, exist_ok=True)
    print("CHIPBENCH " + json.dumps(
        {"what": "start", "workload": opts.workload, "seed": opts.seed,
         "seconds": opts.seconds, "trace": opts.trace,
         "compile_cache_dir": cache_dir, "device": dev["kind"]}), flush=True)

    kind = "per_layer" if opts.trace else "end_to_end"
    result = runner.run_cell(
        found["cell"], found["config"],
        runner.metrics_of(found["bench"], opts.workload, kind),
        opts.seed, opts.seconds, bool(opts.trace), dev, T_PROCESS, out_dir)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
