"""A model of ``KVCacheLLMEngine``'s loop, to choose a serving cell's rate and
window before any chip time is spent: how far a tail swings from seed to seed
under the cell's own generator, at a list of rates.

    python3 chipbench/tools/queue_model.py --workload serve.chat_steady \
        --rates 6,7,8 --seconds 40,51 --full-ms 195 --admit-ms 137

The loop as the engine runs it: admit every waiting request a free slot can
take (each a prefill), then one dispatch for all slots: ``ADMIT_TURBO_K``
tokens if anything was admitted, ``tokens_per_dispatch`` otherwise; tokens
reach their clients when the dispatch ends.  The two dispatch lengths (wall
milliseconds, ``--full-ms`` and ``--admit-ms``) come from a traced chip run.
Runs anywhere, needs no device, and measures nothing: what it prints is a
model's output and is never written under the name of a device metric.
``--sampled`` draws count, gaps and sizes independently instead (a sampled
Poisson stream), for comparison.
"""

import argparse
import collections
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import runner  # noqa: E402
from chipbench.harness.stats import iqr_share, percentile  # noqa: E402
from chipbench.traffic import arrivals  # noqa: E402


def sampled(traffic, rate, seconds, seed):
    """Count, gaps and sizes each drawn on their own."""
    rng = np.random.default_rng([seed, 0x5a])
    due = np.cumsum(rng.exponential(1.0 / rate, int(3 * rate * seconds) + 50))
    due = due[due < seconds]

    def draw(bins):
        w = np.array([b[2] for b in bins], float)
        pick = rng.choice(len(bins), len(due), p=w / w.sum())
        return np.array([rng.integers(bins[i][0], bins[i][1] + 1)
                         for i in pick])

    prompts, out = draw(traffic["prompt_tokens"]), draw(traffic["output_tokens"])
    return due, np.minimum(out, int(traffic["max_total_tokens"]) - prompts)


def simulate(due, out, slots, k_full, k_admit, full_s, admit_s, prefill_s):
    """Each request's time to first token and mean gap between tokens."""
    n, i, t = len(due), 0, 0.0
    waiting, left = collections.deque(), {}
    first, last = np.full(n, np.nan), np.full(n, np.nan)
    got = np.zeros(n, int)
    while i < n or waiting or left:
        while i < n and due[i] <= t:
            waiting.append(i)
            i += 1
        if not left and not waiting:
            t = due[i]
            continue
        admitted = 0
        while waiting and len(left) < slots:
            left[waiting.popleft()] = None
            admitted += 1
        k = k_admit if admitted else k_full
        t += (admit_s if admitted else full_s) + prefill_s * admitted
        for j in list(left):
            if left[j] is None:
                left[j], first[j] = out[j], t
            made = min(k, left[j])
            left[j] -= made
            got[j] += made
            last[j] = t
            if left[j] <= 0:
                del left[j]
    return first - due, ((last - first) / np.maximum(got - 1, 1))[got >= 2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", default="40")
    ap.add_argument("--full-ms", type=float, required=True)
    ap.add_argument("--admit-ms", type=float, required=True)
    ap.add_argument("--prefill-ms", type=float, default=6.0)
    ap.add_argument("--k-full", type=int, default=8)
    ap.add_argument("--k-admit", type=int, default=2)
    ap.add_argument("--sets", type=int, default=10, help="sets of six seeds")
    ap.add_argument("--sampled", action="store_true")
    opts = ap.parse_args()
    traffic = dict(runner.resolve(opts.workload)["cell"]["traffic"])

    for rate in (float(r) for r in opts.rates.split(",")):
        for seconds in (float(s) for s in opts.seconds.split(",")):
            traffic["arrivals"] = dict(traffic["arrivals"], rate_qps=rate)
            p95, t50 = [], []
            for seed in range(6 * opts.sets):
                if opts.sampled:
                    due, out = sampled(traffic, rate, seconds, seed)
                else:
                    plan = arrivals.requests(traffic, seconds, seed, 2)
                    due = np.array([r["due_s"] for r in plan])
                    out = np.array([r["max_new"] for r in plan])
                ttft, tbt = simulate(
                    due, out, int(traffic["max_batch"]), opts.k_full,
                    opts.k_admit, opts.full_ms / 1e3, opts.admit_ms / 1e3,
                    opts.prefill_ms / 1e3)
                p95.append(1e3 * percentile(ttft, 95))
                t50.append(1e3 * statistics.median(tbt))
            line = [f"{'sampled' if opts.sampled else 'cell'} {rate:g}/s "
                    f"{seconds:g}s (model)"]
            for name, v in (("ttft_p95_ms", p95), ("tbt_p50_ms", t50)):
                spreads = [iqr_share(v[k:k + 6]) for k in range(0, len(v), 6)]
                line.append(
                    f"{name} {min(v):.0f}-{max(v):.0f} median "
                    f"{statistics.median(v):.1f}, spread of a set of six: "
                    f"median {100 * statistics.median(spreads):.1f}% largest "
                    f"{100 * max(spreads):.1f}%")
            print("; ".join(line), flush=True)


if __name__ == "__main__":
    main()
