"""MFU probe on the real TPU (VERDICT r3 item 2 evidence).

One invocation = one north-star config variant (bucket count via argv),
so the persistent compilation cache's cross-process behavior is measured
for free: the first run of a config pays the compile, a re-run should
hit the cache.

Prints one JSON line: {buckets, compile_s, rounds_per_sec,
padded_samples_per_round, samples_per_sec, est_mfu}.

Usage:  python benchmarks/mfu_probe.py <n_buckets> [--no-cache]
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
N_BUCKETS = int(_pos[0]) if _pos else 4
USE_CACHE = "--no-cache" not in sys.argv
CONV_IMPL = "patches" if "--patches" in sys.argv else "lax"
NPZ_DIR = os.path.join(REPO, ".data_cache", "northstar")

import jax  # noqa: E402

import numpy as np  # noqa: E402

import fedml_tpu  # noqa: E402
from fedml_tpu.core.mlops.flight_recorder import chip_peak_flops  # noqa: E402
from fedml_tpu.runner import FedMLRunner  # noqa: E402
from fedml_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

if USE_CACHE:
    configure_compile_cache()
else:
    jax.config.update("jax_enable_compilation_cache", False)

RESNET56_FWD_FLOPS = 2 * 126.5e6
TRAIN_MULT = 3.0


def main() -> None:
    peak = chip_peak_flops()
    if peak is None:
        sys.exit(f"no peak FLOP/s known for device_kind "
                 f"{jax.devices()[0].device_kind!r} "
                 f"(fedml_tpu.constants.TPU_PEAK_BF16_FLOPS)")
    args = fedml_tpu.init(fedml_tpu.Config(
        dataset="cifar10", data_cache_dir=NPZ_DIR, model="resnet56",
        backend="parrot", partition_method="hetero", partition_alpha=0.5,
        client_num_in_total=100, client_num_per_round=10, comm_round=512,
        epochs=1, batch_size=32, learning_rate=0.05,
        frequency_of_the_test=1000, enable_tracking=False,
        compute_dtype="bfloat16", hetero_buckets=N_BUCKETS,
        conv_impl=CONV_IMPL))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    api = FedMLRunner(args, device, dataset, bundle).runner

    chunk = api.FUSED_CHUNK_ROUNDS
    rng = jax.random.PRNGKey(7)

    t0 = time.time()
    rng, sub = jax.random.split(rng)
    rms = api.run_rounds_fused(chunk, rng=sub)
    jax.block_until_ready(rms["train_loss"])
    compile_s = time.time() - t0

    n_meas = 2 * chunk
    t0 = time.time()
    rng, sub = jax.random.split(rng)
    rms = api.run_rounds_fused(n_meas, rng=sub)
    jax.block_until_ready(rms["train_loss"])
    dt = time.time() - t0
    rps = n_meas / dt

    if api.buckets is not None:
        padded = sum(b["k"] * b["nb"] for b in api.buckets) * api.bs
        eff_b = [b["k"] for b in api.buckets]
    else:
        padded = api.k * api.nb * api.bs
        eff_b = [api.k]
    flops_round = padded * RESNET56_FWD_FLOPS * TRAIN_MULT
    print(json.dumps({
        "buckets_requested": N_BUCKETS,
        "conv_impl": CONV_IMPL,
        "buckets_effective": len(eff_b),
        "clients_per_bucket": eff_b,
        "cache": USE_CACHE,
        "compile_s": round(compile_s, 1),
        "rounds_per_sec": round(rps, 4),
        "padded_samples_per_round": int(padded),
        "samples_per_sec": round(
            float(np.sum(np.asarray(rms["samples"]))) / dt, 1),
        "padded_samples_per_sec": round(padded * rps, 1),
        "est_mfu": round(flops_round * rps / peak, 4),
    }))


if __name__ == "__main__":
    main()
