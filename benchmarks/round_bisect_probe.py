"""Bisect the real Parrot round: why does a vmapped k=10 step cost ~26x
its isolated cost?  Variants, all on the real 50k north-star data:

  A  full uniform round step (gather + vmap(scan) + aggregate), jitted
     standalone (fixed client ids, no 64-round fusion)
  B  same but batches PRE-GATHERED outside the jit (gather exonerated?)
  C  vmap(scan) alone on the pre-gathered batches (aggregation exonerated?)

Prints ms per variant; compile each once, then 8 timed calls.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

import fedml_tpu
from fedml_tpu.runner import FedMLRunner

NPZ_DIR = os.path.join(REPO, ".data_cache", "northstar")
ITERS = 8


_sync = jax.block_until_ready


def timed(name, fn, *args):
    out = fn(*args)
    _sync(out)
    t0 = time.time()
    for _ in range(ITERS):
        out = fn(*args)
        _sync(out)
    ms = (time.time() - t0) / ITERS * 1e3
    print(json.dumps({"variant": name, "ms": round(ms, 1)}))
    return out


def main():
    args = fedml_tpu.init(fedml_tpu.Config(
        dataset="cifar10", data_cache_dir=NPZ_DIR, model="resnet56",
        backend="parrot", partition_method="hetero", partition_alpha=0.5,
        client_num_in_total=100, client_num_per_round=10, comm_round=512,
        epochs=1, batch_size=32, learning_rate=0.05,
        frequency_of_the_test=1000, enable_tracking=False,
        compute_dtype="bfloat16", hetero_buckets=1))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    api = FedMLRunner(args, device, dataset, bundle).runner

    ids = jnp.asarray(np.arange(10, dtype=np.int32) * 7)
    rng = jax.random.PRNGKey(3)

    # A: the production uniform round step (jit with donation disabled so
    # repeated timing calls can reuse inputs)
    step_a = jax.jit(api._build_round_step())
    gv = api.global_vars
    st = api.server_state
    timed("A_full_round_step", step_a, api.device_data, gv, st, ids, rng)

    # B: gather once OUTSIDE, jit only vmap(scan)+aggregate
    batches = jax.jit(
        lambda data: api._gather_batches(data, ids, data["idx"], api.nb)
    )(api.device_data)
    jax.block_until_ready(batches["x"])
    in_axes_algo = api._in_axes_algo()
    aggregate = api._build_aggregate()
    weights = api.device_data["w"][ids]

    def body_b(gv2, st2, batches, rng2):
        rngs = jax.random.split(rng2, 10)
        new_vars, algo_out, metrics = jax.vmap(
            api.local_update, in_axes=(None, 0, 0, in_axes_algo))(
                gv2, batches, rngs, None)
        return aggregate(gv2, st2, ids, new_vars, algo_out, metrics,
                         weights)

    step_b = jax.jit(body_b)
    timed("B_pregathered_step", step_b, gv, st, batches, rng)

    # C: vmap(scan) only
    def body_c(gv2, batches, rng2):
        rngs = jax.random.split(rng2, 10)
        return jax.vmap(api.local_update, in_axes=(None, 0, 0, None))(
            gv2, batches, rngs, None)

    step_c = jax.jit(body_c)
    timed("C_vmap_scan_only", step_c, gv, batches, rng)

    # D: C but batches cast to bf16 first (storage-dtype effect)
    b16 = dict(batches, x=batches["x"].astype(jnp.bfloat16))
    timed("D_vmap_scan_bf16_batches", step_c, gv, b16, rng)


if __name__ == "__main__":
    main()
