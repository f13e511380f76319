"""Cost of ONE vmapped local-SGD step vs client count (the real hot path).

Uses the production build_local_update on ResNet-56 with a single padded
batch (nb=1) and measures wall per jitted call for K in {1,2,5,10}
vmapped clients.  If per-call cost grows faster than K, the vmapped
(grouped-conv) lowering is the bottleneck and fewer clients per bucket
win; if it grows slower than K, bigger buckets win.

Prints one JSON line per K.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import fedml_tpu
from fedml_tpu.ml.engine.local_update import build_local_update

BS = 32
ITERS = 30


def main():
    args = fedml_tpu.Config(model="resnet56", dataset="cifar10",
                            compute_dtype="bfloat16", learning_rate=0.05,
                            epochs=1)
    bundle = fedml_tpu.model.create(args, 10)
    variables = bundle.init_variables(jax.random.PRNGKey(0), batch_size=8)
    local_update = build_local_update(bundle, args)
    rng = np.random.RandomState(0)

    for k in (1, 2, 5, 10):
        batches = {
            "x": jnp.asarray(rng.randn(k, 1, BS, 32, 32, 3), jnp.bfloat16),
            "y": jnp.asarray(rng.randint(0, 10, (k, 1, BS)), jnp.int32),
            "mask": jnp.ones((k, 1, BS), jnp.float32),
        }
        rngs = jax.random.split(jax.random.PRNGKey(1), k)
        step = jax.jit(jax.vmap(local_update, in_axes=(None, 0, 0, None)))
        out = step(variables, batches, rngs, None)
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(ITERS):
            out = step(variables, batches, rngs, None)
            jax.block_until_ready(out)
        ms = (time.time() - t0) / ITERS * 1e3
        print(json.dumps({"k_clients": k, "ms_per_step": round(ms, 2),
                          "ms_per_client_step": round(ms / k, 3),
                          "samples_per_sec": round(k * BS / ms * 1e3, 1)}))


if __name__ == "__main__":
    main()


def probe_nb(k=5, nb=8):
    """Does per-batch cost stay flat as the in-client scan lengthens?"""
    args = fedml_tpu.Config(model="resnet56", dataset="cifar10",
                            compute_dtype="bfloat16", learning_rate=0.05,
                            epochs=1)
    bundle = fedml_tpu.model.create(args, 10)
    variables = bundle.init_variables(jax.random.PRNGKey(0), batch_size=8)
    local_update = build_local_update(bundle, args)
    rng = np.random.RandomState(0)
    batches = {
        "x": jnp.asarray(rng.randn(k, nb, BS, 32, 32, 3), jnp.bfloat16),
        "y": jnp.asarray(rng.randint(0, 10, (k, nb, BS)), jnp.int32),
        "mask": jnp.ones((k, nb, BS), jnp.float32),
    }
    rngs = jax.random.split(jax.random.PRNGKey(1), k)
    step = jax.jit(jax.vmap(local_update, in_axes=(None, 0, 0, None)))
    out = step(variables, batches, rngs, None)
    jax.block_until_ready(out)
    t0 = time.time()
    iters = max(4, ITERS // nb)
    for _ in range(iters):
        out = step(variables, batches, rngs, None)
        jax.block_until_ready(out)
    ms = (time.time() - t0) / iters * 1e3
    print(json.dumps({"k_clients": k, "nb": nb,
                      "ms_per_step": round(ms, 2),
                      "ms_per_batch_step": round(ms / nb, 3),
                      "samples_per_sec": round(k * nb * BS / ms * 1e3, 1)}))
