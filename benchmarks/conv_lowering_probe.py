"""How should per-client convs lower on the MXU? (VERDICT r3 item 2)

The Parrot hot path vmaps local SGD over clients; after the first step
every client has its OWN weights, so jax's conv batching rule lowers
vmapped convs to feature_group_count=K grouped convolutions.  The mfu
probe showed grouped lowering is SLOWER per sample than running clients
one at a time — this microbench quantifies the alternatives on the three
ResNet-56 stage shapes:

  seq      — K sequential plain convs, batch 32 (what 10 buckets of 1 do)
  grouped  — one vmapped conv, per-client weights (XLA grouped lowering)
  patches  — im2col (conv_general_dilated_patches) + einsum: under vmap
             this is a BATCHED MATMUL, the MXU-native form
  shared   — one conv at batch K*32 with shared weights (upper bound)

Prints one JSON line per (stage, variant): {stage, variant, us_per_step,
samples_per_sec}.
"""

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

K = 10       # clients
BS = 32
STAGES = [   # (H, W, Cin, Cout) — ResNet-56 stage conv shapes
    (32, 32, 16, 16),
    (16, 16, 32, 32),
    (8, 8, 64, 64),
]
DT = jnp.bfloat16


def conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def patches_conv(x, w):
    """im2col + matmul: identical math to conv(), but under vmap the
    contraction stays a plain (batched) matmul instead of a grouped conv."""
    kh, kw, cin, cout = w.shape
    p = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))    # [N,H,W,cin*kh*kw]
    return jnp.einsum("nhwp,pc->nhwc", p,
                      w.transpose(2, 0, 1, 3).reshape(-1, cout))


def bench(fn, *args, iters=50):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters


def main():
    rng = np.random.RandomState(0)
    for (h, w_, cin, cout) in STAGES:
        x1 = jnp.asarray(rng.randn(BS, h, w_, cin), DT)
        xk = jnp.asarray(rng.randn(K, BS, h, w_, cin), DT)
        wk = jnp.asarray(rng.randn(K, 3, 3, cin, cout) * 0.1, DT)
        w1 = wk[0]
        xs = jnp.asarray(rng.randn(K * BS, h, w_, cin), DT)

        @jax.jit
        def seq(xk, wk):
            outs = [conv(xk[i], wk[i]) for i in range(K)]
            return jnp.stack(outs)

        grouped = jax.jit(jax.vmap(conv))
        patches_v = jax.jit(jax.vmap(patches_conv))
        shared = jax.jit(conv)
        patches_1 = jax.jit(patches_conv)

        stage = f"{h}x{w_}x{cin}->{cout}"
        for name, f, a in [
            ("seq", seq, (xk, wk)),
            ("grouped", grouped, (xk, wk)),
            ("patches", patches_v, (xk, wk)),
            ("shared", shared, (xs, w1)),
            ("patches_1client", patches_1, (x1, w1)),
            ("conv_1client", shared, (x1, w1)),
        ]:
            us = bench(f, *a) * 1e6
            n = K * BS if name not in ("patches_1client",
                                       "conv_1client") else BS
            print(json.dumps({"stage": stage, "variant": name,
                              "us_per_step": round(us, 1),
                              "msamples_per_sec": round(n / us, 3)}))


if __name__ == "__main__":
    main()
