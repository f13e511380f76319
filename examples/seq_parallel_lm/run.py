"""Long-context LM training with sequence (context) parallelism.

Shards the token axis over a `seq` mesh (ring attention: K/V blocks rotate
on ICI via ppermute, flash-kernel partials merged exactly) so no device ever
holds the full [B, T] context — the capability SURVEY §2.14 lists as absent
in the reference.  Runs on any device count:

    # 8 virtual CPU devices
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/seq_parallel_lm/run.py

    # real TPU(s): just run it; the mesh sizes to the available chips
    python examples/seq_parallel_lm/run.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.ml.engine.mesh import build_mesh
from fedml_tpu.models.functional_lm import init_lm_params
from fedml_tpu.parallel.seq_parallel import build_seq_parallel_train_step


def main() -> None:
    n = len(jax.devices())
    seq_shards = max(
        [s for s in (1, 2, 4, 8) if s <= n and 256 % s == 0])
    mesh = build_mesh({"seq": seq_shards})
    vocab, heads, t, b = 256, 8, 256, 4

    params = init_lm_params(jax.random.PRNGKey(0), vocab, dim=128,
                            layers=4, heads=heads, max_len=t)
    step, tok_sharding = build_seq_parallel_train_step(
        mesh, heads, strategy="ring", learning_rate=0.3)

    # byte-level "corpus": learn to continue a repeating pattern
    rng = np.random.RandomState(0)
    pattern = rng.randint(0, vocab, size=64)
    stream = np.tile(pattern, 64)

    n_iters = 80
    with mesh:
        for it in range(n_iters):
            start = rng.randint(0, len(stream) - t - 1, size=b)
            tokens = jnp.asarray(np.stack([stream[s:s + t] for s in start]))
            tokens = jax.device_put(tokens, tok_sharding)
            params, loss = step(params, tokens)
            if it % 10 == 0 or it == n_iters - 1:
                print(f"iter {it:3d}  seq_shards={seq_shards}  "
                      f"loss {float(loss):.4f}")
    assert float(loss) < 2.0, "pattern should be learnable"
    print("OK")


if __name__ == "__main__":
    main()
