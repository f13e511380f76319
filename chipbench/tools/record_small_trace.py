"""Record the small device trace that the reduction's tests are checked on.

Run on the chip; writes ``chiprun_out/small_trace.xplane.pb``, which is then
committed as ``chipbench/tests/data/small_trace.xplane.pb``.  Three rounds of:
a jitted chain of matmuls under the annotation ``chipbench.work``, then 20 ms
of sleep under ``chipbench.sleep``.
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def small_matmul_chain(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    jax.block_until_ready(small_matmul_chain(x))
    out = os.path.join(ROOT, "chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("chipbench.work"):
            jax.block_until_ready(small_matmul_chain(x))
        with jax.profiler.TraceAnnotation("chipbench.sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    dst = os.path.join(ROOT, "chiprun_out", "small_trace.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(out, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst))


if __name__ == "__main__":
    main()
