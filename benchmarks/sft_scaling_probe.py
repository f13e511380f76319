"""SFT batch-size MFU inversion probe + large-model datum (VERDICT r4
item 6 / weak #3).

Round 4 measured MFU 0.4925 at bs4 but 0.4258/0.4394 at bs8/16 on
GPT-2-small — bigger batches should not be slower per token.  Hypothesis:
the bench's `reference_attention` materializes [B, H, T, T] score
matrices (bs16: 12 GB of bf16 score traffic per layer fwd+bwd at T=1024),
so the step goes HBM-bound as B grows.  This probe measures every (bs,
attention-impl) pair, plus remat and a ~350M-class (GPT-2-medium
geometry) config, on the real chip.

Usage: python benchmarks/sft_scaling_probe.py
Prints one PROBE_JSON line; results go into BENCH_NOTES round 5.
"""

import json
import os
import subprocess
import sys
import time
from functools import partial
from statistics import median

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

SEQ = 1024

#: (key, dim, layers, heads, bs, attn, remat, accum)
CONFIGS = [
    ("small_ref_bs4", 768, 12, 12, 4, "ref", False, 1),
    ("small_ref_bs8", 768, 12, 12, 8, "ref", False, 1),
    ("small_ref_bs16", 768, 12, 12, 16, "ref", False, 1),
    ("small_flash_bs4", 768, 12, 12, 4, "flash", False, 1),
    ("small_flash_bs8", 768, 12, 12, 8, "flash", False, 1),
    ("small_flash_bs16", 768, 12, 12, 16, "flash", False, 1),
    ("small_ref_bs8_remat", 768, 12, 12, 8, "ref", True, 1),
    ("small_ref_bs16_accum4", 768, 12, 12, 16, "ref", False, 4),
    ("medium_flash_bs4_remat", 1024, 24, 16, 4, "flash", True, 1),
    ("medium_ref_bs4_remat", 1024, 24, 16, 4, "ref", True, 1),
    ("medium_ref_bs8_remat", 1024, 24, 16, 8, "ref", True, 1),
]


def flops_per_token(dim, layers, vocab, remat):
    fwd = layers * (24 * dim * dim + 4 * SEQ * dim) + 2 * dim * vocab
    return fwd * (4.0 if remat else 3.0)


def measure(dim, layers, heads, vocab, bs, attn, remat, accum=1):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from fedml_tpu.core.mlops.flight_recorder import chip_peak_flops
    from fedml_tpu.models.functional_lm import init_lm_params, lm_loss
    from fedml_tpu.ops.pallas_attention import flash_attention
    from fedml_tpu.parallel.ring_attention import reference_attention

    peak = chip_peak_flops()
    if peak is None:
        sys.exit(f"no peak FLOP/s known for device_kind "
                 f"{jax.devices()[0].device_kind!r} "
                 f"(fedml_tpu.constants.TPU_PEAK_BF16_FLOPS)")
    params = init_lm_params(jax.random.PRNGKey(0), vocab, dim=dim,
                            layers=layers, heads=heads, max_len=SEQ)
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)
    attn_fn = (partial(reference_attention, causal=True)
               if attn == "ref" else partial(flash_attention, causal=True))

    def loss_fn(p, t):
        p16 = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), p)
        return lm_loss(p16, t, heads, attn_fn, remat=remat)

    @jax.jit
    def step(params, opt_state, tokens):
        if accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        else:
            # true gradient accumulation: per-microbatch backward inside
            # a scan (activation memory = ONE microbatch), summed grads,
            # one optimizer update
            mb = tokens.reshape(accum, bs // accum, SEQ)

            def body(g_acc, t):
                l, g = jax.value_and_grad(loss_fn)(params, t)
                return jax.tree_util.tree_map(jnp.add, g_acc, g), l

            g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
            grads, losses = jax.lax.scan(body, g0, mb)
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            loss = jnp.mean(losses)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, vocab, (bs, SEQ)), jnp.int32)
    t0 = time.time()
    try:
        p, o, loss = step(params, opt_state, tokens)
        jax.block_until_ready(loss)
    except Exception as e:  # noqa: BLE001 — OOM is a result
        return {"error": str(e)[:160]}
    compile_s = time.time() - t0
    for _ in range(2):
        p, o, loss = step(p, o, tokens)
    jax.block_until_ready(loss)
    windows = []
    for _ in range(8):
        t0 = time.time()
        for _ in range(2):
            p, o, loss = step(p, o, tokens)
        jax.block_until_ready(loss)
        windows.append((time.time() - t0) / 2)
    dt = median(windows)
    tok_s = bs * SEQ / dt
    return {"step_ms": round(dt * 1e3, 1),
            "tokens_per_sec": round(tok_s, 0),
            "mfu": round(tok_s * flops_per_token(dim, layers, vocab,
                                                 remat) / peak, 4),
            "compile_s": round(compile_s, 1)}


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        key = sys.argv[2]
        cfg = next(c for c in CONFIGS if c[0] == key)
        _, dim, layers, heads, bs, attn, remat, accum = cfg
        res = measure(dim, layers, heads, 50257, bs, attn, remat, accum)
        print("ONE_JSON " + json.dumps(res))
        return
    # one SUBPROCESS per config: a prior config's OOM must not poison the
    # allocator for later ones (observed: post-OOM RESOURCE_EXHAUSTED on
    # an init that fits a clean chip).  This parent never touches JAX, so
    # each child in turn is the one process that holds the chip
    out = {}
    for cfg in CONFIGS:
        key = cfg[0]
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one", key],
                capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            # one hung config must not discard the whole sweep
            out[key] = {"error": "timeout (900s)"}
            print(key, out[key], file=sys.stderr)
            continue
        res = {"error": proc.stderr.strip()[-200:] or "no output"}
        for line in reversed(proc.stdout.splitlines()):
            if line.startswith("ONE_JSON "):
                res = json.loads(line[len("ONE_JSON "):])
                break
        out[key] = res
        print(key, res, file=sys.stderr)
    print("PROBE_JSON " + json.dumps(out))


if __name__ == "__main__":
    main()
