"""Absolute LLM-plane performance on the real TPU (VERDICT r3 item 1).

Two measurements, both on a GPT-2-small-class transformer (dim 768,
12 layers, 12 heads, vocab 50257 — the size class the reference's HF
trainer fine-tunes, `train/llm/hf_trainer.py`, and its scalellm wrapper
serves, `scalellm/__init__.py`):

* **SFT train step** — the functional LM (`models/functional_lm.py`)
  under one jitted AdamW step, bf16 matmuls / fp32 optimizer, seq 1024.
  Reports tokens/s and analytic MFU against the chip's bf16 peak.
  FLOP accounting counts what the program EXECUTES (full T x T attention
  scores -- the einsum materializes both triangles), so MFU is never
  flattered by a causal discount the hardware doesn't take:
      fwd/token  = L*(24*D^2 + 4*T*D) + 2*D*V
      train/token = 3x fwd (no remat) or 4x fwd (remat re-runs the fwd)
* **Serving** — `KVCacheLM` prefill/decode at the same size, bf16:
  TTFT (prefill + first decode dispatch, batch 1) and steady-state decode
  tokens/s vs batch size via the on-device multi-token sampler
  (`decode_multi`), replacing round 3's relative "15.7x" with absolute
  numbers.

MEASUREMENT NOTE: every timed window ends in `jax.block_until_ready`
(chip_smoke.py checks on the chip that it waits as long as a host fetch
does), and each metric is the MEDIAN of N short windows; every window is
kept in the results file so the spread stays visible.

Prints ONE JSON line and writes `benchmarks/llm_bench_results.json`.
Regression guard: if `benchmarks/llm_bench_floor.json` exists (committed
after the first accepted run), the script exits 1 when any guarded metric
falls below floor * 0.8 — same contract as the north-star accuracy guard.

Usage: python benchmarks/llm_bench.py [--quick] [--bs N] [--remat]
  --quick  skip the batch-size sweeps (train bs 4 only, decode batches
           8/128 only; results go to llm_bench_results_quick.json)

FEDERATED MODE (--federated, CPU-feasible — this is what CI runs):
  measures the fed-LLM plane (docs/FED_LLM.md) instead of the raw TPU
  step: an INPROC 2-silo LoRA federation on shakespeare/transformer —
  per-silo SFT tokens/s, uplink/downlink bytes-on-wire per round, the
  adapter-vs-full-model bytes reduction, and the quality-vs-central
  curve (same model trained centrally on the union stream with an equal
  round budget).  Results go to llm_bench_federated[_quick].json;
  --guard enforces benchmarks/llm_bench_federated_floor.json (exit 1
  when the bytes reduction falls below 0.8x floor or the 20x hard
  minimum).
"""

import json
import os
import sys
import time
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

QUICK = "--quick" in sys.argv
REMAT = "--remat" in sys.argv
FEDERATED = "--federated" in sys.argv
GUARD = "--guard" in sys.argv
_bs = [a for i, a in enumerate(sys.argv) if sys.argv[i - 1] == "--bs"]
FORCE_BS = int(_bs[0]) if _bs else 0

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from fedml_tpu.models.functional_lm import (  # noqa: E402
    init_lm_params,
    lm_loss,
)
from fedml_tpu.parallel.ring_attention import reference_attention  # noqa: E402
from fedml_tpu.serving.kv_cache_lm import KVCacheLM  # noqa: E402

from fedml_tpu.core.mlops.flight_recorder import chip_peak_flops  # noqa: E402
from fedml_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()

# GPT-2 small class
VOCAB, DIM, LAYERS, HEADS, SEQ = 50257, 768, 12, 12, 1024

#: quick mode writes its (reduced-sweep) results to a separate file so it
#: never clobbers the committed full-sweep artifact that BENCH_NOTES.md
#: references
RESULTS_PATH = os.path.join(
    HERE, "llm_bench_results_quick.json" if QUICK
    else "llm_bench_results.json")
FLOOR_PATH = os.path.join(HERE, "llm_bench_floor.json")


def tree_size(tree) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(tree))


sync = jax.block_until_ready


def train_flops_per_token(remat: bool) -> float:
    fwd = LAYERS * (24 * DIM * DIM + 4 * SEQ * DIM) + 2 * DIM * VOCAB
    return fwd * (4.0 if remat else 3.0)


def bench_train(peak: float, remat: bool):
    """One jitted AdamW SFT step; returns best (bs, tokens/s, mfu)."""
    from statistics import median

    rng = jax.random.PRNGKey(0)
    params = init_lm_params(rng, VOCAB, dim=DIM, layers=LAYERS,
                            heads=HEADS, max_len=SEQ)
    n_params = tree_size(params)
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)
    attn = partial(reference_attention, causal=True)

    def loss_fn(p, t):
        p16 = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), p)
        return lm_loss(p16, t, HEADS, attn, remat=remat)

    def make_step(accum: int):
        @jax.jit
        def step(params, opt_state, tokens):
            if accum == 1:
                loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            else:
                # scan-accumulated microbatches: activation memory = ONE
                # microbatch → less HBM pressure than the single-shot
                # batch (measured best config, BENCH_NOTES round 5)
                mb = tokens.reshape(accum, -1, SEQ)

                def body(g_acc, t):
                    l, g = jax.value_and_grad(loss_fn)(params, t)
                    return jax.tree_util.tree_map(jnp.add, g_acc, g), l

                g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
                grads, losses = jax.lax.scan(body, g0, mb)
                grads = jax.tree_util.tree_map(
                    lambda g: g / accum, grads)
                loss = jnp.mean(losses)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss
        return step

    #: (batch, accum) sweep — 16x4 = 4 scan-accumulated bs4 microbatches
    candidates = ([(FORCE_BS, 1)] if FORCE_BS
                  else ([(4, 1)] if QUICK
                        else [(4, 1), (8, 1), (16, 1), (16, 4)]))
    per_bs = {}
    for bs, accum in candidates:
        key = f"{bs}x{accum}" if accum > 1 else str(bs)
        step = make_step(accum)
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, VOCAB, (bs, SEQ)),
            jnp.int32)
        try:
            t0 = time.time()
            p, o, loss = step(params, opt_state, tokens)
            sync(loss)
            compile_s = time.time() - t0
            for _ in range(2):                       # warmup steady state
                p, o, loss = step(p, o, tokens)
            sync(loss)
            # median of N 2-step windows (see module docstring)
            n_win, spw = (4, 2) if QUICK else (8, 2)
            windows = []
            for _ in range(n_win):
                t0 = time.time()
                for _ in range(spw):
                    p, o, loss = step(p, o, tokens)
                sync(loss)
                windows.append((time.time() - t0) / spw)
            dt = median(windows)
            windows_ms = [round(w * 1e3, 1) for w in windows]
        except Exception as e:                       # OOM at this bs
            per_bs[key] = {"error": str(e)[:200]}
            continue
        tok_s = bs * SEQ / dt
        per_bs[key] = {
            "step_ms": round(dt * 1e3, 1),
            "tokens_per_sec": round(tok_s, 0),
            "mfu": round(tok_s * train_flops_per_token(remat) / peak, 4),
            "compile_s": round(compile_s, 1),
            # every window, not just the median: a floor trip can be
            # diagnosed as variance vs regression
            "windows_ms": windows_ms,
        }
        del p, o
    ok = {b: r for b, r in per_bs.items() if "error" not in r}
    if not ok:
        raise RuntimeError(f"all train batch sizes failed: {per_bs}")
    best = max(ok, key=lambda b: ok[b]["tokens_per_sec"])
    # typed best-config fields: per_bs keys are strings ("16x4"), so keep
    # numeric consumers working via best_bs (int batch) + best_accum
    b_bs, _, b_acc = best.partition("x")
    return {"model": f"gpt2-small-class d{DIM} L{LAYERS} T{SEQ}",
            "n_params": n_params, "remat": remat,
            "best_bs": int(b_bs), "best_accum": int(b_acc or 1),
            **ok[best], "per_bs": per_bs}


def bench_serving(peak: float):
    """KVCacheLM in bf16: TTFT (bs1) + decode tokens/s vs batch."""
    from statistics import median

    rng = jax.random.PRNGKey(2)
    params = init_lm_params(rng, VOCAB, dim=DIM, layers=LAYERS,
                            heads=HEADS, max_len=SEQ)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), params)
    lm = KVCacheLM(params, HEADS, SEQ)
    gen = np.random.default_rng(3)

    def mk_prompts(bs, width):
        toks = jnp.asarray(gen.integers(0, VOCAB, (bs, width)), jnp.int32)
        return toks, jnp.full((bs,), width, jnp.int32)

    # ---- TTFT: prompt 512, batch 1 — prefill + argmax of last logits ----
    toks, length = mk_prompts(1, 512)
    cache, last = lm.prefill(toks, length)           # compile
    sync(last)
    ttfts = []
    for _ in range(5 if QUICK else 8):
        t0 = time.time()
        cache, last = lm.prefill(toks, length)
        first_tok = jnp.argmax(last, -1)
        sync(first_tok)
        ttfts.append(time.time() - t0)
    # one dispatch is short against the host's own jitter, so the
    # device-side prefill cost is measured by chaining N back-to-back
    # prefill dispatches under one sync (in-order execution)
    ttft_ms = 1e3 * median(ttfts)
    n_chain = 8
    prefs = []
    for _ in range(3):
        t0 = time.time()
        for _ in range(n_chain):
            cache, last = lm.prefill(toks, length)
        sync(last)
        prefs.append((time.time() - t0) / n_chain)
    best_pref = median(prefs)
    prefill_ms = 1e3 * best_pref
    prefill_tok_s = 512 / best_pref

    # ---- steady-state decode tokens/s vs batch ----
    # decode FLOPs/token ~ 2*n_params + cache attention reads; the engine
    # is HBM-bound here (reads all params per k-chunk step), so we also
    # report the bandwidth-model ceiling for context.
    decode = {}
    batches = [8, 128] if QUICK else [1, 8, 32, 64, 128]
    K = 64                                           # tokens per dispatch
    n_win = 4 if QUICK else 8
    for bs in batches:
        toks, length = mk_prompts(bs, 128)
        cache, last = lm.prefill(toks, length)
        first = jnp.argmax(last, -1)
        prompt_buf = jnp.zeros((bs, K), jnp.int32).at[:, 0].set(first)
        prompt_n = jnp.ones((bs,), jnp.int32)
        temps = jnp.zeros((bs,), jnp.float32)        # greedy
        top_k = jnp.zeros((bs,), jnp.int32)
        top_p = jnp.ones((bs,), jnp.float32)
        key = jax.random.PRNGKey(4)
        pos = length
        # compile + warm
        cache, emitted = lm.decode_multi(cache, prompt_buf, prompt_n, pos,
                                         temps, top_k, top_p, key, K)
        sync(emitted)
        pos = pos + K
        # median of N one-chunk windows, each chained on-device through
        # emitted[:, -1]
        assert 128 + K * (2 + n_win) <= lm.max_len, \
            "decode windows overrun the cache; lower K or n_win"
        windows = []
        for _ in range(n_win):
            nxt = emitted[:, -1]
            prompt_buf = prompt_buf.at[:, 0].set(nxt)
            t0 = time.time()
            cache, emitted = lm.decode_multi(cache, prompt_buf, prompt_n,
                                             pos, temps, top_k, top_p,
                                             key, K)
            sync(emitted)
            windows.append(time.time() - t0)
            pos = pos + K
        best = median(windows)
        decode[bs] = {
            "tokens_per_sec": round(bs * K / best, 0),
            "ms_per_token_per_seq": round(1e3 * best / K, 3),
        }
        del cache, emitted
    best_bs = max(decode, key=lambda b: decode[b]["tokens_per_sec"])
    return {"ttft_ms_b1_p512": round(ttft_ms, 1),
            "prefill_ms_device_b1_p512": round(prefill_ms, 1),
            "prefill_tokens_per_sec": round(prefill_tok_s, 0),
            "decode": decode,
            "best_decode_bs": best_bs,
            "best_decode_tokens_per_sec":
                decode[best_bs]["tokens_per_sec"]}


FED_RESULTS_PATH = os.path.join(
    HERE, "llm_bench_federated_quick.json" if QUICK
    else "llm_bench_federated.json")
FED_FLOOR_PATH = os.path.join(HERE, "llm_bench_federated_floor.json")

#: ISSUE acceptance: adapter uploads must beat full-model transfer by at
#: least this factor, regardless of what the committed floor says
FED_MIN_REDUCTION = 20.0


def main_federated() -> None:
    import fedml_tpu
    from fedml_tpu.ml.engine.local_update import build_eval_step
    from fedml_tpu.ml.trainer.default_trainer import batches_for
    from fedml_tpu.runner import FedMLRunner
    from fedml_tpu.train.fed_llm.trainer import (
        FED_LLM_TOKENS,
        FED_LLM_TRAIN_SECONDS,
    )
    from fedml_tpu.train.llm.lora import apply_lora
    from fedml_tpu.utils.compression import WIRE_BYTES
    from fedml_tpu.utils.serialization import estimate_nbytes

    run_id = "llm-bench-fed"
    n_silos, rounds = 2, (3 if QUICK else 5)
    lora_rank, seq_len, bs = 4, 32, 4
    args = fedml_tpu.init(fedml_tpu.Config(
        dataset="shakespeare", model="transformer",
        training_type="cross_silo", backend="INPROC", role="simulated",
        client_num_in_total=n_silos, client_num_per_round=n_silos,
        comm_round=rounds, epochs=1, batch_size=bs, learning_rate=3e-3,
        data_scale=0.5 if QUICK else 1.0, frequency_of_the_test=1,
        random_seed=0, run_id=run_id, enable_tracking=False,
        compute_dtype="float32", fed_llm=True, lora_rank=lora_rank,
        fed_llm_seq_len=seq_len))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])

    t0 = time.time()
    metrics = FedMLRunner(args, device, dataset, bundle).run()
    fed_wall = time.time() - t0
    fed_hist = metrics["server_loss_history"]

    # -- bytes on the wire (measured at the transport, not estimated) ----
    codecs = ("raw", "bf16", "int8", "topk", "topk8")
    up = sum(WIRE_BYTES.labels(run_id=run_id, direction="up",
                               codec=c).value for c in codecs)
    down = sum(WIRE_BYTES.labels(run_id=run_id, direction="down",
                                 codec=c).value for c in codecs)
    full_model = estimate_nbytes(
        bundle.init_variables(jax.random.PRNGKey(0)))
    n_uploads = n_silos * rounds
    reduction = full_model / (up / n_uploads)

    per_silo = {}
    for silo in range(n_silos):
        tok = FED_LLM_TOKENS.labels(run_id=run_id, silo=str(silo)).value
        sec = FED_LLM_TRAIN_SECONDS.labels(run_id=run_id,
                                           silo=str(silo)).value
        per_silo[str(silo)] = {
            "train_tokens": tok,
            # counter includes the round-1 compile; steady-state rate is
            # higher (the per-round logs show it)
            "tokens_per_sec": round(tok / max(sec, 1e-9), 0),
        }

    # -- quality vs central: same model + token budget, no federation ----
    from fedml_tpu.train.fed_llm.config import llm_config_from_args
    from fedml_tpu.train.llm.trainer import LLMTrainer

    import numpy as _np

    union = _np.concatenate(
        [_np.asarray(dataset[5][c][0]).reshape(-1)
         for c in range(n_silos)]).astype(_np.int64)
    central = LLMTrainer(bundle, llm_config_from_args(args),
                         rng=jax.random.PRNGKey(0))
    eval_step = jax.jit(build_eval_step(bundle))
    test_global = dataset[3]
    nb = max(1, -(-len(test_global[1]) // bs))
    batches = jax.device_get(  # host-side once; reused every eval
        batches_for(test_global, bs, nb, bundle.input_dtype))
    central_hist = []
    for _ in range(rounds):
        central.train(union)  # fresh opt state per call == per-round SGD
        merged = apply_lora(central.variables["params"], central.lora,
                            central.cfg.lora_alpha)
        out = jax.device_get(eval_step(
            dict(central.variables, params=merged), batches))
        central_hist.append(float(out["loss_sum"]) / max(
            float(out["n"]), 1.0))

    out = {
        "mode": "federated", "quick": QUICK,
        "model": "tiny-transformer d128 L2 (shakespeare char-LM)",
        "silos": n_silos, "rounds": rounds, "lora_rank": lora_rank,
        "seq_len": seq_len, "batch_size": bs,
        "full_model_bytes": full_model,
        "uplink_bytes_total": up,
        "uplink_bytes_per_round": round(up / rounds, 0),
        "downlink_bytes_per_round": round(down / rounds, 0),
        "mean_upload_bytes": round(up / n_uploads, 0),
        "uplink_bytes_reduction": round(reduction, 1),
        "per_silo": per_silo,
        "federated_loss_history": [round(x, 4) for x in fed_hist],
        "central_loss_history": [round(x, 4) for x in central_hist],
        "quality_gap_final": round(fed_hist[-1] - central_hist[-1], 4),
        "federated_wall_s": round(fed_wall, 1),
    }
    with open(FED_RESULTS_PATH, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({
        "fed_llm_uplink_reduction": out["uplink_bytes_reduction"],
        "fed_llm_final_loss": out["federated_loss_history"][-1],
        "fed_llm_quality_gap": out["quality_gap_final"],
        "fed_llm_tokens_per_sec_per_silo":
            [v["tokens_per_sec"] for v in per_silo.values()],
        "detail": FED_RESULTS_PATH,
    }))

    if GUARD:
        bad = {}
        if reduction < FED_MIN_REDUCTION:
            bad["uplink_bytes_reduction(min)"] = (round(reduction, 1),
                                                  FED_MIN_REDUCTION)
        if os.path.exists(FED_FLOOR_PATH):
            with open(FED_FLOOR_PATH) as f:
                floor = json.load(f)
            k = "uplink_bytes_reduction"
            if k in floor and reduction < 0.8 * floor[k]:
                bad[k] = (round(reduction, 1), floor[k])
        if bad:
            print(f"FED LLM GUARD FAILED: {bad}", file=sys.stderr)
            sys.exit(1)


def main() -> None:
    # device rates only: without the chip there is nothing to report
    # (--federated is the count-only mode that runs anywhere)
    if jax.default_backend() != "tpu":
        sys.exit(f"llm_bench.py measures the TPU and found none (JAX "
                 f"backend: {jax.default_backend()}); it does not fall "
                 f"back to the CPU")
    kind = jax.devices()[0].device_kind
    peak = chip_peak_flops()
    if peak is None:
        sys.exit(f"no peak FLOP/s known for device_kind {kind!r} "
                 f"(fedml_tpu.constants.TPU_PEAK_BF16_FLOPS)")
    out = {"device": kind, "n_devices": len(jax.devices()),
           "peak_bf16_flops": peak, "quick": QUICK}
    t0 = time.time()
    out["train"] = bench_train(peak, REMAT)
    out["serving"] = bench_serving(peak)
    out["wall_s"] = round(time.time() - t0, 1)

    with open(RESULTS_PATH, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({
        "llm_sft_mfu": out["train"]["mfu"],
        "llm_sft_tokens_per_sec": out["train"]["tokens_per_sec"],
        "llm_ttft_ms": out["serving"]["ttft_ms_b1_p512"],
        "llm_decode_tokens_per_sec":
            out["serving"]["best_decode_tokens_per_sec"],
        "detail": RESULTS_PATH,
    }))

    if os.path.exists(FLOOR_PATH):
        with open(FLOOR_PATH) as f:
            floor = json.load(f)
        checks = {
            "llm_sft_mfu": out["train"]["mfu"],
            "llm_sft_tokens_per_sec": out["train"]["tokens_per_sec"],
            "llm_decode_tokens_per_sec":
                out["serving"]["best_decode_tokens_per_sec"],
        }
        bad = {k: (v, floor[k]) for k, v in checks.items()
               if k in floor and v < 0.8 * floor[k]}
        if bad:
            print(f"LLM PERF GUARD FAILED: {bad}", file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main_federated() if FEDERATED else main()
