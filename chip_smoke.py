"""Does the system still start on the chip?  One process, one TPU.

Drives the three main entry points once each, at the full width of a model
the repo supports (depth of work cut to a few rounds / steps / requests,
weights random from a seed), and checks what comes out by the repo's own
means.  Any failed check raises; nothing is caught.

    python chip_smoke.py            # one chip: sync check, sft, serve, parrot
    python chip_smoke.py --chips 4  # four chips: the sharded client axis
                                    # against one device, and nothing else

The Parrot round is the main path and runs last: its cold compile is most
of the script's time, so a run cut at its time limit has already printed
what the cheap phases found, and a ``parrot_compile`` line says where it was.

Refuses to start (non-zero, no result line) unless JAX's first device is a
TPU.  The last line of stdout is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
everything else worth seeing is on earlier ``SMOKE`` lines.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import jax  # noqa: E402

#: GPT-2-small geometry, the width both LLM phases run at
LM = dict(vocab=50257, dim=768, layers=12, heads=12, max_len=1024)


def say(phase: str, **fields) -> None:
    print("SMOKE " + json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def has_kernel(compiled_text: str) -> bool:
    """A Pallas kernel that compiled for the chip (not its jnp reference,
    not the interpreter) shows in the program text under this name."""
    return "tpu_custom_call" in compiled_text


def peak_device_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# which call really waits for the device?
# ---------------------------------------------------------------------------

def phase_sync(n: int = 8192, calls: int = 8, per_call: int = 8) -> None:
    """A chain of large bf16 matmuls closed by ``block_until_ready`` and
    again closed by a host fetch of a scalar.  Where the two agree,
    ``block_until_ready`` is the sync everywhere."""
    import jax.numpy as jnp

    w = (jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float32)
         / jnp.sqrt(n)).astype(jnp.bfloat16)

    @jax.jit
    def chain(x, w):             # w as an operand: a closed-over array
        for _ in range(per_call):        # would be baked into the program
            x = x @ w
        return x

    x0 = jnp.eye(n, dtype=jnp.bfloat16)
    jax.block_until_ready(chain(x0, w))              # compile + warm

    def run(close) -> float:
        t0 = time.perf_counter()
        x = x0
        for _ in range(calls):
            x = chain(x, w)
        close(x)
        return time.perf_counter() - t0

    bur_s = [run(jax.block_until_ready) for _ in range(3)]
    fetch_s = [run(lambda x: float(x[0, 0])) for _ in range(3)]
    bur, fetch = sorted(bur_s)[1], sorted(fetch_s)[1]
    flops = 2.0 * n ** 3 * calls * per_call
    say("sync", block_until_ready_s=bur_s, host_fetch_s=fetch_s,
        ratio=bur / fetch, tflops_per_s=flops / bur / 1e12)
    check(0.8 <= bur / fetch <= 1.25,
          f"block_until_ready ({bur:.4f}s) and a host fetch ({fetch:.4f}s) "
          f"disagree on when the device is done")


# ---------------------------------------------------------------------------
# Parrot ResNet-56 round (the main path)
# ---------------------------------------------------------------------------

def _parrot_api(**overrides):
    import bench
    import fedml_tpu
    from fedml_tpu.runner import FedMLRunner

    bench.ensure_northstar_data()
    args = fedml_tpu.init(bench.northstar_config(**overrides))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    return FedMLRunner(args, device, dataset, bundle).runner


def _falling(losses, what: str) -> None:
    import numpy as np

    losses = np.asarray(losses, np.float64)
    check(np.all(np.isfinite(losses)), f"{what}: non-finite loss {losses}")
    half = max(len(losses) // 3, 1)
    check(losses[-half:].mean() < losses[:half].mean(),
          f"{what}: loss did not fall: {losses.tolist()}")


def _timed_rounds(phase: str, rounds: int, **overrides):
    """Build the api, get its fused program ready, run ``rounds`` through
    it: (api, per-round metrics, seconds of each of the three)."""
    t0 = time.perf_counter()
    api = _parrot_api(comm_round=rounds, **overrides)
    t1 = time.perf_counter()
    # the long step: said before it starts, so a run cut here can be read
    say(phase + "_compile", backend=overrides.get("backend", "parrot"),
        setup_s=t1 - t0, buckets=api.n_buckets)
    api._ensure_multi_round_step()       # compile, or load from the cache
    t2 = time.perf_counter()
    rms = jax.block_until_ready(api.run_rounds_fused(rounds))
    return api, rms, (t1 - t0, t2 - t1, time.perf_counter() - t2)


def phase_parrot(rounds: int = 12, **overrides) -> None:
    import numpy as np

    api, rms, (setup_s, ready_s, first_chunk_s) = _timed_rounds(
        "parrot", rounds, **overrides)
    loss = np.asarray(rms["train_loss"])
    say("parrot", setup_s=setup_s, program_ready_s=ready_s,
        first_chunk_s=first_chunk_s, rounds=rounds,
        aot_cache_hit=bool(api.aot_cache_hit),
        train_loss=loss.tolist(),
        samples_per_round=np.asarray(rms["samples"]).tolist(),
        peak_device_bytes=peak_device_bytes())
    check(loss.shape == (rounds,), f"parrot: {loss.shape} losses")
    _falling(loss, "parrot")
    check(not api._fused_is_plain_jit,
          "parrot: the fused program is the plain-jit stand-in, not the "
          "AOT-compiled executable")
    check(has_kernel(api.multi_round_step.as_text()),
          "parrot: no fused-epilogue kernel in the compiled round program")


# ---------------------------------------------------------------------------
# LLMTrainer SFT step
# ---------------------------------------------------------------------------

def _patterned_tokens(n: int, vocab: int, seed: int):
    """A token stream with structure to learn: a random 64-token cycle
    with 10% uniform noise, all from ``seed``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cycle = rng.integers(0, vocab, 64)
    toks = cycle[np.arange(n) % 64]
    noise = rng.random(n) < 0.1
    toks[noise] = rng.integers(0, vocab, int(noise.sum()))
    return toks.astype(np.int64)


def phase_sft(lm=LM, seq_len: int = 1024, batch_size: int = 4,
              steps: int = 4, calls: int = 3) -> None:
    import fedml_tpu
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    args = fedml_tpu.Config(
        model="functional_lm", dataset="shakespeare", lm_dim=lm["dim"],
        lm_layers=lm["layers"], lm_heads=lm["heads"],
        lm_max_len=lm["max_len"])
    bundle = fedml_tpu.model.create(args, lm["vocab"])
    cfg = LLMTrainConfig(seq_len=seq_len, batch_size=batch_size, epochs=1,
                         learning_rate=3e-3)      # LoRA as the defaults
    trainer = LLMTrainer(bundle, cfg, rng=jax.random.PRNGKey(0))
    # train() runs its jitted epoch function.  Stand its AOT-compiled
    # executable in for it, lowered from the operands train() itself
    # passes on its first call: the program whose text is checked below is
    # then the one every step ran, not a second one rebuilt by hand
    epoch_jit, ran = trainer._train_epoch, []

    def epoch_program(*operands):
        if not ran:
            ran.append(epoch_jit.lower(*operands).compile())
        return ran[0](*operands)

    trainer._train_epoch = epoch_program
    tokens = _patterned_tokens(steps * batch_size * seq_len + 1,
                               lm["vocab"], seed=1)
    # one train() = one epoch program over `steps` optimizer steps; the
    # first call compiles it, the later ones only run it
    history, call_s = [], []
    for _ in range(calls):
        t0 = time.perf_counter()
        history += trainer.train(tokens)["loss_history"]
        call_s.append(time.perf_counter() - t0)
    say("sft", first_call_s=call_s[0], warm_call_s=call_s[1:],
        steps_per_call=steps, loss_history=history,
        peak_device_bytes=peak_device_bytes())
    check(len(history) == calls, f"sft: {len(history)} epochs ran")
    _falling(history, "sft")
    check(len(ran) == 1 and has_kernel(ran[0].as_text()),
          "sft: no flash-attention kernel in the epoch program that ran")


# ---------------------------------------------------------------------------
# KV-cache serving engine
# ---------------------------------------------------------------------------

def phase_serve(lm=LM, max_batch: int = 8, max_new: int = 16,
                prompt_lens=(3, 5, 20, 27, 50, 60)) -> None:
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.serving.kv_cache_lm import KVCacheLM
    from fedml_tpu.serving.llm_engine import KVCacheLLMEngine

    model = KVCacheLM.create(jax.random.PRNGKey(2), lm["vocab"],
                             dim=lm["dim"], layers=lm["layers"],
                             heads=lm["heads"], max_len=lm["max_len"])
    gen = np.random.default_rng(3)
    prompts = [gen.integers(0, lm["vocab"], n).tolist() for n in prompt_lens]
    t0 = time.perf_counter()
    eng = KVCacheLLMEngine(model, max_batch=max_batch)
    try:
        futs = [eng.submit(p, max_new=max_new) for p in prompts]
        outs = [np.asarray(f.result(timeout=900)) for f in futs]
        serve_s = time.perf_counter() - t0
        prefilled = eng._metrics.prefill.count
    finally:
        eng.stop()
    check(not eng._worker.is_alive(), "serve: engine thread still running")
    for p, o in zip(prompts, outs):
        check(len(o) == len(p) + max_new and o[:len(p)].tolist() == p,
              f"serve: prompt of {len(p)} came back with {len(o)} tokens")
    # the admission prefill swallows its own failure and falls back to
    # chunked prefill: count that it really ran for every long prompt
    long_prompts = sum(n > eng.tokens_per_dispatch for n in prompt_lens)
    check(prefilled == long_prompts,
          f"serve: {prefilled} admission prefills for {long_prompts} long "
          f"prompts")
    # greedy decoding over the uncached forward, teacher-forced on what the
    # engine served: each served token is that position's argmax.  A served
    # token may differ only where the reference itself is a near-tie (the
    # two paths round differently)
    o = outs[-1]
    n_prompt = prompt_lens[-1]
    logits = np.asarray(model.full_logits(jnp.asarray([o[:-1]]))[0],
                        np.float32)[n_prompt - 1:]
    served = o[n_prompt:]
    gap = logits.max(-1) - logits[np.arange(max_new), served]
    tol = 0.02 * logits.std(-1)
    exact = int((logits.argmax(-1) == served).sum())
    say("serve", serve_s=serve_s, requests=len(prompts), max_new=max_new,
        admission_prefills=int(prefilled), greedy_exact=exact,
        greedy_worst_gap=float(gap.max()), gap_tolerance=float(tol.min()),
        peak_device_bytes=peak_device_bytes())
    check(np.all(gap <= tol) and exact >= max_new - 2,
          f"serve: served tokens are not the greedy tokens of full_logits "
          f"({exact}/{max_new} exact, worst gap {gap.max():.4f})")


# ---------------------------------------------------------------------------
# --chips 4: the sharded client axis against one device
# ---------------------------------------------------------------------------

def _collectives(compiled_text: str) -> dict:
    """How often each cross-device operation is in a program's text
    (an asynchronous one counts once, at its ``-start``)."""
    import re

    names = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
             "reduce-scatter")
    return {n: len(re.findall(rf" {n}(?:-start)?\(", compiled_text))
            for n in names}


def phase_mesh(rounds: int = 4, n_dev: int = 4, rtol: float = 0.05,
               **overrides) -> None:
    import numpy as np

    # 8 clients per round in 2 size buckets: 4 per bucket, one per chip
    cfg = dict(client_num_per_round=8, hetero_buckets=2, **overrides)

    mesh_api, rms, (_, mesh_ready, mesh_s) = _timed_rounds(
        "mesh", rounds, backend="mesh", mesh_shape={"clients": n_dev}, **cfg)
    mesh_loss = np.asarray(rms["train_loss"])
    placed = jax.tree_util.tree_leaves(
        (mesh_api.device_data, mesh_api.global_vars))
    spans = sorted({len(x.sharding.device_set) for x in placed})
    mesh_text = mesh_api.multi_round_step.as_text()
    one_api, rms, (_, one_ready, one_s) = _timed_rounds(
        "mesh", rounds, backend="parrot", **cfg)
    one_loss = np.asarray(rms["train_loss"])
    one_text = one_api.multi_round_step.as_text()
    # the round program's operands are replicated by construction; the
    # per-client arrays exist only inside it.  That they are split over the
    # chips shows in what one chip is given to do: XLA's count for the
    # partitioned program is per device
    flops_share = (mesh_api.program_costs["flops"]
                   / one_api.program_costs["flops"])
    collectives = _collectives(mesh_text)
    say("mesh", rounds=rounds, mesh_loss=mesh_loss.tolist(),
        one_device_loss=one_loss.tolist(), mesh_program_ready_s=mesh_ready,
        one_program_ready_s=one_ready, mesh_chunk_s=mesh_s,
        one_chunk_s=one_s, operand_device_spans=spans,
        per_device_flops_share=flops_share, mesh_collectives=collectives,
        mesh_has_kernel=has_kernel(mesh_text),
        one_has_kernel=has_kernel(one_text),
        peak_device_bytes=peak_device_bytes())
    check(spans == [n_dev],
          f"mesh: operands span {spans} devices, not {n_dev}")
    check(flops_share <= 1.5 / n_dev,
          f"mesh: one chip of {n_dev} does {flops_share:.3f} of the "
          f"one-device program's work: the client axis is not split")
    check(not mesh_api._fused_is_plain_jit and not one_api._fused_is_plain_jit,
          "mesh: a fused program is the plain-jit stand-in")
    check(collectives["all-reduce"] > 0,
          "mesh: no cross-device reduction in the compiled round program")
    # the mesh program's epilogue is XLA's reduce, not the kernel (GSPMD
    # cannot partition it: PERF.md, open questions); what it is compared
    # with must be the kernel path
    check(has_kernel(one_text),
          "mesh: no fused-epilogue kernel in the one-device round program")
    check(np.all(np.isfinite(mesh_loss)) and np.all(np.isfinite(one_loss)),
          "mesh: non-finite loss")
    check(np.allclose(mesh_loss, one_loss, rtol=rtol),
          f"mesh: per-round loss on {n_dev} chips {mesh_loss.tolist()} and "
          f"on one {one_loss.tolist()} differ beyond rtol {rtol}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-client-axis comparison")
    opts = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU and JAX found none: the first "
                 f"device is {devs[0].platform}:{devs[0].device_kind}")
    if len(devs) != opts.chips:
        sys.exit(f"chip_smoke.py --chips {opts.chips} needs exactly "
                 f"{opts.chips} TPU device(s); JAX sees {len(devs)}")

    from fedml_tpu.utils.compile_cache import configure_compile_cache

    t0 = time.perf_counter()
    say("start", compile_cache_dir=configure_compile_cache(),
        cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    if opts.chips == 4:
        phase_mesh()
    else:
        for phase in (phase_sync, phase_sft, phase_serve, phase_parrot):
            phase()
            gc.collect()                 # drop the phase's device arrays
    say("done", total_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
