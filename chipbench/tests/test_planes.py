"""Each plane end to end at a tiny size on the CPU, through the entry a real
run uses; the control of each comes out as not correct; and a run whose timed
path is broken underneath comes out with ``correct`` false."""

import copy
import importlib

import jax
import numpy as np
import pytest

from chipbench.harness import compare
from chipbench.harness.record import Record
from chipbench.reference import gpt2

import tiny

SFT_E2E = ["setup_s", "train_tokens_per_s"]
SFT_LAYER = ["step_ms", "mfu_pct"]
SERVE_E2E = ["setup_s", "ttft_p95_ms", "tbt_p50_ms"]
SERVE_LAYER = ["queue_wait_p95_ms", "prefill_p95_ms", "gen_late_p95_ms",
               "ttft_p50_ms", "tbt_p95_ms", "batch_occupancy_pct"]


def test_sft_plane_runs_and_agrees_with_the_reference(tmp_path):
    r = tiny.run(tiny.SFT, SFT_E2E, 2 ** 31 + 11, 1.0, tmp_path)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] > 0 and r["attempted"] % 3 == 0
    assert set(r["metrics"]) == set(SFT_E2E)
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0


def test_sft_plane_reports_its_layer_metrics_when_traced(tmp_path):
    r = tiny.run(tiny.SFT, SFT_LAYER, 3, 1.0, tmp_path, trace=True)
    assert r["correct"] and set(r["metrics"]) == set(SFT_LAYER)
    assert r["device"]["window_s"] > 0 and "breakdown" in r


def test_serve_plane_runs_and_agrees_with_the_reference(tmp_path):
    r = tiny.run(tiny.SERVE, SERVE_E2E, 2 ** 31 + 5, 11.0, tmp_path)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] == 220
    assert set(r["metrics"]) == set(SERVE_E2E)
    assert 0 < r["metrics"]["ttft_p95_ms"]["value"] < 5000


def test_serve_plane_reports_its_layer_metrics(tmp_path):
    r = tiny.run(tiny.SERVE, SERVE_LAYER, 9, 11.0, tmp_path, trace=True)
    assert r["correct"] and set(r["metrics"]) == set(SERVE_LAYER)
    # a generator on an idle machine sends within a few milliseconds of due
    assert r["metrics"]["gen_late_p95_ms"]["value"] < 50


def test_first_token_is_timed_from_the_due_time(tmp_path, monkeypatch):
    """A generator held up for 300 ms at every send falls behind its
    schedule: that makes the requests late, not the server fast.  The delay
    lands in every request's time to first token and in the lateness."""
    import time

    from chipbench.planes import serve
    from fedml_tpu.serving.llm_engine import KVCacheLLMEngine

    submit = KVCacheLLMEngine.submit

    def slow_submit(self, *a, **kw):
        if kw.get("on_token") is not None:        # the window's sends only
            time.sleep(0.3)
        return submit(self, *a, **kw)

    monkeypatch.setattr(KVCacheLLMEngine, "submit", slow_submit)
    cell = copy.deepcopy(tiny.SERVE)
    cell["traffic"]["arrivals"]["rate_qps"] = 10.0
    rec = Record()
    plane = serve.Plane(cell, tiny.CONFIG, gpt2, 4, rec)
    plane.setup()
    plane.window(2.0)
    plane.finish()
    assert len(plane.done) == 20 and plane.failed == 0
    assert all(r["ttft_s"] > r["late_s"] + 0.3 for r in plane.done)
    assert np.median([r["late_s"] for r in plane.done]) > 1.0


# -- the controls: the reference, computed one precision down ---------------

@pytest.mark.parametrize("mode", ["bfloat16", "fp8"])
def test_sft_control_in_a_lower_precision_is_not_correct(mode):
    """Here the program is float32 throughout (the CPU multiplies in float32),
    so the precision below it is bfloat16, and fp8 below that.  On the chip
    the program's products are already bfloat16 and the control is fp8
    (``PERF.md``, section 2)."""
    from chipbench.planes import sft

    for seed in (1, 2, 3):
        plane = sft.Plane(copy.deepcopy(tiny.SFT), tiny.CONFIG, gpt2, seed,
                          Record())
        plane.setup()
        plane.finish()
        want = plane.reference_reading("float32")
        sound = compare.against_limits(plane.gaps(plane.first, want),
                                       tiny.SFT["limits"])
        control = compare.against_limits(
            plane.gaps(plane.reference_reading(mode), want),
            tiny.SFT["limits"])
        assert all(r["ok"] for r in sound), sound
        by_name = {r["name"]: r["ok"] for r in control}
        assert not by_name["first_grad_gap"], control


def test_serve_control_in_fp8_is_not_correct():
    """At a size where some hundreds of served tokens hold near-ties: the
    program (float32 here) and the reference in bfloat16 stay under the
    limit, the reference in fp8 puts a token first that the float32 logits
    hold well below the best."""
    from chipbench.planes import serve

    config = dict(tiny.CONFIG, vocab_size=4096, n_embd=64, n_layer=4,
                  n_head=4)
    cell = copy.deepcopy(tiny.SERVE)
    limit = 4e-3
    for seed in (1, 2, 3):
        plane = serve.Plane(copy.deepcopy(cell), config, gpt2, seed, Record())
        plane.setup()
        plane.window(4.0)
        plane.finish()
        sample = plane.finished()
        assert plane.gaps_on(sample)["served_logit_gap"] <= limit
        assert plane.gaps_on(sample, "bfloat16")["served_logit_gap"] <= limit
        assert plane.gaps_on(sample, "fp8")["served_logit_gap"] > limit


def test_one_wrong_token_in_any_finished_request_is_seen():
    """Every finished request is compared, not a sample: a single token
    altered in the shortest of them is caught."""
    from chipbench.planes import serve

    plane = serve.Plane(copy.deepcopy(tiny.SERVE), tiny.CONFIG, gpt2, 3,
                        Record())
    plane.setup()
    plane.window(3.0)
    plane.finish()
    done = plane.finished()
    limit = tiny.SERVE["limits"]["served_logit_gap"]
    sound = plane.gaps_on(done)
    assert len(done) == 60 and sound["served_logit_gap"] <= limit
    assert sound["tokens_compared"] == sum(len(s) - p for s, p in done)
    i = min(range(len(done)), key=lambda k: len(done[k][0]))
    seq = done[i][0].copy()
    seq[-1] = (seq[-1] + 1) % tiny.CONFIG["vocab_size"]
    done[i] = (seq, done[i][1])
    assert plane.gaps_on(done)["served_logit_gap"] > limit


def test_a_state_kept_below_float32_is_not_correct():
    """No norm compared tells factors kept in bfloat16 from factors kept in
    float32, so the types of the state are counted."""
    import jax.numpy as jnp

    from chipbench.planes import sft

    plane = sft.Plane(copy.deepcopy(tiny.SFT), tiny.CONFIG, gpt2, 4, Record())
    plane.setup()
    plane.trainer.lora = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), plane.trainer.lora)
    plane.finish()
    rows = {r["name"]: r for r in plane.check()}
    assert rows["state_leaves_not_float32"]["value"] == len(
        gpt2.LORA_TARGETS) * 2 * tiny.CONFIG["n_layer"]
    assert not rows["state_leaves_not_float32"]["ok"]
    assert rows["first_grad_gap"]["ok"]


# -- the timed path broken underneath: ``correct`` comes out false ------------

def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from fedml_tpu.train.llm import trainer

    monkeypatch.setattr(trainer.optax, "apply_updates",
                        lambda params, updates: params)
    r = tiny.run(tiny.SFT, SFT_E2E, 5, 0.5, tmp_path)
    assert not r["correct"] and r["failed"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch):
    from fedml_tpu.serving import kv_cache_lm

    sample = kv_cache_lm._filter_sample

    def off_by_one(logits, *a, **kw):
        return (sample(logits, *a, **kw) + 1) % logits.shape[-1]

    # decode_multi is jitted once per shape for the process: trace it anew
    # with the altered sampler, and again without it afterwards
    jax.clear_caches()
    monkeypatch.setattr(kv_cache_lm, "_filter_sample", off_by_one)
    try:
        r = tiny.run(tiny.SERVE, SERVE_E2E, 6, 4.0, tmp_path)
    finally:
        jax.clear_caches()
    assert not r["correct"] and r["failed"] == 0


def test_a_program_built_inside_the_window_is_not_correct(tmp_path,
                                                          monkeypatch):
    from chipbench.planes import sft

    window = sft.Plane.window

    def compiling_window(self, seconds):
        import jax.numpy as jnp

        jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 5)))
        window(self, seconds)

    monkeypatch.setattr(sft.Plane, "window", compiling_window)
    r = tiny.run(tiny.SFT, SFT_E2E, 8, 0.5, tmp_path)
    assert not r["correct"]
