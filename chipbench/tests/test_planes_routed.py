"""The routed family's plane end to end at a tiny size on the CPU, through the
entry a real run uses, and its controls, as ``test_planes.py`` has them for the
GPT-2 planes: the reference one precision down, a step that returns its state
unchanged, factors kept below float32, picks that are not the reference's and
a program built inside the window all come out as not correct."""

import copy

import jax

from chipbench.harness import compare
from chipbench.harness.record import Record
from chipbench.reference import smallthinker

import tiny_routed

E2E = ["setup_s", "train_tokens_per_s"]
LAYER = ["step_ms", "moe_sft_mfu_pct", "moe_picks_held_pct",
         "moe_expert_load_max_over_mean", "moe_experts_ms_per_step",
         "window_attn_roofline"]


def _plane(seed):
    from chipbench.planes import sft_routed

    return sft_routed.Plane(copy.deepcopy(tiny_routed.SFT), tiny_routed.CONFIG,
                            smallthinker, seed, Record())


def test_routed_plane_runs_and_agrees_with_the_reference(tmp_path):
    r = tiny_routed.run(tiny_routed.SFT, E2E, 2 ** 31 + 11, 1.0, tmp_path)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] > 0 and r["attempted"] % 3 == 0
    assert set(r["metrics"]) == set(E2E)
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0


def test_routed_plane_reports_its_layer_metrics_when_traced(tmp_path):
    """What reads the host's clock and the program's counters is reported;
    what reads a TPU's trace finds none here and is left out, as on a parent
    that has no such kernel."""
    r = tiny_routed.run(tiny_routed.SFT, LAYER, 3, 1.0, tmp_path, trace=True)
    assert r["correct"]
    assert set(r["metrics"]) == set(LAYER[:4])
    # 4 of 16 experts are held
    assert 10 < r["metrics"]["moe_picks_held_pct"]["value"] < 45
    assert r["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1


def test_routed_control_in_fp8_is_not_correct(mode="fp8"):
    """Here the program is float32 but for the experts' products, whose
    operands it rounds to bfloat16 on every backend: the precision below it
    is fp8, as on the chip, where all its products are bfloat16."""
    for seed in (1, 2, 3):
        plane = _plane(seed)
        plane.setup()
        plane.finish()
        want = plane.reference_reading("float32")
        sound = compare.against_limits(plane.gaps(plane.first, want),
                                       tiny_routed.SFT["limits"])
        control = compare.against_limits(
            plane.gaps(plane.reference_reading(mode), want),
            tiny_routed.SFT["limits"])
        assert all(r["ok"] for r in sound), sound
        by_name = {r["name"]: r["ok"] for r in control}
        assert not by_name["first_grad_gap"], control
        assert not by_name["picks_disagree_share"], control


def test_picks_that_are_not_the_references_are_not_correct():
    plane = _plane(4)
    plane.setup()
    plane.first["picks"] = (plane.first["picks"] + 1) % 16
    plane.finish()
    rows = {r["name"]: r for r in plane.check()}
    assert rows["picks_disagree_share"]["value"] == 1.0
    assert not rows["picks_disagree_share"]["ok"]
    assert rows["first_grad_gap"]["ok"]


def test_routed_state_kept_below_float32_is_not_correct():
    import jax.numpy as jnp

    plane = _plane(4)
    plane.setup()
    plane.trainer.lora = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), plane.trainer.lora)
    plane.finish()
    rows = {r["name"]: r for r in plane.check()}
    assert rows["state_leaves_not_float32"]["value"] == len(
        smallthinker.LORA_TARGETS) * 2 * tiny_routed.CONFIG[
            "num_hidden_layers"]
    assert not rows["state_leaves_not_float32"]["ok"]


def test_routed_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from fedml_tpu.train.llm import trainer

    monkeypatch.setattr(trainer.optax, "apply_updates",
                        lambda params, updates: params)
    r = tiny_routed.run(tiny_routed.SFT, E2E, 5, 0.5, tmp_path)
    assert not r["correct"] and r["failed"] == 0


def test_routed_program_built_inside_the_window_is_not_correct(
        tmp_path, monkeypatch):
    from chipbench.planes import sft_routed

    window = sft_routed.Plane.window

    def compiling_window(self, seconds):
        import jax.numpy as jnp

        jax.jit(lambda x: x * 5 + 2)(jnp.ones((3, 11)))
        window(self, seconds)

    monkeypatch.setattr(sft_routed.Plane, "window", compiling_window)
    r = tiny_routed.run(tiny_routed.SFT, E2E, 8, 0.5, tmp_path)
    assert not r["correct"]
