"""The plane of the family with delta-rule layers end to end at a tiny size on
the CPU, through the entry a real run uses, and its controls, as
``test_planes_mla.py`` has them for the latent-attention plane: the reference
one precision down (fp8 operands), a step that returns its state unchanged,
the decay dropped, the writing strength fixed at 1, the convolution dropped,
the output gate dropped, picks that are not the reference's, base matrices
left in float32, factors kept below float32 and a program built inside the
window all come out as not correct.

Readings at this size on seeds 1-3 (``tiny_gdn.SFT``'s limits lie between):
sound ``first_grad_gap`` 0.0036-0.0062, ``first_loss_gap`` 6e-5 to 1.3e-4,
``picks_disagree_share`` 0, ``probe_change_gap`` up to 6.2e-4, ``loss_gap`` up
to 2.5e-4, ``change_norm_gap`` up to 5.8e-3; the fp8 reference
``first_grad_gap`` 0.21-0.32, ``picks_disagree_share`` 0.45-0.56,
``first_loss_gap`` 6e-4 to 2.2e-2; sound with the scan's kernels
interpreted, bfloat16 operands as on the chip, on seeds 2 and 3:
``first_grad_gap`` 0.011-0.041, ``first_loss_gap`` up to 1.1e-3,
``picks_disagree_share`` 0.023-0.055."""

import copy

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import compare
from chipbench.harness.record import Record
from chipbench.reference import qwen3_next

import tiny_gdn

E2E = ["setup_s", "train_tokens_per_s"]
LAYER = ["step_ms", "qnext_sft_mfu_pct", "qnext_picks_held_pct",
         "qnext_expert_load_max_over_mean", "gdn_fwd_ms_per_step",
         "gdn_bwd_roofline", "gdn_mixer_ms_per_step", "qnext_attn_roofline",
         "qnext_experts_roofline"]


def _plane(seed):
    from chipbench.planes import sft_gdn

    return sft_gdn.Plane(copy.deepcopy(tiny_gdn.SFT), tiny_gdn.CONFIG,
                         qwen3_next, seed, Record())


def _rows(plane):
    plane.setup()
    plane.finish()
    return {r["name"]: r for r in plane.check()}


def test_gdn_plane_runs_and_agrees_with_the_reference(tmp_path):
    r = tiny_gdn.run(tiny_gdn.SFT, E2E, 2 ** 31 + 11, 1.0, tmp_path)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] > 0 and r["attempted"] % 3 == 0
    assert set(r["metrics"]) == set(E2E)
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0


def test_gdn_plane_reports_its_layer_metrics_when_traced(tmp_path):
    """What reads the host's clock and the program's counters is reported;
    what reads a TPU's trace finds none here and is left out, as on a parent
    that has no such kernel."""
    r = tiny_gdn.run(tiny_gdn.SFT, LAYER, 3, 1.0, tmp_path, trace=True)
    assert r["correct"]
    assert set(r["metrics"]) == set(LAYER[:4])
    # 4 of 16 experts are held
    assert 10 < r["metrics"]["qnext_picks_held_pct"]["value"] < 45
    assert r["metrics"]["qnext_expert_load_max_over_mean"]["value"] >= 1


def test_gdn_plane_with_the_kernels_interpreted_agrees(monkeypatch):
    """The chunked kernels in place of the recurrence, through the
    interpreter, forward and backward, rounding as on the chip."""
    import functools

    from fedml_tpu.models import functional_lm as flm

    monkeypatch.setattr(flm, "gated_delta_rule", functools.partial(
        flm.gated_delta_rule, interpret=True))
    rows = _rows(_plane(2))
    assert all(r["ok"] for r in rows.values()), rows


def test_gdn_control_in_fp8_is_not_correct():
    for seed in (1, 2):
        plane = _plane(seed)
        plane.setup()
        plane.finish()
        want = plane.reference_reading("float32")
        sound = compare.against_limits(plane.gaps(plane.first, want),
                                       tiny_gdn.SFT["limits"])
        control = compare.against_limits(
            plane.gaps(plane.reference_reading("fp8", follow=False), want),
            tiny_gdn.SFT["limits"])
        assert all(r["ok"] for r in sound), sound
        by_name = {r["name"]: r["ok"] for r in control}
        assert not by_name["first_grad_gap"], control
        assert not by_name["picks_disagree_share"], control


@pytest.mark.parametrize("what", ["decay", "writing strength", "convolution",
                                  "output gate"])
def test_a_dropped_mechanism_is_not_correct(monkeypatch, what):
    from fedml_tpu.models import functional_lm as flm

    real = flm.gated_delta_rule
    if what == "decay":
        monkeypatch.setattr(flm, "gated_delta_rule", lambda q, k, v, g, beta:
                            real(q, k, v, g * 0, beta))
    if what == "writing strength":
        monkeypatch.setattr(flm, "gated_delta_rule", lambda q, k, v, g, beta:
                            real(q, k, v, g, beta * 0 + 1))
    if what == "convolution":
        monkeypatch.setattr(flm, "_causal_conv", lambda x, w: x)
    if what == "output gate":
        monkeypatch.setattr(flm, "_gated", lambda o, gate: o)
    rows = _rows(_plane(5))
    assert not rows["first_grad_gap"]["ok"], rows


def test_gdn_picks_that_are_not_the_references_are_not_correct():
    plane = _plane(4)
    plane.setup()
    plane.first["picks"] = (plane.first["picks"] + 1) % 16
    plane.finish()
    rows = {r["name"]: r for r in plane.check()}
    assert rows["picks_disagree_share"]["value"] == 1.0
    assert not rows["picks_disagree_share"]["ok"]
    assert rows["first_grad_gap"]["ok"]


def test_gdn_base_matrices_left_in_float32_are_not_correct(monkeypatch):
    """The configuration states bfloat16 storage: a program that keeps the
    frozen matrices in float32 holds twice the memory it claims."""
    init = qwen3_next.init_params
    monkeypatch.setattr(qwen3_next, "init_params",
                        lambda cfg, seed: init(cfg, seed, jnp.float32))
    rows = _rows(_plane(4))
    assert rows["state_leaves_not_float32"]["value"] > 20
    assert not rows["state_leaves_not_float32"]["ok"]


def test_gdn_state_kept_below_float32_is_not_correct():
    plane = _plane(4)
    plane.setup()
    plane.trainer.lora = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), plane.trainer.lora)
    plane.finish()
    rows = {r["name"]: r for r in plane.check()}
    assert rows["state_leaves_not_float32"]["value"] == 2 * (3 * 2 + 4)
    assert not rows["state_leaves_not_float32"]["ok"]


def test_gdn_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from fedml_tpu.train.llm import trainer

    monkeypatch.setattr(trainer.optax, "apply_updates",
                        lambda params, updates: params)
    r = tiny_gdn.run(tiny_gdn.SFT, E2E, 5, 0.5, tmp_path)
    assert not r["correct"] and r["failed"] == 0


def test_gdn_program_built_inside_the_window_is_not_correct(
        tmp_path, monkeypatch):
    from chipbench.planes import sft_gdn

    window = sft_gdn.Plane.window

    def compiling_window(self, seconds):
        jax.jit(lambda x: x * 5 + 2)(jnp.ones((3, 11)))
        window(self, seconds)

    monkeypatch.setattr(sft_gdn.Plane, "window", compiling_window)
    r = tiny_gdn.run(tiny_gdn.SFT, E2E, 8, 0.5, tmp_path)
    assert not r["correct"]
