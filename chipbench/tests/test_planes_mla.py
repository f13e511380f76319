"""The latent-attention family's plane end to end at a tiny size on the CPU,
through the entry a real run uses, and its controls, as ``test_planes_routed.py``
has them for the routed plane: the reference one precision down, a step that
returns its state unchanged, picks that are not the reference's, a second loss
that is dropped or taken against the wrong token, a router that drops its
correction bias or ignores its groups, base matrices left in float32 and a
program built inside the window all come out as not correct."""

import copy

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import compare
from chipbench.harness.record import Record
from chipbench.reference import gigachat3

import tiny_mla

E2E = ["setup_s", "train_tokens_per_s"]
LAYER = ["step_ms", "giga_sft_mfu_pct", "giga_picks_held_pct",
         "giga_tokens_in_held_group_pct", "giga_experts_ms_per_step",
         "giga_attn_roofline"]


def _plane(seed):
    from chipbench.planes import sft_mla

    return sft_mla.Plane(copy.deepcopy(tiny_mla.SFT), tiny_mla.CONFIG,
                         gigachat3, seed, Record())


def _compared(got, want, plane):
    """The plane's gaps less the one it shows and does not compare."""
    gaps = plane.gaps(got, want)
    gaps.pop("first_loss_gap")
    return gaps


def _rows(plane):
    plane.setup()
    plane.finish()
    return {r["name"]: r for r in plane.check()}


def test_mla_plane_runs_and_agrees_with_the_reference(tmp_path):
    r = tiny_mla.run(tiny_mla.SFT, E2E, 2 ** 31 + 11, 1.0, tmp_path)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] > 0 and r["attempted"] % 3 == 0
    assert set(r["metrics"]) == set(E2E)
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0


def test_mla_plane_reports_its_layer_metrics_when_traced(tmp_path):
    """What reads the host's clock and the program's counters is reported;
    what reads a TPU's trace finds none here and is left out, as on a parent
    that has no such kernel."""
    r = tiny_mla.run(tiny_mla.SFT, LAYER, 3, 1.0, tmp_path, trace=True)
    assert r["correct"]
    assert set(r["metrics"]) == set(LAYER[:4])
    # 4 of 32 experts are held, one of the 8 groups of which 4 are kept
    assert 3 < r["metrics"]["giga_picks_held_pct"]["value"] < 30
    assert 20 < r["metrics"]["giga_tokens_in_held_group_pct"]["value"] < 80


def test_mla_control_in_fp8_is_not_correct(mode="fp8"):
    for seed in (1, 2, 3):
        plane = _plane(seed)
        plane.setup()
        plane.finish()
        want = plane.reference_reading("float32")
        sound = compare.against_limits(_compared(plane.first, want, plane),
                                       tiny_mla.SFT["limits"])
        control = compare.against_limits(
            _compared(plane.reference_reading(mode, follow=False), want,
                      plane), tiny_mla.SFT["limits"])
        assert all(r["ok"] for r in sound), sound
        by_name = {r["name"]: r["ok"] for r in control}
        assert not by_name["first_grad_gap"], control
        assert not by_name["picks_disagree_share"], control


def test_mla_picks_that_are_not_the_references_are_not_correct():
    plane = _plane(4)
    plane.setup()
    plane.first["picks"] = (plane.first["picks"] + 1) % 32
    plane.finish()
    rows = {r["name"]: r for r in plane.check()}
    assert rows["picks_disagree_share"]["value"] == 1.0
    assert not rows["picks_disagree_share"]["ok"]
    assert rows["first_grad_gap"]["ok"]


def test_a_second_loss_left_out_of_the_sum_is_not_correct(monkeypatch):
    from chipbench.planes import sft_mla

    args = sft_mla.model_args
    monkeypatch.setattr(sft_mla, "model_args",
                        lambda cfg: dict(args(cfg), lm_mtp_weight=0.0))
    rows = _rows(_plane(5))
    assert not rows["loss_gap"]["ok"]
    assert not rows["first_grad_gap"]["ok"]


def test_a_second_loss_against_the_wrong_token_is_not_correct(monkeypatch):
    """The second head held to token i + 1: the sum moves by three tenths of
    what the term does, and the term alone is compared too."""
    from fedml_tpu.models import functional_lm as flm

    real, calls = flm.loss_in_row_blocks, []

    def misaligned(h, w_out, y, mask, *rest):
        calls.append(None)
        if len(calls) % 2 == 0:                 # the second head's call
            y = jnp.roll(y, 1)
        return real(h, w_out, y, mask, *rest)

    monkeypatch.setattr(flm, "loss_in_row_blocks", misaligned)
    rows = _rows(_plane(5))
    assert calls and not rows["mtp_loss_gap"]["ok"]
    assert rows["picks_disagree_share"]["ok"]


@pytest.mark.parametrize("what", ["bias", "groups"])
def test_a_router_that_drops_its_bias_or_its_groups_is_not_correct(
        monkeypatch, what):
    from fedml_tpu.models import functional_lm as flm

    real = flm.route_in_groups

    def altered(h, w_router, bias, experts):
        if what == "bias":
            bias = jnp.zeros_like(bias)
        else:
            experts = experts._replace(groups=1, kept_groups=1)
        picks, weights, kept = real(h, w_router, bias, experts)
        return picks, weights, jnp.broadcast_to(kept[:, :1], (len(kept), 8))

    monkeypatch.setattr(flm, "route_in_groups", altered)
    rows = _rows(_plane(6))
    assert not rows["picks_disagree_share"]["ok"], rows


def test_mla_base_matrices_left_in_float32_are_not_correct(monkeypatch):
    """The configuration states bfloat16 storage: a program that keeps the
    frozen matrices in float32 holds twice the memory it claims."""
    init = gigachat3.init_params
    monkeypatch.setattr(gigachat3, "init_params",
                        lambda cfg, seed: init(cfg, seed, jnp.float32))
    rows = _rows(_plane(4))
    assert rows["state_leaves_not_float32"]["value"] > 20
    assert not rows["state_leaves_not_float32"]["ok"]


def test_mla_state_kept_below_float32_is_not_correct():
    plane = _plane(4)
    plane.setup()
    plane.trainer.lora = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), plane.trainer.lora)
    plane.finish()
    rows = {r["name"]: r for r in plane.check()}
    assert rows["state_leaves_not_float32"]["value"] == len(
        gigachat3.LORA_TARGETS) * 2 * 4
    assert not rows["state_leaves_not_float32"]["ok"]


def test_mla_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from fedml_tpu.train.llm import trainer

    monkeypatch.setattr(trainer.optax, "apply_updates",
                        lambda params, updates: params)
    r = tiny_mla.run(tiny_mla.SFT, E2E, 5, 0.5, tmp_path)
    assert not r["correct"] and r["failed"] == 0


def test_mla_program_built_inside_the_window_is_not_correct(
        tmp_path, monkeypatch):
    from chipbench.planes import sft_mla

    window = sft_mla.Plane.window

    def compiling_window(self, seconds):
        jax.jit(lambda x: x * 5 + 2)(jnp.ones((3, 11)))
        window(self, seconds)

    monkeypatch.setattr(sft_mla.Plane, "window", compiling_window)
    r = tiny_mla.run(tiny_mla.SFT, E2E, 8, 0.5, tmp_path)
    assert not r["correct"]
