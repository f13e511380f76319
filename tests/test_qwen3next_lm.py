"""The family with delta-rule layers (`models/functional_lm`: gated delta-rule
mixers among gated softmax-attention ones, q and k normed by head, a part of a
head rotated, norm scales centred on zero, a sigmoid-gated shared expert) at a
small size on the CPU: the program against the plain float32 reference of
`chipbench/reference/qwen3_next.py`, each mechanism showing when it is
dropped, the four chips' shares of a routed layer adding up to the uncut one,
and what LoRA's default targets reach."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from chipbench.planes.sft_gdn import model_args
from chipbench.reference import qwen3_next as ref
from fedml_tpu.models import functional_lm as flm
from fedml_tpu.ops import routed_experts as rex
from fedml_tpu.train.llm.lora import apply_lora, init_lora

#: every mechanism of the published model at a size a CPU holds: three
#: delta-rule layers to one softmax layer, two value heads a key head, four
#: taps, 16 experts of which 4 are held, 4 picks, a gated shared expert
CFG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "full_attention_interval": 4, "num_hidden_layers": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 24,
    "shared_expert_intermediate_size": 24, "num_experts": 4,
    "num_experts_per_tok": 4, "hidden_act": "silu", "vocab_size": 211,
    "rms_norm_eps": 1e-6, "published": {"num_experts": 16},
    "experts_first_held": 4, "initializer_range": 0.2,
    "weights_stored": "float32"}
T = 32
ALPHA = 16.0


def _module(**changed):
    args = model_args(CFG)
    for key, value in changed.items():
        if isinstance(args.get(key), dict):
            args[key] = dict(args[key], **value)
        else:
            args[key] = value
    return fedml_tpu.model.create(fedml_tpu.Config(**args),
                                  CFG["vocab_size"]).module


def _row(seed):
    toks = np.random.RandomState(seed).randint(0, CFG["vocab_size"], T + 1)
    return jnp.asarray(toks[:-1]), jnp.asarray(toks[1:])


def _lora(seed):
    """Factors with B drawn too, so that every factor has a gradient."""
    rng = np.random.RandomState(seed)
    return {k: {"a": f["a"], "b": jnp.asarray(
        rng.randn(*f["b"].shape) * 0.01, jnp.float32)}
        for k, f in ref.init_lora(CFG, seed, 4).items()}


def _program_loss(module, params, lora, x, y):
    named = {f"blocks/{i}/{name}": f for (i, name), f in lora.items()}

    def loss(named):
        merged = apply_lora(params, named, ALPHA)
        return module.loss({"params": merged}, x[None], y[None],
                           jnp.ones((1, T)))[0]

    value, grads = jax.value_and_grad(loss)(named)
    return value, {k: grads[f"blocks/{k[0]}/{k[1]}"] for k in lora}


def _reference_loss(params, lora, x, y):
    return ref.row_grad(lora, params, x, y, jnp.ones(T), CFG, ALPHA)


def _worst(got, want):
    """The largest gap of a factor's gradient, by its norm."""
    return max(float(jnp.linalg.norm(got[k][ab] - want[k][ab])
                     / (jnp.linalg.norm(want[k][ab]) + 1e-30))
               for k in want for ab in "ab")


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(CFG, 7), _lora(7), _row(7)


def test_program_agrees_with_the_plain_reference(weights):
    """Loss and every factor's gradient, the delta rule by its recurrence
    here and position by position there."""
    params, lora, (x, y) = weights
    want, want_g = _reference_loss(params, lora, x, y)
    got, got_g = _program_loss(_module(), params, lora, x, y)
    assert set(got_g) == set(want_g) and len(got_g) == 3 * 2 + 4
    assert abs(float(got) - float(want)) < 3e-4 * float(want)
    assert _worst(got_g, want_g) < 2e-2     # the experts' bfloat16 operands


def test_program_with_the_kernels_interpreted_agrees_too(weights,
                                                         monkeypatch):
    """The chunked kernels in the layers, forward and backward (float32
    operands: their rounding is `test_delta_rule.py`'s)."""
    from fedml_tpu.ops import delta_rule

    params, lora, (x, y) = weights
    monkeypatch.setattr(delta_rule, "_OPERAND", "float32")
    monkeypatch.setattr(delta_rule, "_CHUNK", 16)
    monkeypatch.setattr(flm, "gated_delta_rule", functools.partial(
        flm.gated_delta_rule, interpret=True))
    want, want_g = _reference_loss(params, lora, x, y)
    got, got_g = _program_loss(_module(), params, lora, x, y)
    assert abs(float(got) - float(want)) < 3e-4 * float(want)
    assert _worst(got_g, want_g) < 2e-2


def test_the_mixers_kernels_interpreted_are_the_jnp_path(weights,
                                                        monkeypatch):
    """The passes around the scan in their kernels (`gated_delta_mixer`:
    operands, scan and gated norm under one `custom_vjp`, blocks of 16
    positions) against the jnp of `_delta_mixer` with the recurrence: the
    loss and every factor's gradient, to float32 rounding (the experts'
    operands float32 on both sides: rounded to bfloat16 they turn a last
    digit's difference into one of 1e-3)."""
    from fedml_tpu.ops import delta_rule

    params, lora, (x, y) = weights
    monkeypatch.setattr(rex, "_OPERAND", jnp.float32)
    want, want_g = _program_loss(_module(), params, lora, x, y)
    for name, value in (("_OPERAND", "float32"), ("_CHUNK", 16),
                        ("_MIXER_ROWS", 16), ("_SLAB", 8)):
        monkeypatch.setattr(delta_rule, name, value)
    calls = []
    monkeypatch.setattr(flm, "gated_delta_mixer", lambda *a, **kw: calls.append(
        1) or delta_rule.gated_delta_mixer(*a, interpret=True, **kw))
    got, got_g = _program_loss(_module(), params, lora, x, y)
    assert len(calls) >= 3                      # the three delta-rule layers
    assert abs(float(got) - float(want)) < 1e-6 * float(want)
    assert _worst(got_g, want_g) < 5e-5


def _without(monkeypatch, what):
    """The module with one mechanism of the family dropped."""
    if what == "output gate":
        monkeypatch.setattr(flm, "_gated", lambda o, gate: o)
    if what == "convolution":
        monkeypatch.setattr(flm, "_causal_conv", lambda x, w: x)
    if what in ("decay", "writing strength"):
        real = flm.gated_delta_rule
        monkeypatch.setattr(flm, "gated_delta_rule", (
            lambda q, k, v, g, beta: real(q, k, v, g * 0, beta))
            if what == "decay" else
            lambda q, k, v, g, beta: real(q, k, v, g, beta * 0 + 1))
    return _module(**{
        "head norms": dict(lm_attention=dict(qk_norm=False)),
        "partial rotation": dict(lm_attention=dict(rotary=None)),
        "centred scale": dict(lm_centred_norm=False),
        "shared gate": dict(lm_shared_gate=False)}.get(what, {}))


@pytest.mark.parametrize("what", [
    "output gate", "head norms", "partial rotation", "centred scale",
    "shared gate", "convolution", "decay", "writing strength"])
def test_a_dropped_mechanism_shows(weights, monkeypatch, what):
    params, _, (x, y) = weights
    operands = {"params": params}, x[None, :16], y[None, :16], jnp.ones((1, 16))
    whole = _module().loss(*operands)[0]
    less = _without(monkeypatch, what).loss(*operands)[0]
    assert abs(float(whole) - float(less)) > 1e-3 * float(whole), what


def test_the_convolution_is_the_loop():
    rng = np.random.RandomState(0)
    x, w = rng.randn(2, 9, 5).astype(np.float32), rng.randn(5, 4).astype(
        np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += w[:, i] * x[:, t - 3 + i]
    np.testing.assert_allclose(flm._causal_conv(jnp.asarray(x),
                                                jnp.asarray(w)), want,
                               rtol=1e-5, atol=1e-6)


def test_a_partial_rotation_leaves_the_rest_of_a_head():
    x = jnp.asarray(np.random.RandomState(1).randn(2, 6, 3, 16), jnp.float32)
    layer = flm.Layer(rope_theta=1e7, rotary=4)
    out = flm._rotate_first(x, layer)
    np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
    np.testing.assert_array_equal(out[:, 0], x[:, 0])       # position 0
    assert float(jnp.max(jnp.abs(out[:, 1:, :, :4] - x[:, 1:, :, :4]))) > 0.1
    # the four numbers that turn are `_rotate`'s of a head of four
    np.testing.assert_allclose(
        out[..., :4], flm._rotate(x[..., :4], flm._rope_freq(1e7, 2)),
        rtol=1e-6)
    whole = flm._rotate_first(x, flm.Layer(rope_theta=1e7))
    np.testing.assert_allclose(
        whole, flm._rotate(x, flm._rope_freq(1e7, 8)), rtol=1e-6)


def test_four_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11, 12-15 on four chips and the gated shared
    expert counted once, against every expert computed over every token."""
    rng = np.random.RandomState(3)
    n, d, f, total, k = 24, 32, 24, 16, 4
    y = jnp.asarray(rng.randn(n, d), jnp.float32)
    blk = {"router": jnp.asarray(rng.randn(d, total) * 0.5, jnp.float32),
           "w_gate_up": jnp.asarray(rng.randn(total, d, 2 * f) * 0.2,
                                    jnp.float32),
           "w_down": jnp.asarray(rng.randn(total, f, d) * 0.2, jnp.float32),
           "shared_gate_up": jnp.asarray(rng.randn(d, 2 * f) * 0.2,
                                         jnp.float32),
           "shared_down": jnp.asarray(rng.randn(f, d) * 0.2, jnp.float32),
           "shared_gate": jnp.asarray(rng.randn(d) * 0.5, jnp.float32)}
    # the uncut layer, plainly: softmax over all, the k largest renormalised
    p = jax.nn.softmax(y @ blk["router"], axis=-1)
    top, picks = jax.lax.top_k(p, k)
    w = top / top.sum(-1, keepdims=True)
    swiglu = lambda x, gu, dn: (jax.nn.silu((x @ gu)[:, :f])
                                * (x @ gu)[:, f:]) @ dn
    want = jax.nn.sigmoid(y @ blk["shared_gate"])[:, None] * swiglu(
        y, blk["shared_gate_up"], blk["shared_down"])
    for e in range(total):
        w_e = jnp.sum(jnp.where(picks == e, w, 0.0), -1)
        want = want + w_e[:, None] * swiglu(y, blk["w_gate_up"][e],
                                            blk["w_down"][e])
    got, landed = 0.0, 0
    for chip in range(4):
        experts = rex.Experts(total, 4, 4 * chip, k, act="silu",
                              reads="normed")
        share = dict(blk, w_gate_up=blk["w_gate_up"][4 * chip:4 * chip + 4],
                     w_down=blk["w_down"][4 * chip:4 * chip + 4])
        out, stats, _ = flm._expert_mlp(y, y, share, experts)
        got, landed = got + out, landed + int(stats["picks_held"])
    assert landed == n * k                      # every pick lands once
    got = got + jax.nn.sigmoid(y @ blk["shared_gate"])[:, None] * flm._swiglu(
        y, blk["shared_gate_up"], blk["shared_down"])
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_default_targets_reach_the_delta_rule_layers():
    """Three layers of four have no ``wq``: their projection and their way
    out are adapted by default, the rest of them stays frozen."""
    params = jax.eval_shape(lambda: ref.init_params(CFG, 0))
    lora = jax.eval_shape(functools.partial(
        init_lora, rank=4, rng=jax.random.PRNGKey(0)), params)
    assert sorted(lora) == sorted(
        f"blocks/{i}/{name}" for i in range(4)
        for name in ref.lora_targets(ref.sizes(CFG), i))
    assert {k.split("/")[-1] for k in lora} == {
        "w_qkvz", "wo", "wq", "wk", "wv"}


def test_the_module_draws_what_the_reference_lays_out():
    """`init_routed_params` and the reference's `init_params` make the same
    tree: names, shapes and (float32 stored here) types."""
    own = jax.eval_shape(lambda: _module().init(jax.random.PRNGKey(0), None))
    want = jax.eval_shape(lambda: ref.init_params(CFG, 0))
    shapes = lambda tree: {jax.tree_util.keystr(p): (a.shape, str(a.dtype))
                           for p, a in jax.tree_util.tree_leaves_with_path(
                               tree)}
    assert shapes(own["params"]) == shapes(want)


def test_trainer_defaults_train_the_family():
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    bundle = fedml_tpu.model.create(fedml_tpu.Config(**model_args(CFG)),
                                    CFG["vocab_size"])
    trainer = LLMTrainer(bundle, LLMTrainConfig(seq_len=T, batch_size=2))
    assert len(trainer.lora) == 3 * 2 + 4
    toks = np.tile(np.random.RandomState(5).randint(0, 211, 16), 40)[:T * 8 + 1]
    first = trainer.train(toks)["train_loss"]
    for _ in range(3):
        last = trainer.train(toks)["train_loss"]
    assert np.isfinite(last) and last < first


def test_a_delta_rule_layer_cannot_be_served_a_position():
    layer = _module().layers[0]
    blk = ref.init_params(CFG, 0)["blocks"][0]
    with pytest.raises(NotImplementedError, match="recurrent state"):
        flm.block(jnp.zeros((2, 32)), blk, 4, None, layer)
