"""The latent-attention family (`models/functional_lm.RoutedLMModule` described
with `Latent` attention, a group-limited sigmoid router, a shared expert, a
leading dense SwiGLU layer and a second head) at a small size on the CPU: the
program against the plain float32 reference of
`chipbench/reference/gigachat3.py` on both loss terms, LoRA gradients and
picks; the router alone against a written-out one; YaRN's frequencies against
the closed form; a head of 192 through the flash kernel's interpreter; and
matrices stored in bfloat16 under `apply_lora` and the expert kernel."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import gigachat3 as ref
from fedml_tpu.models import functional_lm as flm
from fedml_tpu.ops import routed_experts as rex
from fedml_tpu.train.llm.lora import apply_lora
from mla_tiny import CFG, T, module

ALPHA = 16.0
#: as `test_routed_lm.py`: with the experts' operands left in float32 the
#: program is a float32 computation here (read: loss 1e-7, gradients 5e-6 of
#: a leaf's largest entry)
LOSS_TOL, GRAD_TOL = 1e-5, 1e-4


@pytest.fixture
def float32_experts(monkeypatch):
    monkeypatch.setattr(rex, "_OPERAND", jnp.float32)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _row(seed):
    toks = np.random.RandomState(seed).randint(0, CFG["vocab_size"], T + 1)
    return jnp.asarray(toks[:-1]), jnp.asarray(toks[1:])


def _lora(seed):
    """Factors with B drawn too, so that every factor has a gradient."""
    rng = np.random.RandomState(seed)
    return {k: {"a": f["a"], "b": jnp.asarray(
        rng.randn(*f["b"].shape) * 0.01, jnp.float32)}
        for k, f in ref.init_lora(CFG, seed, 4).items()}


def _named(lora):
    return {f"blocks/{i}/{n}": f for (i, n), f in sorted(lora.items())}


def _worst(got, want):
    return max(float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
               for g, w in zip(jax.tree_util.tree_leaves(got),
                               jax.tree_util.tree_leaves(want)))


def _program(params, lora, x, y):
    mod = module()

    def loss(named):
        return mod.loss({"params": apply_lora(params, named, ALPHA)},
                        x[None], y[None], jnp.ones((1, T)))

    return jax.value_and_grad(loss, has_aux=True)(_named(lora))


@pytest.mark.parametrize("seed", [1, 2])
def test_program_matches_reference_loss_terms_gradients_and_picks(
        seed, float32_experts):
    params, lora = ref.init_params(CFG, seed), _lora(seed)
    x, y = _row(seed)
    (loss, counted), grads = _program(params, lora, x, y)
    (want, (main, mtp)), want_grads = ref.row_grad(
        lora, params, x, y, jnp.ones(T), CFG, ALPHA)
    for got, wanted in ((loss, want), (counted["loss_main"], main / T),
                        (counted["loss_mtp"], mtp / (T - 1))):
        assert abs(float(got) - float(wanted)) / float(wanted) < LOSS_TOL
    assert float(counted["mtp_positions"]) == T - 1
    assert _worst(grads, _named(want_grads)) < GRAD_TOL
    # one precision down, the reference misses both by far more
    (low, _), low_grads = ref.row_grad(lora, params, x, y, jnp.ones(T), CFG,
                                       ALPHA, "bfloat16")
    assert abs(float(low) - float(want)) / float(want) > 10 * LOSS_TOL
    assert _worst(_named(low_grads), _named(want_grads)) > 100 * GRAD_TOL
    # the picks of the two routed layers and of the second head's block
    got = module().picks({"params": params}, x[None], y[None])[:, 0]
    picks = ref.forward_one(params, x, y, ref.sizes(CFG))["picks"]
    assert got.shape == (3, T, 4)
    assert np.array_equal(np.sort(np.asarray(got), -1),
                          np.sort(np.asarray(picks), -1))


def test_a_second_loss_that_is_dropped_or_shifted_shows():
    """The sum hides the second term behind its weight; the term alone does
    not: against token i + 1 in place of i + 2 it reads another number."""
    params = ref.init_params(CFG, 3)
    x, y = _row(3)
    z = ref.sizes(CFG)
    out = ref.forward_one(params, x, y, z)
    right = ref._loss_sum(out["mtp"][:-1], params["w_out"], y[1:],
                          jnp.ones(T - 1), "float32") / (T - 1)
    wrong = ref._loss_sum(out["mtp"], params["w_out"], y, jnp.ones(T),
                          "float32") / T
    _, counted = module().loss({"params": params}, x[None], y[None],
                               jnp.ones((1, T)))
    assert abs(float(counted["loss_mtp"]) - float(right)) < 1e-4 * float(right)
    assert abs(float(wrong) - float(right)) > 1e-2 * float(right)


def test_loss_counts_the_picks_and_the_tokens_in_the_held_group():
    x, y = _row(4)
    _, counted = module().loss({"params": ref.init_params(CFG, 4)}, x[None],
                               y[None], jnp.ones((1, T)))
    # two routed layers and the second head's block, four picks a token
    assert int(counted["picks"]) == 3 * T * 4
    assert 0 < int(counted["picks_held"]) < int(counted["picks"])
    # 4 of 8 groups are kept: about half the tokens keep the held one, and
    # only those can have picked a held expert
    kept = int(counted["tokens_in_held_group"])
    assert 0 < kept < 3 * T
    assert int(counted["picks_held"]) <= 4 * kept


# -- the router alone ----------------------------------------------------------

def _written_out(scores, bias, groups, kept_groups, top_k, scale):
    """The group-limited top-k token by token, with sorts that keep the order
    of equals (the lower number wins a tie)."""
    picks, weights = [], []
    for s in np.asarray(scores, np.float64):
        choice = s + bias
        per = len(s) // groups
        group_score = [np.sort(choice[g * per:(g + 1) * per])[-2:].sum()
                       for g in range(groups)]
        best = np.argsort(-np.asarray(group_score), kind="stable")[
            :kept_groups]
        allowed = [e for e in range(len(s)) if e // per in best]
        order = sorted(allowed, key=lambda e: (-choice[e], e))[:top_k]
        picks.append(order)
        weights.append(s[order] / (s[order].sum() + 1e-20) * scale)
    return np.asarray(picks), np.asarray(weights)


@pytest.mark.parametrize("case", ["random", "ties", "a bias that flips a pick",
                                  "the best experts in a dropped group"])
def test_router_matches_a_written_out_group_limited_top_k(case):
    experts = rex.Experts(total=32, held=4, first_held=0, top_k=4,
                          scores="sigmoid", groups=8, kept_groups=4, scale=2.5)
    rng = np.random.RandomState(5)
    n, d = 24, 32
    logits = rng.randn(n, 32)
    bias = np.zeros(32)
    if case == "ties":
        # whole rows of equal scores, and pairs of equal groups
        logits = np.round(logits)
        logits[:4] = 0.0
    elif case == "a bias that flips a pick":
        # expert 1 scores just under expert 0 everywhere; the bias lifts it
        logits[:] = -4.0
        logits[:, 0], logits[:, 1] = 1.0, 0.9
        logits[:, 8:11] = 0.5
        bias[1] = 0.05
    elif case == "the best experts in a dropped group":
        # group 7 holds one expert far above all, but a second near nothing:
        # four groups with two good experts each push it out
        logits[:] = -6.0
        logits[:, 28] = 5.0
        for g in range(4):
            logits[:, 4 * g], logits[:, 4 * g + 1] = 2.0, 1.5 - 0.1 * g
    # logits from an exactly invertible product: h = logits, W = identity
    h = jnp.asarray(logits, jnp.float32)
    picks, weights, kept = rex.route_in_groups(
        h, jnp.eye(d, 32, dtype=jnp.float32), jnp.asarray(bias, jnp.float32),
        experts)
    scores = jax.nn.sigmoid(h)
    want_picks, want_weights = _written_out(scores, bias, 8, 4, 4, 2.5)
    assert np.array_equal(np.asarray(picks), want_picks)
    np.testing.assert_allclose(np.asarray(weights), want_weights, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    assert np.all(np.asarray(kept).sum(-1) == 4)
    if case == "a bias that flips a pick":
        assert np.all(np.asarray(picks)[:, 0] == 1)
        # the weight is of the score alone: expert 1's stays under expert 0's
        assert np.all(np.asarray(weights)[:, 0] < np.asarray(weights)[:, 1])
    if case == "the best experts in a dropped group":
        assert not np.any(np.asarray(picks) == 28)
        assert not np.any(np.asarray(kept)[:, 7])
    # the reference's router is the same function, written otherwise
    r_picks, r_weights, r_kept = ref.route(
        h, jnp.eye(d, 32, dtype=jnp.float32), jnp.asarray(bias, jnp.float32),
        dict(groups=8, kept_groups=4, top_k=4, scale=2.5))
    assert np.array_equal(np.asarray(r_picks), want_picks)
    assert np.array_equal(np.asarray(r_kept), np.asarray(kept))
    np.testing.assert_allclose(np.asarray(r_weights), want_weights, rtol=1e-5)


# -- positions -------------------------------------------------------------------

PUBLISHED = flm.Latent(q_rank=1536, kv_rank=512, nope=128, rope=64, v=192,
                       theta=1e5, factor=64.0, original=4096, beta_fast=32.0,
                       beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)


def test_yarn_frequencies_at_the_published_parameters():
    """Pair i turns ``theta^(-2i/64)`` a position.  It keeps that where it
    turns more than 32 times in 4096 positions (pairs 0-8), has it divided by
    64 where it turns less than once (pairs 19-31), and lies on the straight
    line between for pairs 9-18."""
    got = flm.yarn_freq(PUBLISHED)
    plain = 1e5 ** (-np.arange(32) / 32.0)
    turns = 4096 * plain / (2 * math.pi)
    assert np.all(turns[:9] > 32) and turns[9] < 32
    assert turns[18] > 1 > turns[19]
    np.testing.assert_allclose(got[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(got[19:], plain[19:] / 64, rtol=1e-6)
    # the ramp runs from pair 8 (the last that keeps its own) to pair 19
    share = (np.arange(9, 19) - 8) / (19 - 8)
    np.testing.assert_allclose(
        got[9:19], plain[9:19] * (1 - share) + plain[9:19] / 64 * share,
        rtol=1e-6)
    np.testing.assert_allclose(got, ref.yarn_freq(ref.sizes(dict(
        CFG, qk_rope_head_dim=64, rope_scaling=dict(
            CFG["rope_scaling"], original_max_position_embeddings=4096)))),
        rtol=1e-6)
    # scores are scaled by 192^-0.5 times (0.1 ln 64 + 1)^2
    m = 0.1 * math.log(64) + 1
    assert abs(flm.yarn_softmax_scale(PUBLISHED) - 192 ** -0.5 * m * m) < 1e-9
    # and without a factor the rotation is the plain one
    plain_layer = PUBLISHED._replace(factor=1.0)
    np.testing.assert_allclose(flm.yarn_freq(plain_layer), plain, rtol=1e-6)
    assert abs(flm.yarn_softmax_scale(plain_layer) - 192 ** -0.5) < 1e-12


# -- a head of 192 ---------------------------------------------------------------

@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_attention_at_head_size_192(grad):
    """One and a half lane tiles a head, through the kernel's interpreter,
    against the plain attention; the tiled backward takes the same size."""
    from fedml_tpu.ops import pallas_attention as pa

    rng = np.random.RandomState(6)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 256, 192), jnp.float32)
               for _ in range(3))
    kernel = functools.partial(pa.flash_attention, causal=True, interpret=True)
    plain = functools.partial(pa._reference, causal=True)
    if not grad:
        np.testing.assert_allclose(
            np.asarray(kernel(q, k, v)), np.asarray(plain(q, k, v)),
            atol=2e-2, rtol=2e-2)
        return
    w = jnp.asarray(rng.randn(1, 2, 256, 192), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for g, wanted in zip(got, want):
        assert float(jnp.max(jnp.abs(g - wanted))) < 2e-2 * float(
            jnp.max(jnp.abs(wanted)))


def test_backward_kernel_at_head_size_192():
    """`flash_bwd` at 192, one and a half lane tiles, over four q tiles of
    two key passes each and two K/V blocks: the gradients of the jnp
    backward that rounds the same operands (operands of a few bits, so that
    no rounding to bfloat16 can fall the other way)."""
    from conftest import few_bits, rounded_flash_backward
    from fedml_tpu.ops import pallas_attention as pa

    q, k, v, do = (few_bits(seed, (1, 2, 128, 192)) for seed in range(4))
    q = q * (192 ** 0.5 / 16)           # q x scale: a multiple of 1/64
    o, l, m = pa._reference_residuals(q, k, v, True)
    o = jnp.round(o * 64) / 64
    got = pa._flash_bwd_call(*pa._rounded(q, k, v), o, l, m, do, causal=True,
                             block_q=32, block_k=32, block_kv=64,
                             t_valid=128, interpret=True)
    want = rounded_flash_backward(q, k, v, o, l, m, do, True)
    for g, wanted in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wanted),
                                   atol=1e-4, rtol=1e-4)


# -- matrices stored in bfloat16 ---------------------------------------------------

def test_apply_lora_on_a_bfloat16_leaf_is_the_float32_merge_rounded_once():
    rng = np.random.RandomState(8)
    w = jnp.asarray(rng.randn(48, 40) * 0.02, jnp.bfloat16)
    lora = {"blocks/0/wo": {
        "a": jnp.asarray(rng.randn(48, 4) * 0.3, jnp.float32),
        "b": jnp.asarray(rng.randn(4, 40) * 0.3, jnp.float32)}}
    params = {"blocks": [{"wo": w, "ln1": {"scale": jnp.ones(48)}}]}
    got = apply_lora(params, lora, ALPHA)["blocks"][0]["wo"]
    delta = (ALPHA / 4) * (lora["blocks/0/wo"]["a"] @ lora["blocks/0/wo"]["b"])
    once = (w.astype(jnp.float32) + delta).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(once, np.float32))
    # rounding the product first and the sum again is another number
    twice = w + delta.astype(jnp.bfloat16)
    assert not np.array_equal(np.asarray(twice, np.float32),
                              np.asarray(once, np.float32))
    # a float32 leaf is merged as it was, and the gradient reaches the factors
    w32 = w.astype(jnp.float32)
    merged = apply_lora({"blocks": [{"wo": w32}]}, lora, ALPHA)
    np.testing.assert_allclose(np.asarray(merged["blocks"][0]["wo"]),
                               np.asarray(w32 + delta), rtol=1e-6)
    grads = jax.grad(lambda f: jnp.sum(apply_lora(params, f, ALPHA)[
        "blocks"][0]["wo"].astype(jnp.float32) ** 2))(lora)
    assert float(jnp.max(jnp.abs(grads["blocks/0/wo"]["b"]))) > 0


@pytest.mark.parametrize("transposed", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_expert_product_takes_a_matrix_in_column_blocks(monkeypatch, dtype,
                                                        transposed):
    """A matrix too large for a grid step goes through in blocks of whole
    lane tiles of its columns, the outer axis of the grid; a bfloat16 one is
    multiplied as it is, with no copy beside it."""
    monkeypatch.setattr(rex, "TILE", 16)
    monkeypatch.setattr(rex, "_W_BLOCK_BYTES", 128 * 24 * 4)
    experts = rex.Experts(total=8, held=4, first_held=2, top_k=2)
    rng = np.random.RandomState(9)
    k, n = 24, 512
    assert rex._column_blocks(n, k * jnp.dtype(dtype).itemsize) == (
        2 if dtype == jnp.bfloat16 else 4)
    picks = jnp.asarray(rng.randint(0, 8, (40, 2)))
    plan = rex.plan_rows(picks, experts, 16)
    m = plan.real.shape[0]
    x = jnp.asarray(rng.randn(m, k), jnp.bfloat16)
    w = jnp.asarray(rng.randn(4, *((n, k) if transposed else (k, n))) * 0.1,
                    dtype)
    got = rex.grouped_matmul(x, w, plan, transposed, interpret=True)
    want = rex.grouped_matmul(x, w, plan, transposed)
    assert got.shape == (m, n)
    live = np.asarray(plan.real)
    # the fallback multiplies in float32: held to bfloat16's rounding
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=3e-2, rtol=3e-2)


def test_bfloat16_stored_weights_stay_near_the_reference():
    """As the cell runs: the frozen matrices stored in bfloat16 on both
    sides, the experts' operands rounded to bfloat16.  The loss terms alone,
    loosely (a pick that flips on a rounding moves a token by an expert)."""
    cfg = dict(CFG, weights_stored="bfloat16")
    params = ref.init_params(cfg, 2)
    blk = params["blocks"][1]
    assert blk["wo"].dtype == blk["w_gate_up"].dtype == jnp.bfloat16
    assert blk["router"].dtype == blk["ln1"]["scale"].dtype == jnp.float32
    x, y = _row(2)
    _, counted = module(cfg).loss({"params": params}, x[None], y[None],
                                  jnp.ones((1, T)))
    (_, (main, mtp)), _ = ref.row_grad(ref.init_lora(cfg, 2, 4), params, x, y,
                                       jnp.ones(T), cfg, ALPHA)
    assert abs(float(counted["loss_main"]) - float(main) / T) < 2e-2 * float(
        main) / T
    assert abs(float(counted["loss_mtp"]) - float(mtp) / (T - 1)) < 2e-2 * (
        float(mtp) / (T - 1))


def test_module_draws_its_frozen_matrices_in_the_stated_type():
    mod = module(dict(CFG, weights_stored="bfloat16"))
    params = jax.eval_shape(lambda k: mod.init(k, None),
                            jax.random.PRNGKey(0))["params"]
    assert len(params["blocks"]) == 4           # the second head's block last
    assert "router" not in params["blocks"][0]
    blk = params["blocks"][3]
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate_up",
                 "w_down", "shared_gate_up", "shared_down"):
        assert blk[name].dtype == jnp.bfloat16, name
    for leaf in (blk["router"], blk["router_bias"], blk["ln1"]["scale"],
                 blk["q_norm"]["scale"], params["mtp"]["norm_e"]["scale"]):
        assert leaf.dtype == jnp.float32
    assert params["embed"].dtype == params["w_out"].dtype == jnp.bfloat16
    assert params["mtp"]["w_eh"].shape == (64, 32)
    with pytest.raises(ValueError, match="one kernel takes one head size"):
        module(dict(CFG, v_head_dim=8))


# -- through the trainer -----------------------------------------------------------

def test_llm_trainer_trains_the_family_and_reports_both_loss_terms():
    import fedml_tpu
    from chipbench.planes.sft_mla import model_args
    from fedml_tpu.core.mlops import metrics
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    bundle = fedml_tpu.model.create(fedml_tpu.Config(**model_args(CFG)),
                                    CFG["vocab_size"])
    trainer = LLMTrainer(bundle, LLMTrainConfig(seq_len=T, batch_size=2))
    # the five attention matrices of every block, the second head's too;
    # nothing of the experts, the dense MLP, the router or the joining matrix
    assert sorted(trainer.lora) == sorted(
        f"blocks/{i}/{w}" for i in range(4) for w in ref.LORA_TARGETS)

    def count(name):
        m = metrics.REGISTRY.collect().get(name)
        return sum(c.value for c in m.children().values()) if m else 0.0

    names = ("fedml_moe_picks_total", "fedml_moe_tokens_in_held_group_total",
             "fedml_sft_mtp_positions_total")
    before = {n: count(n) for n in names}
    stream = np.tile(np.random.RandomState(0).randint(0, 211, 16), 13)[
        :T * 6 + 1]
    outs = [trainer.train(stream) for _ in range(6)]
    assert outs[-1]["train_loss"] < outs[0]["train_loss"]
    for out in outs:
        assert abs(out["loss_main"] + 0.3 * out["loss_mtp"]
                   - out["train_loss"]) < 1e-4 * out["train_loss"]
    # 6 calls of 3 steps of 2 rows: 3 routed blocks, 4 picks a token
    assert count(names[0]) - before[names[0]] == 6 * 3 * 3 * 2 * T * 4
    assert 0 < count(names[1]) - before[names[1]] < 6 * 3 * 3 * 2 * T
    assert count(names[2]) - before[names[2]] == 6 * 3 * 2 * (T - 1)
