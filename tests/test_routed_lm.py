"""The routed family (`models/functional_lm.RoutedLMModule`: RMSNorm, rotary or
no positions and a window by layer, grouped heads, routed ReGLU experts of
which a chip holds a share) at a small size on the CPU: the program against
the plain float32 reference of `chipbench/reference/smallthinker.py`, the
shares adding up to the uncut layer, what a layer without positions ignores,
what the expert layer never drops, and GPT-2 through the one `block` as
before."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fedml_tpu
from chipbench.planes.sft_routed import model_args
from chipbench.reference import smallthinker as ref
from fedml_tpu.models import functional_lm as flm
from fedml_tpu.ops import routed_experts as rex
from fedml_tpu.train.llm.lora import apply_lora

#: every mechanism of the published model at a size a CPU holds: a NoPE full
#: layer to three RoPE window layers, twice; grouped heads; 16 experts of
#: which 4 are held, top-3; a window shorter than the sequence
CFG = {
    "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 8, "vocab_size": 211,
    "moe_ffn_hidden_size": 24, "moe_num_primary_experts": 4,
    "moe_num_active_primary_experts": 3, "experts_first_held": 8,
    "published": {"moe_num_primary_experts": 16},
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "sliding_window_size": 12,
    "rope_layout": [0, 1, 1, 1] * 2, "sliding_window_layout": [0, 1, 1, 1] * 2,
    "initializer_range": 0.2}
T = 32
ALPHA = 16.0


def _module(cfg=CFG):
    return fedml_tpu.model.create(fedml_tpu.Config(**model_args(cfg)),
                                  cfg["vocab_size"]).module


def _row(seed, cfg=CFG):
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, cfg["vocab_size"], T + 1)
    return jnp.asarray(toks[:-1]), jnp.asarray(toks[1:])


def _lora(seed, cfg=CFG):
    """Factors with B drawn too, so that every factor has a gradient."""
    lora = ref.init_lora(cfg, seed, 4)
    rng = np.random.RandomState(seed)
    return {k: {"a": f["a"], "b": jnp.asarray(
        rng.randn(*f["b"].shape) * 0.01, jnp.float32)} for k, f in lora.items()}


def _worst(got, want):
    """The largest gap of a tree against the reference's, each leaf by its
    own largest magnitude."""
    return max(float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
               for g, w in zip(jax.tree_util.tree_leaves(got),
                               jax.tree_util.tree_leaves(want)))


def _reference(params, lora, x, y, mode):
    stacked = ref.stack_lora(lora, CFG["num_hidden_layers"])
    loss, grads = ref.row_grad(stacked, ref.stack_blocks(params), x, y,
                               jnp.ones(T), CFG, ALPHA, mode)
    grads = ref.unstack_lora(jax.tree_util.tree_map(lambda g: g / T, grads))
    return float(loss) / T, {f"blocks/{i}/{n}": g
                             for (i, n), g in sorted(grads.items())}


#: with the experts' operands left in float32 too (they are rounded to
#: bfloat16 on every backend otherwise) the program is a float32 computation
#: here, and stands within float32 rounding of the reference: 1e-5 of the
#: loss and 1e-4 of each gradient leaf's largest entry (read: 8e-8 and 4e-6).
#: The reference computed in bfloat16, the precision below, misses the loss
#: by 2e-4 or more and the gradients by 0.17 or more of a leaf's largest
LOSS_TOL, GRAD_TOL = 1e-5, 1e-4


@pytest.fixture
def float32_experts(monkeypatch):
    monkeypatch.setattr(rex, "_OPERAND", jnp.float32)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_program_matches_reference_loss_and_lora_gradients(seed,
                                                           float32_experts):
    module = _module()
    params = ref.init_params(CFG, seed)
    lora = _lora(seed)
    x, y = _row(seed)
    named = {f"blocks/{i}/{n}": f for (i, n), f in sorted(lora.items())}

    def loss(named):
        merged = apply_lora(params, named, ALPHA)
        return module.loss({"params": merged}, x[None], y[None],
                           jnp.ones((1, T)))[0]

    got_loss, got_grads = jax.value_and_grad(loss)(named)
    want_loss, want_grads = _reference(params, lora, x, y, "float32")
    assert abs(float(got_loss) - want_loss) / want_loss < LOSS_TOL
    assert _worst(got_grads, want_grads) < GRAD_TOL
    low_loss, low_grads = _reference(params, lora, x, y, "bfloat16")
    assert abs(low_loss - want_loss) / want_loss > 10 * LOSS_TOL
    assert _worst(low_grads, want_grads) > 100 * GRAD_TOL


def test_bfloat16_operands_in_the_experts_stay_near_the_reference():
    """As the program runs: the experts' operands rounded to bfloat16.  A
    pick that flips on that rounding moves a token's output by a whole
    expert, so this holds the loss alone, loosely."""
    module = _module()
    x, y = _row(2)
    params = ref.init_params(CFG, 2)
    got = module.loss({"params": params}, x[None], y[None],
                      jnp.ones((1, T)))[0]
    want, _ = _reference(params, ref.init_lora(CFG, 2, 4), x, y, "float32")
    assert 1e-7 < abs(float(got) - want) / want < 5e-3


@pytest.mark.parametrize("seed", [1, 2])
def test_program_matches_reference_forward_and_picks(seed, float32_experts):
    module = _module()
    params = ref.init_params(CFG, seed)
    x, _ = _row(seed)
    logits = module.apply({"params": params}, x[None])[0]
    h, picks = ref.hidden_one(params, x, ref.sizes(CFG))
    want = jnp.matmul(h, params["w_out"], precision="highest")
    assert float(jnp.max(jnp.abs(logits - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))
    got = module.picks({"params": params}, x[None])[:, 0]
    assert got.shape == (8, T, 3)
    assert np.array_equal(np.sort(np.asarray(got), -1),
                          np.sort(np.asarray(picks), -1))


def test_loss_counts_the_picks():
    module = _module()
    x, y = _row(4)
    _, counted = module.loss({"params": ref.init_params(CFG, 4)}, x[None],
                             y[None], jnp.ones((1, T)))
    assert int(counted["picks"]) == 8 * T * 3
    assert 0 < int(counted["picks_held"]) < int(counted["picks"])
    assert int(counted["picks_held"]) / 32 <= int(
        counted["expert_picks_max"]) <= T


def test_blocks_rematerialised_or_not_give_one_loss(monkeypatch):
    module = _module()
    params = {"params": ref.init_params(CFG, 5)}
    x, y = _row(5)

    def grad():
        return jax.value_and_grad(lambda p: module.loss(
            p, x[None], y[None], jnp.ones((1, T)))[0])(params)

    kept = grad()
    monkeypatch.setattr(flm, "_REMAT_OVER", 0)
    again = grad()
    np.testing.assert_allclose(float(kept[0]), float(again[0]), rtol=1e-6)
    assert _worst(again[1], kept[1]) < 1e-5


@pytest.mark.parametrize("n,rows", [(64, 16), (50, 16), (7, 16)])
def test_loss_in_row_blocks_is_the_masked_loss(n, rows):
    from fedml_tpu.ml.engine.model_bundle import masked_loss

    rng = np.random.RandomState(n)
    h = jnp.asarray(rng.randn(n, 12), jnp.float32)
    w = jnp.asarray(rng.randn(12, 37), jnp.float32)
    y = jnp.asarray(rng.randint(0, 37, n))
    mask = jnp.asarray(rng.rand(n) < 0.7, jnp.float32)
    want = masked_loss("lm", h @ w, y, mask)
    np.testing.assert_allclose(
        float(flm.loss_in_row_blocks(h, w, y, mask, rows)), float(want),
        rtol=1e-6)
    g = jax.grad(lambda h: flm.loss_in_row_blocks(h, w, y, mask, rows))(h)
    gw = jax.grad(lambda h: masked_loss("lm", h @ w, y, mask))(h)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gw), atol=1e-6)


# -- the shares add up ---------------------------------------------------------

def _uncut():
    """The whole layer group's configuration: every expert, head and
    vocabulary row of which ``CFG`` would be one of four shares."""
    return dict(CFG, num_hidden_layers=1, moe_num_primary_experts=16,
                experts_first_held=0, num_attention_heads=8,
                num_key_value_heads=4, vocab_size=4 * 53,
                rope_layout=[1], sliding_window_layout=[1])


def _share(params, s):
    """Chip ``s``'s quarter of the uncut layer's weights: 4 experts, 2 query
    heads with their key/value head, 53 columns of the head."""
    blk = params["blocks"][0]
    q, kv = slice(16 * s, 16 * s + 16), slice(8 * s, 8 * s + 8)
    return {"embed": params["embed"], "ln_f": params["ln_f"],
            "w_out": params["w_out"][:, 53 * s:53 * s + 53],
            "blocks": [dict(
                blk, wq=blk["wq"][:, q], wk=blk["wk"][:, kv],
                wv=blk["wv"][:, kv], wo=blk["wo"][q],
                w_gate_up=blk["w_gate_up"][4 * s:4 * s + 4],
                w_down=blk["w_down"][4 * s:4 * s + 4])]}


def _four_shares_of_the_grouped_layer():
    """(got, want) of the softmax-routed family: each chip's part of the
    attention term, of the expert term and of the logits, summed (the
    logits: laid side by side), against the uncut layer of the reference;
    the residual, the norms and the router, which all compute alike, are
    counted once.  A part is isolated by zeroing the other's output matrix,
    since the expert term reads the attention's."""
    from fedml_tpu.ops.pallas_attention import flash_attention

    whole = _uncut()
    z = ref.sizes(whole)
    params = ref.init_params(whole, 7)
    h = jnp.asarray(np.random.RandomState(7).randn(T, 32), jnp.float32)
    blk = params["blocks"][0]
    zero = lambda name: dict(blk, **{name: jnp.zeros_like(blk[name])})
    want_attn = ref._block(h, zero("w_down"), True, True, z, "float32")[0] - h
    want_experts = ref._block(h, zero("wo"), True, True, z, "float32")[0] - h
    want_logits = jnp.matmul(ref._rms_norm(h, params["ln_f"], z["eps"]),
                             params["w_out"], precision="highest")

    attn = functools.partial(flash_attention, causal=True)
    got_attn = got_experts = 0.0
    logits = []
    for s in range(4):
        cfg = dict(CFG, num_hidden_layers=1, experts_first_held=4 * s,
                   num_attention_heads=2, num_key_value_heads=1,
                   vocab_size=53, rope_layout=[1], sliding_window_layout=[1])
        module = _module(cfg)
        mine = _share(params, s)
        for name in ("w_down", "wo"):
            part = dict(mine["blocks"][0])
            part[name] = jnp.zeros_like(part[name])
            out = flm.block(h[None], part, module.heads,
                            flm._over_sequence(attn, module.layers[0]),
                            module.layers[0])[0] - h
            if name == "w_down":
                got_attn = got_attn + out
            else:
                got_experts = got_experts + out
        logits.append(flm.head(h, mine, module.layers[0]))
    return ((got_attn, want_attn), (got_experts, want_experts),
            (jnp.concatenate(logits, -1), want_logits))


def _thirty_two_shares_of_the_latent_layer():
    """(got, want) of the latent-attention family, whose deployment cuts the
    experts alone: the 32 chips' routed terms (one expert each, isolated as
    the layer with its experts less the layer without) on top of attention
    and the shared expert counted once, against the uncut routed layer of
    the reference; and the dense layer, which no chip cuts, as it is."""
    from chipbench.reference import gigachat3
    from fedml_tpu.ops.pallas_attention import flash_attention
    from mla_tiny import CFG as LATENT, module as latent_module

    whole = dict(LATENT, n_routed_experts=32, experts_first_held=0)
    z = gigachat3.sizes(whole)
    params = gigachat3.init_params(whole, 7)
    h = jnp.asarray(np.random.RandomState(7).randn(T, 32), jnp.float32)
    dense, routed = params["blocks"][0], params["blocks"][1]
    want = gigachat3._block(h, routed, z, "float32")[0]
    want_dense = gigachat3._block(h, dense, z, "float32")[0]

    attn = functools.partial(flash_attention, causal=True)

    def run(module, blk, i):
        layer = module.layers[i]
        return flm.block(h[None], blk, module.heads,
                         flm._over_sequence(attn, layer), layer)[0]

    got = 0.0
    for s in range(32):
        module = latent_module(dict(LATENT, n_routed_experts=1,
                                    experts_first_held=s))
        mine = dict(routed, w_gate_up=routed["w_gate_up"][s:s + 1],
                    w_down=routed["w_down"][s:s + 1])
        without = run(module, dict(mine, w_down=jnp.zeros_like(
            mine["w_down"])), 1)
        got = got + run(module, mine, 1) - without
    return ((got + without, want), (run(module, dense, 0), want_dense))


@pytest.mark.parametrize("family", ["grouped heads, softmax router",
                                    "latent attention, sigmoid router"])
def test_the_shares_add_up_to_the_uncut_layer(family):
    pairs = (_four_shares_of_the_grouped_layer() if family.startswith("group")
             else _thirty_two_shares_of_the_latent_layer())
    for got, want in pairs:
        assert float(jnp.max(jnp.abs(got - want))) < 1e-2 * float(
            jnp.max(jnp.abs(want)))


# -- positions -------------------------------------------------------------------

@pytest.mark.parametrize("rotates", [False, True])
def test_a_layer_without_positions_ignores_the_order_of_a_prefix(rotates):
    """Under the full causal mask a layer of `rope_layout` 0 sees the keys
    before a position as a set: permuting a prefix leaves every later
    position's output as it was.  A rotating layer's moves."""
    from fedml_tpu.ops.pallas_attention import flash_attention

    cfg = dict(CFG, num_hidden_layers=1, rope_layout=[int(rotates)],
               sliding_window_layout=[0])
    module = _module(cfg)
    blk = ref.init_params(cfg, 8)["blocks"][0]
    layer = module.layers[0]
    assert (layer.rope_theta is not None) == rotates and layer.window is None
    rng = np.random.RandomState(8)
    h = jnp.asarray(rng.randn(1, T, 32), jnp.float32)
    prefix = rng.permutation(12)
    moved = h.at[0, :12].set(h[0, prefix])
    attend = flm._over_sequence(
        functools.partial(flash_attention, causal=True), layer)
    # the expert term reads its own row only: the attention decides
    a, b = (flm.block(z, blk, module.heads, attend, layer)[0, 12:]
            for z in (h, moved))
    gap = float(jnp.max(jnp.abs(a - b)))
    assert gap > 1e-3 if rotates else gap < 1e-5


# -- the expert layer ------------------------------------------------------------

def _dense_share(y, picks, weights, w_gate_up, w_down, experts):
    """Every held expert over every token, weighted where picked."""
    f = w_down.shape[1]
    out = 0.0
    for e in range(experts.held):
        w_e = jnp.sum(jnp.where(picks == e + experts.first_held, weights, 0.0),
                      -1)
        gu = jnp.matmul(y, w_gate_up[e], precision="highest")
        out = out + w_e[:, None] * jnp.matmul(
            jax.nn.relu(gu[:, :f]) * gu[:, f:], w_down[e], precision="highest")
    return out


def _layer(seed, n=150, d=32, f=24, held=4):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, d), jnp.float32),
            jnp.asarray(rng.randn(held, d, 2 * f) * 0.2, jnp.float32),
            jnp.asarray(rng.randn(held, f, d) * 0.2, jnp.float32))


@pytest.mark.parametrize("interpret", [None, True], ids=["ragged", "kernel"])
def test_no_token_is_dropped_when_all_pick_one_expert(
        monkeypatch, interpret, float32_experts):
    """The worst imbalance: every token's every pick on held experts, one of
    them taking a pick of every token.  Nothing is dropped and nothing
    capped: the share is the dense computation."""
    monkeypatch.setattr(rex, "TILE", 16)
    experts = rex.Experts(total=16, held=4, first_held=4, top_k=2)
    y, w_gate_up, w_down = _layer(9)
    n = y.shape[0]
    picks = jnp.stack([jnp.full((n,), 5), 4 + 2 * (jnp.arange(n) % 2)], -1)
    weights = jnp.asarray(np.random.RandomState(9).dirichlet([1, 1], n),
                          jnp.float32)
    got, counts, _ = rex.held_experts(y, picks, weights, w_gate_up, w_down,
                                      experts, interpret)
    assert counts.tolist() == [n // 2, n, n // 2, 0]
    want = _dense_share(y, picks, weights, w_gate_up, w_down, experts)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("interpret", [None, True], ids=["ragged", "kernel"])
@pytest.mark.parametrize("first_held", [0, 8, 12])
def test_held_share_and_its_gradients_match_the_dense_layer(
        monkeypatch, interpret, first_held, float32_experts):
    """Forward, and the gradients to the tokens, the router's input, the
    router and the experts' own matrices, against every held expert computed
    over every token; picks that landed elsewhere add nothing."""
    monkeypatch.setattr(rex, "TILE", 16)
    experts = rex.Experts(total=16, held=4, first_held=first_held, top_k=3)
    y, w_gate_up, w_down = _layer(10)
    rng = np.random.RandomState(11)
    h = jnp.asarray(rng.randn(*y.shape), jnp.float32)
    w_r = jnp.asarray(rng.randn(y.shape[1], 16), jnp.float32)

    def run(share, y, h, w_r, w_gate_up, w_down):
        picks, weights = rex.route(h, w_r, experts.top_k)
        return jnp.sum(jnp.sin(share(y, picks, weights, w_gate_up, w_down)))

    program = lambda *a: rex.held_experts(*a, experts, interpret)[0]
    dense = lambda *a: _dense_share(*a, experts)
    args = (y, h, w_r, w_gate_up, w_down)
    got = jax.grad(functools.partial(run, program), argnums=range(5))(*args)
    want = jax.grad(functools.partial(run, dense), argnums=range(5))(*args)
    assert abs(float(run(program, *args) - run(dense, *args))) < 1e-3
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * float(
            jnp.max(jnp.abs(w)))


def _picks_that_fall(how, n, experts):
    """[n, top_k] picks for a case of the test below; ``chunk`` rows a
    chunk, ``TILE`` 16 and 4 held experts of 16 from the fourth on."""
    k, first, held = experts.top_k, experts.first_held, experts.held
    chunk = rex.CHUNK_TILES * rex.TILE
    picks = np.zeros((n, k), np.int64)           # expert 0: held by another
    if how == "none":
        return picks
    if how == "a quarter":
        return np.random.RandomState(13).randint(0, experts.total, (n, k))
    if how == "all on one":
        picks[:] = np.arange(k) + (first + held) % experts.total
        picks[:, 0] = first + 1
        return picks
    if how == "every pick":
        return first + np.stack([np.random.RandomState(i).permutation(held)[:k]
                                 for i in range(n)])
    # one expert's rows: a whole number of chunks, or one row more
    picks[:chunk + (how == "a row past a chunk"), 0] = first + 2
    return picks


@pytest.mark.parametrize("interpret", [None, True], ids=["ragged", "kernel"])
@pytest.mark.parametrize("how", ["none", "a quarter", "a whole chunk",
                                 "a row past a chunk", "all on one",
                                 "every pick"])
def test_share_and_gradients_match_the_dense_layer_however_the_picks_fall(
        monkeypatch, interpret, how, float32_experts):
    """The passes follow the rows that landed: nothing landed, a quarter,
    live rows that end on a chunk and one row past it, every token on one
    held expert, and the worst case the rows are laid out for (every pick
    of every token held here).  Output and the gradients to the tokens, the
    weights and the matrices, against every held expert over every token."""
    monkeypatch.setattr(rex, "TILE", 16)
    monkeypatch.setattr(rex, "CHUNK_TILES", 4)
    every = how == "every pick"
    experts = (rex.Experts(total=4, held=4, first_held=0, top_k=3) if every
               else rex.Experts(total=16, held=4, first_held=4, top_k=3))
    y, w_gate_up, w_down = _layer(14)
    n = y.shape[0]
    picks = jnp.asarray(_picks_that_fall(how, n, experts))
    weights = jnp.asarray(np.random.RandomState(14).dirichlet([1] * 3, n),
                          jnp.float32)
    landed = int(np.sum((np.asarray(picks) >= experts.first_held)
                        & (np.asarray(picks) < experts.first_held + 4)))
    assert landed == {"none": 0, "a whole chunk": 64, "a row past a chunk": 65,
                      "all on one": n, "every pick": 3 * n}.get(how, landed)

    def run(share, y, weights, w_gate_up, w_down):
        return jnp.sum(jnp.sin(share(y, picks, weights, w_gate_up, w_down)))

    program = lambda *a: rex.held_experts(*a, experts, interpret)[0]
    dense = lambda *a: _dense_share(*a, experts)
    args = (y, weights, w_gate_up, w_down)
    _, counts, passed = rex.held_experts(y, picks, weights, w_gate_up, w_down,
                                         experts, interpret)
    assert int(counts.sum()) == landed
    if how in ("none", "a whole chunk", "a row past a chunk"):
        assert int(passed) == {"none": 0, "a whole chunk": 64,
                               "a row past a chunk": 128}[how]
    got = jax.grad(functools.partial(run, program), argnums=range(4))(*args)
    want = jax.grad(functools.partial(run, dense), argnums=range(4))(*args)
    assert abs(float(run(program, *args) - run(dense, *args))) < 1e-3
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * float(
            jnp.max(jnp.abs(w)))


@pytest.mark.parametrize("interpret", [None, True], ids=["ragged", "kernel"])
def test_rows_that_hold_nothing_never_reach_a_result(monkeypatch, interpret,
                                                     float32_experts):
    """The buffers of rows are made without a value and written up to the
    last live chunk: with NaN where nothing is written, the output and every
    gradient are finite and the same."""
    monkeypatch.setattr(rex, "TILE", 16)
    monkeypatch.setattr(rex, "CHUNK_TILES", 4)
    experts = rex.Experts(total=16, held=4, first_held=4, top_k=3)
    y, w_gate_up, w_down = _layer(15)
    n = y.shape[0]
    picks = jnp.asarray(np.random.RandomState(15).randint(0, 16, (n, 3)))
    weights = jnp.asarray(np.random.RandomState(15).dirichlet([1] * 3, n),
                          jnp.float32)

    def run(y, weights, w_gate_up, w_down):
        out = rex.held_experts(y, picks, weights, w_gate_up, w_down, experts,
                               interpret)[0]
        return jnp.sum(jnp.sin(out)), out

    def readings():
        jax.clear_caches()
        grads, out = jax.grad(run, argnums=range(4), has_aux=True)(
            y, weights, w_gate_up, w_down)
        return [np.asarray(a) for a in (out,) + grads]

    made = []

    def poisoned(shape, dtype):
        made.append(shape)
        return jnp.full(shape, jnp.nan, dtype)

    clean = readings()
    monkeypatch.setattr(rex, "_row_buffer", poisoned)
    dirty = readings()
    # x, hidden (forward); d_rows, d_gate_up, hidden again (backward)
    assert len(made) >= 4
    for a, b in zip(clean, dirty):
        assert np.isfinite(b).all() and np.array_equal(a, b)


def _combine_case(how, n, width, block_tokens, monkeypatch):
    """A plan over ``n`` tokens for a case of the combine's tests, rows of
    ``width`` with NaN wherever no pick computed one, and weights; the
    combine's block is set to ``block_tokens``.  ``TILE`` 16, 4 held
    experts of 16 from the fourth on."""
    monkeypatch.setattr(rex, "_SUM_BLOCK_BYTES", block_tokens * 4 * width)
    experts = rex.Experts(total=16, held=4, first_held=4, top_k=3)
    rng = np.random.RandomState(17)
    plan = rex.plan_rows(jnp.asarray(_picks_that_fall(how, n, experts)),
                         experts, 16)
    rows = jnp.where(plan.real[:, None], jnp.asarray(
        rng.randn(plan.real.shape[0], width), jnp.float32), jnp.nan)
    return plan, rows, jnp.asarray(rng.rand(n, 3), jnp.float32)


@pytest.mark.parametrize("width", [128, 384])
@pytest.mark.parametrize("n", [96, 100], ids=["whole blocks", "a part block"])
@pytest.mark.parametrize("how", ["none", "all on one", "a quarter"])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weights", "no weights"])
def test_the_combine_kernel_is_the_scan(monkeypatch, weighted, how, n, width):
    """``moe_sum_picks`` through the interpreter against the `jnp` scan: with
    and without weights; nothing landed, every token on one held expert, a
    mixed draw of which a quarter lands; tokens a whole number of the kernel's blocks of 32 or not;
    rows of one lane tile and of three.  Float32 sums of float32 rows in
    another order: equal to rounding.  Every row no pick computed is NaN,
    as are the rows of dead tiles: none reaches a sum."""
    plan, rows, weights = _combine_case(how, n, width, 32, monkeypatch)
    weights = weights if weighted else None
    want = rex._sum_picks_scan(rows, plan, weights)
    got = rex._sum_picks(rows, plan, weights, interpret=True)
    assert got.shape == want.shape == (n, width)
    assert got.dtype == jnp.float32
    landed = int(plan.landed.sum())
    assert landed == {"none": 0, "all on one": n}.get(how, landed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    if how == "none":
        assert not np.asarray(got).any()


def test_the_combine_reads_no_row_that_did_not_land(monkeypatch):
    """Every row past the landed ones of its expert, and every row of a
    dead tile, is NaN: the kernel's sums hold none, and they are the sums of
    the same plan over rows that are zero there."""
    plan, rows, weights = _combine_case("a quarter", 100, 128, 32, monkeypatch)
    real = np.asarray(plan.real)
    assert 0 < real.sum() < real.size // 2
    assert np.isnan(np.asarray(rows)[~real]).all()
    got = np.asarray(rex._sum_picks(rows, plan, weights, interpret=True))
    clean = np.asarray(rex._sum_picks(
        jnp.where(plan.real[:, None], rows, 0.0), plan, weights,
        interpret=True))
    assert np.isfinite(got).all() and np.array_equal(got, clean)
    # a token none of whose picks landed sums to zero
    nothing = ~np.asarray(plan.landed).any(axis=1)
    assert nothing.any() and not got[nothing].any()


@pytest.mark.parametrize("interpret,path,block", [
    (None, "jnp", 0), (True, "interpret", 32)])
def test_a_traced_combine_is_counted_once_with_its_path(monkeypatch,
                                                        interpret, path,
                                                        block):
    from fedml_tpu.core.mlops import metrics

    plan, rows, weights = _combine_case("a quarter", 96, 128, 32, monkeypatch)

    def count():
        m = metrics.REGISTRY.collect().get("fedml_moe_combine_traces_total")
        return {p: m.labels(path=p, width=128, block_tokens=b).value
                if m else 0
                for p, b in (("jnp", 0), ("interpret", 32), ("kernel", 32))}

    jax.clear_caches()
    before = count()
    summed = jax.jit(lambda r, w: rex._sum_picks(r, plan, w, interpret))
    summed(rows, weights)
    summed(rows, weights)           # a call of a traced program counts nothing
    after = count()
    assert {p: after[p] - before[p] for p in after} == {
        p: int(p == path) for p in after}


def test_rows_passed_are_whole_chunks_over_the_landed_picks():
    """`rows_passed` is what the counted picks call for, chunk by chunk,
    not the layout's worst case."""
    experts = rex.Experts(total=64, held=16, first_held=16, top_k=6)
    picks = jnp.asarray(np.random.RandomState(16).randint(0, 64, (512, 6)))
    plan = rex.plan_rows(picks, experts, tile=8)
    chunk = rex.CHUNK_TILES * 8
    live = int(plan.group_rows.sum())
    assert int(plan.counts.sum()) <= live < int(plan.counts.sum()) + 16 * 8
    assert int(rex.rows_passed(plan)) == -(-live // chunk) * chunk
    assert int(rex.rows_passed(plan)) < plan.real.shape[0] // 3
    # beyond them the plan's rows are as rows that compute nothing
    assert not np.asarray(plan.real)[live:].any()
    assert not np.asarray(plan.token_of_row)[live:].any()


def test_plan_gives_every_landed_pick_a_row_of_its_experts_tiles():
    experts = rex.Experts(total=16, held=4, first_held=4, top_k=3)
    picks = jnp.asarray(np.random.RandomState(12).randint(0, 16, (40, 3)))
    plan = rex.plan_rows(picks, experts, tile=8)
    landed = np.asarray(plan.landed)
    rows = np.asarray(plan.row_of_pick)[landed]
    assert len(set(rows.tolist())) == landed.sum() == int(plan.counts.sum())
    local = np.asarray(picks)[landed] - 4
    assert (np.asarray(plan.tile_expert)[rows // 8] == local).all()
    assert np.asarray(plan.real)[rows].all()
    assert int(plan.real.sum()) == landed.sum()
    # the rows are laid out for the worst case, and one dead tile beyond it
    assert plan.real.shape[0] == (-(-(40 * 3 + 4 * 7) // 8) + 1) * 8
    assert int(plan.live_tiles[0]) == int(plan.group_rows.sum()) // 8


# -- GPT-2 through the one block --------------------------------------------------

def _gpt2_block_as_it_was(h, blk, heads, attend):
    """`functional_lm.block` before it took a description (PR 29's)."""
    def ln(x, g):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g["scale"] + g["bias"]

    bias = lambda z, key: z + blk[key] if key in blk else z
    dim = h.shape[-1]
    y = ln(h, blk["ln1"])
    proj = lambda w, b: bias(y @ blk[w], b).reshape(
        *y.shape[:-1], heads, dim // heads)
    o = attend(proj("wq", "bq"), proj("wk", "bk"), proj("wv", "bv"))
    h = h + bias(o.reshape(h.shape) @ blk["wo"], "bo")
    y = ln(h, blk["ln2"])
    return h + bias(jax.nn.gelu(bias(y @ blk["w1"], "b1")) @ blk["w2"], "b2")


@pytest.mark.parametrize("biases", [False, True])
def test_gpt2_through_the_described_block_bit_for_bit(biases):
    from fedml_tpu.ops.pallas_attention import flash_attention

    params = flm.init_lm_params(jax.random.PRNGKey(3), 50, dim=32, layers=2,
                                heads=4, max_len=16)
    if biases:
        for i, blk in enumerate(params["blocks"]):
            for j, (b, w) in enumerate((("bq", "wq"), ("bk", "wk"),
                                        ("bv", "wv"), ("bo", "wo"),
                                        ("b1", "w1"), ("b2", "w2"))):
                blk[b] = jax.random.normal(jax.random.PRNGKey(10 * i + j),
                                           (blk[w].shape[1],)) * 0.1
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 50, (2, 16)))
    attn = functools.partial(flash_attention, causal=True)
    attend = flm._over_sequence(attn, flm.GPT2)
    h = old = flm.embed(params, tokens)
    for blk in params["blocks"]:
        h = flm.block(h, blk, 4, attend)
        old = _gpt2_block_as_it_was(old, blk, 4, attend)
        assert np.array_equal(np.asarray(h), np.asarray(old))
    assert np.array_equal(np.asarray(flm.lm_forward(params, tokens, 4, attn)),
                          np.asarray(flm.head(old, params)))


# -- through LLMTrainer ------------------------------------------------------------

def test_llm_trainer_trains_the_routed_family_and_counts_its_picks():
    from fedml_tpu.core.mlops import metrics
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    bundle = fedml_tpu.model.create(fedml_tpu.Config(**model_args(CFG)),
                                    CFG["vocab_size"])
    trainer = LLMTrainer(bundle, LLMTrainConfig(seq_len=T, batch_size=2))
    # the four attention matrices of every block, nothing of the experts
    assert sorted(trainer.lora) == sorted(
        f"blocks/{i}/{w}" for i in range(8) for w in ("wq", "wk", "wv", "wo"))

    def count(name):
        m = metrics.REGISTRY.collect().get(name)
        return sum(c.value for c in m.children().values()) if m else 0.0

    before = {n: count(n) for n in ("fedml_moe_picks_total",
                                    "fedml_moe_picks_held_total",
                                    "fedml_moe_rows_passed_total",
                                    "fedml_moe_expert_picks_max")}
    stream = np.tile(np.random.RandomState(0).randint(0, 211, 16), 13)[:T * 6 + 1]
    losses = [trainer.train(stream)["train_loss"] for _ in range(6)]
    assert losses[-1] < losses[0]
    picks = count("fedml_moe_picks_total") - before["fedml_moe_picks_total"]
    held = count("fedml_moe_picks_held_total") - before[
        "fedml_moe_picks_held_total"]
    assert picks == 6 * 3 * 8 * 2 * T * 3 and 0 < held < picks
    # whole chunks over the landed picks, of a layout that holds all picks
    passed = count("fedml_moe_rows_passed_total") - before[
        "fedml_moe_rows_passed_total"]
    assert held <= passed and passed % rex.TILE == 0
    assert count("fedml_moe_expert_picks_max") > before[
        "fedml_moe_expert_picks_max"]
    # the epoch program's third result: the loss, the counts beside it
    out = trainer._train_epoch(
        trainer.lora, trainer.tx.init(trainer.lora),
        trainer.variables["params"], {},
        {k: jnp.zeros((2, 2, T), jnp.int32 if k != "mask" else jnp.float32)
         for k in ("x", "y", "mask")}, jax.random.PRNGKey(0))
    trainer.lora = out[0]
    assert set(out[2]) == {"loss", "picks", "picks_held", "rows_passed",
                           "expert_picks_max"}
