"""Plain GigaChat3 (ai-sage/GigaChat3.1-702B-A36B, ``model_type: deepseek_v3``):
weights from a seed, forward, the two next-token losses, LoRA and AdamW, in
straightforward ``jax.numpy``, for the share of the model that one chip of a
32-chip layer group holds.

The yardstick of the ``gigachat31_*`` configurations.  It imports nothing of
the program and takes nothing the program has made; the precision switch of
its products (``_mm``), the optimizer step and the seed's key are
``reference/gpt2.py``'s.

A layer, as published (``h`` its input ``[T, hidden_size]``; every norm an
RMSNorm with ``rms_norm_eps``):

* ``y = norm1(h)``; ``cq = norm_q(y Wqa)`` (rank ``q_lora_rank``); ``q = cq
  Wqb`` as heads of ``[q_nope | q_rope]`` (``qk_nope_head_dim`` |
  ``qk_rope_head_dim``);
* ``[ckv | k_rope] = y Wkva`` (``kv_lora_rank`` | ``qk_rope_head_dim``: one
  rotary key for all heads); ``norm_kv(ckv) Wkvb`` as heads of ``[k_nope | v]``
  (``qk_nope_head_dim`` | ``v_head_dim``);
* ``q_rope`` and ``k_rope`` rotated by YaRN's frequencies (``yarn_freq``), cos
  and sin times ``mscale``'s term over ``mscale_all_dim``'s; ``k = [k_nope |
  k_rope]``; causal softmax of ``q k^T * s`` with ``s = (nope + rope)^-0.5 *
  m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; ``a = h + o Wo``;
* ``z = norm2(a)``.  One of the ``first_k_dense_replace`` leading layers:
  ``a + (silu(z Wg) * (z Wu)) Wd`` of width ``intermediate_size``.  A routed
  layer: scores ``sigmoid(z Wr)``; choosing reads ``scores + bias``: a group
  (``n_routed_experts / n_group`` consecutive experts) scores the sum of its two
  best, the ``topk_group`` best groups stay, the ``num_experts_per_tok`` best
  experts of those are picked; weights are the picked *scores* over their sum,
  times ``routed_scaling_factor``; ``a + shared(z) + sum_i w_i expert_i(z)``,
  each a SwiGLU of width ``moe_intermediate_size``;
* a final RMSNorm and an untied output head;
* multi-token prediction, depth 1: ``h' = [norm_e(embed(token i+1)) ;
  norm_h(trunk's last stream i, before its final norm)] Weh``, one whole routed
  layer over ``h'``, its own final norm, the trunk's head, cross-entropy against
  token i+2; ``loss = main + mtp_loss_weight * mtp``.

Departures from the published description, each because one chip holds a share
and not the model, or because the config does not say (``assumed`` in the
configuration's file):

* the counts of layers, leading dense layers, experts and vocabulary rows are
  the *held* ones.  Of the experts, ``experts_first_held .. + held`` are
  computed and a pick that landed on another adds nothing (the router still
  scores all ``published.n_routed_experts``);
* the frozen matrices are *stored* in bfloat16, as the configuration states,
  and taken up to float32 one at a time where they are used; the norms'
  scales, the router and its bias are float32;
* the router's product and scores are float32 at ``highest`` in every
  ``mode``, as the program's are, so that picks differ only where the residual
  streams do;
* a head's rotary numbers are paired by halves (the source's checkpoints
  interleave them: a permutation of ``Wqb``'s and ``Wkva``'s columns);
* an expert's gate and up matrices are kept side by side, ``w_gate_up``;
* the second head runs over all T positions and leaves the last out of its
  loss: under a causal mask positions 0..T-2 do not see it;
* attention and the losses are computed in row blocks: the same sums in
  another order.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.gpt2 import HIGHEST, _mm, adamw_step, seed_key

__all__ = ["seed_key", "init_params", "init_lora", "finetune", "row_grad",
           "picks_one", "forward_one", "sizes", "yarn_freq", "route",
           "LORA_TARGETS"]

#: the five attention matrices LoRA adapts in every block, the second head's
#: included: what the program's default targets reach in this layout
LORA_TARGETS = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")

#: queries of an attention block, rows of a loss block
ATTN_ROWS = 256
LOSS_ROWS = 1024


def sizes(cfg: dict) -> dict:
    """The static sizes of the share, from the configuration's own keys."""
    rope = cfg["rope_scaling"]
    return dict(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        layers=int(cfg["num_hidden_layers"]),
        dense=int(cfg["first_k_dense_replace"]),
        mtp=int(cfg["num_nextn_predict_layers"]),
        mtp_weight=float(cfg["mtp_loss_weight"]),
        heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), dense_ffn=int(cfg["intermediate_size"]),
        ffn=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]),
        experts=int(cfg["published"]["n_routed_experts"]),
        held=int(cfg["n_routed_experts"]),
        first_held=int(cfg["experts_first_held"]),
        top_k=int(cfg["num_experts_per_tok"]), groups=int(cfg["n_group"]),
        kept_groups=int(cfg["topk_group"]),
        scale=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        factor=float(rope["factor"]),
        original=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        mscale=float(rope["mscale"]),
        mscale_all_dim=float(rope["mscale_all_dim"]))


def _static(cfg: dict):
    return tuple(sorted(sizes(cfg).items()))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _block_shapes(z: dict, dense: bool) -> dict:
    """name -> (shape, kind): "w" a frozen matrix, "res" one that writes to
    the residual stream, "norm" a scale, "router", "bias"."""
    d, h = z["dim"], z["heads"]
    out = {
        "ln1": ((d,), "norm"), "wq_a": ((d, z["q_rank"]), "w"),
        "q_norm": ((z["q_rank"],), "norm"),
        "wq_b": ((z["q_rank"], h * (z["nope"] + z["rope"])), "w"),
        "wkv_a": ((d, z["kv_rank"] + z["rope"]), "w"),
        "kv_norm": ((z["kv_rank"],), "norm"),
        "wkv_b": ((z["kv_rank"], h * (z["nope"] + z["v"])), "w"),
        "wo": ((h * z["v"], d), "res"), "ln2": ((d,), "norm")}
    if dense:
        out.update(w_gate_up=((d, 2 * z["dense_ffn"]), "w"),
                   w_down=((z["dense_ffn"], d), "res"))
        return out
    f = z["ffn"]
    out.update(router=((d, z["experts"]), "router"),
               router_bias=((z["experts"],), "bias"),
               w_gate_up=((z["held"], d, 2 * f), "w"),
               w_down=((z["held"], f, d), "res"))
    if z["shared"]:
        out.update(shared_gate_up=((d, 2 * f * z["shared"]), "w"),
                   shared_down=((f * z["shared"], d), "res"))
    return out


@functools.partial(jax.jit, static_argnames=("z", "init_range", "bias_std",
                                             "stored"))
def _init(key, z, init_range, bias_std, stored):
    z = dict(z)
    s_res = init_range / math.sqrt(2 * z["layers"])     # residual projections
    count = iter(range(10 ** 6))

    def draw(shape, kind):
        n = jax.random.normal(jax.random.fold_in(key, next(count)), shape,
                              jnp.float32)
        if kind == "norm":
            # a published model's norm scales are trained away from one;
            # drawn here, so that a path that drops one shows
            return {"scale": 1.0 + n * init_range}
        if kind == "bias":
            return n * bias_std
        if kind == "router":
            return n * init_range
        return (n * (s_res if kind == "res" else init_range)).astype(stored)

    def block(dense):
        return {name: draw(*spec)
                for name, spec in _block_shapes(z, dense).items()}

    d = z["dim"]
    params = {"embed": draw((z["vocab"], d), "w"),
              "blocks": [block(i < z["dense"])
                         for i in range(z["layers"] + z["mtp"])],
              "ln_f": draw((d,), "norm"),
              "w_out": draw((d, z["vocab"]), "w")}
    if z["mtp"]:
        params["mtp"] = {"norm_e": draw((d,), "norm"),
                         "norm_h": draw((d,), "norm"),
                         "w_eh": draw((2 * d, d), "w"),
                         "ln_f": draw((d,), "norm")}
    return params


def init_params(cfg: dict, seed: int, stored=None):
    """The share's weights in the program's layout (the second head's block
    the last of ``blocks``, its joining matrix and norms under ``"mtp"``),
    made on the device in one jitted call: the matrices in the type the
    configuration states
    (``weights_stored``; ``stored`` overrides it), the norms' scales, the
    routers and their biases in float32; all drawn, so that a dropped term
    shows."""
    if int(cfg["num_nextn_predict_layers"]) > 1:
        raise ValueError("one multi-token-prediction module is described")
    return _init(seed_key(seed), _static(cfg),
                 float(cfg["initializer_range"]),
                 float(cfg["router_bias_std"]),
                 jnp.dtype(stored or cfg["weights_stored"]))


def init_lora(cfg: dict, seed: int, rank: int):
    """LoRA factors as published (Hu et al. 2021): A normal, B zero.
    ``{(block, name): {"a", "b"}}`` over the five attention matrices of every
    block, the second head's (the last) included."""
    z = sizes(cfg)
    shapes = _block_shapes(z, True)
    key = jax.random.fold_in(seed_key(seed), 0x10a)

    @jax.jit
    def make(key):
        out = {}
        for i in range(z["layers"] + z["mtp"]):
            for j, name in enumerate(LORA_TARGETS):
                d_in, d_out = shapes[name][0]
                k = jax.random.fold_in(key, i * len(LORA_TARGETS) + j)
                out[(i, name)] = {
                    "a": jax.random.normal(k, (d_in, rank), jnp.float32) * 0.01,
                    "b": jnp.zeros((rank, d_out), jnp.float32)}
        return out

    return make(key)


def _merged(blk, factors, alpha: float):
    """The block with ``W + (alpha / rank) * A B`` on every adapted matrix
    (``factors``: ``{name: {"a", "b"}}``): formed in float32, as one value;
    the stored matrix is not changed."""
    if factors is None:
        return blk
    blk = dict(blk)
    for name, f in factors.items():
        delta = jnp.matmul(f["a"], f["b"], precision=HIGHEST)
        blk[name] = blk[name].astype(jnp.float32) + (
            alpha / f["a"].shape[-1]) * delta
    return blk


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms_norm(x, g, eps):
    f = x.astype(jnp.float32)
    return (f / jnp.sqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
            * g["scale"]).astype(x.dtype)


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freq(z: dict) -> np.ndarray:
    """Frequencies of the ``rope / 2`` rotary pairs under YaRN (Peng et al.
    2023; the source's ``DeepseekV3YarnRotaryEmbedding``): a pair that turns
    more than ``beta_fast`` times over the original positions keeps
    ``theta^(-2i/rope)``, one that turns fewer than ``beta_slow`` times has it
    divided by ``factor``, with a linear ramp over the pairs between (the
    ramp's ends rounded outwards to whole pairs)."""
    dim, half = z["rope"], z["rope"] // 2
    plain = z["theta"] ** (-np.arange(half, dtype=np.float64) / half)
    if z["factor"] <= 1:
        return plain.astype(np.float32)

    def pair_of(turns):
        return dim * math.log(z["original"] / (turns * 2 * math.pi)) / (
            2 * math.log(z["theta"]))

    low = max(math.floor(pair_of(z["beta_fast"])), 0)
    high = min(math.ceil(pair_of(z["beta_slow"])), dim - 1)
    stretched = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (plain * (1 - stretched)
            + plain / z["factor"] * stretched).astype(np.float32)


def _rotate(x, z: dict):
    """YaRN's rotation on [T, H, rope] (position = row), the halves of a head
    holding the pairs' first and second members."""
    t, half = x.shape[0], x.shape[-1] // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_freq(z)[None, :]
    m = _mscale(z["factor"], z["mscale"]) / _mscale(z["factor"],
                                                   z["mscale_all_dim"])
    cos, sin = (jnp.cos(angle) * m)[:, None, :], (jnp.sin(angle) * m)[:, None, :]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, scale: float, mode: str):
    """Causal attention of [T, H, Dq] queries over [T, H, Dq] keys and
    [T, H, Dv] values, ``ATTN_ROWS`` queries at a time."""
    t, h, _ = q.shape
    rows = min(ATTN_ROWS, t)
    if t % rows:
        raise ValueError(f"{t} positions are not whole blocks of {rows}")
    k_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def some(args):
        q_i, i = args
        s = _mm(q_i, k, mode, "qhd,khd->hqk").astype(jnp.float32) * scale
        seen = i * rows + jnp.arange(rows)[:, None] >= k_pos
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm(p.astype(q.dtype), v, mode, "hqk,khd->qhd")

    o = jax.lax.map(some, (q.reshape(t // rows, rows, h, -1),
                           jnp.arange(t // rows)))
    return o.reshape(t, -1)


def _swiglu(x, w_gate_up, w_down, mode: str):
    gate_up = _mm(x, w_gate_up, mode)
    f = w_down.shape[0]
    return _mm(jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:], w_down, mode)


def route(x, w_router, bias, z: dict):
    """``x`` [T, D] -> (picks [T, top_k], weights [T, top_k], kept [T, groups])
    of the group-limited sigmoid router, float32 at ``highest``."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=HIGHEST))
    choice = scores + bias
    t, g = choice.shape[0], z["groups"]
    grouped = choice.reshape(t, g, -1)
    two_best = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    # the kept groups: rank of a group among the groups, ties to the lower
    order = jnp.argsort(-two_best, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    kept = rank < z["kept_groups"]
    allowed = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, -1)
    picks = jnp.argsort(-allowed, axis=-1, stable=True)[:, :z["top_k"]]
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * z["scale"]
    return picks, weights, kept


def _experts(x, blk, z: dict, mode: str):
    """The held experts' share of the routed term, expert by expert over all
    the tokens: a token's weight for an expert is its pick's weight, or
    nothing where it did not pick it.  Also the picks and the kept groups."""
    picks, weights, kept = route(x, blk["router"], blk["router_bias"], z)

    def one(out, e):
        w_e = jnp.sum(jnp.where(picks == e + z["first_held"], weights, 0.0),
                      axis=-1)
        return out + w_e[:, None].astype(x.dtype) * _swiglu(
            x, blk["w_gate_up"][e], blk["w_down"][e], mode), None

    out, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                          jnp.arange(z["held"]))
    return out, picks, kept


def _block(h, blk, z: dict, mode: str):
    """One layer over [T, D]: dense where the block has no router.  Returns
    the stream, and the picks and kept groups of a routed layer."""
    t, heads = h.shape[0], z["heads"]
    nope, rope, v_dim = z["nope"], z["rope"], z["v"]
    y = _rms_norm(h, blk["ln1"], z["eps"])
    cq = _rms_norm(_mm(y, blk["wq_a"], mode), blk["q_norm"], z["eps"])
    q = _mm(cq, blk["wq_b"], mode).reshape(t, heads, nope + rope)
    down = _mm(y, blk["wkv_a"], mode)
    ckv = _rms_norm(down[:, :z["kv_rank"]], blk["kv_norm"], z["eps"])
    kv = _mm(ckv, blk["wkv_b"], mode).reshape(t, heads, nope + v_dim)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], z)], -1)
    k_rope = _rotate(down[:, None, z["kv_rank"]:], z)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (t, heads, rope))], -1)
    scale = (nope + rope) ** -0.5 * _mscale(z["factor"],
                                            z["mscale_all_dim"]) ** 2
    a = h + _mm(_attention(q, k, kv[..., nope:], scale, mode), blk["wo"],
                mode)
    x = _rms_norm(a, blk["ln2"], z["eps"])
    if "router" not in blk:
        return a + _swiglu(x, blk["w_gate_up"], blk["w_down"], mode), None, None
    out, picks, kept = _experts(x, blk, z, mode)
    if "shared_gate_up" in blk:
        out = out + _swiglu(x, blk["shared_gate_up"], blk["shared_down"], mode)
    return a + out, picks, kept


def forward_one(params, x, y, z: dict, mode: str = "float32",
                remat: bool = False, lora=None, alpha: float = 0.0):
    """``[T]`` tokens ``x`` of one sequence and the tokens after them ``y``
    (left out: ``x`` turned by one, the last position reading the first) ->
    the trunk's stream after its final norm ``main`` [T, D], the second head's
    after its own ``mtp`` [T, D] (nothing without one), every routed block's
    picks ``picks`` [L, T, top_k] and kept groups ``kept`` [L, T, groups], the
    second head's block last.  ``lora``: factors merged into each block as it
    is used."""
    act = jnp.float32 if mode == "float32" else jnp.bfloat16
    y = jnp.roll(x, -1) if y is None else y

    def run(h, blk, factors):
        blk = _merged(blk, factors, alpha)
        # the norms, the router and its bias stay float32 whatever the mode
        blk = {k: v if isinstance(v, dict) or k.startswith("router")
               else v.astype(act) for k, v in blk.items()}
        return _block(h, blk, z, mode)

    def factors_of(which):
        return None if lora is None else {
            name: lora[(which, name)] for name in LORA_TARGETS}

    if remat:
        run = jax.checkpoint(run)
    h = params["embed"][x].astype(act)
    picks, kept = [], []
    for i, blk in enumerate(params["blocks"][:z["layers"]]):
        h, p, g = run(h, blk, factors_of(i))
        if p is not None:
            picks.append(p)
            kept.append(g)
    out = {"main": _rms_norm(h, params["ln_f"], z["eps"]), "mtp": None}
    if z["mtp"]:
        m = params["mtp"]
        joined = jnp.concatenate(
            [_rms_norm(params["embed"][y].astype(act), m["norm_e"], z["eps"]),
             _rms_norm(h, m["norm_h"], z["eps"])], axis=-1)
        h2, p, g = run(_mm(joined, m["w_eh"], mode).astype(act),
                       params["blocks"][z["layers"]], factors_of(z["layers"]))
        picks.append(p)
        kept.append(g)
        out["mtp"] = _rms_norm(h2, m["ln_f"], z["eps"])
    return dict(out, picks=jnp.stack(picks), kept=jnp.stack(kept))


def _loss_sum(h, w_out, y, mask, mode: str):
    """Masked sum of the cross-entropy of [T, D] against targets ``y``, the
    logits taken ``LOSS_ROWS`` positions at a time."""
    t = h.shape[0]
    rows = min(LOSS_ROWS, t)

    @jax.checkpoint
    def some(args):
        h_i, y_i, m_i = args
        logits = _mm(h_i, w_out, mode).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, y_i[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * m_i)

    return jnp.sum(jax.lax.map(some, (
        h.reshape(t // rows, rows, -1), y.reshape(-1, rows),
        mask.reshape(-1, rows))))


def loss_sums(params, x, y, mask, z: dict, mode: str = "float32",
              lora=None, alpha: float = 0.0):
    """The two masked sums of one row's cross-entropy: the trunk's head at
    position i against ``y[i]`` (token i+1), and the second head's against
    ``y[i+1]`` (token i+2), which a row's last position does not have; and
    the positions the second was taken over."""
    out = forward_one(params, x, y, z, mode, True, lora, alpha)
    main = _loss_sum(out["main"], params["w_out"], y, mask, mode)
    if out["mtp"] is None:
        return main, jnp.zeros(()), jnp.zeros(())
    ahead = lambda a: jnp.concatenate([a[1:], jnp.zeros_like(a[:1])])
    mask2 = mask * ahead(mask)
    return (main, _loss_sum(out["mtp"], params["w_out"], ahead(y), mask2,
                            mode), jnp.sum(mask2))


# ---------------------------------------------------------------------------
# LoRA fine-tuning: AdamW over the factors, gradient clipped by global norm
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("z", "alpha", "mode", "n_main",
                                             "n_mtp"))
def _row_grad(lora, params, x, y, mask, z, alpha, mode, n_main, n_mtp):
    z = dict(z)

    def f(lora):
        main, mtp, _ = loss_sums(params, x, y, mask, z, mode, lora, alpha)
        return main / n_main + z["mtp_weight"] * mtp / n_mtp, (main, mtp)

    return jax.value_and_grad(f, has_aux=True)(lora)


def row_grad(lora, params, x, y, mask, cfg: dict, alpha: float,
             mode: str = "float32", n_main: float = None,
             n_mtp: float = None):
    """One row's part of the batch's loss (``main / n_main + mtp_loss_weight *
    mtp / n_mtp``: the batch's counts of positions under each head; left out,
    this row's own with every position kept), its two masked sums, and its LoRA
    gradient."""
    t = len(x)
    return _row_grad(lora, params, x, y, mask, _static(cfg), alpha, mode,
                     float(n_main or t), float(n_mtp or t - 1))


@functools.partial(jax.jit, static_argnames=("z", "mode"))
def _picks_one(params, x, z, mode):
    return forward_one(params, x, None, dict(z), mode)["picks"]


def picks_one(params, x, cfg: dict, mode: str = "float32"):
    """``[L, T, top_k]``: the experts each token of one row picks in each
    routed block (the second head's last, reading the row turned by one for
    the tokens after it), at the base weights."""
    return _picks_one(params, x, _static(cfg), mode)


def finetune(params, lora, batches_x, batches_y, cfg: dict, alpha: float,
             lr: float, clip: float, mode: str = "float32",
             steps_with_data: int = None):
    """Follow ``len(batches_x)`` optimizer steps, each over a ``[B, T]`` batch
    taken row by row (each loss term is the mean over the batch's positions
    under its head, as the program's).  From step ``steps_with_data`` on the
    batches count as masked out: loss and gradient are zero there and only the
    optimizer's state moves the factors.  Returns the loss of every step, the
    factors after the last, AdamW's two moments after the last, each as
    ``{(block, name): ...}``, and every step's two terms ``(main, mtp)``."""
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    mu, nu = zeros(lora), zeros(lora)
    losses, terms = [], []
    for step, (bx, by) in enumerate(zip(batches_x, batches_y)):
        if steps_with_data is not None and step >= steps_with_data:
            losses.append(0.0)
            terms.append((0.0, 0.0))
            lora, mu, nu = adamw_step(lora, zeros(lora), mu, nu, step, lr,
                                      clip)
            continue
        n_main, n_mtp = bx.size, bx.size - len(bx)
        total, main, mtp, grads = 0.0, 0.0, 0.0, None
        for x, y in zip(bx, by):
            (loss, sums), g = row_grad(
                lora, params, jnp.asarray(x), jnp.asarray(y),
                jnp.ones(len(x), jnp.float32), cfg, alpha, mode, n_main, n_mtp)
            total, main, mtp = total + loss, main + sums[0], mtp + sums[1]
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        losses.append(float(total))
        terms.append((float(main) / n_main, float(mtp) / n_mtp))
        lora, mu, nu = adamw_step(lora, grads, mu, nu, step, lr, clip)
    return losses, lora, mu, nu, terms
