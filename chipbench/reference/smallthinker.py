"""Plain SmallThinker (PowerInfer/SmallThinker-21BA3B-Instruct, 2025): weights
from a seed, forward, next-token loss, LoRA and AdamW, in straightforward
``jax.numpy``, for the share of the model that one chip of a 4-chip layer
group holds.

The yardstick of the ``smallthinker_*`` configurations.  It imports nothing of
the program and takes nothing the program has made; the precision switch of
its products (``_mm``), the optimizer step and the seed's key are
``reference/gpt2.py``'s.

The layer, as published (``h`` is its input ``[T, hidden_size]``):

* ``r = h W_r``: the router's ``moe_num_primary_experts`` (64) logits, read
  from the layer's input *ahead of attention*;
* ``a = h + Attn(RMSNorm(h)) W_o``: no biases, grouped heads of ``head_dim``,
  scores scaled by 1/sqrt(head_dim).  A layer with ``rope_layout`` 1 rotates
  q and k (``rope_theta``, no scaling), one with 0 uses no positions at all;
  a layer with ``sliding_window_layout`` 1 lets query i see key j iff
  0 <= i - j < ``sliding_window_size``, one with 0 is fully causal;
* picks = the ``moe_num_active_primary_experts`` (6) largest of ``r``, weights
  = softmax over all 64 renormalised over the six, which is the softmax of the
  six picked logits;
* ``y = RMSNorm(a)``; ``out = a + sum over picks e of w_e * ((relu(y G_e) *
  (y U_e)) D_e)``: sparse ReGLU experts, no shared expert, no dropped token;
* a final RMSNorm and an untied output head.

Departures from the published description, each because one chip holds a
share and not the model:

* the configuration's counts of layers, heads, key/value heads, experts and
  vocabulary rows are the *held* ones.  Of the experts, ``experts_first_held
  .. + held`` are computed and a pick that landed on another adds nothing
  (the router still has all 64 outputs); the heads and the vocabulary are
  simply fewer;
* the router reads ``h`` before ``input_layernorm`` (the config does not say
  which side of the norm; ``assumed`` in the configuration's file), and its
  product and softmax are float32 at ``highest`` in every ``mode``, as the
  program's are, so that picks differ only where the residual streams do;
* the activation is ReGLU (``described_as``; not among the config's keys);
* an expert's gate and up matrices are kept side by side, ``w_gate_up``
  [held, D, 2F], the program's layout: the same numbers as two matrices;
* attention and the loss are computed in row blocks, so that 16,384 positions
  fit: the same sums in another order.
"""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2 import (HIGHEST, _mm, adamw_step, merge_lora,
                                      seed_key, stack_blocks, unstack_lora)

__all__ = ["seed_key", "init_params", "init_lora", "finetune", "row_grad",
           "picks_one", "hidden_one", "sizes", "LORA_TARGETS"]

#: the four attention matrices LoRA adapts: what the program's default
#: targets reach in this layout (the 3-D expert arrays and the router stay
#: frozen, PEFT's default for such models)
LORA_TARGETS = ("wq", "wk", "wv", "wo")

#: queries of an attention block, rows of a loss block
ROWS = 1024


def sizes(cfg: dict) -> dict:
    """The static sizes of the share, from the configuration's own keys."""
    n = int(cfg["num_hidden_layers"])
    return dict(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        layers=n, heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), ffn=int(cfg["moe_ffn_hidden_size"]),
        experts=int(cfg["published"]["moe_num_primary_experts"]),
        held=int(cfg["moe_num_primary_experts"]),
        first_held=int(cfg["experts_first_held"]),
        top_k=int(cfg["moe_num_active_primary_experts"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        window=int(cfg["sliding_window_size"]),
        rotates=tuple(int(v) for v in cfg["rope_layout"][:n]),
        windowed=tuple(int(v) for v in cfg["sliding_window_layout"][:n]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("vocab", "dim", "layers",
                                             "heads", "kv_heads", "head_dim",
                                             "ffn", "experts", "held",
                                             "init_range", "dtype",
                                             "stacked_layout"))
def _init(key, vocab, dim, layers, heads, kv_heads, head_dim, ffn, experts,
          held, init_range, dtype, stacked_layout):
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    s = init_range
    s_res = init_range / math.sqrt(2 * layers)      # residual projections
    stacked = {
        "wq": normal((layers, dim, heads * head_dim), s),
        "wk": normal((layers, dim, kv_heads * head_dim), s),
        "wv": normal((layers, dim, kv_heads * head_dim), s),
        "wo": normal((layers, heads * head_dim, dim), s_res),
        "router": normal((layers, dim, experts), s),
        "w_gate_up": normal((layers, held, dim, 2 * ffn), s),
        "w_down": normal((layers, held, ffn, dim), s_res),
        # a published model's norm scales are trained away from one; drawn
        # here, so that a path that drops one shows
        "ln1": 1.0 + normal((layers, dim), s),
        "ln2": 1.0 + normal((layers, dim), s),
    }
    blocks = {k: v for k, v in stacked.items() if not k.startswith("ln")}
    blocks["ln1"] = {"scale": stacked["ln1"]}
    blocks["ln2"] = {"scale": stacked["ln2"]}
    if not stacked_layout:
        blocks = [jax.tree_util.tree_map(lambda a: a[i], blocks)
                  for i in range(layers)]
    return {"embed": normal((vocab, dim), s), "blocks": blocks,
            "ln_f": {"scale": 1.0 + normal((dim,), s)},
            "w_out": normal((dim, vocab), s)}


def init_params(cfg: dict, seed: int, dtype=jnp.float32,
                stacked: bool = False):
    """The share's weights in the program's layout (a list of blocks), made
    on the device in one jitted call; expert arrays and norm scales drawn
    too.  ``stacked``: the same numbers with the blocks' arrays along a
    leading layer axis, which is how the reference walks them (a second
    5.6 GB copy would not fit beside the first)."""
    z = sizes(cfg)
    return _init(seed_key(seed), z["vocab"], z["dim"], z["layers"],
                 z["heads"], z["kv_heads"], z["head_dim"], z["ffn"],
                 z["experts"], z["held"], float(cfg["initializer_range"]),
                 jnp.dtype(dtype), bool(stacked))


def init_lora(cfg: dict, seed: int, rank: int):
    """LoRA factors as published (Hu et al. 2021): A normal, B zero.
    ``{(layer, name): {"a", "b"}}`` over the four attention matrices."""
    z = sizes(cfg)
    d, q, kv = z["dim"], z["heads"] * z["head_dim"], \
        z["kv_heads"] * z["head_dim"]
    shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    key = jax.random.fold_in(seed_key(seed), 0x10a)

    @jax.jit
    def make(key):
        out = {}
        for i in range(z["layers"]):
            for j, name in enumerate(LORA_TARGETS):
                d_in, d_out = shapes[name]
                k = jax.random.fold_in(key, i * len(LORA_TARGETS) + j)
                out[(i, name)] = {
                    "a": jax.random.normal(k, (d_in, rank), jnp.float32) * 0.01,
                    "b": jnp.zeros((rank, d_out), jnp.float32)}
        return out

    return make(key)


def stack_lora(lora, layers: int):
    return {name: {ab: jnp.stack([lora[(i, name)][ab] for i in range(layers)])
                   for ab in ("a", "b")} for name in LORA_TARGETS}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms_norm(x, g, eps):
    f = x.astype(jnp.float32)
    return (f / jnp.sqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
            ).astype(x.dtype) * g["scale"]


def _rotate(x, theta: float):
    """Rotary positions on [T, H, Dh] (position = row), the halves of a head
    holding the pairs' first and second members."""
    t, half = x.shape[0], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, span, mode: str):
    """Causal attention of [T, H, Dh] queries over [T, Hk, Dh] keys and
    values, query i seeing key j iff 0 <= i - j < ``span``; ``ROWS`` queries
    at a time."""
    t, h, dh = q.shape
    hk = k.shape[1]
    rows = min(ROWS, t)
    if t % rows:
        raise ValueError(f"{t} positions are not whole blocks of {rows}")
    qb = q.reshape(t // rows, rows, hk, h // hk, dh)
    k_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def some(args):
        q_i, i = args
        s = _mm(q_i, k, mode, "qcgd,kcd->cgqk") / math.sqrt(dh)
        gap = i * rows + jnp.arange(rows)[:, None] - k_pos
        s = jnp.where((gap >= 0) & (gap < span), s.astype(jnp.float32),
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return _mm(p, v, mode, "cgqk,kcd->qcgd")

    o = jax.lax.map(some, (qb, jnp.arange(t // rows)))
    return o.reshape(t, h * dh)


def _experts(y, h_in, blk, z: dict, mode: str):
    """The held experts' share of the routed layer, expert by expert over
    all the tokens: a token's weight for an expert is its pick's weight, or
    nothing where it did not pick it.  Also the picks [T, top_k]."""
    logits = jnp.matmul(h_in.astype(jnp.float32),
                        blk["router"].astype(jnp.float32), precision=HIGHEST)
    top, picks = jax.lax.top_k(logits, z["top_k"])
    weights = jax.nn.softmax(top, axis=-1)
    ffn = z["ffn"]

    def one(out, e):
        w_e = jnp.sum(jnp.where(picks == e + z["first_held"], weights, 0.0),
                      axis=-1)
        gate_up = _mm(y, blk["w_gate_up"][e], mode)
        hidden = jax.nn.relu(gate_up[:, :ffn]) * gate_up[:, ffn:]
        return out + w_e[:, None].astype(y.dtype) * _mm(
            hidden, blk["w_down"][e], mode), None

    out, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(y),
                          jnp.arange(z["held"]))
    return out, picks


def _block(h, blk, rotates, windowed, z: dict, mode: str):
    t = h.shape[0]
    y = _rms_norm(h, blk["ln1"], z["eps"])
    q = _mm(y, blk["wq"], mode).reshape(t, z["heads"], z["head_dim"])
    k = _mm(y, blk["wk"], mode).reshape(t, z["kv_heads"], z["head_dim"])
    v = _mm(y, blk["wv"], mode).reshape(t, z["kv_heads"], z["head_dim"])
    # a layer of rope_layout 0 uses no positions at all
    q = jnp.where(rotates, _rotate(q, z["theta"]), q)
    k = jnp.where(rotates, _rotate(k, z["theta"]), k)
    span = jnp.where(windowed, z["window"], t)
    a = h + _mm(_attention(q, k, v, span, mode), blk["wo"], mode)
    out, picks = _experts(_rms_norm(a, blk["ln2"], z["eps"]), h, blk, z, mode)
    return a + out, picks


def hidden_one(params, tokens, z: dict, mode: str = "float32",
               remat: bool = False, lora=None, alpha: float = 0.0):
    """``[T]`` tokens of one sequence -> the residual stream after the final
    norm ``[T, D]``, and every layer's picks ``[L, T, top_k]``.  ``lora``:
    stacked factors (``stack_lora``) merged into each block as it is used."""
    act = jnp.float32 if mode == "float32" else jnp.bfloat16
    params = stack_blocks(params)
    cast = functools.partial(jax.tree_util.tree_map, lambda a: a.astype(act))
    h = params["embed"][tokens].astype(act)

    def block(h, layer):
        blk, factors, rotates, windowed = layer
        if factors is not None:
            blk = merge_lora(blk, factors, alpha)
        # the router stays float32 whatever the mode
        blk = dict(cast(blk), router=blk["router"])
        return _block(h, blk, rotates, windowed, z, mode)

    if remat:
        block = jax.checkpoint(block)
    h, picks = jax.lax.scan(block, h, (
        params["blocks"], lora, jnp.asarray(z["rotates"], bool),
        jnp.asarray(z["windowed"], bool)))
    return _rms_norm(h, cast(params["ln_f"]), z["eps"]), picks


def next_token_loss(params, x, y, mask, z: dict, mode: str = "float32",
                    lora=None, alpha: float = 0.0):
    """Masked sum of one row's cross-entropy (``x`` [T] in, ``y`` [T] the
    targets, ``mask`` [T]), the logits taken ``ROWS`` positions at a time."""
    h, _ = hidden_one(params, x, z, mode, True, lora, alpha)
    t = x.shape[0]
    rows = min(ROWS, t)
    w_out = params["w_out"] if mode == "float32" else \
        params["w_out"].astype(jnp.bfloat16)

    @jax.checkpoint
    def some(args):
        h_i, y_i, m_i = args
        logits = _mm(h_i, w_out, mode).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, y_i[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * m_i)

    return jnp.sum(jax.lax.map(some, (
        h.reshape(t // rows, rows, -1), y.reshape(-1, rows),
        mask.reshape(-1, rows))))


# ---------------------------------------------------------------------------
# LoRA fine-tuning: AdamW over the factors, gradient clipped by global norm
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("z", "alpha", "mode"))
def _row_grad(lora, params, x, y, mask, z, alpha, mode):
    def f(lora):
        return next_token_loss(params, x, y, mask, dict(z), mode, lora, alpha)
    return jax.value_and_grad(f)(lora)


def _static(cfg: dict):
    return tuple(sorted(sizes(cfg).items()))


def row_grad(lora, params, x, y, mask, cfg: dict, alpha: float,
             mode: str = "float32"):
    """Masked loss sum and its LoRA gradient, of one row (stacked weights
    and factors)."""
    return _row_grad(lora, params, x, y, mask, _static(cfg), alpha, mode)


@functools.partial(jax.jit, static_argnames=("z", "mode"))
def _picks_one(params, x, z, mode):
    return hidden_one(params, x, dict(z), mode)[1]


def picks_one(params, x, cfg: dict, mode: str = "float32"):
    """``[L, T, top_k]``: the experts each token of one row picks in each
    layer, at the base weights."""
    return _picks_one(params, x, _static(cfg), mode)


def finetune(params, lora, batches_x, batches_y, cfg: dict, alpha: float,
             lr: float, clip: float, mode: str = "float32",
             steps_with_data: int = None):
    """Follow ``len(batches_x)`` optimizer steps, each over a ``[B, T]`` batch
    taken row by row (the loss is the mean over the batch's tokens, as the
    program's).  From step ``steps_with_data`` on the batches count as masked
    out: loss and gradient are zero there and only the optimizer's state
    moves the factors.  Returns the loss of every step, the factors after the
    last, and AdamW's two moments after the last, each as ``{(layer, name):
    ...}``."""
    layers = sizes(cfg)["layers"]
    if not isinstance(params["blocks"], dict):
        params = jax.jit(stack_blocks)(params)
    lora = stack_lora(lora, layers)
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    mu, nu = zeros(lora), zeros(lora)
    losses = []
    for step, (bx, by) in enumerate(zip(batches_x, batches_y)):
        if steps_with_data is not None and step >= steps_with_data:
            losses.append(0.0)
            lora, mu, nu = adamw_step(lora, zeros(lora), mu, nu, step, lr,
                                      clip)
            continue
        total, grads = 0.0, None
        for x, y in zip(bx, by):
            mask = jnp.ones(len(x), jnp.float32)
            loss, g = row_grad(lora, params, jnp.asarray(x), jnp.asarray(y),
                               mask, cfg, alpha, mode)
            total = total + loss
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        n = bx.size
        grads = jax.tree_util.tree_map(lambda a: a / n, grads)
        losses.append(total / n)
        lora, mu, nu = adamw_step(lora, grads, mu, nu, step, lr, clip)
    return ([float(v) for v in losses], unstack_lora(lora), unstack_lora(mu),
            unstack_lora(nu))
