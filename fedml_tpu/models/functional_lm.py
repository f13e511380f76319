"""The functional transformer LM: its parameters, its block and forward
pass, and the model-hub adapter over them.

One parameter pytree serves three roles with zero conversion:

* training through the engine / `train/llm` (the adapter below gives it the
  flax-module `.init/.apply` surface `ModelBundle` expects);
* sequence-parallel training (`parallel/seq_parallel.py` shards this
  module's `lm_loss` over the sequence);
* KV-cache serving (`serving/kv_cache_lm.KVCacheLM(variables["params"],
  heads, max_len)`).

All three run the one `block` below and differ only in the ``attend`` they
hand it.  The reference's fine-tune → deploy path crosses HF checkpoints and
ONNX conversion (`device_model_deployment.py:839`); here the train and serve
stacks literally share the pytree.

What a block is made of comes from a description, `Layer`: its norm, whether
it rotates q and k, the shape of its attention (query heads, key/value heads,
head size, window; or latent attention, `Latent`: q through a low rank, keys
and values through one shared latent beside one rotary key) and its MLP
(dense GELU, dense SwiGLU, or routed experts of which this chip holds a
share, with or without a shared expert beside them, gated or not).  A layer's
mixer may also be no attention at all but a gated delta rule (`DeltaRule`: a
linear-attention layer that mixes tokens through a recurrent state a head,
`ops/delta_rule`), and its attention may norm q and k by head, rotate only a
head's first numbers, gate its output by a second half of ``wq``, and centre
its norms' scales on zero.  GPT-2 is the default
description; a model of another family (`routed_lm`: RMSNorm, rotary or no
positions by layer, grouped heads or latent attention, windows by layer,
delta-rule layers among the attention ones, routed experts behind a softmax
or a group-limited sigmoid router, dense layers ahead of the routed ones, an
untied head, a second head that predicts one token further) is another,
through the same `block`, `embed`, `head` and `lm_forward`.  A third kind of
mixer is a gated short convolution (`ShortConv`): two gates around a causal
depthwise convolution of a few taps, whose state a row carries is its last
``taps - 1`` inputs; its ``attend`` is the convolution itself.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import (Any, Callable, Dict, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mlops import tracing
from ..ops.delta_rule import (causal_conv as _causal_conv, gated_delta_mixer,
                              gated_delta_rule, plain_gate, unit as _unit)
from ..ops.routed_experts import (Experts, held_experts, route,
                                  route_in_groups)

#: LayerNorm epsilon — 1e-5 matches the HF GPT-2 default so imported
#: checkpoints (`train/llm/weight_import.py`) reproduce reference logits
LN_EPS = 1e-5


class Latent(NamedTuple):
    """Latent attention: q through rank ``q_rank`` (a norm between its two
    matrices), keys and values through one latent of ``kv_rank`` (normed)
    from which every head's ``nope`` key numbers and ``v`` values are made,
    and one rotary key of ``rope`` numbers that all heads share.  A head's
    q and k are ``nope + rope`` wide; the rotation turns the last ``rope``
    of them, by YaRN's frequencies where ``factor`` is over 1."""

    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    #: YaRN: positions stretched ``factor`` times over ``original`` trained
    #: ones; pairs that turn more than ``beta_fast`` times in ``original``
    #: positions keep their frequency, those under ``beta_slow`` turns have
    #: it divided by ``factor``, a linear ramp between
    factor: float = 1.0
    original: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


class DeltaRule(NamedTuple):
    """A gated delta-rule mixer in place of attention (Yang et al. 2024;
    `ops/delta_rule`): ``w_qkvz`` makes q and k for ``key_heads`` heads of
    ``key_dim``, v and an output gate z for ``value_heads`` heads of
    ``value_dim`` (value head j reads key head j // (value_heads /
    key_heads)); ``w_ba`` a writing strength and a decay a value head; q, k
    and v cross a causal depthwise convolution of ``conv`` taps and a SiLU,
    q and k are normed to length 1 by head (q over ``sqrt(key_dim)``
    besides); the rule's output is RMS-normed by head, times ``silu(z)``."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv: int = 4


class ShortConv(NamedTuple):
    """A gated short convolution in place of attention (LFM2's ``conv``
    layers): ``[b, c, x] = y w_in`` ([D, 3 D]), ``u = b * x``, ``z`` the
    causal depthwise convolution of ``u`` over ``taps`` positions (``conv``
    [D, taps], zeros left of position 0), out ``(c * z) wo``.  No
    activation.  What a row carries from one position to the next is ``u``
    at its last ``taps - 1`` positions."""

    taps: int = 3


class Rows(NamedTuple):
    """What a caller that serves knows of the rows it hands `block` and a
    whole-sequence pass does not need to be told."""

    #: each row's position, in the shape of ``h`` less its last axis; None:
    #: a row's index along the axis before the heads'
    pos: Optional[jnp.ndarray] = None
    #: [N] bool over the flattened rows: those that hold a request's token.
    #: The picks of the others land on no expert (a slot that holds no
    #: request, a prompt's padding: they would fetch matrices for nothing)
    live: Optional[jnp.ndarray] = None
    #: rows of a tile of the expert layer's layout (`routed_experts.
    #: fit_tile`); None: `routed_experts.TILE`
    tile: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Layer:
    """What one block is made of.  The defaults are GPT-2's."""

    #: "layernorm" (scale and bias, mean removed) | "rmsnorm" (scale only)
    norm: str = "layernorm"
    eps: float = LN_EPS
    #: key/value heads, where fewer than the query heads share them
    kv_heads: Optional[int] = None
    #: size of a head, where it is not ``dim // heads``
    head_dim: Optional[int] = None
    #: base of the rotation applied to q and k; None: none here (positions
    #: come from the embedding's table, or from nowhere)
    rope_theta: Optional[float] = None
    #: query i sees key j iff 0 <= i - j < window; None: fully causal
    window: Optional[int] = None
    #: routed experts in place of the dense GELU MLP, and this chip's share
    experts: Optional[Experts] = None
    #: latent attention in place of the three projections ``wq wk wv``
    latent: Optional[Latent] = None
    #: width of a dense SwiGLU MLP in place of GELU's two matrices
    swiglu: Optional[int] = None
    #: width of a SwiGLU expert that every token crosses, beside the routed
    shared: Optional[int] = None
    #: a gated delta rule in place of the attention (no ``attend`` then)
    delta: Optional[DeltaRule] = None
    #: numbers of a head, its first, that the rotation turns; None: all
    rotary: Optional[int] = None
    #: q and k normed by head (scales ``q_norm``, ``k_norm``) before they turn
    qk_norm: bool = False
    #: ``wq`` makes, beside a head's q, as many gate numbers: the attention's
    #: output is taken times their sigmoid
    out_gate: bool = False
    #: an RMSNorm's scale is ``1 + g``: the stored ``g`` is centred on zero
    centred: bool = False
    #: the shared expert's output times ``sigmoid(y w)``, ``shared_gate`` [D]
    shared_gate: bool = False
    #: a gated short convolution in place of the attention: ``attend`` is
    #: then handed ``u`` and the taps, and gives the convolution
    conv: Optional[ShortConv] = None


GPT2 = Layer()


def init_lm_params(key: jax.Array, vocab: int, dim: int = 64,
                   layers: int = 2, heads: int = 4,
                   max_len: int = 512) -> Dict[str, Any]:
    """Transformer-LM parameter pytree (pre-LN blocks, learned positions)."""
    keys = jax.random.split(key, 2 + layers)
    p: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (vocab, dim)) * 0.02,
        "pos": jax.random.normal(keys[1], (max_len, dim)) * 0.02,
        "blocks": [],
        "ln_f": {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))},
    }
    for i in range(layers):
        kq, kk, kv, ko, k1, k2 = jax.random.split(keys[2 + i], 6)
        s = 1.0 / np.sqrt(dim)
        p["blocks"].append({
            "ln1": {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))},
            "wq": jax.random.normal(kq, (dim, dim)) * s,
            "wk": jax.random.normal(kk, (dim, dim)) * s,
            "wv": jax.random.normal(kv, (dim, dim)) * s,
            "wo": jax.random.normal(ko, (dim, dim)) * s,
            "ln2": {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))},
            "w1": jax.random.normal(k1, (dim, 4 * dim)) * s,
            "w2": jax.random.normal(k2, (4 * dim, dim)) * (s / 2.0),
        })
    return p


@tracing.scope("norm")
def _norm(x, g, layer: Layer = GPT2):
    if layer.norm == "rmsnorm":
        # in the stream's type, whatever the scale is kept in
        return (x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + layer.eps
        ) * (1.0 + g["scale"] if layer.centred else g["scale"])
                ).astype(x.dtype)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + layer.eps) * g["scale"] + g["bias"]


def _rope_freq(theta: float, half: int):
    return theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def _rotate(x, freq, pos=None):
    """Rotary positions on [..., T, H, Dh], position = index along T (or
    ``pos``, in the shape of ``x`` less its last two axes), pair i turning
    ``freq[i]`` a position: the two halves of a head are the pairs' first and
    second members."""
    t, half = x.shape[-3], x.shape[-1] // 2
    if pos is None:
        pos = jnp.arange(t, dtype=jnp.float32)
    angle = pos.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(angle)[..., None, :], jnp.sin(angle)[..., None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _rotate_first(x, layer: Layer, pos=None):
    """`_rotate` on the first ``layer.rotary`` numbers of a head (all of
    them where it names none), the rest passing through."""
    n = layer.rotary or x.shape[-1]
    turned = _rotate(x[..., :n], _rope_freq(layer.rope_theta, n // 2), pos)
    if n == x.shape[-1]:
        return turned
    return jnp.concatenate([turned, x[..., n:]], axis=-1)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freq(la: Latent) -> np.ndarray:
    """The ``rope // 2`` frequencies of a latent layer's rotation (Peng et
    al. 2023, as the published implementations of this attention have it):
    ``theta^(-2i/rope)`` up to the pair that turns ``beta_fast`` times in
    the ``original`` positions (its number rounded down), that over
    ``factor`` from the pair that turns ``beta_slow`` times (rounded up) on,
    a linear ramp between."""
    half = la.rope // 2
    plain = la.theta ** (-np.arange(half, dtype=np.float64) / half)
    if la.factor <= 1:
        return plain.astype(np.float32)

    def pair_that_turns(turns: float) -> float:
        return la.rope * math.log(la.original / (turns * 2 * math.pi)) / (
            2 * math.log(la.theta))

    low = max(math.floor(pair_that_turns(la.beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(la.beta_slow)), la.rope - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / la.factor * ramp + plain * (1 - ramp)).astype(np.float32)


def yarn_softmax_scale(la: Latent) -> float:
    """What a latent layer's scores are scaled by: ``(nope + rope)^-0.5``
    times the square of YaRN's ``mscale_all_dim`` term."""
    return (la.nope + la.rope) ** -0.5 * _yarn_mscale(
        la.factor, la.mscale_all_dim) ** 2


def _latent_qkv(y, blk, heads: int, layer: Layer):
    """q, k [..., H, nope + rope] and v [..., H, v] of a latent layer from
    its normed input: the one rotary key lies under every head, and q
    carries what the layer's scale is over ``(nope + rope)^-0.5``, which is
    all that ``attend`` applies."""
    la, lead = layer.latent, y.shape[:-1]
    q = (_norm(y @ blk["wq_a"], blk["q_norm"], layer) @ blk["wq_b"]).reshape(
        *lead, heads, la.nope + la.rope)
    down = y @ blk["wkv_a"]
    kv = (_norm(down[..., :la.kv_rank], blk["kv_norm"], layer)
          @ blk["wkv_b"]).reshape(*lead, heads, la.nope + la.v)
    freq = jnp.asarray(yarn_freq(la))
    # cos and sin carry mscale / mscale_all_dim's term
    turn = _yarn_mscale(la.factor, la.mscale) / _yarn_mscale(
        la.factor, la.mscale_all_dim)
    q_rope = _rotate(q[..., la.nope:], freq) * turn
    k_rope = _rotate(down[..., None, la.kv_rank:], freq) * turn
    fold = yarn_softmax_scale(la) * math.sqrt(la.nope + la.rope)
    q = jnp.concatenate([q[..., :la.nope], q_rope], axis=-1) * fold
    k = jnp.concatenate([kv[..., :la.nope], jnp.broadcast_to(
        k_rope, (*lead, heads, la.rope))], axis=-1)
    return q, k, kv[..., la.nope:]


def _delta_mixer(y, blk, layer: Layer):
    """The gated delta-rule mixer over whole rows ``y`` [B, T, D] from a zero
    state: what it adds to the stream.  Matrices: ``w_qkvz`` [D, 2 Hk Dk + 2
    Hv Dv] (q, k, v, z side by side), ``w_ba`` [D, 2 Hv] (b, then a),
    ``conv`` [2 Hk Dk + Hv Dv, taps], ``a_log`` and ``dt_bias`` [Hv], the
    gated norm's scale ``gdn_norm`` [Dv], ``wo`` [Hv Dv, D].  Between the
    projection and ``wo`` the rows cross `ops/delta_rule`'s kernels where
    they run (`gated_delta_mixer`); where they do not, the jnp below, which
    is their definition."""
    if y.ndim != 3:
        raise NotImplementedError(
            "a delta-rule layer runs over whole rows [B, T, D]: the serving "
            "cache holds no recurrent state")
    de, (b, t, _) = layer.delta, y.shape
    nq, nv = de.key_heads * de.key_dim, de.value_heads * de.value_dim
    with tracing.scope("gdn.proj"):
        qkvz = y @ blk["w_qkvz"]
        ba = (y @ blk["w_ba"]).astype(jnp.float32)
    with tracing.scope("gdn.gates"):
        beta = jax.nn.sigmoid(ba[..., :de.value_heads])
        g = -jnp.exp(blk["a_log"]) * jax.nn.softplus(
            ba[..., de.value_heads:] + blk["dt_bias"])
    scale = blk["gdn_norm"]["scale"]
    o = gated_delta_mixer(qkvz, g, beta, blk["conv"], scale, eps=layer.eps,
                          heads=(de.key_heads, de.value_heads))
    if o is None:
        with tracing.scope("gdn.conv"):
            qkv = jax.nn.silu(_causal_conv(qkvz[..., :2 * nq + nv],
                                           blk["conv"]))
        with tracing.scope("gdn.gates"):
            heads = (b, t, de.key_heads, de.key_dim)
            q = _unit(qkv[..., :nq].reshape(heads)) * de.key_dim ** -0.5
            k = _unit(qkv[..., nq:2 * nq].reshape(heads))
            v = qkv[..., 2 * nq:].reshape(b, t, de.value_heads, de.value_dim)
        o = gated_delta_rule(q, k, v, g, beta)
        with tracing.scope("gdn.out"):
            o = plain_gate(o, qkvz, scale, layer.eps)
    with tracing.scope("gdn.out"):
        return o.astype(y.dtype).reshape(b, t, nv) @ blk["wo"]


@tracing.scope("attn.gate")
def _gated(o, gate):
    """An attention's output, a head's numbers times the sigmoid of as many
    gate numbers."""
    return o * jax.nn.sigmoid(gate)


def _bias(z, blk, key):
    """Optional-bias add (imported HF checkpoints carry biases; native init
    is bias-free)."""
    return z + blk[key] if key in blk else z


@tracing.scope("embed")
def embed(params: Dict[str, Any], tokens: jnp.ndarray,
          pos: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Token plus position embedding.  ``tokens`` [B, T] with no ``pos``
    are whole sequences from position 0; ``tokens`` [B] with ``pos`` [B]
    are one token a row at its own position (a position beyond the table
    is clamped to its last row, as jnp indexing does).  A model without a
    table of positions gets the token embedding alone."""
    if "pos" not in params:
        return params["embed"][tokens]
    if pos is None:
        # NOTE positions must be GLOBAL: tokens arrive [B, T] logically;
        # under jit the T axis may be sharded and XLA partitions the slice
        return params["embed"][tokens] + params["pos"][:tokens.shape[1]][None]
    return params["embed"][tokens] + params["pos"][pos]


def _dense_mlp(y, blk):
    return _bias(
        jax.nn.gelu(_bias(y @ blk["w1"], blk, "b1")) @ blk["w2"], blk, "b2")


def _swiglu(y, w_gate_up, w_down):
    """``(silu(y G) * (y U)) D``, gate and up columns side by side."""
    gate, up = jnp.split(y @ w_gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down


def _expert_mlp(y, h_in, blk, experts: Experts, rows: Rows = Rows()):
    """The held experts' share of the routed layer (`ops/routed_experts`).
    The router reads what ``experts`` says: ``h_in``, the block's input
    ahead of its first norm, or ``y``, which the experts read.  Also how the
    layer's picks fell, and the picks themselves [N, top_k].  Where ``rows``
    says which rows are live, the picks of the others land on no expert and
    are not counted."""
    src = y if experts.reads == "normed" else h_in
    src = src.reshape(-1, src.shape[-1])
    kept = None
    if experts.scores == "sigmoid":
        picks, weights, kept = route_in_groups(
            src, blk["router"], blk["router_bias"], experts)
    else:
        picks, weights = route(src, blk["router"], experts.top_k)
    n_picks = jnp.asarray(picks.size, jnp.int32)
    if rows.live is not None:
        picks = jnp.where(rows.live[:, None], picks, experts.total)
        n_picks = jnp.sum(rows.live, dtype=jnp.int32) * picks.shape[1]
    out, counts, rows_passed = held_experts(
        y.reshape(-1, y.shape[-1]), picks, weights, blk["w_gate_up"],
        blk["w_down"], experts, tile=rows.tile)
    stats = {"picks": n_picks,
             "picks_held": jnp.sum(counts),
             "rows_passed": rows_passed,
             "expert_picks_max": jnp.max(counts)}
    if rows.live is not None:
        # held experts a live row picked at all: whose matrices were fetched
        stats["experts_touched"] = jnp.sum(counts > 0, dtype=jnp.int32)
    if kept is not None:
        # tokens that kept the group the held experts lie in
        stats["tokens_in_held_group"] = jnp.sum(
            kept[:, experts.first_held // (experts.total // experts.groups)],
            dtype=jnp.int32)
    return out.reshape(y.shape).astype(y.dtype), stats, picks


def _conv_mixer(y, blk, convolve: Callable):
    """The gated short convolution's mixer (`ShortConv`): what it adds to
    the stream.  ``convolve(u, taps)`` is the causal convolution of ``u``
    [..., D]: over whole rows from zeros, or one position from the inputs a
    row carries (`serving.kv_cache_lm`)."""
    with tracing.scope("conv.proj"):
        b, c, x = jnp.split(y @ blk["w_in"], 3, axis=-1)
    with tracing.scope("conv.taps"):
        z = convolve(b * x, blk["conv"])
    with tracing.scope("conv.out"):
        return (c * z) @ blk["wo"]


def block(h: jnp.ndarray, blk: Dict[str, Any], heads: int,
          attend: Callable, layer: Layer = GPT2,
          note: Optional[Callable[[Dict[str, jnp.ndarray], jnp.ndarray],
                                  None]] = None,
          rows: Rows = Rows()) -> jnp.ndarray:
    """One pre-norm block over ``h`` [..., D], made as ``layer`` says.
    ``attend(q, k, v)`` takes the three projections as [..., H, Dh] (k and
    v [..., Hk, Dh] under grouped heads) and returns the attention's output
    in q's shape: it is all that differs between training (an attention
    over the whole sequence), prefill (the same, keeping K and V) and
    decode (one position against a cache).  A rotation, where the layer
    has one, takes a row's position from ``rows.pos`` or, where the caller
    gives none, from its index along the axis before the heads'.  A layer
    whose mixer is a short convolution hands ``attend`` the gated input and
    the taps instead, ``attend(u, taps)``, and takes the convolution back.
    The MLP gets the block's input beside its own (a router
    may read either); a routed layer hands ``note`` how its picks fell, and
    the picks.  A matrix kept below the stream's type is taken up to it at
    its product.  A delta-rule layer has no ``attend``: its mixer
    (`_delta_mixer`) runs over whole rows."""
    dim = h.shape[-1]
    dh = layer.head_dim or dim // heads
    y = _norm(h, blk["ln1"], layer)

    def proj(w, b, n):
        return _bias(y @ blk[w], blk, b).reshape(*y.shape[:-1], n, -1)

    def turned(z, norm):        # a q or a k on its way to the scores
        if layer.qk_norm:
            z = _norm(z, blk[norm], layer)
        if layer.rope_theta is not None:
            z = _rotate_first(z, layer, rows.pos)
        return z

    if layer.delta is not None:
        a = h + _delta_mixer(y, blk, layer)
    elif layer.conv is not None:
        a = h + _conv_mixer(y, blk, attend)
    else:
        kv = layer.kv_heads or heads
        gate = None
        with tracing.scope("attn.qkv"):
            if layer.latent is not None:
                qkv = _latent_qkv(y, blk, heads, layer)
            else:
                q = proj("wq", "bq", heads)
                if layer.out_gate:          # a head's q beside its gate
                    q, gate = q[..., :dh], q[..., dh:]
                qkv = (turned(q, "q_norm"),
                       turned(proj("wk", "bk", kv), "k_norm"),
                       proj("wv", "bv", kv))
        with tracing.scope("attn"):
            o = attend(*qkv)
        if gate is not None:
            o = _gated(o, gate)
        with tracing.scope("attn.out"):
            a = h + _bias(o.reshape(*h.shape[:-1], -1) @ blk["wo"], blk,
                          "bo")
    y = _norm(a, blk["ln2"], layer)
    if layer.experts is None:
        with tracing.scope("mlp"):
            return a + (_dense_mlp(y, blk) if layer.swiglu is None else
                        _swiglu(y, blk["w_gate_up"], blk["w_down"]))
    out, stats, picks = _expert_mlp(y, h, blk, layer.experts, rows)
    if note is not None:
        note(stats, picks)
    if layer.shared is not None:
        with tracing.scope("mlp.shared"):
            shared = _swiglu(y, blk["shared_gate_up"], blk["shared_down"])
            if layer.shared_gate:
                shared = shared * jax.nn.sigmoid(
                    y @ blk["shared_gate"])[..., None]
            out = out + shared
    return a + out


@tracing.scope("head")
def head(h: jnp.ndarray, params: Dict[str, Any],
         layer: Layer = GPT2) -> jnp.ndarray:
    """Final norm and the output projection."""
    h = _norm(h, params["ln_f"], layer)
    if "w_out" in params:                          # optional untied head
        return h @ params["w_out"]
    return h @ params["embed"].T                   # tied output embedding


def _add_stats(a: Dict[str, jnp.ndarray],
               b: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """How the picks of two layers (or steps) fell, taken together: counts
    add, the heaviest expert is the heavier of the two."""
    if not a or not b:
        return a or b
    return {k: (jnp.maximum if k.endswith("_max") else jnp.add)(a[k], b[k])
            for k in a}


def _over_sequence(attn_fn, layer: Layer) -> Callable:
    """The ``attend`` of a whole-sequence pass: ``attn_fn`` on [B, H, T,
    Dh], under the layer's window where it has one; for a short-convolution
    layer the convolution from zeros."""
    if layer.conv is not None:
        return _causal_conv
    if layer.window is not None:
        attn_fn = partial(attn_fn, window=layer.window)

    def attend(q, k, v):
        o = attn_fn(*(z.transpose(0, 2, 1, 3) for z in (q, k, v)))
        return o.transpose(0, 2, 1, 3)

    return attend


def blocks_over(h: jnp.ndarray, blocks: Sequence[Dict[str, Any]], heads: int,
                attn_fn, remat: bool, layers: Sequence[Layer]
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The residual stream [B, T, D] through ``blocks``, whole sequences
    from position 0, and how the picks of the routed ones fell (nothing
    where there is none).  A layer's window reaches ``attn_fn`` as
    ``window=``; ``remat``: each block is made again in the backward pass."""

    def run(h, blk, layer):
        seen = []
        h = block(h, blk, heads, _over_sequence(attn_fn, layer), layer,
                  lambda stats, picks: seen.append(stats))
        return h, (seen[0] if seen else {})

    if remat:
        run = jax.checkpoint(run, static_argnums=(2,))
    stats = {}
    for blk, layer in zip(blocks, layers):
        h, seen = run(h, blk, layer)
        stats = _add_stats(stats, seen)
    return h, stats


def lm_hidden(params: Dict[str, Any], tokens: jnp.ndarray, heads: int,
              attn_fn, remat: bool = False,
              layers: Optional[Sequence[Layer]] = None
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """[B, T] int tokens → the residual stream [B, T, D] after the last
    block, and how the picks of the routed layers fell (nothing for a model
    without any).  ``layers``: each block's description; left out, GPT-2's
    for all."""
    layers = layers or (GPT2,) * len(params["blocks"])
    return blocks_over(embed(params, tokens), params["blocks"], heads,
                       attn_fn, remat, layers)


def lm_forward(params: Dict[str, Any], tokens: jnp.ndarray, heads: int,
               attn_fn, remat: bool = False,
               layers: Optional[Sequence[Layer]] = None) -> jnp.ndarray:
    """[B, T] int tokens → [B, T, V] logits.  ``attn_fn(q, k, v)`` consumes
    [B, H, T, D_h] — plug in full attention, a shard_map'd ring, or Ulysses;
    everything else is position-wise and sharding-constraint friendly.
    ``remat=True`` rematerializes each block's activations in the backward
    pass (`jax.checkpoint`), trading FLOPs for the activation memory that
    dominates long-context training."""
    h, _ = lm_hidden(params, tokens, heads, attn_fn, remat, layers)
    return head(h, params, (layers or (GPT2,))[-1])


def lm_loss(params, tokens, heads, attn_fn,
            remat: bool = False) -> jnp.ndarray:
    """Next-token CE over [B, T].  The model runs on the FULL (sharded) T —
    the last position is masked out of the loss instead of sliced off, so
    the sequence axis stays evenly divisible by the mesh."""
    b, t = tokens.shape
    logits = lm_forward(params, tokens, heads, attn_fn, remat)  # [B, T, V]
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    mask = (jnp.arange(t) < t - 1).astype(jnp.float32)[None]
    return jnp.sum((logz - gold) * mask) / (jnp.sum(mask) * b)


class FunctionalLMModule:
    """Duck-typed flax module over the pure LM above."""

    def __init__(self, vocab: int, dim: int = 64, layers: int = 2,
                 heads: int = 4, max_len: int = 256) -> None:
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.layers = int(layers)
        self.heads = int(heads)
        self.max_len = int(max_len)

    def init(self, rngs: Any, x, train: bool = False) -> Dict[str, Any]:
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        return {"params": init_lm_params(
            key, self.vocab, dim=self.dim, layers=self.layers,
            heads=self.heads, max_len=self.max_len)}

    def apply(self, variables: Dict[str, Any], x, train: bool = False,
              rngs: Optional[Dict[str, Any]] = None, mutable=None):
        from ..ops.pallas_attention import flash_attention

        logits = lm_forward(variables["params"], x, self.heads,
                            partial(flash_attention, causal=True))
        if mutable:
            return logits, {}
        return logits


# ---------------------------------------------------------------------------
# the routed family: RMSNorm, positions and windows by layer, grouped heads or
# latent attention, routed experts, an untied head, a second head
# ---------------------------------------------------------------------------

#: rows of the residual stream whose logits are alive at a time when the
#: loss is taken in blocks (`loss_in_row_blocks`)
LOSS_ROWS = 2048
#: float32 [B, T, D] arrays a block keeps for its backward where it is not
#: rematerialised, near enough; and the bytes of them, over all blocks, from
#: which each block is rematerialised instead
_KEPT_PER_BLOCK = 16
_REMAT_OVER = 2 * 2 ** 30


@tracing.scope("loss")
def loss_in_row_blocks(h: jnp.ndarray, w_out: jnp.ndarray, y: jnp.ndarray,
                       mask: jnp.ndarray, rows: int = LOSS_ROWS
                       ) -> jnp.ndarray:
    """Mean next-token cross-entropy over the positions ``mask`` keeps, as
    `ml.engine.model_bundle.masked_loss` takes it, from the normed stream
    ``h`` [N, D] and the head ``w_out`` [D, V] without the [N, V] logits:
    ``rows`` positions' logits at a time, made again in the backward pass."""
    n = h.shape[0]
    rows = min(rows, n)
    pad = -n % rows
    h, y, mask = (jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1))
                  for z in (h, y.astype(jnp.int32), mask.astype(jnp.float32)))

    @jax.checkpoint
    def some(args):
        hb, yb, mb = args
        with tracing.scope("head"):
            logits = (hb @ w_out).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * mb)

    total = jnp.sum(jax.lax.map(some, (
        h.reshape(-1, rows, h.shape[-1]), y.reshape(-1, rows),
        mask.reshape(-1, rows))))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def _draws(layer: Layer) -> int:
    """Arrays `init_routed_params` draws for a block."""
    mlp = 2 if layer.experts is None else 3 + 2 * (layer.shared is not None)
    mixer = (6 if layer.delta is not None else
             3 if layer.conv is not None else
             5 if layer.latent is not None else 4)
    return mixer + mlp + layer.shared_gate


@partial(jax.jit, static_argnames=("vocab", "dim", "heads", "ffn", "layers",
                                   "mtp", "store"))
def init_routed_params(key: jax.Array, vocab: int, dim: int, heads: int,
                       ffn: int, layers: Tuple[Layer, ...],
                       mtp: Optional[Layer] = None, store: str = "float32"
                       ) -> Dict[str, Any]:
    """The routed family's parameter pytree, drawn in one program.  A block:
    ``wq`` [D, H Dh], ``wk``/``wv`` [D, Hk Dh], ``wo`` [H Dh, D] (a latent
    layer: ``wq_a`` [D, q_rank], ``wq_b`` [q_rank, H (nope + rope)],
    ``wkv_a`` [D, kv_rank + rope], ``wkv_b`` [kv_rank, H (nope + v)], ``wo``
    [H v, D] and the two latents' norms), ``router`` [D, experts] (with a
    ``router_bias`` [experts] where it scores by sigmoid), ``w_gate_up``
    [held, D, 2 F] (an expert's gate columns, then its up columns),
    ``w_down`` [held, F, D], a shared expert's ``shared_gate_up`` [D, 2 F]
    and ``shared_down`` [F, D] (a dense SwiGLU layer: ``w_gate_up`` [D, 2 W]
    and ``w_down`` [W, D] alone), two norms' scales; then the final norm and
    the untied head ``w_out`` [D, V].  ``mtp``: the block of a second head
    that predicts one token further, the last of ``blocks``, with its
    joining matrix ``w_eh`` [2 D, D] and three norms under ``"mtp"``.
    A short-convolution layer: ``w_in`` [D, 3 D], ``conv`` [D, taps], ``wo``
    [D, D] in place of the attention's.
    A delta-rule layer: `_delta_mixer`'s arrays in place of the attention's,
    ``a_log`` the log of a decay rate drawn in (0, 16) and ``dt_bias`` the
    inverse softplus of a step drawn log-uniformly in [0.001, 0.1] (Yang et
    al. 2024's initialiser).  A layer with ``out_gate``: ``wq`` [D, 2 H Dh];
    with ``qk_norm``: ``q_norm`` and ``k_norm`` [Dh]; with ``shared_gate``:
    ``shared_gate`` [D]; ``centred``: norm scales start at 0, not 1.
    ``store``: the type the matrices
    that training leaves frozen or merges factors into are kept in; norms'
    scales, routers and their biases are float32 whatever it is."""
    every = tuple(layers) + ((mtp,) if mtp is not None else ())
    ks = iter(jax.random.split(
        key, 2 + sum(map(_draws, every)) + (mtp is not None)))

    def normal(shape, fan_in, dtype=jnp.dtype(store)):
        return (jax.random.normal(next(ks), shape)
                / np.sqrt(fan_in)).astype(dtype)

    def scale(n=dim, centred=False):
        return {"scale": jnp.zeros((n,)) if centred else jnp.ones((n,))}

    def delta_mixer(de: DeltaRule):
        nq, nv = de.key_heads * de.key_dim, de.value_heads * de.value_dim
        rate = jax.random.uniform(next(ks), (de.value_heads,), minval=1e-3,
                                  maxval=16.0)
        step = jnp.exp(jax.random.uniform(
            next(ks), (de.value_heads,), minval=math.log(1e-3),
            maxval=math.log(0.1)))
        return dict(
            w_qkvz=normal((dim, 2 * nq + 2 * nv), dim),
            w_ba=normal((dim, 2 * de.value_heads), dim),
            conv=normal((2 * nq + nv, de.conv), de.conv),
            a_log=jnp.log(rate), dt_bias=step + jnp.log(-jnp.expm1(-step)),
            gdn_norm=scale(de.value_dim), wo=normal((nv, dim), nv))

    def make(layer):
        dh, kv, ex, la = (layer.head_dim, layer.kv_heads, layer.experts,
                          layer.latent)
        scale_ = partial(scale, centred=layer.centred)
        blk = {"ln1": scale_()}
        if layer.delta is not None:
            blk.update(delta_mixer(layer.delta))
        elif layer.conv is not None:
            blk.update(w_in=normal((dim, 3 * dim), dim),
                       conv=normal((dim, layer.conv.taps), layer.conv.taps),
                       wo=normal((dim, dim), dim))
        elif la is None:
            blk.update(wq=normal((dim, heads * dh * (1 + layer.out_gate)),
                                 dim),
                       wk=normal((dim, kv * dh), dim),
                       wv=normal((dim, kv * dh), dim),
                       wo=normal((heads * dh, dim), heads * dh))
            if layer.qk_norm:
                blk.update(q_norm=scale_(dh), k_norm=scale_(dh))
        else:
            blk.update(
                wq_a=normal((dim, la.q_rank), dim), q_norm=scale(la.q_rank),
                wq_b=normal((la.q_rank, heads * (la.nope + la.rope)),
                            la.q_rank),
                wkv_a=normal((dim, la.kv_rank + la.rope), dim),
                kv_norm=scale(la.kv_rank),
                wkv_b=normal((la.kv_rank, heads * (la.nope + la.v)),
                             la.kv_rank),
                wo=normal((heads * la.v, dim), heads * la.v))
        blk["ln2"] = scale_()
        if ex is None:
            blk.update(w_gate_up=normal((dim, 2 * layer.swiglu), dim),
                       w_down=normal((layer.swiglu, dim), layer.swiglu))
            return blk
        blk.update(router=normal((dim, ex.total), dim, jnp.float32),
                   w_gate_up=normal((ex.held, dim, 2 * ffn), dim),
                   w_down=normal((ex.held, ffn, dim), ffn))
        if ex.scores == "sigmoid":
            blk["router_bias"] = jnp.zeros((ex.total,))
        if layer.shared is not None:
            blk.update(
                shared_gate_up=normal((dim, 2 * layer.shared), dim),
                shared_down=normal((layer.shared, dim), layer.shared))
            if layer.shared_gate:
                blk["shared_gate"] = normal((dim,), dim, jnp.float32)
        return blk

    params = {"blocks": [make(layer) for layer in every]}
    if mtp is not None:
        params["mtp"] = {"norm_e": scale(), "norm_h": scale(),
                         "w_eh": normal((2 * dim, dim), 2 * dim),
                         "ln_f": scale()}
    return dict(params, ln_f=scale(centred=every[-1].centred),
                embed=(jax.random.normal(next(ks), (vocab, dim))
                       * 0.02).astype(store),
                w_out=normal((dim, vocab), dim))


class RoutedLMModule:
    """The routed family behind the surface `ModelBundle` expects, with two
    things more: `loss`, which `train/llm` takes in place of logits and
    `masked_loss` (the vocabulary's loss in row blocks, each block
    rematerialised where the sizes ask for it, the picks' counts beside
    it), and `picks`, the experts every token picked in every routed layer.

    ``mtp``: the description of one more block, behind the trunk, whose head
    predicts token i + 2 at position i from the trunk's last stream and the
    embedding of token i + 1; its loss is added ``mtp_weight`` times.
    ``store``: the type the frozen matrices are drawn in."""

    def __init__(self, vocab: int, dim: int, heads: int, ffn: int,
                 layers: Sequence[Layer], mtp: Optional[Layer] = None,
                 mtp_weight: float = 0.0, store: str = "float32") -> None:
        self.vocab, self.dim, self.heads = int(vocab), int(dim), int(heads)
        self.ffn = int(ffn)
        self.layers = tuple(layers)
        self.mtp, self.mtp_weight = mtp, float(mtp_weight)
        self.store = str(store)
        for layer in self.layers + ((mtp,) if mtp is not None else ()):
            la = layer.latent
            if la is not None and la.nope + la.rope != la.v:
                raise ValueError(
                    f"latent attention with keys of {la.nope + la.rope} and "
                    f"values of {la.v}: one kernel takes one head size")

    def init(self, rngs: Any, x, train: bool = False) -> Dict[str, Any]:
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        return {"params": init_routed_params(
            key, self.vocab, self.dim, self.heads, self.ffn, self.layers,
            self.mtp, self.store)}

    @staticmethod
    def _attention():
        from ..ops.pallas_attention import flash_attention

        return partial(flash_attention, causal=True)

    @staticmethod
    def _embed(params, tokens):
        # the stream is float32 whatever the table is kept in
        return embed(params, tokens).astype(jnp.float32)

    def _hidden(self, params, x, remat: bool = False):
        # the trunk's blocks: a second head's block lies behind them
        return blocks_over(self._embed(params, x),
                           params["blocks"][:len(self.layers)], self.heads,
                           self._attention(), remat, self.layers)

    @tracing.scope("mtp.join")
    def _joined(self, params, last, y):
        """What the second head's block reads: the trunk's ``last`` stream
        [B, T, D] (ahead of its final norm) joined at every position with
        the embedding of the next token, ``y``."""
        m = params["mtp"]
        return jnp.concatenate(
            [_norm(self._embed(params, y), m["norm_e"], self.mtp),
             _norm(last, m["norm_h"], self.mtp)], axis=-1) @ m["w_eh"]

    def apply(self, variables: Dict[str, Any], x, train: bool = False,
              rngs: Optional[Dict[str, Any]] = None, mutable=None):
        h, _ = self._hidden(variables["params"], x)
        logits = head(h, variables["params"], self.layers[-1])
        if mutable:
            return logits, {}
        return logits

    def loss(self, variables: Dict[str, Any], x, y, mask
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Masked mean next-token loss of [B, T] tokens ``x`` against ``y``,
        and how the picks fell: ``picks`` (all of them), ``picks_held``
        (those on held experts), ``rows_passed`` (the rows the expert
        layers' passes went over for them), ``expert_picks_max`` (the
        heaviest held expert of any layer), and under a group-limited router
        ``tokens_in_held_group``.  With a second head the loss is ``main +
        mtp_weight * mtp`` and both terms are counted beside it
        (``loss_main``, ``loss_mtp``) with the positions the second was
        taken over (``mtp_positions``): position i predicts token i + 2, so
        a row's last position has no target."""
        params = variables["params"]
        b, t = x.shape
        blocks = len(self.layers) + (self.mtp is not None)
        remat = blocks * _KEPT_PER_BLOCK * b * t * self.dim * 4 > _REMAT_OVER
        last, stats = self._hidden(params, x, remat=remat)
        mask = jnp.broadcast_to(mask, (b, t))
        main = loss_in_row_blocks(
            _norm(last, params["ln_f"], self.layers[-1]).reshape(b * t, -1),
            params["w_out"], y.reshape(-1), mask.reshape(-1))
        if self.mtp is None:
            return main, stats
        h, seen = blocks_over(
            self._joined(params, last, y), params["blocks"][-1:],
            self.heads, self._attention(), remat, (self.mtp,))

        def shifted(z):
            return jnp.concatenate([z[:, 1:], jnp.zeros_like(z[:, :1])], 1)

        mask = mask * shifted(mask)
        second = loss_in_row_blocks(
            _norm(h, params["mtp"]["ln_f"], self.mtp).reshape(b * t, -1),
            params["w_out"], shifted(y).reshape(-1), mask.reshape(-1))
        stats = dict(_add_stats(stats, seen), loss_main=main,
                     loss_mtp=second, mtp_positions=jnp.sum(mask))
        return main + self.mtp_weight * second, stats

    def picks(self, variables: Dict[str, Any], x, y=None) -> jnp.ndarray:
        """[L, B, T, top_k]: the experts each token picked in each routed
        layer, a second head's block last (``y``: the tokens after ``x``'s;
        left out, ``x`` turned by one, the last position reading the row's
        first token)."""
        params, out = variables["params"], []

        def note(stats, picks):
            out.append(picks.reshape(*x.shape, -1))

        h = self._embed(params, x)
        attn = self._attention()
        for blk, layer in zip(params["blocks"], self.layers):
            h = block(h, blk, self.heads, _over_sequence(attn, layer), layer,
                      note)
        if self.mtp is not None:
            y = jnp.roll(x, -1, axis=1) if y is None else y
            block(self._joined(params, h, y), params["blocks"][-1],
                  self.heads, _over_sequence(attn, self.mtp), self.mtp, note)
        return jnp.stack(out)
