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
head size, window) and its MLP (dense GELU, or routed experts of which this
chip holds a share).  GPT-2 is the default description; a model of another
family (`routed_lm`: RMSNorm, rotary or no positions by layer, grouped heads,
windows by layer, routed ReGLU experts, an untied head) is another, through
the same `block`, `embed`, `head` and `lm_forward`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.routed_experts import Experts, held_experts, route

#: LayerNorm epsilon — 1e-5 matches the HF GPT-2 default so imported
#: checkpoints (`train/llm/weight_import.py`) reproduce reference logits
LN_EPS = 1e-5


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


def _norm(x, g, layer: Layer = GPT2):
    if layer.norm == "rmsnorm":
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + layer.eps
        ) * g["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + layer.eps) * g["scale"] + g["bias"]


def _rotate(x, theta: float):
    """Rotary positions on [..., T, H, Dh], position = index along T: the
    two halves of a head are the pairs' first and second members."""
    t, half = x.shape[-3], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _bias(z, blk, key):
    """Optional-bias add (imported HF checkpoints carry biases; native init
    is bias-free)."""
    return z + blk[key] if key in blk else z


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


def _expert_mlp(y, h_in, blk, experts: Experts):
    """The held experts' share of the routed layer (`ops/routed_experts`).
    The router reads ``h_in``, the block's input ahead of its first norm;
    the experts read ``y``.  Also how the layer's picks fell."""
    picks, weights = route(h_in.reshape(-1, h_in.shape[-1]), blk["router"],
                           experts.top_k)
    out, counts, rows_passed = held_experts(
        y.reshape(-1, y.shape[-1]), picks, weights, blk["w_gate_up"],
        blk["w_down"], experts)
    stats = {"picks": jnp.asarray(picks.size, jnp.int32),
             "picks_held": jnp.sum(counts),
             "rows_passed": rows_passed,
             "expert_picks_max": jnp.max(counts)}
    return out.reshape(y.shape).astype(y.dtype), stats


def block(h: jnp.ndarray, blk: Dict[str, Any], heads: int,
          attend: Callable, layer: Layer = GPT2,
          note: Optional[Callable[[Dict[str, jnp.ndarray]], None]] = None
          ) -> jnp.ndarray:
    """One pre-norm block over ``h`` [..., D], made as ``layer`` says.
    ``attend(q, k, v)`` takes the three projections as [..., H, Dh] (k and
    v [..., Hk, Dh] under grouped heads) and returns the attention's output
    in q's shape: it is all that differs between training (an attention
    over the whole sequence), prefill (the same, keeping K and V) and
    decode (one position against a cache).  A rotation, where the layer
    has one, takes a row's position from its index along the axis before
    the heads'.  The MLP gets the block's input beside its own (a router
    reads the former); a routed layer hands ``note`` how its picks fell."""
    dim = h.shape[-1]
    dh = layer.head_dim or dim // heads
    y = _norm(h, blk["ln1"], layer)

    def proj(w, b, n):
        z = _bias(y @ blk[w], blk, b).reshape(*y.shape[:-1], n, dh)
        if layer.rope_theta is not None and w != "wv":
            z = _rotate(z, layer.rope_theta)
        return z

    kv = layer.kv_heads or heads
    o = attend(proj("wq", "bq", heads), proj("wk", "bk", kv),
               proj("wv", "bv", kv))
    a = h + _bias(o.reshape(*h.shape[:-1], heads * dh) @ blk["wo"], blk, "bo")
    y = _norm(a, blk["ln2"], layer)
    if layer.experts is None:
        return a + _dense_mlp(y, blk)
    out, stats = _expert_mlp(y, h, blk, layer.experts)
    if note is not None:
        note(stats)
    return a + out


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
    Dh], under the layer's window where it has one."""
    if layer.window is not None:
        attn_fn = partial(attn_fn, window=layer.window)

    def attend(q, k, v):
        o = attn_fn(*(z.transpose(0, 2, 1, 3) for z in (q, k, v)))
        return o.transpose(0, 2, 1, 3)

    return attend


def lm_hidden(params: Dict[str, Any], tokens: jnp.ndarray, heads: int,
              attn_fn, remat: bool = False,
              layers: Optional[Sequence[Layer]] = None
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """[B, T] int tokens → the residual stream [B, T, D] after the last
    block, and how the picks of the routed layers fell (nothing for a model
    without any).  ``layers``: each block's description; left out, GPT-2's
    for all.  A layer's window reaches ``attn_fn`` as ``window=``."""
    layers = layers or (GPT2,) * len(params["blocks"])

    def run(h, blk, layer):
        seen = []
        h = block(h, blk, heads, _over_sequence(attn_fn, layer), layer,
                  seen.append)
        return h, (seen[0] if seen else {})

    if remat:
        run = jax.checkpoint(run, static_argnums=(2,))
    h, stats = embed(params, tokens), {}
    for blk, layer in zip(params["blocks"], layers):
        h, seen = run(h, blk, layer)
        stats = _add_stats(stats, seen)
    return h, stats


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
# the routed family: RMSNorm, positions and windows by layer, grouped heads,
# routed ReGLU experts, an untied head
# ---------------------------------------------------------------------------

#: rows of the residual stream whose logits are alive at a time when the
#: loss is taken in blocks (`loss_in_row_blocks`)
LOSS_ROWS = 2048
#: float32 [B, T, D] arrays a block keeps for its backward where it is not
#: rematerialised, near enough; and the bytes of them, over all blocks, from
#: which each block is rematerialised instead
_KEPT_PER_BLOCK = 16
_REMAT_OVER = 2 * 2 ** 30


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
        logits = (hb @ w_out).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * mb)

    total = jnp.sum(jax.lax.map(some, (
        h.reshape(-1, rows, h.shape[-1]), y.reshape(-1, rows),
        mask.reshape(-1, rows))))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


@partial(jax.jit, static_argnames=("vocab", "dim", "heads", "ffn", "layers"))
def init_routed_params(key: jax.Array, vocab: int, dim: int, heads: int,
                       ffn: int, layers: Tuple[Layer, ...]
                       ) -> Dict[str, Any]:
    """The routed family's parameter pytree, drawn in one program.  A block:
    ``wq`` [D, H Dh], ``wk``/``wv`` [D, Hk Dh], ``wo`` [H Dh, D], ``router``
    [D, experts], ``w_gate_up`` [held, D, 2 F] (an expert's gate columns,
    then its up columns), ``w_down`` [held, F, D], two norms' scales; then
    the final norm and the untied head ``w_out`` [D, V]."""
    ks = iter(jax.random.split(key, 2 + 7 * len(layers)))

    def normal(shape, fan_in):
        return jax.random.normal(next(ks), shape) / np.sqrt(fan_in)

    blocks = []
    for layer in layers:
        dh, kv, ex = layer.head_dim, layer.kv_heads, layer.experts
        blocks.append({
            "ln1": {"scale": jnp.ones((dim,))},
            "wq": normal((dim, heads * dh), dim),
            "wk": normal((dim, kv * dh), dim),
            "wv": normal((dim, kv * dh), dim),
            "wo": normal((heads * dh, dim), heads * dh),
            "ln2": {"scale": jnp.ones((dim,))},
            "router": normal((dim, ex.total), dim),
            "w_gate_up": normal((ex.held, dim, 2 * ffn), dim),
            "w_down": normal((ex.held, ffn, dim), ffn),
        })
    return {"embed": jax.random.normal(next(ks), (vocab, dim)) * 0.02,
            "blocks": blocks, "ln_f": {"scale": jnp.ones((dim,))},
            "w_out": normal((dim, vocab), dim)}


class RoutedLMModule:
    """The routed family behind the surface `ModelBundle` expects, with two
    things more: `loss`, which `train/llm` takes in place of logits and
    `masked_loss` (the vocabulary's loss in row blocks, each block
    rematerialised where the sizes ask for it, the picks' counts beside
    it), and `picks`, the experts every token picked in every layer."""

    def __init__(self, vocab: int, dim: int, heads: int, ffn: int,
                 layers: Sequence[Layer]) -> None:
        self.vocab, self.dim, self.heads = int(vocab), int(dim), int(heads)
        self.ffn = int(ffn)
        self.layers = tuple(layers)

    def init(self, rngs: Any, x, train: bool = False) -> Dict[str, Any]:
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        return {"params": init_routed_params(
            key, self.vocab, self.dim, self.heads, self.ffn, self.layers)}

    @staticmethod
    def _attention():
        from ..ops.pallas_attention import flash_attention

        return partial(flash_attention, causal=True)

    def _hidden(self, params, x, remat: bool = False):
        return lm_hidden(params, x, self.heads, self._attention(), remat,
                         self.layers)

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
        heaviest held expert of any layer)."""
        params = variables["params"]
        b, t = x.shape
        kept = len(self.layers) * _KEPT_PER_BLOCK * b * t * self.dim * 4
        h, stats = self._hidden(params, x, remat=kept > _REMAT_OVER)
        h = _norm(h, params["ln_f"], self.layers[-1])
        return loss_in_row_blocks(
            h.reshape(b * t, -1), params["w_out"], y.reshape(-1),
            jnp.broadcast_to(mask, (b, t)).reshape(-1)), stats

    def picks(self, variables: Dict[str, Any], x) -> jnp.ndarray:
        """[L, B, T, top_k]: the experts each token picked in each layer."""
        params, out = variables["params"], []
        h = embed(params, x)
        attn = self._attention()
        for blk, layer in zip(params["blocks"], self.layers):
            out.append(route(h.reshape(-1, self.dim), blk["router"],
                             layer.experts.top_k)[0].reshape(*x.shape, -1))
            h = block(h, blk, self.heads, _over_sequence(attn, layer), layer)
        return jnp.stack(out)
