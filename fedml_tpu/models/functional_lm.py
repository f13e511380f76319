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
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


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


#: LayerNorm epsilon — 1e-5 matches the HF GPT-2 default so imported
#: checkpoints (`train/llm/weight_import.py`) reproduce reference logits
LN_EPS = 1e-5


def _ln(x, g):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g["scale"] + g["bias"]


def _bias(z, blk, key):
    """Optional-bias add (imported HF checkpoints carry biases; native init
    is bias-free)."""
    return z + blk[key] if key in blk else z


def embed(params: Dict[str, Any], tokens: jnp.ndarray,
          pos: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Token plus position embedding.  ``tokens`` [B, T] with no ``pos``
    are whole sequences from position 0; ``tokens`` [B] with ``pos`` [B]
    are one token a row at its own position (a position beyond the table
    is clamped to its last row, as jnp indexing does)."""
    if pos is None:
        # NOTE positions must be GLOBAL: tokens arrive [B, T] logically;
        # under jit the T axis may be sharded and XLA partitions the slice
        return params["embed"][tokens] + params["pos"][:tokens.shape[1]][None]
    return params["embed"][tokens] + params["pos"][pos]


def block(h: jnp.ndarray, blk: Dict[str, Any], heads: int,
          attend: Callable) -> jnp.ndarray:
    """One pre-LN GPT-2 block over ``h`` [..., D].  ``attend(q, k, v)``
    takes the three projections as [..., H, Dh] and returns the attention's
    output in that shape: it is all that differs between training (an
    attention over the whole sequence), prefill (the same, keeping K and V)
    and decode (one position against a cache)."""
    dim = h.shape[-1]
    y = _ln(h, blk["ln1"])

    def proj(w, b):
        return _bias(y @ blk[w], blk, b).reshape(
            *y.shape[:-1], heads, dim // heads)

    o = attend(proj("wq", "bq"), proj("wk", "bk"), proj("wv", "bv"))
    h = h + _bias(o.reshape(h.shape) @ blk["wo"], blk, "bo")
    y = _ln(h, blk["ln2"])
    return h + _bias(
        jax.nn.gelu(_bias(y @ blk["w1"], blk, "b1")) @ blk["w2"], blk, "b2")


def head(h: jnp.ndarray, params: Dict[str, Any]) -> jnp.ndarray:
    """Final LayerNorm and the output projection."""
    h = _ln(h, params["ln_f"])
    if "w_out" in params:                          # optional untied head
        return h @ params["w_out"]
    return h @ params["embed"].T                   # tied output embedding


def lm_forward(params: Dict[str, Any], tokens: jnp.ndarray, heads: int,
               attn_fn, remat: bool = False) -> jnp.ndarray:
    """[B, T] int tokens → [B, T, V] logits.  ``attn_fn(q, k, v)`` consumes
    [B, H, T, D_h] — plug in full attention, a shard_map'd ring, or Ulysses;
    everything else is position-wise and sharding-constraint friendly.
    ``remat=True`` rematerializes each block's activations in the backward
    pass (`jax.checkpoint`), trading FLOPs for the activation memory that
    dominates long-context training."""
    def attend(q, k, v):
        o = attn_fn(*(z.transpose(0, 2, 1, 3) for z in (q, k, v)))
        return o.transpose(0, 2, 1, 3)

    layer = partial(block, heads=heads, attend=attend)
    if remat:
        layer = jax.checkpoint(layer)
    h = embed(params, tokens)
    for blk in params["blocks"]:
        h = layer(h, blk)
    return head(h, params)


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
