"""Int8 weight quantization for serving.

Single-token decode is weight-bandwidth-bound: every step streams the full
parameter set from HBM for one row of activations.  Storing matmul weights
as per-output-channel int8 halves the at-rest footprint vs bf16 (4x vs
f32) and bounds quantization error to the per-channel scale.  The dequant
(`int8 → f32 · scale`) runs inside the jitted step; realizing the full
bandwidth win additionally requires XLA to fuse the dequant into the
matmul operand read — when a profile shows it materializing the converted
matrix instead, the next step is an in-kernel dequant matmul per the
pallas quantization pattern (/opt/skills/guides/pallas_guide.md).

API: ``quantize_lm_params`` converts the functional-LM pytree
(`models/functional_lm.init_lm_params` layout) into a quantized variant;
``QuantizedKVCacheLM`` is a drop-in `KVCacheLM` whose prefill/decode
dequantize on the fly.  Norm scales/biases and embeddings stay in f32
(embeddings are gathers, not matmuls, and norm params are tiny).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kv_cache_lm import KVCacheLM

_MATMUL_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2")


def quantize_matrix_int8(w: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """[in, out] → {"q": int8 [in, out], "s": f32 [out]} per-output-channel
    symmetric quantization."""
    s = jnp.max(jnp.abs(w), axis=0) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(w / s[None, :]), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s.astype(jnp.float32)}


def dequantize_matrix(qs: Dict[str, jnp.ndarray],
                      dtype=jnp.float32) -> jnp.ndarray:
    return qs["q"].astype(dtype) * qs["s"].astype(dtype)[None, :]


def quantize_lm_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize every transformer matmul weight; leave embeddings, position
    table, and layernorm params full-precision."""
    out = dict(params)
    out["blocks"] = []
    for blk in params["blocks"]:
        qblk = dict(blk)
        for k in _MATMUL_KEYS:
            qblk[k] = quantize_matrix_int8(blk[k])
        out["blocks"].append(qblk)
    return out


def _dequant_blocks(params: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(params)
    out["blocks"] = [
        {k: (dequantize_matrix(v) if k in _MATMUL_KEYS else v)
         for k, v in blk.items()}
        for blk in params["blocks"]]
    return out


class QuantizedKVCacheLM(KVCacheLM):
    """KVCacheLM over int8-quantized weights: same prefill/decode_multi API,
    the dequant happens inside the jitted steps (fused into the matmuls by
    XLA), so HBM weight traffic is ~half of the bf16 baseline."""

    @classmethod
    def from_lm(cls, lm: KVCacheLM) -> "QuantizedKVCacheLM":
        return cls(quantize_lm_params(lm.params), lm.heads, lm.max_len)

    def prefill(self, tokens, length, max_len: int = -1):
        ml = self.max_len if max_len == -1 else max_len
        return _q_prefill(self.params, tokens, length, self.heads, ml)

    def decode_multi(self, cache, prompt_buf, prompt_n, pos0, temps,
                     top_k, top_p, rng, k: int,
                     exact_filters: bool = False):
        return _q_decode_multi(self.params, cache, prompt_buf, prompt_n,
                               pos0, temps, top_k, top_p, rng, self.heads,
                               k, exact_filters)

    def full_logits(self, tokens):
        return KVCacheLM(_dequant_blocks(self.params), self.heads,
                         self.max_len).full_logits(tokens)


@partial(jax.jit, static_argnames=("heads", "max_len"))
def _q_prefill(params, tokens, length, heads, max_len=0):
    from . import kv_cache_lm as _k

    return _k.prefill.__wrapped__(_dequant_blocks(params), tokens, length,
                                  heads, max_len)


@partial(jax.jit, static_argnames=("heads", "k", "exact_filters"),
         donate_argnums=(1,))
def _q_decode_multi(params, cache, prompt_buf, prompt_n, pos0, temps,
                    top_k, top_p, rng, heads, k, exact_filters=False):
    from . import kv_cache_lm as _k

    return _k.decode_multi.__wrapped__(_dequant_blocks(params), cache,
                                       prompt_buf, prompt_n, pos0, temps,
                                       top_k, top_p, rng, heads, k,
                                       exact_filters)
