"""Fused wire-compression kernels for cross-silo update payloads.

Per the TPU kernel playbook (/opt/skills/guides/pallas_guide.md): the
compression hot path is HBM-bandwidth-bound element-wise work over the
flattened update — exactly the shape pallas wins at when the quantize
(reduce → scale → round → cast) chain is fused into one pass instead of
XLA materializing the intermediate f32 tensors between ops.

* ``quantize_int8_blocked``  — symmetric per-block int8 quantization of a
  flat f32 update: one [32, BLOCK] VMEM tile computes per-row max-abs,
  scales, rounds and casts in a single HBM read.  Layout respects the
  int8 (32, 128) / f32 (8, 128) minimum tiles: the flat vector is
  reshaped to rows of ``BLOCK`` lanes and the grid walks 32-row groups.
* ``dequantize_int8_blocked`` — the inverse (int8 · scale → f32), fused
  the same way; pure jnp fallback is bit-identical so it can run inside
  the server's aggregation jit off-TPU.

Top-k sparsification stays on ``jax.lax.top_k`` (XLA's sort-based top-k
is already a fused single program; a hand deasort would not beat it) —
see ``utils/compression.py`` for the codec that composes delta → top-k →
int8 for the wire.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_ops import _on_tpu

#: lanes per quantization block (one scale per row of this many values);
#: multiple of 128 per the lane-dim tiling constraint
BLOCK = 512
#: rows per grid step — the int8 minimum sublane tile
_ROWS = 32


def _as_rows(flat: jnp.ndarray) -> Tuple[jnp.ndarray, int, int]:
    """flat [D] → padded [R, BLOCK] with R a multiple of ``_ROWS``."""
    d = flat.shape[0]
    rows = -(-d // BLOCK)
    rows_padded = -(-rows // _ROWS) * _ROWS
    pad = rows_padded * BLOCK - d
    x = jnp.pad(flat.astype(jnp.float32), (0, pad))
    return x.reshape(rows_padded, BLOCK), d, rows_padded


def _quant_kernel(x_ref, q_ref, s_ref):
    # x: [32, BLOCK] f32 tile.  Per-row max-abs → scale → round → int8,
    # one VMEM pass; a zero row keeps scale 0 and quantizes to 0.
    x = x_ref[:]
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = amax / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    q_ref[:] = jnp.clip(jnp.round(x * inv), -127, 127).astype(jnp.int8)
    s_ref[:] = scale


def quantize_int8_blocked(
        flat: jnp.ndarray,
        interpret: Optional[bool] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """f32 [D] → (int8 [D], f32 scales [ceil(D/BLOCK)...padded rows]).

    Symmetric per-block quantization: block b covers
    ``flat[b·BLOCK:(b+1)·BLOCK]`` with scale ``max|x|/127``.  Returns the
    padded row count's worth of scales; ``dequantize_int8_blocked``
    consumes the pair and trims back to D.
    """
    use_pallas = interpret is True or _on_tpu()
    if interpret is None:
        interpret = not _on_tpu()
    x, d, rows = _as_rows(flat)
    n_scales = -(-d // BLOCK)   # only the rows that carry data go on the
    #                             wire — the sublane padding stays local
    if not use_pallas:
        amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        scale = amax / 127.0
        inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
        q = jnp.clip(jnp.round(x * inv), -127, 127).astype(jnp.int8)
        return q.reshape(-1)[:d], scale.reshape(-1)[:n_scales]
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(rows // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((_ROWS, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return q.reshape(-1)[:d], s.reshape(-1)[:n_scales]


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]


def dequantize_int8_blocked(q: jnp.ndarray, scales: jnp.ndarray, d: int,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """(int8 [D], f32 [rows]) → f32 [D].  Inverse of
    ``quantize_int8_blocked``; jnp fallback is bit-identical, so the
    decode can run inside the aggregation jit on any backend."""
    use_pallas = interpret is True or _on_tpu()
    if interpret is None:
        interpret = not _on_tpu()
    rows = scales.shape[0]
    pad = rows * BLOCK - q.shape[0]
    qr = jnp.pad(q, (0, pad)).reshape(rows, BLOCK)
    sr = scales.reshape(rows, 1)
    if use_pallas and rows % _ROWS:
        # re-grow the sublane padding the sender trimmed off the wire
        grow = -(-rows // _ROWS) * _ROWS - rows
        qr = jnp.pad(qr, ((0, grow), (0, 0)))
        sr = jnp.pad(sr, ((0, grow), (0, 0)))
        rows += grow
    if not use_pallas:
        # off-TPU the fused jnp form lets XLA fold this into the caller's
        # jit (pallas interpret mode would block that fusion)
        return (qr.astype(jnp.float32) * sr).reshape(-1)[:d]
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0)),
                  pl.BlockSpec((_ROWS, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, BLOCK), jnp.float32),
        interpret=interpret,
    )(qr, sr)
    return out.reshape(-1)[:d]


def topk_select(flat: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k(|x|) selection on a flat f32 update → (values f32 [k],
    indices int32 [k]).  ``k`` must be static (shape-stable under jit)."""
    k = max(1, min(int(k), flat.shape[0]))
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx], idx.astype(jnp.int32)


def scatter_flat(values: jnp.ndarray, indices: jnp.ndarray,
                 size: int) -> jnp.ndarray:
    """(values [k], indices [k]) → dense f32 [size] (top-k inverse)."""
    return jnp.zeros(int(size), jnp.float32).at[indices].set(
        values.astype(jnp.float32))
