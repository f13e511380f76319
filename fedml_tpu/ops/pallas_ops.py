"""Pallas TPU kernels for the framework's hot non-matmul ops.

Per the TPU kernel playbook (/opt/skills/guides/pallas_guide.md): XLA already
fuses elementwise chains into the matmuls of the training step; the ops worth
hand-writing are the HBM-bandwidth-bound reductions the aggregation plane
runs every round:

* ``weighted_average_flat`` — the FedAvg reduction Σ_c w_c·X[c] over the
  stacked client axis, tiled so each [C, block] tile is one VMEM-resident
  [1,C]x[C,block] contraction on the MXU.
* ``quantize_mask`` — SecAgg's fused quantize(+round)→int32→uint32 mask-add,
  one pass over HBM instead of three.

On the TPU they compile; on any other backend the same kernels run through
the pallas interpreter (``interpret`` defaults to ``not _on_tpu()``).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

_BLOCK = 1024  # lane-dim block (multiple of 128)


def _on_tpu() -> bool:
    """The one backend probe of ``fedml_tpu.ops``: kernels compile for the
    chip exactly when this is true, and run interpreted otherwise."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# weighted average over stacked clients
# ---------------------------------------------------------------------------

def _wavg_kernel(w_ref, x_ref, o_ref):
    # x_ref: [C, BLOCK] VMEM tile; w_ref: [1, C] (normalized weights)
    o_ref[:] = jnp.dot(w_ref[:], x_ref[:],
                       preferred_element_type=jnp.float32)


def weighted_average_flat(stacked: jnp.ndarray, weights: jnp.ndarray,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """[C, D] stacked flat updates, [C] weights → [D] weighted average."""
    if interpret is None:
        interpret = not _on_tpu()
    c, d = stacked.shape
    norm = jnp.maximum(jnp.sum(weights), 1e-12)
    w = (weights / norm).astype(jnp.float32).reshape(1, c)
    pad = (-d) % _BLOCK
    x = jnp.pad(stacked.astype(jnp.float32), ((0, 0), (0, pad)))
    dp = d + pad
    grid = (dp // _BLOCK,)
    out = pl.pallas_call(
        _wavg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((c, _BLOCK), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, _BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
    )(w, x)
    return out.reshape(dp)[:d]


def agg_stacked_pallas(stacked_tree: Any, weights: jnp.ndarray,
                       interpret: Optional[bool] = None) -> Any:
    """Pytree variant of `agg_stacked` routed through the pallas reduction:
    flattens leaves into one [C, D] matrix, reduces once, unflattens."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked_tree)
    c = leaves[0].shape[0]
    flat = jnp.concatenate(
        [leaf.reshape(c, -1).astype(jnp.float32) for leaf in leaves], axis=1)
    avg = weighted_average_flat(flat, weights, interpret=interpret)
    out, off = [], 0
    for leaf in leaves:
        shape = leaf.shape[1:]
        size = int(jnp.size(leaf) // c)
        out.append(avg[off:off + size].reshape(shape).astype(leaf.dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# fused quantize + mask (SecAgg bulk path)
# ---------------------------------------------------------------------------

def _qmask_kernel(x_ref, m_ref, o_ref, *, scale):
    q = jnp.round(x_ref[:] * scale).astype(jnp.int32)
    o_ref[:] = q.view(jnp.uint32) + m_ref[:]


def quantize_mask(x: jnp.ndarray, mask: jnp.ndarray, scale: float = 2.0**16,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """float32 [D] + uint32 mask [D] → masked uint32 [D] in one HBM pass."""
    if interpret is None:
        interpret = not _on_tpu()
    d = x.shape[0]
    pad = (-d) % _BLOCK
    xp = jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(1, -1)
    mp = jnp.pad(mask, (0, pad)).reshape(1, -1)
    dp = d + pad
    out = pl.pallas_call(
        functools.partial(_qmask_kernel, scale=scale),
        grid=(dp // _BLOCK,),
        in_specs=[pl.BlockSpec((1, _BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((1, _BLOCK), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, _BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.uint32),
        interpret=interpret,
    )(xp, mp)
    return out.reshape(dp)[:d]


# ---------------------------------------------------------------------------
# int8 weight matmul with in-kernel dequant (serving decode path)
# ---------------------------------------------------------------------------

_MM_BLOCK_N = 512


def _int8_mm_kernel(x_ref, q_ref, s_ref, o_ref):
    # x: [M, K]; q: [K, BN] int8; s: [1, BN] per-channel scales.
    # dequant happens on the VMEM tile — the int8 matrix is what crossed
    # HBM, which is the bandwidth the decode path is bound by.
    acc = jnp.dot(x_ref[:], q_ref[:].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[:] = acc * s_ref[:]


def int8_matmul(x: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """x [M, K] (f32/bf16) @ dequant(q [K, N] int8, s [N]) → [M, N] f32.

    The pallas "quantization kernel" pattern: weights stream HBM→VMEM as
    int8 (half of bf16), dequantize in-register, hit the MXU per [K, BN]
    tile.  Off-TPU the same kernel runs interpreted."""
    if interpret is None:
        interpret = not _on_tpu()
    m, k = x.shape
    n = q.shape[1]
    bn = min(_MM_BLOCK_N, n)
    pad = (-n) % bn
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad)))
        s = jnp.pad(s, (0, pad))
    npad = n + pad
    out = pl.pallas_call(
        _int8_mm_kernel,
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((k, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, npad), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), q, s.astype(jnp.float32).reshape(1, -1))
    return out[:, :n]
