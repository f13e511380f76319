"""In-place store of a dispatch's new positions into a KV cache.

`store_positions` writes, for every row of every array it is given, the k
newest positions at that row's own offset, into arrays that are aliased to
the outputs: on the chip nothing but the tiles that hold those positions is
read or written (`serving.kv_cache_lm.decode_multi` donates the cache, so
the store is in place from the program's argument to its result).

The cache is laid out ``[B, H, Dh, T]``, positions last, so a block of 128
positions of one row is ``[H, Dh, 128]``: whole lane tiles.  Every length is
cut into such blocks, the last one ragged where T is not a multiple of 128.
A row's window of new positions lies in one block or straddles two; the grid
visits, per row, the blocks it can touch.  The arrays that share ``pos0`` (a
layer's K and V) bring their chunks as one operand, side by side in one lane
tile; the kernel rotates that tile along the lanes until each new position
stands over its place in the block (the rotation is circular, so one shift
serves the block a window starts in and the block it runs on into), takes
it where the block's position lies in the window, the old value elsewhere,
and writes the block back.  No arithmetic, and the same work whatever k is.
A block that comes up twice for a row (no straddle, or the cache's last
block) is merged from the same input twice, to the same result.  What the
chip's VMEM holds is the blocks in flight, ``4 * len(arrays)`` of them (2.6
MB at GPT-2 large), whatever T and k are.

Off TPU the same kernel runs through the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _on_tpu

_LANES = 128


def _block_of(p, s, nblocks: int):
    """Index of the s-th block of positions that a window starting at
    position ``p`` touches, held inside the cache."""
    return jnp.clip(p // _LANES + s, 0, nblocks - 1)


def _store_kernel(n: int, k: int, nblocks: int, pos_ref, new_ref, *refs):
    olds, outs = refs[:n], refs[n:]
    p = pos_ref[pl.program_id(0)]
    first = _block_of(p, pl.program_id(1), nblocks) * _LANES
    position = first + jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    in_window = (position >= p) & (position < p + k)
    # values narrower than 32 bits move as the 32-bit words the chip packs
    # them into (along Dh, never along the positions): a word holds values
    # of one position, so they turn and land together, on half the registers
    packed = jnp.dtype(outs[0].dtype).itemsize < 4
    words = ((lambda x: pltpu.bitcast(x, jnp.uint32)) if packed
             else (lambda x: x))
    for hd in range(outs[0].shape[1]):
        new = words(new_ref[0, hd])
        for a, (old, out) in enumerate(zip(olds, outs)):
            # lane l of the block is position first + l and wants slot
            # first + l - p of array a's chunk, lane a * k + that of `new`
            placed = pltpu.roll(new, jnp.mod(p - first - a * k, _LANES), 1)
            merged = jnp.where(in_window, placed, words(old[0, hd]))
            out[0, hd] = pltpu.bitcast(merged, out.dtype) if packed else merged


@functools.partial(jax.jit, static_argnames=("interpret",))
def _store_window(arrays, chunks, pos0, interpret: bool):
    """`store_positions` for chunks that fit one lane tile side by side.

    Under its own `jit`, so that a program that stores layer after layer
    traces and lowers the kernel once and calls it: at GPT-2 large's 36
    layers tracing and lowering `decode_multi` takes 1.3 s where it took 9.0
    (parent 1.2; timed on the host, PR 25).  XLA inlines the calls: the
    compiled program is operation for operation the one without."""
    n = len(arrays)
    b, h, dh, t = arrays[0].shape
    k = chunks[0].shape[-1]
    nblocks = -(-t // _LANES)
    block = pl.BlockSpec(
        (1, h, dh, _LANES),
        lambda i, s, pos: (i, 0, 0, _block_of(pos[i], s, nblocks)))
    new = jnp.concatenate(chunks, axis=-1)
    new = jnp.pad(new, ((0, 0), (0, 0), (0, 0), (0, _LANES - n * k)))
    new_block = pl.BlockSpec((1, h, dh, _LANES), lambda i, s, pos: (i, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_store_kernel, n, k, nblocks),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # a window no wider than a block touches two blocks at most
            num_scalar_prefetch=1, grid=(b, min(nblocks, 2)),
            in_specs=[new_block] + [block] * n, out_specs=[block] * n),
        # operand 0 is pos0, operand 1 the chunks, then the arrays
        input_output_aliases={2 + a: a for a in range(n)},
        interpret=interpret, name="kv_store_positions",
    )(pos0, new, *arrays)


def store_positions(arrays: Sequence[jnp.ndarray],
                    chunks: Sequence[jnp.ndarray],
                    pos0: jnp.ndarray) -> List[jnp.ndarray]:
    """``arrays[a][i, :, :, pos0[i] + j] = chunks[a][i, :, :, j]`` for every
    array a, row i and j < k with ``pos0[i] + j`` inside the array; every
    other value is the input's, and each output aliases its input.

    ``arrays``: ``[B, H, Dh, T]`` each, ``chunks``: ``[B, H, Dh, k]`` each,
    ``pos0``: int32 ``[B]``.  A position at or beyond T is dropped, as is one
    below 0: a row's window is never moved to fit.  A dispatch longer than
    the ``128 // len(arrays)`` positions that fit one lane tile side by side
    is stored that many positions a call."""
    arrays = list(arrays)
    pos0 = pos0.astype(jnp.int32)
    per_call = _LANES // len(arrays)
    for j0 in range(0, chunks[0].shape[-1], per_call):
        arrays = _store_window(
            arrays, [c[..., j0:j0 + per_call] for c in chunks], pos0 + j0,
            interpret=not _on_tpu())
    return arrays
