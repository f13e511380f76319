"""One query position a row against the live part of a KV cache.

`decode_attention` is the full-cache half of `serving.kv_cache_lm`'s
decode attention: for row i the scores of ``q[i]`` against the positions
below ``lengths[i]`` of one layer's K, their softmax statistics, and the
sum over V that goes with them.  What it reads of the cache is the blocks
of positions that hold a live position, of K and of V, and nothing of a
row of length 0: the work is in proportion to the lengths, which is all
the kernel is told.

The cache is ``[B, H, Dh, T]``, positions last (`kv_cache_lm.init_cache`),
so a block of positions of one row is ``[H, Dh, 512]``, whole lane tiles.
Every (row, block) pair with a live position is one step of a grid whose
length is counted on the device: the pairs come as prefetched scalars, K's
and V's blocks through the pipeline, so the next pair's blocks are in flight
while this one's are used, across rows too, and a cache whose length is not
whole blocks has a ragged last one.  (When no row holds anything the grid
is one step long all the same, and that step's block is fetched and not
used.)  Inside a step the block's live tiles of 128 positions are taken in
one after another: each of the 128 lanes keeps its own running maximum, sum
and ``[Dh]`` accumulator (an online softmax 128 wide: no lane meets another
until the row's last block), scores and sums in float32, K and V read as
stored.  Under grouped heads the cache holds ``Hk`` key/value heads for
``H`` query heads: query head h reads key/value head ``h // (H / Hk)`` of
the same block, so a block is fetched once for its whole group.  The row's
last step folds the 128 lanes into the row's maximum ``m``, sum ``l`` and
unnormalised output ``o``: ``softmax(s) @ v == o / l``,
and a caller with more positions of its own (the dispatch's chunk) merges
them by the two statistics.

Off TPU the same kernel runs through the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _on_tpu

_LANES = 128
#: positions a step reads of K and of V.  With all 32 rows of GPT-2 large's
#: cache near their end, 512 keeps `decode_multi` within 5% of the two
#: contractions it replaced, which ran at 92% of the HBM's rate (128: 13%
#: over, 256: 10%: a step costs some 0.3 us beside its traffic); at the
#: serving cell's lengths the three lie within 5% of each other (PERF.md,
#: PR 28)
_BLOCK = 512
#: the score of a position that is not live, and a maximum over none
MASKED = -1e30


def _live_blocks(lengths, block: int, nblocks: int):
    """The (row, block) pairs that hold a live position, row by row, as two
    vectors of the longest such list's length, and the length of this one
    (1 at least: a step that finds nothing live does nothing)."""
    b = lengths.shape[0]
    per_row = jnp.minimum((lengths + block - 1) // block, nblocks)
    ends = jnp.cumsum(per_row)
    step = jnp.arange(b * nblocks, dtype=jnp.int32)
    rows = jnp.minimum(
        jnp.sum(step[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        b - 1)
    blocks = jnp.clip(step - (ends - per_row)[rows], 0, nblocks - 1)
    return rows, blocks, jnp.maximum(ends[-1], 1)


def _attention_kernel(scale: float, block: int, ragged: bool,
                      rows_ref, blocks_ref, len_ref, q_ref, k_ref, v_ref,
                      o_ref, m_ref, l_ref, q_wide, acc, m_lane, l_lane):
    heads, dh = acc.shape[:2]
    group = heads // k_ref.shape[1]     # query heads a key/value head
    step = pl.program_id(0)
    blk = blocks_ref[step]
    length = len_ref[rows_ref[step]]

    @pl.when(blk == 0)
    def _():
        q = q_ref[0].astype(jnp.float32)                 # [Dh, H]
        for h in range(heads):
            q_wide[h] = jnp.broadcast_to(q[:, h:h + 1], (dh, _LANES))
        acc[...] = jnp.zeros_like(acc)
        m_lane[...] = jnp.full_like(m_lane, MASKED)
        l_lane[...] = jnp.zeros_like(l_lane)

    def tile(sub, _):
        """The block's sub-th 128 positions: every lane's running maximum,
        sum and accumulator take its one position in."""
        first = blk * block + sub * _LANES
        lanes = pl.ds(pl.multiple_of(sub * _LANES, _LANES), _LANES)
        position = first + jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
        live = position < length
        for h in range(heads):
            k = k_ref[0, h // group, :, lanes].astype(jnp.float32)
            s = jnp.sum(k * q_wide[h], axis=0, keepdims=True) * scale
            s = jnp.where(live, s, MASKED)
            m_old = m_lane[h:h + 1, :]
            m_new = jnp.maximum(m_old, s)
            shrink = jnp.exp(m_old - m_new)
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            m_lane[h:h + 1, :] = m_new
            l_lane[h:h + 1, :] = shrink * l_lane[h:h + 1, :] + p
            v = v_ref[0, h // group, :, lanes].astype(jnp.float32)
            if ragged:      # past the cache's end a block holds no value
                v = jnp.where(live, v, 0.0)
            acc[h] = shrink * acc[h] + p * v

    # the tiles of this block that hold a live position
    jax.lax.fori_loop(
        0, jnp.clip((length - blk * block + _LANES - 1) // _LANES, 0,
                    block // _LANES), tile, None)

    @pl.when(blk >= (length + block - 1) // block - 1)
    def _():
        m_all = m_lane[...]                               # [H, 128]
        m = jnp.max(m_all, axis=1, keepdims=True)
        weight = jnp.exp(m_all - m)
        m_ref[0] = jnp.broadcast_to(m, m_all.shape)
        l_ref[0] = jnp.broadcast_to(
            jnp.sum(l_lane[...] * weight, axis=1, keepdims=True), m_all.shape)
        # head h's [Dh] output goes to lane h: the output is [Dh, lanes]
        lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape[1:], 1)
        out = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for h in range(heads):
            folded = jnp.sum(acc[h] * weight[h:h + 1, :], axis=1,
                             keepdims=True)               # [Dh, 1]
            out = jnp.where(lane == h, folded, out)
        o_ref[0] = out


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _attend(q, k, v, lengths, scale: float, interpret: bool):
    """`decode_attention`, under its own `jit`: a program that attends layer
    after layer traces and lowers the kernel once and calls it (as
    `pallas_kv_store._store_window`)."""
    b, hk, dh, t = k.shape
    h = q.shape[1]
    lengths = jnp.clip(lengths, 0, t)
    nblocks = -(-t // _BLOCK)
    rows, blocks, steps = _live_blocks(lengths, _BLOCK, nblocks)
    out_lanes = -(-h // _LANES) * _LANES

    def of_row(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda n, rows, blocks, lens: (rows[n], 0, 0))

    kv_block = pl.BlockSpec(
        (1, hk, dh, _BLOCK),
        lambda n, rows, blocks, lens: (rows[n], 0, 0, blocks[n]))
    o, m, l = pl.pallas_call(
        functools.partial(_attention_kernel, scale, _BLOCK,
                          t % _BLOCK != 0),
        out_shape=[jax.ShapeDtypeStruct((b, dh, out_lanes), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[of_row(dh, h), kv_block, kv_block],
            out_specs=[of_row(dh, out_lanes), of_row(h, _LANES),
                       of_row(h, _LANES)],
            scratch_shapes=[pltpu.VMEM((h, dh, _LANES), jnp.float32),
                            pltpu.VMEM((h, dh, _LANES), jnp.float32),
                            pltpu.VMEM((h, _LANES), jnp.float32),
                            pltpu.VMEM((h, _LANES), jnp.float32)]),
        interpret=interpret, name="decode_attention",
    )(rows, blocks, lengths, q.transpose(0, 2, 1), k, v)
    # a row of length 0 was no step's: what stands in its place is not read
    live = lengths > 0
    return (jnp.where(live[:, None, None], o[:, :, :h].transpose(0, 2, 1), 0.0),
            jnp.where(live[:, None], m[:, :, 0], MASKED),
            jnp.where(live[:, None], l[:, :, 0], 0.0))


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, scale: float
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(o, m, l)`` of row i's query against positions ``[0, lengths[i])``
    of ``k`` and ``v``: with ``s = scale * q[i] . k[i, :, :, :lengths[i]]``,
    ``m = max(s)``, ``l = sum(exp(s - m))`` and ``o = exp(s - m) @ v``, per
    head, in float32.  A row of length 0 gives ``m = MASKED``, ``l = 0`` and
    ``o = 0``, and none of its cache is read.

    ``q``: ``[B, H, Dh]``, ``k`` and ``v``: ``[B, Hk, Dh, T]`` (read in the
    type they are stored in; ``Hk`` divides ``H``, query head h reading
    key/value head ``h // (H / Hk)``), ``lengths``: int ``[B]``, taken as at
    most T.
    Returns ``o [B, H, Dh]``, ``m [B, H]`` and ``l [B, H]``."""
    return _attend(q, k, v, lengths.astype(jnp.int32), scale=float(scale),
                   interpret=not _on_tpu())
