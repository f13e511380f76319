"""Routed experts: each token picks its top-k of E experts (by softmax over
all of them, or by sigmoid scores within the best groups of them), and this
chip computes the share of the result that the experts it holds give.

The layer is told ``(total, held, first_held)``: it routes over all ``total``
experts, computes the weighted outputs of experts ``first_held ..
first_held + held`` for the tokens that picked them, and adds nothing for
picks that landed elsewhere (on one chip the layer runs without its
exchange; nothing here stands in for absent chips).  No capacity, no dropped
token.

How: the picks that landed here are sorted by expert (`plan_rows`), each
expert's rows start at a tile boundary, and one Pallas kernel,
``moe_experts``, multiplies every tile of rows by its expert's matrix
(`grouped_matmul`): a grid step a tile, the expert's matrix fetched when the
expert changes and, where it is kept in float32, rounded to bfloat16 once (a
matrix kept in bfloat16 is multiplied as it is).  A matrix too large for a
grid step's VMEM is taken in blocks of its columns, each fetched once an
expert (`_column_blocks`).  Rows are laid out by a gather by index, forward
and backward alike (a gather's transpose is written as the other movement
of rows, never as a scatter).  The way back, the combine, is a second
kernel, ``moe_sum_picks`` (`_sum_picks`): a grid step a block of tokens,
whose sums start at zero in VMEM and take each row one of the block's
picks landed on, fetched from HBM in chunks of `_SUM_CHUNK` rows (the rows
a block has on one expert are consecutive, since within an expert's rows
the tokens ascend) and never rounded: float32 rows, float32 sums.  The
router's product and scores are float32 at ``highest`` precision, so that
picks differ from a float32 reference's only where the residual streams do.

What is laid out for the worst case, every pick of every token on a held
expert: the allocation of every array of rows (``(tiles + 1) * TILE`` of
them, a static shape) and the kernels' grid, whose dead steps neither fetch
nor compute.  What follows the count: every pass over rows outside the
kernels (the plan's own row-wise part, the gather that lays tokens out, the
elementwise work between the products) is a loop over the chunks of
`CHUNK_TILES` tiles that hold a landed row (`_over_live_chunks`), written in
place into a buffer of the worst case's shape whose dead part nothing
writes and nothing reads (`_row_buffer`).  When every pick lands the loop
goes over every chunk: the worst case is the same computation, not another
path.  The combine follows the count more closely still: it fetches the
chunks of rows that hold a landed row of the block's tokens, lists them
from the plan (`_chunks_of_blocks`), and adds the rows whose pick is one
of the block's; a pick that did not land reads nothing, and a token none of
whose picks landed gets zeros.

Paths as the other kernels of `fedml_tpu.ops`: on TPU the kernels; off TPU
with ``interpret=True`` the same kernels through the Pallas interpreter;
otherwise `jax.lax.ragged_dot` over the same layout, and for the combine a
`scan` of one gather a pick (`_sum_picks_scan`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.mlops import metrics as _metrics
from ..core.mlops import tracing
from .pallas_ops import _on_tpu

#: what the operands of every expert product are rounded to (float32
#: accumulation), on every backend: XLA's default for float32 on a TPU, made
#: explicit so that the kernel, its interpreter and the fallback agree
_OPERAND = jnp.bfloat16

#: rows of a tile: what one grid step of ``moe_experts`` multiplies by one
#: expert's matrix, and the boundary an expert's rows start at
TILE = 256


def fit_tile(picks: int, held: int) -> int:
    """Rows of a tile for a layer that lays out ``picks`` picks over ``held``
    experts: the power of two at or over twice an expert's even share, from
    16 (a bfloat16 sublane tile) to `TILE`.  A training step's rows get
    `TILE`; a decode step of 32 rows x 4 picks over 64 experts, two rows an
    expert, gets 16, where a tile of 256 would do 128 times the products its
    rows need, about as long as the expert's matrix takes to fetch."""
    share = max(2 * picks // max(held, 1), 1)
    return min(max(1 << (share - 1).bit_length(), 16), TILE)


#: tiles of a chunk: what one trip of a row-wise pass goes over.  Half a
#: chunk a pass goes over for nothing, and a trip costs next to nothing
#: beside its 2,048 rows; at 16 tiles and more XLA sums a row's dot in
#: another order than it does over the whole layout
CHUNK_TILES = 8


class Experts(NamedTuple):
    """The routed-expert layer of a model, and this chip's share of it.  The
    defaults are a softmax router ahead of the block's first norm over ReGLU
    experts."""

    total: int          # experts the router chooses among
    held: int           # experts whose matrices this chip holds
    first_held: int     # the first of them, in the router's numbering
    top_k: int          # experts a token picks
    #: "softmax": the top_k largest logits, weighted by their softmax;
    #: "sigmoid": sigmoid scores, chosen with a per-expert bias added and
    #: within the ``kept_groups`` best of ``groups`` groups, weighted by the
    #: picked scores normalised to 1, times ``scale`` (`route_in_groups`)
    scores: str = "softmax"
    groups: int = 1
    kept_groups: int = 1
    scale: float = 1.0
    #: the gate's activation: "relu" (ReGLU) | "silu" (SwiGLU)
    act: str = "relu"
    #: what the router reads: "input", the block's input ahead of its first
    #: norm | "normed", what the experts read
    reads: str = "input"


class Plan(NamedTuple):
    """Where each pick that landed on a held expert is computed.  Rows are
    those of the sorted, tile-padded layout, ``(tiles + 1) * TILE`` of them:
    the last tile is never live and takes the dead steps' output.  The
    arrays over rows are written up to the last live chunk and zero beyond
    it, which is what a row that computes nothing reads anyway."""

    token_of_row: jax.Array     # [M] the token a row computes (0 where none)
    pick_of_row: jax.Array      # [M] its pick, flat over [N, top_k]
    real: jax.Array             # [M] bool: the row computes a pick
    row_of_pick: jax.Array      # [N, top_k] the row of a pick that landed
    landed: jax.Array           # [N, top_k] bool
    tile_expert: jax.Array      # [tiles + 1] held expert of a tile's rows
    live_tiles: jax.Array       # [1] tiles that hold a real row
    group_rows: jax.Array       # [held] rows of each expert, padding and all
    counts: jax.Array           # [held] picks that landed on each expert


@tracing.scope("router")
def route(h: jax.Array, w_router: jax.Array,
          top_k: int) -> Tuple[jax.Array, jax.Array]:
    """``h`` [N, D] -> the ``top_k`` largest of the router's logits a token
    ([N, top_k] expert numbers) and their weights: the softmax over all
    experts renormalised over the picked, which is the softmax of the picked
    logits.  Float32 at ``highest`` precision."""
    logits = jnp.matmul(h.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top, picks = jax.lax.top_k(logits, top_k)
    return picks, jax.nn.softmax(top, axis=-1)


@tracing.scope("router")
def route_in_groups(h: jax.Array, w_router: jax.Array, bias: jax.Array,
                    experts: Experts
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``h`` [N, D] -> picks and weights [N, top_k] of a sigmoid router
    limited to groups: scores ``sigmoid(h W)``; choosing reads ``scores +
    bias`` and nothing else does; a group (``total / groups`` consecutive
    experts) scores the sum of its two best, the ``kept_groups`` best groups
    stay, and a token picks its ``top_k`` best experts of those; weights are
    the picked *scores* normalised to 1, times ``scale``.  Also [N, groups]
    bool: the groups a token kept.  Float32 at ``highest`` precision; ties
    go to the lower number, as `jax.lax.top_k` breaks them."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    choice = scores + bias.astype(jnp.float32)
    n, g = choice.shape[0], experts.groups
    by_group = choice.reshape(n, g, experts.total // g)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, experts.kept_groups)
    kept = jnp.any(best[:, :, None] == jnp.arange(g)[None, None, :], axis=1)
    _, picks = jax.lax.top_k(jnp.where(
        kept[:, :, None], by_group, -jnp.inf).reshape(n, -1), experts.top_k)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    weights = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return picks, weights * experts.scale, kept


# ---------------------------------------------------------------------------
# passes over rows: the chunks that hold a landed row, in place
# ---------------------------------------------------------------------------

def _row_buffer(shape, dtype):
    """Room for the worst case's rows, written by nobody yet: a pass writes
    the live chunks of it and the rest is never read, so making it costs no
    pass over the worst case."""
    return jax.lax.empty(shape, dtype)


def _chunk(a, start, rows: int):
    return jax.lax.dynamic_slice_in_dim(a, start, rows, 0)


def _put(buf, start, chunk):
    """``chunk`` into ``buf`` from row ``start`` on.  Rows of some width are
    kept by tiles, [tiles, TILE, width], and updated by whole tiles: an
    offset on the leading axis is one the compiler can write at in place,
    where a row offset it cannot prove aligned costs a copy of the chunk."""
    if buf.ndim == 3:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, chunk.reshape((-1,) + buf.shape[1:]), start // buf.shape[1],
            0)
    return jax.lax.dynamic_update_slice_in_dim(buf, chunk, start, 0)


def _live_chunks(live_tiles, m: int, tile: int):
    """The rows of a chunk, and how many chunks of a layout of ``m`` rows
    it takes to hold every live tile."""
    rows = min(m, CHUNK_TILES * tile)
    return rows, -(-live_tiles[0] * tile // rows)


def _over_live_chunks(live_tiles, m: int, tile: int, body, bufs):
    """``body(start, rows, bufs) -> bufs`` once for every chunk of ``rows``
    rows, of ``m`` laid out, that holds a live tile; ``bufs`` are arrays of
    ``m`` rows that the body updates in place at ``start``.  The last
    chunk of the worst case is moved back to end with the layout (a row's
    value depends on the row alone, so the rows it passes over twice are
    written twice the same)."""
    rows, chunks = _live_chunks(live_tiles, m, tile)

    def trip(c, bufs):
        return body(jnp.minimum(c * rows, m - rows), rows, bufs)

    return jax.lax.fori_loop(0, chunks, trip, bufs)


def _layout(plan: Plan):
    """A plan's ``(live_tiles, rows laid out, rows of a tile)``."""
    m = plan.real.shape[0]
    return plan.live_tiles, m, m // plan.tile_expert.shape[0]


def _pass_over(plan: Plan, width: int, dtype, chunk_of):
    """[M, width]: ``chunk_of(start, rows)`` [rows, width] for every chunk up
    to the last live one, nothing defined beyond."""
    _, m, tile = _layout(plan)

    def write(start, rows, buf):
        return _put(buf, start, chunk_of(start, rows))

    return _over_live_chunks(*_layout(plan), write, _row_buffer(
        (m // tile, tile, width), dtype)).reshape(m, width)


def rows_passed(plan: Plan):
    """The rows each pass over a plan's rows goes over: whole chunks up to
    the last that holds a landed row."""
    rows, chunks = _live_chunks(*_layout(plan))
    return rows * chunks


@tracing.scope("experts.plan")
def plan_rows(picks: jax.Array, experts: Experts, tile: int = TILE) -> Plan:
    """Sort the picks that landed on held experts by expert and give each
    expert whole tiles.  Everything is an index computation on
    ``N * top_k`` integers; nothing is dropped."""
    n, k = picks.shape
    held = experts.held
    local = picks.reshape(-1).astype(jnp.int32) - experts.first_held
    landed = (local >= 0) & (local < held)
    key = jnp.where(landed, local, held)              # elsewhere sorts last
    # stable, over the expert alone: within an expert's rows the flat picks,
    # which are token-major, ascend (`_chunks_of_blocks` counts on it)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)  # sorted -> flat
    place = jnp.argsort(order).astype(jnp.int32)      # flat pick -> sorted
    starts = jnp.searchsorted(key[order], jnp.arange(held + 1)).astype(
        jnp.int32)
    counts = starts[1:] - starts[:-1]
    group_rows = -(-counts // tile) * tile
    ends = jnp.cumsum(group_rows)
    begins = ends - group_rows
    # the worst case: every pick a token may make of held experts lands
    tiles = -(-(n * min(k, held) + held * (tile - 1)) // tile)
    tile_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(tiles + 1) * tile, side="right"), held - 1).astype(
            jnp.int32)
    # a tile's rows are consecutive sorted picks: row r of tile t computes
    # sorted pick r + shift[t] while r < limit[t]
    shift = (starts[:-1] - begins)[tile_expert]
    limit = jnp.minimum((begins + counts)[tile_expert], ends[-1])
    m = (tiles + 1) * tile
    live_tiles = (ends[-1:] // tile).astype(jnp.int32)

    def lay(start, rows, laid):
        token_of_row, pick_of_row, real = laid
        row = start + jnp.arange(rows, dtype=jnp.int32)
        of_tile = lambda a: jnp.repeat(
            _chunk(a, start // tile, rows // tile), tile)
        live = row < of_tile(limit)
        pick = jnp.where(live, order[jnp.clip(
            row + of_tile(shift), 0, n * k - 1)], 0)
        return (_put(token_of_row, start, pick // k),
                _put(pick_of_row, start, pick), _put(real, start, live))

    token_of_row, pick_of_row, real = _over_live_chunks(
        live_tiles, m, tile, lay,
        (jnp.zeros((m,), jnp.int32),) * 2 + (jnp.zeros((m,), bool),))
    e_pick = jnp.minimum(key, held - 1)
    row_of_pick = jnp.where(landed, begins[e_pick] + place - starts[e_pick], 0)
    return Plan(token_of_row, pick_of_row, real,
                row_of_pick.reshape(n, k), landed.reshape(n, k), tile_expert,
                live_tiles, group_rows, counts)


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------

#: bytes of an expert's matrix a grid step holds at once: the pipeline keeps
#: two such blocks, and beside a float32 one its bfloat16 copy
_W_BLOCK_BYTES = 16 * 2 ** 20


def _column_blocks(n: int, column_bytes: int) -> int:
    """Blocks the ``n`` columns of an expert's matrix are taken in: one
    where the whole matrix fits `_W_BLOCK_BYTES`, else the fewest blocks of
    whole 128-column lane tiles that do."""
    if n * column_bytes <= _W_BLOCK_BYTES:
        return 1
    return next(b for b in range(2, n // 128 + 1)
                if n % (b * 128) == 0
                and n // b * column_bytes <= _W_BLOCK_BYTES)


def _experts_kernel(tile_expert, live_tiles, x_ref, w_ref, o_ref, *w_bf16,
                    transposed: bool, tile_axis: int):
    """One grid step: a tile of rows times (a block of the columns of) its
    expert's matrix, bfloat16 operands, float32 accumulation.  A float32
    matrix is rounded into ``w_bf16`` when the expert changes, not every
    step; a bfloat16 one is multiplied as it is.  A dead step does nothing
    (its blocks are the last live step's, so nothing is fetched for it
    either)."""
    i = pl.program_id(tile_axis)

    @pl.when(i < live_tiles[0])
    def _live():
        if w_bf16:
            @pl.when((i == 0)
                     | (tile_expert[i] != tile_expert[jnp.maximum(i - 1, 0)]))
            def _round():
                w_bf16[0][...] = w_ref[0].astype(_OPERAND)

        o_ref[...] = jax.lax.dot_general(
            x_ref[...].astype(_OPERAND),
            w_bf16[0][...] if w_bf16 else w_ref[0],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("transposed", "interpret"))
def _experts_call(x, w, tile_expert, live_tiles, *, transposed: bool,
                  interpret: bool):
    """`_experts_kernel` over [M, K] rows and [E, K, N] matrices ([E, N, K]
    ``transposed``).  Under its own `jit`, as the other kernels of the
    epoch program: traced and lowered once, called once a product.  The
    grid is the tiles; where a matrix is taken in column blocks they are the
    outer axis, so that a block is fetched once an expert and a tile of rows
    once a block."""
    m, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    tiles = tile_expert.shape[0]
    tile = m // tiles
    blocks = _column_blocks(n, k * w.dtype.itemsize)
    bn = n // blocks
    grid = (tiles,) if blocks == 1 else (blocks, tiles)

    def ids(a):
        """(tile, column block, tile_expert, live_tiles) of an index map's
        arguments: the grid's indices, then the two prefetched arrays."""
        return a[len(grid) - 1], (0 if blocks == 1 else a[0]), a[-2], a[-1]

    def last_live(i, live):
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))

    def x_map(*a):
        i, _, _, live = ids(a)
        return last_live(i, live), 0

    def w_map(*a):
        i, j, te, live = ids(a)
        e = te[last_live(i, live)]
        return (e, j, 0) if transposed else (e, 0, j)

    def o_map(*a):      # a dead step's output is the last tile, which
        i, j, _, live = ids(a)                          # nothing reads
        return jnp.where(i < live[0], i, tiles - 1), j

    w_block = (1, bn, k) if transposed else (1, k, bn)
    return pl.pallas_call(
        functools.partial(_experts_kernel, transposed=transposed,
                          tile_axis=len(grid) - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[pl.BlockSpec((tile, k), x_map),
                      pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec((tile, bn), o_map),
            scratch_shapes=[] if w.dtype == _OPERAND else [
                pltpu.VMEM(w_block[1:], _OPERAND)]),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # a block of an expert's matrix, double-buffered, and its
            # bfloat16 copy
            vmem_limit_bytes=3 * bn * k * w.dtype.itemsize + 16 * 2 ** 20),
        interpret=interpret,
        name="moe_experts_t" if transposed else "moe_experts",
    )(tile_expert, live_tiles, x, w)


@tracing.scope("experts.products")
def grouped_matmul(x, w, plan: Plan, transposed: bool = False,
                   interpret: Optional[bool] = None):
    """Row ``r`` of ``x`` [M, K] times the matrix of its tile's expert,
    ``w[plan.tile_expert[r // TILE]]`` ([K, N]; ``transposed``: [N, K],
    contracted on its last axis).  Float32 [M, N]; the rows of dead tiles
    hold nothing defined."""
    if interpret is None and not _on_tpu():
        rhs = jnp.swapaxes(w, 1, 2) if transposed else w
        return jax.lax.ragged_dot(
            x.astype(jnp.float32), rhs.astype(jnp.float32), plan.group_rows)
    return _experts_call(x, w, plan.tile_expert, plan.live_tiles,
                         transposed=transposed, interpret=bool(interpret))


@tracing.scope("experts.products")
def _matrices_grad(a, b, plan: Plan):
    """``sum over an expert's real rows of a_r (x) b_r`` [held, Ka, Kb]: the
    gradient of the matrices themselves, for a caller that trains them.
    Plain jnp (LoRA leaves the experts frozen, and the compiler then drops
    it)."""
    live = plan.real[:, None]
    return jax.lax.ragged_dot_general(
        jnp.where(live, a, 0).astype(jnp.float32),
        jnp.where(live, b, 0).astype(jnp.float32), plan.group_rows,
        jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]))


# ---------------------------------------------------------------------------
# rows in, rows out: each the other's transpose, both gathers
# ---------------------------------------------------------------------------

def _rows_of(y, plan: Plan):
    """[N, D] -> [M, D]: each row, up to the last live chunk, its token's
    vector."""
    return _pass_over(plan, y.shape[1], y.dtype, lambda start, rows: jnp.take(
        y, _chunk(plan.token_of_row, start, rows), axis=0, mode="clip"))


def _sum_picks_scan(rows, plan: Plan, weights=None):
    """`_sum_picks` off the TPU, and what the tests hold the kernel to: one
    gather a pick and one at a time (a `scan`), so that no [N, top_k, D]
    array is made; a row nothing computed is gathered and thrown away
    (`where`, not a product by 0)."""
    n, k = plan.landed.shape
    if weights is None:
        weights = jnp.ones((n, k), jnp.float32)

    def one(out, pick):
        row, landed, w = pick
        got = jnp.take(rows, row, axis=0, mode="clip").astype(jnp.float32)
        return out + jnp.where(landed[:, None], got * w[:, None], 0.0), None

    out, _ = jax.lax.scan(
        one, jnp.zeros((n, rows.shape[1]), jnp.float32),
        (plan.row_of_pick.T, plan.landed.T, weights.astype(jnp.float32).T))
    return out


#: bytes of the block of tokens a grid step of ``moe_sum_picks`` sums into
#: (the pipeline keeps two such blocks), and the most tokens of one whatever
#: their width: its list of chunks and its picks' weights are held in SMEM
_SUM_BLOCK_BYTES = 16 * 2 ** 20
_SUM_BLOCK_TOKENS = 1024

#: rows a copy of ``moe_sum_picks`` fetches, and copies it keeps under way.
#: An expert's rows start at a tile, so a chunk that divides the tile holds
#: one expert's rows, in which tokens ascend
_SUM_CHUNK = 16
_SUM_COPIES = 8

#: elements of a slice of a 1-D array that Mosaic copies from HBM to SMEM:
#: a whole tile of the array's layout there
_SMEM_SLICE = 1024


def _sum_block(n: int, d: int) -> int:
    """Tokens a grid step of ``moe_sum_picks`` sums: the largest power of
    two whose float32 rows of width ``d`` fit `_SUM_BLOCK_BYTES`, no more
    than `_SUM_BLOCK_TOKENS` nor than the ``n`` there are (in whole
    sublanes of 8)."""
    fit = max(8, _SUM_BLOCK_BYTES // (4 * d))
    return min(1 << (fit.bit_length() - 1), _SUM_BLOCK_TOKENS,
               -(-n // 8) * 8)


@tracing.scope("experts.plan")
def _chunks_of_blocks(plan: Plan, block: int, chunk: int):
    """What each block of ``block`` tokens fetches: ``[blocks, stride]``
    int32, a block's count of chunks and then the chunks themselves (of
    ``chunk`` rows of the layout), padded to whole slices of `_SMEM_SLICE`.
    The rows a block's tokens have on one held expert are consecutive
    (`plan_rows` sorts picks by expert alone and stably, and a flat pick
    index is token-major), so a block needs at most ``held`` ranges of
    rows; their chunks are listed expert by expert, and a chunk on the
    border of two blocks' ranges by both.  Index arithmetic on
    ``N * top_k`` integers."""
    n, k = plan.landed.shape
    held = plan.counts.shape[0]
    blocks = -(-n // block)
    begins = jnp.cumsum(plan.group_rows) - plan.group_rows
    row = jnp.pad(jnp.where(plan.landed, plan.row_of_pick, -1),
                  ((0, blocks * block - n), (0, 0)), constant_values=-1)
    # compared, not looked up: a gather of N * top_k numbers costs more
    # than the kernel
    on = (row[:, :, None] >= begins) & (row[:, :, None]
                                         < begins + plan.group_rows)
    rows = jnp.sum(on.reshape(blocks, block * k, held), axis=1,
                   dtype=jnp.int32)                           # [blocks, held]
    first_row = begins + jnp.cumsum(rows, axis=0) - rows
    first = first_row // chunk
    count = jnp.where(rows > 0, (first_row + rows - 1) // chunk - first + 1, 0)
    ends = jnp.cumsum(count, axis=1)
    # every range may start and end inside a chunk
    most = block * min(k, held) // chunk + 2 * held
    slot = jnp.arange(most, dtype=jnp.int32)[None, :, None]
    begun = (ends - count)[:, None, :]
    listed = jnp.sum(jnp.where(
        (slot >= begun) & (slot < ends[:, None, :]),
        first[:, None, :] + slot - begun, 0), axis=-1)
    return jnp.pad(jnp.concatenate([ends[:, -1:], listed], axis=1).astype(
        jnp.int32), ((0, 0), (0, -(most + 1) % _SMEM_SLICE)))


def _sum_picks_kernel(chunks, *refs, block: int, chunk: int, k: int,
                      weighted: bool):
    """One grid step: a block of tokens' sums.  The block starts at zero in
    VMEM; its chunks of rows are copied in from HBM, `_SUM_COPIES` under way
    at once, each with the slice of ``pick`` that says whose its rows are;
    a row of one of the block's picks is added (times the pick's weight) to
    its token's row, and no other row of a chunk is looked at (what a row
    past the landed ones holds is copied and never loaded)."""
    w_ref = refs[0] if weighted else None
    rows_hbm, pick_hbm, o_ref, buf, pick, sem, pick_sem = refs[weighted:]
    first_pick = pl.program_id(0) * block * k
    count = chunks[0]

    def copies(i):
        slot = i % _SUM_COPIES
        row = chunks[1 + i] * chunk
        start = pl.multiple_of(row // _SMEM_SLICE * _SMEM_SLICE, _SMEM_SLICE)
        return (pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(row, chunk), chunk)],
            buf.at[slot], sem.at[slot]), pltpu.make_async_copy(
                pick_hbm.at[pl.ds(start, _SMEM_SLICE)],
                pick.at[pl.ds(pl.multiple_of(slot * _SMEM_SLICE, _SMEM_SLICE),
                              _SMEM_SLICE)], pick_sem.at[slot]))

    def start(i):
        @pl.when(i < count)
        def _():
            for copy in copies(i):
                copy.start()

    o_ref[...] = jnp.zeros_like(o_ref)
    for i in range(_SUM_COPIES - 1):
        start(i)

    def one_chunk(i, _):
        start(i + _SUM_COPIES - 1)
        for copy in copies(i):
            copy.wait()
        slot = i % _SUM_COPIES
        at = slot * _SMEM_SLICE + chunks[1 + i] * chunk % _SMEM_SLICE
        for r in range(chunk):
            p = pick[at + r] - first_pick

            @pl.when((p >= 0) & (p < block * k))
            def _ours():
                got = buf[slot, r:r + 1, :]
                if weighted:
                    got = got * w_ref[p]
                token = pl.ds(p // k, 1)
                o_ref[token, :] = o_ref[token, :] + got
        return 0

    jax.lax.fori_loop(0, count, one_chunk, 0)


@functools.partial(jax.jit, static_argnames=("block", "chunk", "k",
                                             "interpret"))
def _sum_picks_call(rows, pick, chunks, weights, *, block: int, chunk: int,
                    k: int, interpret: bool):
    """`_sum_picks_kernel` over [M, D] rows: [blocks * block, D].  Under its
    own `jit`, as `_experts_call`.  ``pick`` [M'] is a row's flat pick, -1
    where it computes none; ``chunks`` [blocks, stride] from
    `_chunks_of_blocks`; ``weights`` [blocks, >= block * k] or None."""
    d = rows.shape[1]
    blocks, stride = chunks.shape
    weighted = weights is not None
    smem = lambda width: pl.BlockSpec((width,), lambda b: (b,),
                                      memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_sum_picks_kernel, block=block, chunk=chunk,
                          k=k, weighted=weighted),
        grid=(blocks,),
        in_specs=[smem(stride)] + (
            [smem(weights.shape[1])] if weighted else []) + [hbm, hbm],
        out_specs=pl.BlockSpec((block, d), lambda b: (b, 0)),
        scratch_shapes=[pltpu.VMEM((_SUM_COPIES, chunk, d), jnp.float32),
                        pltpu.SMEM((_SUM_COPIES * _SMEM_SLICE,), jnp.int32),
                        pltpu.SemaphoreType.DMA((_SUM_COPIES,)),
                        pltpu.SemaphoreType.DMA((_SUM_COPIES,))],
        out_shape=jax.ShapeDtypeStruct((blocks * block, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # the block of sums, double-buffered, and the chunks under way
            vmem_limit_bytes=4 * d * (2 * block + _SUM_COPIES * chunk)
            + 16 * 2 ** 20),
        interpret=interpret,
        name="moe_sum_picks",
    )(chunks.reshape(-1), *([weights.reshape(-1)] if weighted else []),
      rows, pick)


def _note_combine(path: str, width: int, block_tokens: int = 0) -> None:
    """Counts, as a call of the combine is traced, which path it took, over
    rows of which width and in blocks of how many tokens
    (docs/OBSERVABILITY.md)."""
    _metrics.counter(
        "fedml_moe_combine_traces_total",
        "calls of the routed layer's combine traced, by the path, the rows' "
        "width and the tokens of a block they took",
        labels=("path", "width", "block_tokens"),
    ).labels(path=path, width=width, block_tokens=block_tokens).inc()


@tracing.scope("experts.combine")
def _sum_picks(rows, plan: Plan, weights=None,
               interpret: Optional[bool] = None):
    """[M, D] -> [N, D] float32: over a token's picks that landed, its rows
    (times their weights), never rounded; a row nothing computed never
    reaches a sum, and a pick that did not land reads nothing.  On TPU (or
    interpreted) the kernel ``moe_sum_picks``, else `_sum_picks_scan`."""
    n, k = plan.landed.shape
    d = rows.shape[1]
    if interpret is None and not _on_tpu():
        _note_combine("jnp", d)
        return _sum_picks_scan(rows, plan, weights)
    _, m, tile = _layout(plan)
    block, chunk = _sum_block(n, d), math.gcd(_SUM_CHUNK, tile)
    _note_combine("interpret" if interpret else "kernel", d, block)
    chunks = _chunks_of_blocks(plan, block, chunk)
    padded = chunks.shape[0] * block
    pick = jnp.pad(jnp.where(plan.real, plan.pick_of_row, -1),
                   (0, -m % _SMEM_SLICE), constant_values=-1)
    if weights is not None:
        weights = jnp.pad(weights.astype(jnp.float32),
                          ((0, padded - n), (0, 0))).reshape(-1, block * k)
        weights = jnp.pad(weights, ((0, 0), (0, -(block * k) % _SMEM_SLICE)))
    out = _sum_picks_call(rows.astype(jnp.float32), pick, chunks, weights,
                          block=block, chunk=chunk, k=k,
                          interpret=bool(interpret))
    return out if padded == n else out[:n]


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _held_share(y, weights, w_gate_up, w_down, plan: Plan,
                interpret: Optional[bool], act: str):
    return _held_share_fwd(y, weights, w_gate_up, w_down, plan, interpret,
                           act)[0]


def _gate(gate, act: str):
    return jax.nn.relu(gate) if act == "relu" else jax.nn.silu(gate)


def _glu(gate_up, act: str):
    gate, up = jnp.split(gate_up, 2, axis=1)
    return (_gate(gate, act) * up).astype(_OPERAND)


def _hidden(gate_up, plan: Plan, act: str):
    """[M, 2F] -> [M, F]: ``act(gate) * up`` of the rows up to the last
    live chunk."""
    return _pass_over(
        plan, gate_up.shape[1] // 2, _OPERAND,
        lambda start, rows: _glu(_chunk(gate_up, start, rows), act))


@tracing.scope("experts.layout")
def _held_share_fwd(y, weights, w_gate_up, w_down, plan, interpret, act):
    # rounded once here: every product's operands are bfloat16
    x = _rows_of(y.astype(_OPERAND), plan)
    gate_up = grouped_matmul(x, w_gate_up, plan, False, interpret)
    rows = grouped_matmul(_hidden(gate_up, plan, act), w_down, plan, False,
                          interpret)
    return (_sum_picks(rows, plan, weights, interpret),
            (x, gate_up, weights, w_gate_up, w_down, plan))


@tracing.scope("experts.layout")
def _held_share_bwd(interpret, act, res, d_out):
    """By hand, so that every movement of rows is a gather (autodiff would
    transpose each into a scatter), every cotangent stays float32, and the
    [M, D] rows of the forward need not be kept: a pick's weight meets its
    row only through ``<d_out, row> = <d_out D^T, hidden>``."""
    x, gate_up, weights, w_gate_up, w_down, plan = res
    m, f = gate_up.shape[0], gate_up.shape[1] // 2
    d_rows = _rows_of(d_out.astype(_OPERAND), plan)
    by_down = grouped_matmul(d_rows, w_down, plan, True, interpret)  # [M, F]

    def through_hidden(start, rows, bufs):
        dots, w_row, d_gate_up = bufs
        real = _chunk(plan.real, start, rows)
        by, gu = _chunk(by_down, start, rows), _chunk(gate_up, start, rows)
        gate, up = jnp.split(gu, 2, axis=1)
        dot = jnp.where(real, jnp.sum(by * _glu(gu, act), axis=-1), 0.0)
        w = jnp.where(real, jnp.take(
            weights.reshape(-1), _chunk(plan.pick_of_row, start, rows),
            mode="clip"), 0.0)
        d_hidden = by * w[:, None]
        if act == "relu":
            d_gate = jnp.where(gate > 0, d_hidden * up, 0.0)
        else:
            sig = jax.nn.sigmoid(gate)
            d_gate = d_hidden * up * (sig * (1.0 + gate * (1.0 - sig)))
        d = jnp.concatenate([d_gate, d_hidden * _gate(gate, act)],
                            axis=1).astype(_OPERAND)
        return (_put(dots, start, dot), _put(w_row, start, w),
                _put(d_gate_up, start, d))

    # a row's dot and weight are read by index and by the matrices'
    # gradient: zero where nothing is written
    _, _, tile = _layout(plan)
    dots, w_row, d_gate_up = _over_live_chunks(
        *_layout(plan), through_hidden,
        (jnp.zeros((m,), jnp.float32),) * 2 + (
            _row_buffer((m // tile, tile, 2 * f), _OPERAND),))
    d_gate_up = d_gate_up.reshape(m, 2 * f)
    d_weights = jnp.where(plan.landed, jnp.take(
        dots, plan.row_of_pick.reshape(-1), mode="clip").reshape(
            weights.shape), 0.0)
    d_x = grouped_matmul(d_gate_up, w_gate_up, plan, True, interpret)
    return (_sum_picks(d_x, plan, None, interpret),
            d_weights.astype(weights.dtype),
            _matrices_grad(x, d_gate_up, plan).astype(w_gate_up.dtype),
            _matrices_grad(_hidden(gate_up, plan, act), d_rows * w_row[
                :, None].astype(_OPERAND), plan).astype(w_down.dtype), None)


_held_share.defvjp(_held_share_fwd, _held_share_bwd)


def held_experts(y, picks, weights, w_gate_up, w_down, experts: Experts,
                 interpret: Optional[bool] = None,
                 tile: Optional[int] = None):
    """The held experts' share of a gated expert layer.  ``y`` [N, D] the
    layer's normed input (float32), ``picks``/``weights`` [N, top_k] from
    the router, ``w_gate_up`` [held, D, 2F] (an expert's gate columns, then
    its up columns), ``w_down`` [held, F, D], float32 or bfloat16.  Returns
    ``sum over picks e held here of w_e * (act(y G_e) * (y U_e)) D_e``
    [N, D] float32 (``act`` relu or silu, as ``experts`` says), and the
    picks that landed on each held expert [held], and the rows each of the
    layer's passes went over.  Differentiable to ``y``, the weights and the
    matrices.  ``tile``: the rows of a tile of the layout, `TILE` where the
    caller names none (`fit_tile` for a caller with few rows); a pick of an
    expert number outside ``0 .. total`` lands nowhere."""
    plan = plan_rows(picks, experts, tile or TILE)
    return (_held_share(y.astype(jnp.float32), weights, w_gate_up, w_down,
                        plan, interpret, experts.act), plan.counts,
            rows_passed(plan))
