"""Routed experts: each token picks its top-k of E experts by softmax, and
this chip computes the share of the result that the experts it holds give.

The layer is told ``(total, held, first_held)``: it routes over all ``total``
experts, computes the weighted outputs of experts ``first_held ..
first_held + held`` for the tokens that picked them, and adds nothing for
picks that landed elsewhere (on one chip the layer runs without its
exchange; nothing here stands in for absent chips).  No capacity, no dropped
token: the rows are laid out for the worst case, every pick of every token
on a held expert.

How: the picks that landed here are sorted by expert (`plan_rows`), each
expert's rows start at a tile boundary, and one Pallas kernel,
``moe_experts``, multiplies every tile of rows by its expert's matrix
(`grouped_matmul`): a grid step a tile, the expert's matrix fetched when the
expert changes and rounded to bfloat16 once, dead tiles (the worst case's
room) neither fetched nor computed.  Rows are gathered in and out by index,
forward and backward alike (a gather's transpose is written as the other
gather, never as a scatter).  The router's product and softmax are float32
at ``highest`` precision, so that picks differ from a float32 reference's
only where the residual streams do.

Paths as the other kernels of `fedml_tpu.ops`: on TPU the kernel; off TPU
with ``interpret=True`` the same kernel through the Pallas interpreter;
otherwise `jax.lax.ragged_dot` over the same layout.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _on_tpu

#: what the operands of every expert product are rounded to (float32
#: accumulation), on every backend: XLA's default for float32 on a TPU, made
#: explicit so that the kernel, its interpreter and the fallback agree
_OPERAND = jnp.bfloat16

#: rows of a tile: what one grid step of ``moe_experts`` multiplies by one
#: expert's matrix, and the boundary an expert's rows start at
TILE = 256


class Experts(NamedTuple):
    """The routed-expert layer of a model, and this chip's share of it."""

    total: int          # experts the router chooses among
    held: int           # experts whose matrices this chip holds
    first_held: int     # the first of them, in the router's numbering
    top_k: int          # experts a token picks


class Plan(NamedTuple):
    """Where each pick that landed on a held expert is computed.  Rows are
    those of the sorted, tile-padded layout, ``(tiles + 1) * TILE`` of them:
    the last tile is never live and takes the dead steps' output."""

    token_of_row: jax.Array     # [M] the token a row computes (0 where none)
    pick_of_row: jax.Array      # [M] its pick, flat over [N, top_k]
    real: jax.Array             # [M] bool: the row computes a pick
    row_of_pick: jax.Array      # [N, top_k] the row of a pick that landed
    landed: jax.Array           # [N, top_k] bool
    tile_expert: jax.Array      # [tiles + 1] held expert of a tile's rows
    live_tiles: jax.Array       # [1] tiles that hold a real row
    group_rows: jax.Array       # [held] rows of each expert, padding and all
    counts: jax.Array           # [held] picks that landed on each expert


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


def plan_rows(picks: jax.Array, experts: Experts, tile: int = TILE) -> Plan:
    """Sort the picks that landed on held experts by expert and give each
    expert whole tiles.  Everything is an index computation on
    ``N * top_k`` integers; nothing is dropped."""
    n, k = picks.shape
    held = experts.held
    local = picks.reshape(-1).astype(jnp.int32) - experts.first_held
    landed = (local >= 0) & (local < held)
    key = jnp.where(landed, local, held)              # elsewhere sorts last
    order = jnp.argsort(key).astype(jnp.int32)        # sorted -> flat pick
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
    row = jnp.arange((tiles + 1) * tile, dtype=jnp.int32)
    e = tile_expert[row // tile]
    within = row - begins[e]
    real = (within < counts[e]) & (row < ends[-1])
    pick_of_row = jnp.where(
        real, order[jnp.clip(starts[e] + within, 0, n * k - 1)], 0)
    e_pick = jnp.minimum(key, held - 1)
    row_of_pick = jnp.where(landed, begins[e_pick] + place - starts[e_pick], 0)
    return Plan(pick_of_row // k, pick_of_row, real,
                row_of_pick.reshape(n, k), landed.reshape(n, k), tile_expert,
                (ends[-1:] // tile).astype(jnp.int32), group_rows, counts)


# ---------------------------------------------------------------------------
# the grouped product
# ---------------------------------------------------------------------------

def _experts_kernel(tile_expert, live_tiles, x_ref, w_ref, o_ref, w_bf16, *,
                    transposed: bool):
    """One grid step: a tile of rows times its expert's matrix, bfloat16
    operands, float32 accumulation.  The matrix is rounded into ``w_bf16``
    when the expert changes, not every step; a dead step does nothing (its
    blocks are the last live step's, so nothing is fetched for it either)."""
    i = pl.program_id(0)

    @pl.when(i < live_tiles[0])
    def _live():
        @pl.when((i == 0)
                 | (tile_expert[i] != tile_expert[jnp.maximum(i - 1, 0)]))
        def _round():
            w_bf16[...] = w_ref[0].astype(w_bf16.dtype)

        o_ref[...] = jax.lax.dot_general(
            x_ref[...].astype(w_bf16.dtype), w_bf16[...],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("transposed", "interpret"))
def _experts_call(x, w, tile_expert, live_tiles, *, transposed: bool,
                  interpret: bool):
    """`_experts_kernel` over [M, K] rows and [E, K, N] matrices ([E, N, K]
    ``transposed``).  Under its own `jit`, as the other kernels of the
    epoch program: traced and lowered once, called once a product."""
    m, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    tiles = tile_expert.shape[0]
    tile = m // tiles

    def last_live(i, live):
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))

    return pl.pallas_call(
        functools.partial(_experts_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda i, te, live: (last_live(i, live), 0)),
                pl.BlockSpec((1,) + w.shape[1:],
                             lambda i, te, live: (te[last_live(i, live)],
                                                  0, 0)),
            ],
            # a dead step's output is the last tile, which nothing reads
            out_specs=pl.BlockSpec(
                (tile, n), lambda i, te, live: (
                    jnp.where(i < live[0], i, tiles - 1), 0)),
            scratch_shapes=[pltpu.VMEM(w.shape[1:], _OPERAND)]),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # an expert's matrix, double-buffered, and its bfloat16 copy
            vmem_limit_bytes=3 * w[0].size * w.dtype.itemsize
            + 16 * 2 ** 20),
        interpret=interpret,
        name="moe_experts_t" if transposed else "moe_experts",
    )(tile_expert, live_tiles, x, w)


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
    """[N, D] -> [M, D]: each row its token's vector."""
    return jnp.take(y, plan.token_of_row, axis=0, mode="clip")


def _sum_picks(rows, plan: Plan, weights=None):
    """[M, D] -> [N, D]: over a token's picks that landed, its rows (times
    their weights).  One gather a pick and one at a time (a `scan`), so that
    no [N, top_k, D] array is made; a row nothing computed is never read
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


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _held_share(y, weights, w_gate_up, w_down, plan: Plan,
                interpret: Optional[bool]):
    return _held_share_fwd(y, weights, w_gate_up, w_down, plan, interpret)[0]


def _hidden(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=1)
    return (jax.nn.relu(gate) * up).astype(_OPERAND)


def _held_share_fwd(y, weights, w_gate_up, w_down, plan, interpret):
    # rounded once here: every product's operands are bfloat16
    x = _rows_of(y.astype(_OPERAND), plan)
    gate_up = grouped_matmul(x, w_gate_up, plan, False, interpret)
    rows = grouped_matmul(_hidden(gate_up), w_down, plan, False, interpret)
    return (_sum_picks(rows, plan, weights),
            (x, gate_up, weights, w_gate_up, w_down, plan))


def _held_share_bwd(interpret, res, d_out):
    """By hand, so that every movement of rows is a gather (autodiff would
    transpose each into a scatter), every cotangent stays float32, and the
    [M, D] rows of the forward need not be kept: a pick's weight meets its
    row only through ``<d_out, row> = <d_out D^T, hidden>``."""
    x, gate_up, weights, w_gate_up, w_down, plan = res
    gate, up = jnp.split(gate_up, 2, axis=1)
    d_rows = _rows_of(d_out.astype(_OPERAND), plan)
    by_down = grouped_matmul(d_rows, w_down, plan, True, interpret)  # [M, F]
    hidden = _hidden(gate_up)
    dots = jnp.where(plan.real, jnp.sum(by_down * hidden, axis=-1), 0.0)
    d_weights = jnp.where(plan.landed, jnp.take(
        dots, plan.row_of_pick.reshape(-1), mode="clip").reshape(
            weights.shape), 0.0)
    w_row = jnp.where(plan.real, jnp.take(
        weights.reshape(-1), plan.pick_of_row, mode="clip"), 0.0)
    d_hidden = by_down * w_row[:, None]
    d_gate_up = jnp.concatenate(
        [jnp.where(gate > 0, d_hidden * up, 0.0),
         d_hidden * jax.nn.relu(gate)], axis=1).astype(_OPERAND)
    d_x = grouped_matmul(d_gate_up, w_gate_up, plan, True, interpret)
    return (_sum_picks(d_x, plan), d_weights.astype(weights.dtype),
            _matrices_grad(x, d_gate_up, plan).astype(w_gate_up.dtype),
            _matrices_grad(hidden, d_rows * w_row[:, None].astype(
                _OPERAND), plan).astype(w_down.dtype), None)


_held_share.defvjp(_held_share_fwd, _held_share_bwd)


def held_experts(y, picks, weights, w_gate_up, w_down, experts: Experts,
                 interpret: Optional[bool] = None):
    """The held experts' share of a ReGLU expert layer.  ``y`` [N, D] the
    layer's normed input (float32), ``picks``/``weights`` [N, top_k] from
    `route`, ``w_gate_up`` [held, D, 2F] (an expert's gate columns, then
    its up columns), ``w_down`` [held, F, D].  Returns ``sum over picks e
    held here of w_e * (relu(y G_e) * (y U_e)) D_e`` [N, D] float32, and the
    picks that landed on each held expert [held].  Differentiable to ``y``,
    the weights and the matrices."""
    plan = plan_rows(picks, experts)
    return (_held_share(y.astype(jnp.float32), weights, w_gate_up, w_down,
                        plan, interpret), plan.counts)
