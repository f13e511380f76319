"""The gated delta rule: a linear-attention mixer whose memory is one matrix
a head, written and read position by position.

For each value head a state ``S`` [Dk, Dv], zero at the row's start; at
position t, with a decay ``g_t <= 0`` and a writing strength ``beta_t`` (a
head each), a key ``k_t`` and a query ``q_t`` [Dk], a value ``v_t`` [Dv]:

    S~  = exp(g_t) S_{t-1}
    u_t = beta_t (v_t - S~^T k_t)
    S_t = S~ + k_t u_t^T
    o_t = S_t^T q_t

`_recurrence` is that, a `lax.scan` over the positions: the definition, and
what runs off the TPU.  Written so on a TPU it would be T dependent rank-one
updates a head, so the kernels compute it in chunks of C positions (Yang et
al. 2024, "Gated Delta Networks"; the chunked form of the WY representation).
With ``gamma`` the running sum of ``g`` inside a chunk, ``Gamma_ij =
exp(gamma_i - gamma_j)`` for i >= j (a difference, never ``exp(-gamma)``
alone), ``A = strict_lower(diag(beta) (K K^T * Gamma))`` and ``X = (I +
A)^-1``, a chunk that enters with state ``S``:

    V' = X (beta * (V - (K * exp(gamma)) S))
    O  = (Q * exp(gamma)) S + lower(Q K^T * Gamma) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

The kernel ``gdn_fwd`` takes a grid step a (value head, block of chunks): what
the positions of a chunk owe each other (``K K^T``, ``Q K^T``, the decays) is
made for all the block's chunks first, their triangular systems are solved
together, row by row on the vector unit in float32 (forward substitution;
the chunks' chains interleave), and then the state, float32 in VMEM across
the head's blocks, crosses the chunks in order.  Products take operands
rounded to bfloat16 and accumulate in float32; the decays, ``gamma``, the
solve and the state's accumulation are float32.  Scores are held [keys,
queries], as `ops/pallas_attention` holds them.  Key heads may be fewer than
value heads (value head j reads key head j // ratio, through the block
index).  q, k, v and o stay ``[B, T, heads * D]``: a head is a lane block.

The backward, ``gdn_bwd``, walks the blocks and their chunks in reverse from
the state each chunk entered with, which the forward keeps when it is called
for a gradient (``[B * Hv, T / C, Dk, Dv]``, rounded to bfloat16 as the
backward's products take it: 32 KB a chunk and head at 128 x 128, 0.27 GB a
layer of 32 heads at 16,384 positions), makes the chunk's forward again and
spends it in VMEM.
Nothing of ``[T, T]`` reaches HBM in either direction.

The mixer around the rule (`gated_delta_mixer`): what a delta-rule layer does
to rows between its projection ``qkvz`` [B, T, 2 Hk Dk + 2 Hv Dv] and the
rule, and between the rule and its way out, in four kernels more, so that
each array crosses HBM once a direction and autodiff keeps nothing of it.
``gdn_operands_fwd`` takes a block of positions x whole heads straight out of
the projection (its q, k, v columns through the blocks' indices, the 8
positions before the block as a second block: the convolution's halo, zeros
at a row's start) through the causal depthwise convolution (shifted reads
along sublanes), a SiLU and, for q and k, the norm to length 1 over a head's
lanes, and writes q, k, v as ``gdn_fwd`` reads them.  ``gdn_gate_fwd`` takes
the rule's output and the z columns to ``o rsqrt(mean o^2 + eps) scale
silu(z)`` by head.  Backwards ``gdn_gate_bwd`` writes the gradient of o and
the z columns of the projection's gradient, ``gdn_bwd`` a value head's own dq
and dk, and ``gdn_operands_bwd`` makes the convolution's output again in
VMEM, sums a key head's value heads as it reads them, and writes the q, k, v
columns of the same gradient array in place.  All of it float32, a slab of
positions x one head's lanes in registers at a time.  One `custom_vjp`
(`_mixed`) holds the six calls; the gradients of the convolution's weights
and the norm's scale, frozen under LoRA, are XLA's from the definitions
(`plain_operands`, `plain_gate`) and vanish where nothing reads them.

Paths as the other kernels of `fedml_tpu.ops`: on a TPU the kernels (heads
of whole 128-lane tiles; others take the recurrence, and the mixer's
caller its jnp); off it with ``interpret=True`` the same kernels through the
Pallas interpreter; otherwise the recurrence, the jnp and autodiff.
`fedml_delta_rule_traces_total` counts, as calls are traced, which form the
rule took, with which chunk and head size; `fedml_delta_mixer_traces_total`
which each part of the mixer around it took.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.mlops import metrics as _metrics
from ..core.mlops import tracing
from .pallas_ops import _on_tpu

#: what the operands of the kernels' products are rounded to (float32
#: accumulation), as every other product of a float32 program on a TPU
_OPERAND = "bfloat16"
#: positions of a chunk: one triangular system, one step of the state
_CHUNK = 64
#: chunks of a grid step, at most: their solves interleave, and unrolled
#: code grows with them
_SUBS = 4
#: VMEM a grid step may spend on its blocks (q, k, v, o and, in the
#: backward, the chunks' states and five gradients, double-buffered)
_VMEM_BUDGET = 12 * 2 ** 20
_HIGHEST = jax.lax.Precision.HIGHEST
#: added to a head's squared length before q and k are normed by it
_UNIT = 1e-6


def _recurrence(q, k, v, g, beta):
    """The definition: q, k [B, T, Hk, Dk], v [B, T, Hv, Dv], g and beta
    [B, T, Hv] -> o [B, T, Hv, Dv], float32, position by position."""
    ratio = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(z.astype(jnp.float32), ratio, axis=2) for z in (q, k))

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", state, k_t, precision=_HIGHEST))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)

    b, _, h, dk = q.shape
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[3]), jnp.float32),
        tuple(jnp.moveaxis(z.astype(jnp.float32), 1, 0)
              for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


# ---------------------------------------------------------------------------
# what both kernels make of a chunk
# ---------------------------------------------------------------------------

_LAST = (((1,), (1,)), ((), ()))        # a b^T
_FIRST = (((0,), (0,)), ((), ()))       # a^T b


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _masks(c: int):
    """[keys, queries] of a chunk: the diagonal, and the pairs whose query
    comes after its key."""
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return j == i, i > j


def _col(row, eye):
    """[1, C] -> [C, 1]."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """[C, 1] -> [1, C]."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _total(a):
    """[M, N] -> [1, 1]."""
    return jnp.sum(jnp.sum(a, axis=1, keepdims=True), axis=0, keepdims=True)


def _within(q, k, gam_row, beta_row, eye, after, operand):
    """What a chunk's positions owe each other, all [keys, queries]: the
    decay between a key and a later query ``exp(gamma_i - gamma_j)``, ``k_j
    . k_i``, and with them ``A^T`` (strictly after) and ``P^T`` (the
    diagonal too); and ``gamma`` as a column."""
    gam_col = _col(gam_row, eye)
    decay = jnp.where(eye | after,
                      jnp.exp(jnp.minimum(gam_row - gam_col, 0.0)), 0.0)
    kb = k.astype(operand)
    kk = _dot(kb, kb, _LAST)
    a_t = jnp.where(after, kk * decay * beta_row, 0.0)
    p_t = _dot(kb, q.astype(operand), _LAST) * decay
    return gam_col, decay, kk, a_t, p_t


def _solve(a_ts, x_ref, eye):
    """``x_ref[s] = (I + A_s)^-1`` for every chunk s of the block, by forward
    substitution in float32: row i is ``e_i - sum_{j<i} A[i, j] X[j, :]``,
    and ``A[i, :]`` stands as a column in ``A^T``.  Only the sublane tiles
    that hold a row before i are read; the chunks' chains are independent,
    so their rows are taken in turn and overlap."""
    c = eye.shape[0]
    for s in range(len(a_ts)):
        x_ref[s] = eye.astype(jnp.float32)
    for i in range(1, c):
        above = -(-i // 8) * 8
        for s, a_t in enumerate(a_ts):
            x_ref[s, i:i + 1, :] = x_ref[s, i:i + 1, :] - jnp.sum(
                a_t[:above, i:i + 1] * x_ref[s, :above, :], axis=0,
                keepdims=True)


def _through(state, q, k, v, gam_col, beta_col, x, p_t, operand):
    """One chunk from the state it enters with: its output, the state it
    leaves, and what the backward uses again."""
    c = q.shape[0]
    last = gam_col[c - 1:c, :]
    e, d, e_last = jnp.exp(gam_col), jnp.exp(last - gam_col), jnp.exp(last)
    qe, ke, kd = q * e, k * e, k * d
    sb = state.astype(operand)
    vm = v - _dot(ke.astype(operand), sb)
    vn = _dot(x.astype(operand), (beta_col * vm).astype(operand))
    vnb = vn.astype(operand)
    o = _dot(qe.astype(operand), sb) + _dot(p_t.astype(operand), vnb, _FIRST)
    new = e_last * state + _dot(kd.astype(operand), vnb, _FIRST)
    return o, new, (e, d, e_last, qe, ke, kd, sb, vm, vnb)


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, o_ref, *rest,
                    chunk: int, subs: int, keep: bool, operand):
    """One grid step of grid (B x value heads, blocks of ``subs`` chunks).
    ``rest``: the chunks' entering states [1, subs, Dk, Dv] where they are
    kept, then the scratch: the state [Dk, Dv] and the solves [subs, C, C],
    float32."""
    states_ref = rest[0] if keep else None
    s_ref, x_ref = rest[-2:]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)

    eye, after = _masks(chunk)
    rows = [slice(s * chunk, (s + 1) * chunk) for s in range(subs)]
    made = [_within(q_ref[0, r, :], k_ref[0, r, :], gam_ref[0, s:s + 1, :],
                    beta_ref[0, s:s + 1, :], eye, after, operand)
            for s, r in enumerate(rows)]
    _solve([m[3] for m in made], x_ref, eye)
    state = s_ref[...]
    for s, r in enumerate(rows):
        gam_col, _, _, _, p_t = made[s]
        if keep:        # as the backward's products take it
            states_ref[0, s] = state.astype(states_ref.dtype)
        o, state, _ = _through(
            state, q_ref[0, r, :], k_ref[0, r, :], v_ref[0, r, :], gam_col,
            _col(beta_ref[0, s:s + 1, :], eye), x_ref[s], p_t, operand)
        o_ref[0, r, :] = o.astype(o_ref.dtype)
    s_ref[...] = state


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, states_ref,
                    do_ref, dq_ref, dk_ref, dv_ref, dgam_ref, dbeta_ref,
                    ds_ref, x_ref, *, chunk: int, subs: int, operand):
    """One grid step of the same grid walked backwards: the block's chunks
    in reverse, each made again from the state it entered with, the
    gradient of the state it leaves carried in ``ds_ref`` [Dk, Dv].  ``dq``
    and ``dk`` are a value head's own (the heads of a key head's group are
    summed outside); ``dgam`` is the gradient of the running sum."""
    @pl.when(pl.program_id(1) == 0)
    def _start():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    eye, after = _masks(chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    rows = [slice(s * chunk, (s + 1) * chunk) for s in range(subs)]
    made = [_within(q_ref[0, r, :], k_ref[0, r, :], gam_ref[0, s:s + 1, :],
                    beta_ref[0, s:s + 1, :], eye, after, operand)
            for s, r in enumerate(rows)]
    _solve([m[3] for m in made], x_ref, eye)
    d_state = ds_ref[...]
    for s in reversed(range(subs)):
        r = rows[s]
        gam_col, decay, kk, a_t, p_t = made[s]
        q, k, v = q_ref[0, r, :], k_ref[0, r, :], v_ref[0, r, :]
        beta_row = beta_ref[0, s:s + 1, :]
        beta_col = _col(beta_row, eye)
        state = states_ref[0, s]
        xb = x_ref[s].astype(operand)
        _, _, (e, d, e_last, qe, ke, kd, sb, vm, vnb) = _through(
            state, q, k, v, gam_col, beta_col, xb, p_t, operand)
        dob = do_ref[0, r, :].astype(operand)
        d_left = d_state                    # of the state the chunk leaves
        dsb = d_left.astype(operand)
        kdb, keb, qeb = (z.astype(operand) for z in (kd, ke, qe))
        # through O = Qe S + P V' and S' = e_C S + Kd^T V'
        d_vn = _dot(p_t.astype(operand), dob) + _dot(kdb, dsb)
        d_pt = jnp.where(eye | after, _dot(vnb, dob, _LAST), 0.0)
        d_qe = _dot(dob, sb, _LAST)
        d_kd = _dot(vnb, dsb, _LAST)
        # through V' = X R, X = (I + A)^-1: dA = -strict_lower(dR V'^T)
        d_r = _dot(xb, d_vn.astype(operand), _FIRST)
        d_at = jnp.where(after, -_dot(vnb, d_r.astype(operand), _LAST), 0.0)
        d_ksb = (-beta_col * d_r).astype(operand)
        d_ke = _dot(d_ksb, sb, _LAST)
        d_state = (e_last * d_left + _dot(qeb, dob, _FIRST)
                   + _dot(keb, d_ksb, _FIRST))
        # through A = beta K K^T Gamma and P = Q K^T Gamma
        m_tb = (d_at * decay * beta_row).astype(operand)
        n_tb = (d_pt * decay).astype(operand)
        kb, qb = k.astype(operand), q.astype(operand)
        dq_ref[0, r, :] = e * d_qe + _dot(n_tb, kb, _FIRST)
        dk_ref[0, r, :] = (e * d_ke + d * d_kd + _dot(m_tb, kb, _FIRST)
                           + _dot(m_tb, kb) + _dot(n_tb, qb))
        dv_ref[0, r, :] = beta_col * d_r
        # gamma: through the decays between positions, then through the
        # three scalings by position and the state's own decay
        z_t = d_at * a_t + d_pt * p_t
        by_pos = (jnp.sum(d_qe * qe + d_ke * ke - d_kd * kd, axis=1,
                          keepdims=True)
                  - jnp.sum(z_t, axis=1, keepdims=True))
        tail = _total(d_kd * kd) + e_last * _total(
            state.astype(jnp.float32) * d_left)
        dgam_ref[0, s:s + 1, :] = (
            jnp.sum(z_t, axis=0, keepdims=True) + _row(by_pos, eye)
            + jnp.where(lane == chunk - 1, tail, 0.0))
        dbeta_ref[0, s:s + 1, :] = (
            jnp.sum(d_at * kk * decay, axis=0, keepdims=True)
            + _row(jnp.sum(d_r * vm, axis=1, keepdims=True), eye))
    ds_ref[...] = d_state


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------

def _maps(t, hk, hv, bt, backwards: bool):
    """Index maps of a grid (B x Hv, blocks of ``bt`` positions) over q and
    k ``[B, T, Hk * Dk]``, v ``[B, T, Hv * Dv]``, the rows of ``gamma`` and
    ``beta`` ``[B * Hv * blocks, subs, C]`` and the kept states ``[B * Hv,
    T / C, Dk, Dv]``; ``backwards``: the blocks from the last to the first."""
    nt, ratio = t // bt, hv // hk

    def at(i):
        return nt - 1 - i if backwards else i

    return (lambda bh, i: (bh // hv, at(i), bh % hv // ratio),
            lambda bh, i: (bh // hv, at(i), bh % hv),
            lambda bh, i: (bh * nt + at(i), 0, 0),
            lambda bh, i: (bh, at(i), 0, 0))


@functools.partial(jax.jit, static_argnames=(
    "heads", "chunk", "subs", "keep", "operand", "interpret"))
def _gdn_fwd_call(q, k, v, gam, beta, *, heads: Tuple[int, int], chunk: int,
                  subs: int, keep: bool, operand: str, interpret: bool):
    """`_gdn_fwd_kernel` over q, k ``[B, T, Hk * Dk]``, v ``[B, T, Hv *
    Dv]`` and ``gamma`` (the running sum of g inside each chunk), ``beta``
    ``[B * Hv * T / (subs C), subs, C]``: o ``[B, T, Hv * Dv]`` float32 and,
    ``keep``, the state every chunk entered with, rounded as the backward's
    products take it.  Under its own `jit`, as
    the other kernels of an epoch program: traced and lowered once."""
    hk, hv = heads
    b, t, _ = q.shape
    dk, dv = q.shape[2] // hk, v.shape[2] // hv
    bt = chunk * subs
    qk_map, v_map, row_map, state_map = _maps(t, hk, hv, bt, False)
    out_shape = [jax.ShapeDtypeStruct((b, t, hv * dv), jnp.float32)]
    out_specs = [pl.BlockSpec((1, bt, dv), v_map)]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (b * hv, t // chunk, dk, dv), jnp.dtype(operand)))
        out_specs.append(pl.BlockSpec((1, subs, dk, dv), state_map))
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, chunk=chunk, subs=subs, keep=keep,
                          operand=jnp.dtype(operand)),
        grid=(b * hv, t // bt),
        in_specs=[pl.BlockSpec((1, bt, dk), qk_map),
                  pl.BlockSpec((1, bt, dk), qk_map),
                  pl.BlockSpec((1, bt, dv), v_map),
                  pl.BlockSpec((1, subs, chunk), row_map),
                  pl.BlockSpec((1, subs, chunk), row_map)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((subs, chunk, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_fwd",         # the kernel's name in a device trace
    )(q, k, v, gam, beta)


@functools.partial(jax.jit, static_argnames=(
    "heads", "chunk", "subs", "operand", "interpret"))
def _gdn_bwd_call(q, k, v, gam, beta, states, do, *, heads: Tuple[int, int],
                  chunk: int, subs: int, operand: str, interpret: bool):
    """`_gdn_bwd_kernel` over `_gdn_fwd_call`'s operands, the states it kept
    and the cotangent of its output: float32 dq, dk ``[B, T, Hv * Dk]`` (a
    value head's own), dv ``[B, T, Hv * Dv]``, and the gradients of
    ``gamma`` and ``beta`` in their rows' shape."""
    hk, hv = heads
    b, t, _ = q.shape
    dk, dv = q.shape[2] // hk, v.shape[2] // hv
    bt = chunk * subs
    qk_map, v_map, row_map, state_map = _maps(t, hk, hv, bt, True)
    rows = pl.BlockSpec((1, subs, chunk), row_map)
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, chunk=chunk, subs=subs,
                          operand=jnp.dtype(operand)),
        grid=(b * hv, t // bt),
        in_specs=[pl.BlockSpec((1, bt, dk), qk_map),
                  pl.BlockSpec((1, bt, dk), qk_map),
                  pl.BlockSpec((1, bt, dv), v_map), rows, rows,
                  pl.BlockSpec((1, subs, dk, dv), state_map),
                  pl.BlockSpec((1, bt, dv), v_map)],
        out_specs=[pl.BlockSpec((1, bt, dk), v_map),
                   pl.BlockSpec((1, bt, dk), v_map),
                   pl.BlockSpec((1, bt, dv), v_map), rows, rows],
        out_shape=[jax.ShapeDtypeStruct((b, t, hv * dk), jnp.float32),
                   jax.ShapeDtypeStruct((b, t, hv * dk), jnp.float32),
                   jax.ShapeDtypeStruct((b, t, hv * dv), jnp.float32),
                   jax.ShapeDtypeStruct(gam.shape, jnp.float32),
                   jax.ShapeDtypeStruct(gam.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((subs, chunk, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_bwd",
    )(q, k, v, gam, beta, states, do)


def _note_trace(path: str, chunk: int, head_dim: int) -> None:
    """Counts, as a call is traced, which form it took, with which chunk and
    at which head size (docs/OBSERVABILITY.md)."""
    _metrics.counter(
        "fedml_delta_rule_traces_total",
        "gated delta-rule calls traced, by the form, chunk and head size "
        "they took",
        labels=("path", "chunk", "head_dim"),
    ).labels(path=path, chunk=chunk, head_dim=head_dim).inc()


def _tiles(t: int, dk: int, dv: int) -> Tuple[int, int]:
    """(positions of a chunk, chunks of a grid step) for ``t`` positions at
    heads of ``dk`` x ``dv``: `_CHUNK`, or the whole of a shorter row in
    sublanes of 8; as many chunks, up to `_SUBS`, as the row has and as the
    backward's blocks (seven arrays of rows and a state a chunk, all
    double-buffered) leave within `_VMEM_BUDGET`."""
    chunk = min(_CHUNK, -(-t // 8) * 8)
    # q, k and their gradients; v, do and dv; the state it entered with
    a_chunk = 2 * 4 * (chunk * (4 * dk + 3 * dv) + dk * dv)
    return chunk, max(1, min(_SUBS, -(-t // chunk), _VMEM_BUDGET // a_chunk))


def _of_key_heads(z, heads: Tuple[int, int]):
    """[B, T, Hv * D], a value head's own -> [B, T, Hk, D]: the value heads
    of a key head, summed."""
    hk, hv = heads
    return z.reshape(*z.shape[:2], hk, hv // hk, -1).sum(3)


def _chunk_rows(g, beta, chunk: int, subs: int):
    """[B, T, Hv] -> [B * Hv * T / (subs C), subs, C]: the running sum of g
    inside each chunk, and beta."""
    by_chunk = lambda z: jnp.moveaxis(z, 1, 2).reshape(
        z.shape[0], z.shape[2], -1, chunk)
    return (jnp.cumsum(by_chunk(g), axis=-1).reshape(-1, subs, chunk),
            by_chunk(beta).reshape(-1, subs, chunk))


def _by_position(dgam, dbeta, b: int, t: int, chunk: int):
    """`_gdn_bwd_call`'s gradients of ``gamma`` and ``beta``, in their rows'
    shape, as those of g and beta [B, T, Hv]: ``gamma_i`` sums g up to i
    inside its chunk, so ``g_j`` reaches every gamma from j to the chunk's
    end."""
    dgam = dgam.reshape(b, -1, t // chunk, chunk)
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgam, -1), axis=-1), -1)
    return tuple(jnp.moveaxis(z.reshape(b, -1, t), 1, 2)
                 for z in (dg, dbeta))


@functools.lru_cache(maxsize=64)
def _chunked(heads: Tuple[int, int], chunk: int, subs: int, operand: str,
             interpret: bool):
    """The kernels under a `custom_vjp`, over q, k ``[B, T, Hk * Dk]``, v
    ``[B, T, Hv * Dv]``, g and beta ``[B, T, Hv]`` with T whole blocks."""
    hk, hv = heads
    kw = dict(heads=heads, chunk=chunk, subs=subs, operand=operand,
              interpret=interpret)
    rows = functools.partial(_chunk_rows, chunk=chunk, subs=subs)

    @jax.custom_vjp
    def f(q, k, v, g, beta):
        return _gdn_fwd_call(q, k, v, *rows(g, beta), keep=False, **kw)[0]

    def fwd(q, k, v, g, beta):
        gam, bet = rows(g, beta)
        o, states = _gdn_fwd_call(q, k, v, gam, bet, keep=True, **kw)
        return o, (q, k, v, gam, bet, states)

    @tracing.scope("gdn.scan_bwd")
    def bwd(res, do):
        q, k, v, gam, bet, states = res
        b, t, _ = q.shape
        _note_trace("kernel_bwd", chunk, q.shape[2] // hk)
        dq, dk, dv, dgam, dbeta = _gdn_bwd_call(
            q, k, v, gam, bet, states, do.astype(jnp.float32), **kw)
        return (*(_of_key_heads(z, heads).reshape(q.shape) for z in (dq, dk)),
                dv, *_by_position(dgam, dbeta, b, t, chunk))

    f.defvjp(fwd, bwd)
    return f


@tracing.scope("gdn.scan")
def gated_delta_rule(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     g: jnp.ndarray, beta: jnp.ndarray,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """The gated delta rule over whole rows from a zero state.  ``q``, ``k``
    [B, T, Hk, Dk] (as the caller has normed and scaled them), ``v`` [B, T,
    Hv, Dv] with Hv a multiple of Hk (value head j reads key head j // (Hv /
    Hk)), ``g`` (<= 0) and ``beta`` [B, T, Hv]: o [B, T, Hv, Dv] float32.
    Differentiable to all five.  Any T: a row is padded to whole blocks
    with positions that neither write nor decay."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    if hv % hk:
        raise ValueError(f"{hv} value heads over {hk} key heads")
    if interpret is None and _on_tpu() and dk % 128 == 0 and dv % 128 == 0:
        interpret = False
    if interpret is None:
        _note_trace("recurrence", 0, dk)
        return _recurrence(q, k, v, g, beta)
    chunk, subs = _tiles(t, dk, dv)
    _note_trace("interpret" if interpret else "kernel", chunk, dk)
    pad = -t % (chunk * subs)
    flat = [z.astype(jnp.float32).reshape(b, t, -1) for z in (q, k, v)] + [
        g.astype(jnp.float32), beta.astype(jnp.float32)]
    if pad:
        flat = [jnp.pad(z, ((0, 0), (0, pad), (0, 0))) for z in flat]
    o = _chunked((hk, hv), chunk, subs, _OPERAND, bool(interpret))(*flat)
    return (o[:, :t] if pad else o).reshape(b, t, hv, dv)


# ---------------------------------------------------------------------------
# the mixer around the scan: what a delta-rule layer does to rows between
# its projection and the rule, and between the rule and its way out
# ---------------------------------------------------------------------------

def causal_conv(x, w):
    """Depthwise over the channels of ``x`` [B, T, C], causal along T:
    ``c_t = sum_i w[:, i] x_{t - (taps - 1) + i}``, zeros before the row's
    start.  As many shifted multiply-adds as taps, which XLA fuses into one
    pass over ``x``."""
    taps, t = w.shape[1], x.shape[1]
    x = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(x[:, i:i + t] * w[:, i].astype(x.dtype) for i in range(taps))


def unit(x):
    """``x`` over its length, by head."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + _UNIT)


def plain_operands(qkvz, conv, heads: Tuple[int, int]):
    """The definition of the rule's operands: the q, k and v columns of the
    projection ``qkvz`` [B, T, 2 Hk Dk + 2 Hv Dv] through the causal
    convolution ``conv`` [2 Hk Dk + Hv Dv, taps] and a SiLU, q and k normed
    to length 1 by head, q over ``sqrt(Dk)`` besides: q, k [B, T, Hk, Dk],
    v [B, T, Hv, Dv]."""
    hk, hv = heads
    b, t, _ = qkvz.shape
    nv = qkvz.shape[2] - conv.shape[0]
    nq = (conv.shape[0] - nv) // 2
    qkv = jax.nn.silu(causal_conv(qkvz[..., :2 * nq + nv], conv))
    q = unit(qkv[..., :nq].reshape(b, t, hk, nq // hk)) * (nq // hk) ** -0.5
    k = unit(qkv[..., nq:2 * nq].reshape(b, t, hk, nq // hk))
    return q, k, qkv[..., 2 * nq:].reshape(b, t, hv, nv // hv)


def plain_gate(o, qkvz, scale, eps: float):
    """The definition of the gated norm: the rule's output ``o`` [B, T, Hv,
    Dv] RMS-normed by head, times ``scale`` [Dv], times the SiLU of the z
    columns of ``qkvz`` (its last Hv Dv)."""
    z = qkvz[..., qkvz.shape[2] - o.shape[2] * o.shape[3]:].reshape(o.shape)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + eps) * scale
    return o * jax.nn.silu(z)


#: rows of a halo block: one sublane tile, which holds the ``taps - 1``
#: positions before a block (and, in the backward, after it)
_HALO = 8
#: rows the mixer's kernels take through their arithmetic at a time: a slab
#: of a head's lanes stays in registers from its loads to its store
_SLAB = 128
#: positions and lanes of a grid step of the mixer's kernels, at most
_MIXER_ROWS = 512
_MIXER_LANES = 512


def _over_slabs(rows: int, body) -> None:
    """``body(first row, rows)`` over a block's rows, a slab at a time.  The
    first slab and a last short one are written where they stand; the whole
    slabs between them are one loop, so that a kernel's body is traced and
    lowered a few times whatever the block's size (unrolled, 64 slabs x 4
    heads x 3 kinds cost a program 6 s of tracing on the host)."""
    whole = rows // _SLAB
    body(0, min(_SLAB, rows))
    if whole > 1:
        def step(s, carry):
            body(pl.multiple_of(s * _SLAB, _SLAB), _SLAB)
            return carry

        jax.lax.fori_loop(1, whole, step, None)
    if whole and rows % _SLAB:
        body(whole * _SLAB, rows % _SLAB)


def _sum(terms):
    return functools.reduce(operator.add, terms)


def _shifted_sum(wide, w, n: int, first: int, step: int = 1):
    """``sum_i w[i] wide[first + step i:first + step i + n]``: the taps over
    rows (``step`` -1: transposed)."""
    return _sum(w[i:i + 1, :] * wide[first + step * i:first + step * i + n, :]
                for i in range(w.shape[0]))


def _lanes_sum(a):
    return jnp.sum(a, axis=-1, keepdims=True)


def _conv_rows(x_ref, before_ref, lo_ref, w, col, first, after=None):
    """The convolution's output over a block's rows, a slab at a time, for
    the head at lanes ``col``: a function of (first row, rows).  A slab's
    shifted rows are read where they lie in the block; the first slab's, with
    the halo ``before_ref`` (zeros at a row's start), from the scratch
    ``lo_ref``, and the slab behind the block's end, with the halo
    ``after[0]``, from the scratch ``after[1]``."""
    taps, d = w.shape
    rows = x_ref.shape[1]
    n0 = min(_SLAB, rows)
    f32 = jnp.float32
    lo_ref[0:_HALO, 0:d] = jnp.where(
        first, 0.0, before_ref[0, :, col].astype(f32))
    lo_ref[_HALO:_HALO + n0, 0:d] = x_ref[0, 0:n0, col].astype(f32)
    if after is not None:
        after_ref, hi_ref = after
        hi_ref[0:_HALO, 0:d] = x_ref[0, rows - _HALO:rows, col].astype(f32)
        hi_ref[_HALO:2 * _HALO, 0:d] = after_ref[0, :, col].astype(f32)

    def at(r0, n):
        # the slab with the sublane tile before it: its shifted rows are
        # slices of that
        if isinstance(r0, int) and r0 == 0:
            wide = lo_ref[0:_HALO + n, 0:d]
        elif isinstance(r0, int) and r0 == rows:
            wide = hi_ref[0:_HALO + n, 0:d]
        else:
            wide = x_ref[0, pl.ds(r0 - _HALO, _HALO + n), col].astype(f32)
        return _shifted_sum(wide, w, n, _HALO - (taps - 1))

    return at


def _by_kind(nqb: int, run, q, k, v):
    """A grid step's block of lanes is q's, k's or v's by its place among
    the column blocks: ``run`` on the arguments of its kind."""
    j = pl.program_id(2)
    pl.when(j < nqb)(lambda: run(*q))
    pl.when((j >= nqb) & (j < 2 * nqb))(lambda: run(*k))
    pl.when(j >= 2 * nqb)(lambda: run(*v))


def _operands_fwd_kernel(x_ref, before_ref, w_ref, q_ref, k_ref, v_ref,
                         lo_ref, *, dims, nqb: int):
    """One grid step of grid (B, blocks of positions, blocks of the q, k, v
    columns): the block of the projection with the 8 positions before it,
    through the convolution and a SiLU and, a q or a k, normed to length 1
    by head (q over ``sqrt(Dk)`` besides), float32."""
    dk, dv = dims
    first = pl.program_id(1) == 0
    rows, lanes = x_ref.shape[1:]

    def run(o_ref, d, mult):
        for c0 in range(0, lanes, d):
            col = slice(c0, c0 + d)
            conv = _conv_rows(x_ref, before_ref, lo_ref, w_ref[:, col], col,
                              first)

            def slab(r0, n):
                c = conv(r0, n)
                a = c * jax.nn.sigmoid(c)
                if mult is not None:
                    a = a * (jax.lax.rsqrt(_lanes_sum(a * a) + _UNIT) * mult)
                o_ref[0, pl.ds(r0, n), col] = a

            _over_slabs(rows, slab)

    _by_kind(nqb, run, (q_ref, dk, dk ** -0.5), (k_ref, dk, 1.0),
             (v_ref, dv, None))


def _operands_bwd_kernel(x_ref, before_ref, after_ref, w_ref, dq_ref,
                         dq_after, dk_ref, dk_after, dv_ref, dv_after, _,
                         dx_ref, lo_ref, hi_ref, dc_ref, *, dims, nqb: int,
                         ratio: int, t: int):
    """One grid step of the same grid: the convolution's output made again
    over the block and the 8 positions behind it, the gradients of q, k
    (the value heads of a key head summed as they are read) or v taken
    through the norm and the SiLU to the convolution's output, kept in
    ``dc_ref``, and from there through the taps to the block of the
    projection's gradient.  Positions at or past ``t`` give nothing."""
    dk, dv = dims
    at = pl.program_id(1)
    rows, lanes = x_ref.shape[1:]
    taps = w_ref.shape[0]

    def run(dy_ref, dy_after, d, mult):
        for c0 in range(0, lanes, d):
            col = slice(c0, c0 + d)
            w = w_ref[:, col]
            conv = _conv_rows(x_ref, before_ref, lo_ref, w, col, at == 0,
                              (after_ref, hi_ref))

            def to_conv(r0, n, src, base):
                """The gradient at the convolution's output, rows ``r0`` on:
                those of q, k or v from ``src``'s rows ``base`` on."""
                c = conv(r0, n)
                sig = jax.nn.sigmoid(c)
                if mult is None:
                    da = src[0, pl.ds(base, n), col]
                else:
                    dy = _sum(src[0, pl.ds(base, n),
                                  c0 * ratio + m * d:c0 * ratio + (m + 1) * d]
                              for m in range(ratio)) * mult
                    a = c * sig
                    r = jax.lax.rsqrt(_lanes_sum(a * a) + _UNIT)
                    u = a * r
                    da = r * (dy - u * _lanes_sum(dy * u))
                dc = da * (sig * (1.0 + c * (1.0 - sig)))
                if t % rows or src is dy_after:
                    row = at * rows + r0 + jax.lax.broadcasted_iota(
                        jnp.int32, (n, 1), 0)
                    dc = jnp.where(row < t, dc, 0.0)
                dc_ref[pl.ds(r0, n), 0:d] = dc

            def through_taps(r0, n):
                wide = dc_ref[pl.ds(r0, n + _HALO), 0:d]
                dx_ref[0, pl.ds(r0, n), col] = _shifted_sum(
                    wide, w, n, taps - 1, -1).astype(dx_ref.dtype)

            _over_slabs(rows, lambda r0, n: to_conv(r0, n, dy_ref, r0))
            to_conv(rows, _HALO, dy_after, 0)
            _over_slabs(rows, through_taps)

    _by_kind(nqb, run, (dq_ref, dq_after, dk, dk ** -0.5),
             (dk_ref, dk_after, dk, 1.0), (dv_ref, dv_after, dv, None))


def _gate_fwd_kernel(o_ref, z_ref, s_ref, y_ref, *, d: int, eps: float):
    """One grid step of grid (B, blocks of positions, blocks of the value
    heads' lanes): ``o`` RMS-normed by head, times the scale and
    ``silu(z)``, float32."""
    rows, lanes = o_ref.shape[1:]
    for c0 in range(0, lanes, d):
        col = slice(c0, c0 + d)

        def slab(r0, n):
            at = pl.ds(r0, n)
            o, z = o_ref[0, at, col], z_ref[0, at, col].astype(jnp.float32)
            r = jax.lax.rsqrt(_lanes_sum(o * o) * (1.0 / d) + eps)
            y_ref[0, at, col] = o * r * s_ref[...] * (z * jax.nn.sigmoid(z))

        _over_slabs(rows, slab)


def _gate_bwd_kernel(o_ref, z_ref, s_ref, dy_ref, do_ref, dz_ref, *, d: int,
                     eps: float):
    """The same grid step backwards: from ``o``, ``z`` and the cotangent of
    the gated output to the gradients of both."""
    rows, lanes = o_ref.shape[1:]
    for c0 in range(0, lanes, d):
        col = slice(c0, c0 + d)

        def slab(r0, n):
            at = pl.ds(r0, n)
            o, z = o_ref[0, at, col], z_ref[0, at, col].astype(jnp.float32)
            dy = dy_ref[0, at, col] * s_ref[...]
            r = jax.lax.rsqrt(_lanes_sum(o * o) * (1.0 / d) + eps)
            u, sig = o * r, jax.nn.sigmoid(z)
            dn = dy * (z * sig)
            do_ref[0, at, col] = r * (
                dn - u * (_lanes_sum(dn * u) * (1.0 / d)))
            dz_ref[0, at, col] = (
                dy * u * (sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)

        _over_slabs(rows, slab)


def _columns(qkvz, wt):
    """(q's or k's columns, v's or z's) of a projection and its taps."""
    nv = qkvz.shape[2] - wt.shape[1]
    return (wt.shape[1] - nv) // 2, nv


_MIXER_STATIC = ("heads", "rows", "lanes", "interpret")


@functools.partial(jax.jit, static_argnames=_MIXER_STATIC)
def _operands_fwd_call(qkvz, wt, *, heads: Tuple[int, int], rows: int,
                       lanes: int, interpret: bool):
    """`_operands_fwd_kernel` over the projection ``qkvz`` [B, T, 2 Hk Dk +
    2 Hv Dv], read where it lies (its q, k and v columns through the
    blocks' indices, the positions before a block as a second block of 8
    rows), and the taps ``wt`` [taps, 2 Hk Dk + Hv Dv] float32: q, k [B, T,
    Hk Dk] and v [B, T, Hv Dv] float32, a head a lane block, as `gdn_fwd`
    reads them.  An output's block stays where it is while the grid is at
    another kind's columns.  Under its own `jit`, as the scan's calls."""
    b, t, _ = qkvz.shape
    nq, nv = _columns(qkvz, wt)
    nqb, nvb, per = nq // lanes, nv // lanes, rows // _HALO

    def out(first, blocks):
        return pl.BlockSpec((1, rows, lanes), lambda b_, i, j: (
            b_, i, jnp.clip(j - first, 0, blocks - 1)))

    return pl.pallas_call(
        functools.partial(_operands_fwd_kernel, nqb=nqb,
                          dims=(nq // heads[0], nv // heads[1])),
        grid=(b, pl.cdiv(t, rows), 2 * nqb + nvb),
        in_specs=[pl.BlockSpec((1, rows, lanes), lambda b_, i, j: (b_, i, j)),
                  pl.BlockSpec((1, _HALO, lanes), lambda b_, i, j: (
                      b_, jnp.maximum(i * per - 1, 0), j)),
                  pl.BlockSpec((wt.shape[0], lanes), lambda b_, i, j: (0, j))],
        out_specs=[out(0, nqb), out(nqb, nqb), out(2 * nqb, nvb)],
        out_shape=[jax.ShapeDtypeStruct((b, t, n), jnp.float32)
                   for n in (nq, nq, nv)],
        scratch_shapes=[pltpu.VMEM((_HALO + _SLAB, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="gdn_operands_fwd",
    )(qkvz, qkvz, wt)


@functools.partial(jax.jit, static_argnames=_MIXER_STATIC)
def _operands_bwd_call(qkvz, wt, dq, dk, dv, d_qkvz, *,
                       heads: Tuple[int, int], rows: int, lanes: int,
                       interpret: bool):
    """`_operands_bwd_kernel` over `_operands_fwd_call`'s operands and the
    gradients of its outputs as `gdn_bwd` leaves them (dq, dk [B, T, Hv Dk],
    a value head's own; dv [B, T, Hv Dv]): the q, k and v columns of
    ``d_qkvz`` [B, T, 2 Hk Dk + 2 Hv Dv], written in place beside the z
    columns that `_gate_bwd_call` left there."""
    b, t, _ = qkvz.shape
    nq, nv = _columns(qkvz, wt)
    nqb, nvb, per = nq // lanes, nv // lanes, rows // _HALO
    ratio, last = heads[1] // heads[0], pl.cdiv(t, _HALO) - 1

    def before(b_, i, j):
        return b_, jnp.maximum(i * per - 1, 0), j

    def behind(col):
        return lambda b_, i, j: (b_, jnp.minimum((i + 1) * per, last),
                                 col(j))

    def both(width, col):
        return [pl.BlockSpec((1, rows, width),
                             lambda b_, i, j: (b_, i, col(j))),
                pl.BlockSpec((1, _HALO, width), behind(col))]

    def kind(first, blocks):
        return lambda j: jnp.clip(j - first, 0, blocks - 1)

    return pl.pallas_call(
        functools.partial(_operands_bwd_kernel, nqb=nqb, ratio=ratio, t=t,
                          dims=(nq // heads[0], nv // heads[1])),
        grid=(b, pl.cdiv(t, rows), 2 * nqb + nvb),
        in_specs=[pl.BlockSpec((1, rows, lanes), lambda b_, i, j: (b_, i, j)),
                  pl.BlockSpec((1, _HALO, lanes), before),
                  pl.BlockSpec((1, _HALO, lanes), behind(lambda j: j)),
                  pl.BlockSpec((wt.shape[0], lanes), lambda b_, i, j: (0, j)),
                  *both(lanes * ratio, kind(0, nqb)),
                  *both(lanes * ratio, kind(nqb, nqb)),
                  *both(lanes, kind(2 * nqb, nvb)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, lanes), lambda b_, i, j: (b_, i, j)),
        out_shape=jax.ShapeDtypeStruct(d_qkvz.shape, d_qkvz.dtype),
        input_output_aliases={10: 0},
        scratch_shapes=[pltpu.VMEM((_HALO + _SLAB, lanes), jnp.float32),
                        pltpu.VMEM((2 * _HALO, lanes), jnp.float32),
                        pltpu.VMEM((rows + _HALO, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="gdn_operands_bwd",
    )(qkvz, qkvz, qkvz, wt, dq, dq, dk, dk, dv, dv, d_qkvz)


def _gate_specs(qkvz, nv, rows, lanes):
    """(grid's blocks of lanes, the block of ``o``, the block of the z
    columns of the projection)."""
    first = (qkvz.shape[2] - nv) // lanes
    return (nv // lanes,
            pl.BlockSpec((1, rows, lanes), lambda b_, i, j: (b_, i, j)),
            pl.BlockSpec((1, rows, lanes), lambda b_, i, j: (b_, i,
                                                             first + j)))


@functools.partial(jax.jit, static_argnames=_MIXER_STATIC + ("eps",))
def _gate_fwd_call(o, qkvz, scale, *, heads: Tuple[int, int], eps: float,
                   rows: int, lanes: int, interpret: bool):
    """`_gate_fwd_kernel` over the rule's output ``o`` [B, T, Hv Dv], the z
    columns of ``qkvz`` where they lie, and ``scale`` [1, Dv]: the gated
    output [B, T, Hv Dv] float32."""
    b, t, nv = o.shape
    blocks, here, z = _gate_specs(qkvz, nv, rows, lanes)
    d = nv // heads[1]
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, d=d, eps=eps),
        grid=(b, pl.cdiv(t, rows), blocks),
        in_specs=[here, z, pl.BlockSpec((1, d), lambda b_, i, j: (0, 0))],
        out_specs=here,
        out_shape=jax.ShapeDtypeStruct(o.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="gdn_gate_fwd",
    )(o, qkvz, scale)


@functools.partial(jax.jit, static_argnames=_MIXER_STATIC + ("eps",))
def _gate_bwd_call(o, qkvz, scale, dy, *, heads: Tuple[int, int], eps: float,
                   rows: int, lanes: int, interpret: bool):
    """`_gate_bwd_kernel` over `_gate_fwd_call`'s operands and the cotangent
    of its output: the gradient of ``o``, and the projection's gradient
    [B, T, 2 Hk Dk + 2 Hv Dv] with its z columns written; the others are
    `_operands_bwd_call`'s to write."""
    b, t, nv = o.shape
    blocks, here, z = _gate_specs(qkvz, nv, rows, lanes)
    d = nv // heads[1]
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, d=d, eps=eps),
        grid=(b, pl.cdiv(t, rows), blocks),
        in_specs=[here, z, pl.BlockSpec((1, d), lambda b_, i, j: (0, 0)),
                  here],
        out_specs=[here, z],
        out_shape=[jax.ShapeDtypeStruct(o.shape, jnp.float32),
                   jax.ShapeDtypeStruct(qkvz.shape, qkvz.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="gdn_gate_bwd",
    )(o, qkvz, scale, dy)


# ---------------------------------------------------------------------------
# the mixer's kernels and the scan's under one `custom_vjp`
# ---------------------------------------------------------------------------

def _note_mixer(part: str, path: str) -> None:
    """Counts, as a call is traced, which form a part of the mixer around
    the scan took (docs/OBSERVABILITY.md)."""
    _metrics.counter(
        "fedml_delta_mixer_traces_total",
        "calls of the delta-rule mixer's passes around the scan traced, by "
        "the part and the form it took",
        labels=("part", "path"),
    ).labels(part=part, path=path).inc()


def _mixer_lanes(heads: Tuple[int, int], dims: Tuple[int, int]
                 ) -> Optional[int]:
    """Lanes of a grid step of the mixer's kernels: the most whole heads up
    to `_MIXER_LANES` lanes that tile the q (and k) columns and the v (and
    z) columns alike; None where no block does."""
    (hk, hv), (dk, dv) = heads, dims
    head = dk * dv // math.gcd(dk, dv)
    return max((n for n in range(head, _MIXER_LANES + 1, head)
                if (hk * dk) % n == 0 and (hv * dv) % n == 0), default=None)


def _mixer_rows(t: int, lanes_held: int) -> int:
    """Positions of a grid step that holds ``lanes_held`` lanes of them in
    float32 blocks, double-buffered: a power of two of sublane tiles within
    `_VMEM_BUDGET`, up to `_MIXER_ROWS` and the row's own length."""
    fit = max(_HALO, _VMEM_BUDGET // (2 * 4 * lanes_held))
    return min(_MIXER_ROWS, 1 << fit.bit_length() - 1, -(-t // 8) * 8)


@functools.lru_cache(maxsize=64)
def _mixed(heads: Tuple[int, int], eps: float, chunk: int, subs: int,
           t: int, lanes: int, operand: str, interpret: bool):
    """The mixer from its projection to its gated output under one
    `custom_vjp`, over ``qkvz`` [B, T, 2 Hk Dk + 2 Hv Dv], g and beta [B, T,
    Hv] float32, the convolution [2 Hk Dk + Hv Dv, taps] and the gated
    norm's scale [Dv]: `_operands_fwd_call`, `_gdn_fwd_call`,
    `_gate_fwd_call`, and backwards `_gate_bwd_call`, `_gdn_bwd_call`,
    `_operands_bwd_call`.  One, not three, because the projection's gradient
    is then one array that two kernels write (a sum of two cotangents would
    be a pass over it), and a value head's own dq and dk go to the kernel
    that sums them as it reads.  Kept for the backward: the projection, q,
    k, v, o and the scan's rows and states; nothing of the convolution."""
    hk, hv = heads
    scan = dict(heads=heads, chunk=chunk, subs=subs, operand=operand,
                interpret=interpret)
    path = "interpret" if interpret else "kernel"

    def tile(blocks):
        """A grid step that holds ``blocks`` blocks of ``lanes`` lanes."""
        return dict(heads=heads, rows=_mixer_rows(t, blocks * lanes),
                    lanes=lanes, interpret=interpret)

    def whole(t, *arrays):
        """Rows padded to whole blocks of the scan with positions that
        neither write nor decay."""
        pad = -t % (chunk * subs)
        return [jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z
                for z in arrays]

    def taps(conv):
        return conv.T.astype(jnp.float32)

    def lane_row(scale):
        return scale.astype(jnp.float32).reshape(1, -1)

    def forward(qkvz, g, beta, conv, scale, keep):
        with tracing.scope("gdn.conv"):
            _note_mixer("operands", path)
            q, k, v = _operands_fwd_call(qkvz, taps(conv), **tile(4))
        with tracing.scope("gdn.scan"):
            q, k, v, g, beta = whole(t, q, k, v, g, beta)
            gam, bet = _chunk_rows(g, beta, chunk, subs)
            o, *states = _gdn_fwd_call(q, k, v, gam, bet, keep=keep, **scan)
            o = o[:, :t]
        with tracing.scope("gdn.out"):
            _note_mixer("gate", path)
            y = _gate_fwd_call(o, qkvz, lane_row(scale), eps=eps,
                               **tile(3))
        return y, (qkvz, conv, scale, q, k, v, gam, bet, *states, o)

    @jax.custom_vjp
    def f(qkvz, g, beta, conv, scale):
        return forward(qkvz, g, beta, conv, scale, False)[0]

    def fwd(qkvz, g, beta, conv, scale):
        return forward(qkvz, g, beta, conv, scale, True)

    def bwd(res, dy):
        qkvz, conv, scale, q, k, v, gam, bet, states, o = res
        b = qkvz.shape[0]
        dy = dy.astype(jnp.float32)
        by_head = lambda z, h: z.reshape(b, t, h, -1)
        with tracing.scope("gdn.out"):
            _note_mixer("gate_bwd", path)
            do, d_qkvz = _gate_bwd_call(o, qkvz, lane_row(scale), dy,
                                        eps=eps, **tile(5))
            # frozen under LoRA: XLA drops it where nothing reads it
            d_scale, = jax.vjp(lambda s: plain_gate(
                by_head(o, hv), qkvz, s, eps), scale)[1](by_head(dy, hv))
        with tracing.scope("gdn.scan_bwd"):
            _note_trace("kernel_bwd", chunk, q.shape[2] // hk)
            dq, dk, dv, dgam, dbeta = _gdn_bwd_call(
                q, k, v, gam, bet, states, *whole(t, do), **scan)
            dq, dk, dv, dg, dbeta = (z[:, :t] for z in (
                dq, dk, dv, *_by_position(dgam, dbeta, b, q.shape[1], chunk)))
        with tracing.scope("gdn.conv"):
            _note_mixer("operands_bwd", path)
            d_qkvz = _operands_bwd_call(qkvz, taps(conv), dq, dk, dv, d_qkvz,
                                        **tile(3 + 2 * (hv // hk)))
            # the same: the value heads of a key head summed for it in XLA
            d_conv, = jax.vjp(lambda w: plain_operands(qkvz, w, heads), conv)[
                1]((_of_key_heads(dq, heads), _of_key_heads(dk, heads),
                    by_head(dv, hv)))
        return d_qkvz, dg, dbeta, d_conv, d_scale

    f.defvjp(fwd, bwd)
    return f


def gated_delta_mixer(qkvz: jnp.ndarray, g: jnp.ndarray, beta: jnp.ndarray,
                      conv: jnp.ndarray, scale: jnp.ndarray, *,
                      heads: Tuple[int, int], eps: float,
                      interpret: Optional[bool] = None
                      ) -> Optional[jnp.ndarray]:
    """A delta-rule mixer between its projection and its way out, in
    kernels: the q, k and v columns of ``qkvz`` [B, T, 2 Hk Dk + 2 Hv Dv]
    through the convolution, the SiLU and the norms of q and k
    (`plain_operands`), the gated delta rule under ``g`` and ``beta`` [B, T,
    Hv], its output through the gated norm with the z columns
    (`plain_gate`): [B, T, Hv Dv] float32, differentiable to all five.
    Each array crosses HBM once a direction.  **None where no kernel runs**
    (off the TPU without ``interpret``; heads that are no whole 128-lane
    tiles or a projection that is not float32 on it; more taps than a halo
    holds; no block of whole heads that tiles the columns): the caller's
    jnp then, and `fedml_delta_mixer_traces_total` says so."""
    hk, hv = heads
    if hv % hk:
        raise ValueError(f"{hv} value heads over {hk} key heads")
    t = qkvz.shape[1]
    nv = qkvz.shape[2] - conv.shape[0]
    dk, dv = (conv.shape[0] - nv) // 2 // hk, nv // hv
    if (interpret is None and _on_tpu() and dk % 128 == 0 and dv % 128 == 0
            and qkvz.dtype == jnp.float32):
        interpret = False
    lanes = _mixer_lanes(heads, (dk, dv))
    if interpret is None or lanes is None or conv.shape[1] - 1 > _HALO:
        for part in ("operands", "gate"):
            _note_mixer(part, "jnp")
        return None
    chunk, subs = _tiles(t, dk, dv)
    _note_trace("interpret" if interpret else "kernel", chunk, dk)
    return _mixed(heads, float(eps), chunk, subs, t, lanes, _OPERAND,
                  bool(interpret))(
        qkvz, g.astype(jnp.float32), beta.astype(jnp.float32), conv, scale)
