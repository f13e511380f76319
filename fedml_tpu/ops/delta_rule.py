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

Paths as the other kernels of `fedml_tpu.ops`: on a TPU the kernels (heads
of whole 128-lane tiles; others take the recurrence); off it with
``interpret=True`` the same kernels through the Pallas interpreter; otherwise
the recurrence and autodiff.  `fedml_delta_rule_traces_total` counts, as
calls are traced, which form ran, with which chunk and head size.
"""

from __future__ import annotations

import functools
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


@functools.lru_cache(maxsize=64)
def _chunked(heads: Tuple[int, int], chunk: int, subs: int, operand: str,
             interpret: bool):
    """The kernels under a `custom_vjp`, over q, k ``[B, T, Hk * Dk]``, v
    ``[B, T, Hv * Dv]``, g and beta ``[B, T, Hv]`` with T whole blocks."""
    hk, hv = heads
    kw = dict(heads=heads, chunk=chunk, subs=subs, operand=operand,
              interpret=interpret)

    def rows(g, beta):
        """[B, T, Hv] -> [B * Hv * T / (subs C), subs, C]: the running sum
        of g inside each chunk, and beta."""
        by_chunk = lambda z: jnp.moveaxis(z, 1, 2).reshape(
            z.shape[0], hv, -1, chunk)
        return (jnp.cumsum(by_chunk(g), axis=-1).reshape(-1, subs, chunk),
                by_chunk(beta).reshape(-1, subs, chunk))

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

        def of_key_heads(z):        # the value heads of a key head, summed
            return z.reshape(b, t, hk, hv // hk, -1).sum(3).reshape(q.shape)

        def by_position(z):
            return jnp.moveaxis(z.reshape(b, hv, t), 1, 2)

        # gamma_i sums g up to i inside its chunk: g_j reaches every gamma
        # from j to the chunk's end
        dgam = dgam.reshape(b, hv, t // chunk, chunk)
        dg = jnp.flip(jnp.cumsum(jnp.flip(dgam, -1), axis=-1), -1)
        return (of_key_heads(dq), of_key_heads(dk), dv, by_position(dg),
                by_position(dbeta))

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
