"""Fused round-epilogue kernel family — ONE HBM pass per leaf.

The round epilogue used to be a chain of separately-materialized
full-model passes: ``agg_stacked`` weighted reduce → ``mix_global``
staleness/server_lr mixing → server-optimizer apply → cast back to the
global's dtype.  Each link reads and writes every parameter in HBM, so
on TPU the epilogue is bandwidth-bound × chain-length.  This module
collapses the chain into one pallas program per leaf:

    [C, ...] stacked client updates ─┐
    [C]      weight/mask vector      ├─► weighted reduce (MXU [1,C]x[C,B])
    [...]    global leaf             │   → staleness/server_lr mix
    [...]    optimizer state (m, v)  ┘   → none|sgd|momentum|adam update
                                         → cast back, all on the VMEM tile

Contracts (shared with the unfused chain, bit-for-bit off TPU):

* weights need not be normalized; weight 0 masks a client out
  (selective aggregation without dynamic shapes).  Normalization is
  ``w / max(Σw, 1e-12)`` — exactly ``agg_stacked``.
* accumulation runs in f32; the reduced leaf is cast back to the STACKED
  leaf's dtype before mixing (``agg_stacked``'s cast-back), then the mix
  runs in f32 and casts to the GLOBAL leaf's dtype (``mix_global``).
  Non-float global leaves take the aggregate as-is.
* the optimizer channel consumes the pseudo-gradient
  ``server_lr · (global − agg)`` and matches optax arithmetic:
  ``sgd``/``momentum`` ≡ ``optax.sgd(lr, momentum)``, ``adam`` ≡
  ``optax.adam(lr, b1, b2, eps)`` — state (m, v, t) threads through the
  call so the whole server step stays inside one jit.

Off-TPU the jnp fallback composes the legacy math verbatim, so CPU
trajectories (CI, reference-parity tests) are unchanged; tests drive the
pallas kernels in interpret mode and assert 1e-6 parity.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

from .pallas_ops import _BLOCK, _on_tpu

#: lane width of the traced-scalar params row (lane dim must be a
#: multiple of 128 on TPU; slots: server_lr, adam bias corrections)
_PARAMS_LANES = 128


class EpilogueSpec(NamedTuple):
    """Static server-optimizer channel of the fused epilogue.

    ``opt``: none | sgd | momentum | adam (anything else — yogi,
    adagrad — stays on the optax fallback outside this module).
    ``lr`` is the server-optimizer step size (FedOpt's ``server_lr``);
    the *mixing* rate is the traced ``server_lr`` argument of
    ``fused_epilogue`` — the two compose (staleness-damped FedOpt scales
    the pseudo-gradient before the optimizer sees it).
    """

    opt: str = "none"
    lr: float = 1.0
    momentum: float = 0.9
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


NONE_SPEC = EpilogueSpec()


def spec_from_args(args: Any) -> Optional[EpilogueSpec]:
    """The fused-channel spec for ``args``'s server optimizer, or None
    when the optimizer has no fused mapping (yogi/adagrad) or the fused
    epilogue is switched off (``fused_epilogue: false``)."""
    if not bool(getattr(args, "fused_epilogue", True)):
        return None
    name = str(getattr(args, "server_optimizer", "adam") or "adam").lower()
    lr = float(getattr(args, "server_lr", 1e-3) or 1e-3)
    if name == "adam":
        return EpilogueSpec(opt="adam", lr=lr)
    if name == "sgd":
        mom = getattr(args, "server_momentum", 0.9)
        if mom:
            return EpilogueSpec(opt="momentum", lr=lr, momentum=float(mom))
        return EpilogueSpec(opt="sgd", lr=lr)
    return None


def init_opt_state(global_tree: Any, spec: EpilogueSpec) -> Optional[Any]:
    """Zero optimizer state matching ``spec`` — f32 moments (optax keeps
    moments in the params dtype; the fused channel deliberately holds
    them in f32, the dtype the kernel accumulates in)."""

    def _zeros(t):
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros(jnp.shape(a), jnp.float32), t)

    if spec.opt == "momentum":
        return {"m": _zeros(global_tree)}
    if spec.opt == "adam":
        return {"m": _zeros(global_tree), "v": _zeros(global_tree),
                "t": jnp.zeros((), jnp.int32)}
    return None


def _norm_weights(weights: jnp.ndarray) -> jnp.ndarray:
    w = weights.astype(jnp.float32)
    return w / jnp.maximum(jnp.sum(w), 1e-12)


def _use_pallas(prefer_pallas: Optional[bool]) -> bool:
    return _on_tpu() if prefer_pallas is None else bool(prefer_pallas)


def _pad_cols(x: jnp.ndarray, dp: int) -> jnp.ndarray:
    pad = dp - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _params_row(*vals) -> jnp.ndarray:
    """Traced scalars (server_lr, adam bias corrections) as one
    [1, _PARAMS_LANES] f32 row replicated into every grid step."""
    row = jnp.zeros((_PARAMS_LANES,), jnp.float32)
    for i, v in enumerate(vals):
        row = row.at[i].set(jnp.asarray(v, jnp.float32))
    return row.reshape(1, _PARAMS_LANES)


# ---------------------------------------------------------------------------
# kernels — one per optimizer channel (pallas refs are positional, so
# each channel gets exactly the refs it reads/writes)
# ---------------------------------------------------------------------------

def _acc_tile(w_ref, x_ref, acc_dtype):
    """The shared reduce head: [1,C]x[C,B] MXU contraction in f32, then
    agg_stacked's cast-back to the stacked dtype (in-register — the
    double rounding is the bit-compatibility contract, not an HBM trip)."""
    acc = jnp.dot(w_ref[:], x_ref[:].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return acc.astype(acc_dtype).astype(jnp.float32)


def _reduce_kernel(w_ref, x_ref, o_ref, *, out_dtype):
    acc = jnp.dot(w_ref[:], x_ref[:].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[:] = acc.astype(out_dtype)


def _mix_kernel(p_ref, w_ref, x_ref, g_ref, o_ref, *, acc_dtype, out_dtype):
    acc = _acc_tile(w_ref, x_ref, acc_dtype)
    gf = g_ref[:].astype(jnp.float32)
    o_ref[:] = (gf + p_ref[0, 0] * (acc - gf)).astype(out_dtype)


def _sgd_kernel(p_ref, w_ref, x_ref, g_ref, o_ref, *,
                lr, acc_dtype, out_dtype):
    acc = _acc_tile(w_ref, x_ref, acc_dtype)
    gf = g_ref[:].astype(jnp.float32)
    grad = p_ref[0, 0] * (gf - acc)
    o_ref[:] = (gf - lr * grad).astype(out_dtype)


def _momentum_kernel(p_ref, w_ref, x_ref, g_ref, m_ref, o_ref, om_ref, *,
                     lr, momentum, acc_dtype, out_dtype):
    acc = _acc_tile(w_ref, x_ref, acc_dtype)
    gf = g_ref[:].astype(jnp.float32)
    grad = p_ref[0, 0] * (gf - acc)
    m = momentum * m_ref[:] + grad
    om_ref[:] = m
    o_ref[:] = (gf - lr * m).astype(out_dtype)


def _adam_kernel(p_ref, w_ref, x_ref, g_ref, m_ref, v_ref,
                 o_ref, om_ref, ov_ref, *,
                 lr, b1, b2, eps, acc_dtype, out_dtype):
    acc = _acc_tile(w_ref, x_ref, acc_dtype)
    gf = g_ref[:].astype(jnp.float32)
    grad = p_ref[0, 0] * (gf - acc)
    m = b1 * m_ref[:] + (1.0 - b1) * grad
    v = b2 * v_ref[:] + (1.0 - b2) * grad * grad
    om_ref[:] = m
    ov_ref[:] = v
    # p[0,1] = 1−b1^t, p[0,2] = 1−b2^t (traced — they change per step)
    mhat = m / p_ref[0, 1]
    vhat = v / p_ref[0, 2]
    o_ref[:] = (gf - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(out_dtype)


def _delta_kernel(p_ref, a_ref, d_ref, o_ref, *, out_dtype):
    o_ref[:] = (a_ref[:].astype(jnp.float32)
                + p_ref[0, 0] * d_ref[:].astype(jnp.float32)
                ).astype(out_dtype)


# ---------------------------------------------------------------------------
# per-leaf drivers
# ---------------------------------------------------------------------------

def _row_spec(dp_cols):
    return pl.BlockSpec((1, dp_cols), lambda i: (0, 0))


def _tile_spec(rows):
    return pl.BlockSpec((rows, _BLOCK), lambda i: (0, i))


def _leaf_pallas_call(kernel, inputs, out_dtypes, dp, interpret):
    """Run ``kernel`` over a [*, dp] leaf tiled on the lane dim.  Inputs
    are (array, rows_or_None) pairs: None rows → whole-row blocks
    replicated per grid step (params/weights); int rows → [rows, _BLOCK]
    tiles walking the lane dim."""
    grid = (dp // _BLOCK,)
    in_specs = []
    ops = []
    for arr, rows in inputs:
        if rows is None:
            in_specs.append(_row_spec(arr.shape[-1]))
        else:
            in_specs.append(_tile_spec(rows))
        ops.append(arr)
    out_specs = tuple(_tile_spec(1) for _ in out_dtypes)
    out_shape = tuple(jax.ShapeDtypeStruct((1, dp), dt) for dt in out_dtypes)
    if len(out_dtypes) == 1:
        out_specs, out_shape = out_specs[0], out_shape[0]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*ops)


def _flatten_leaf(x: jnp.ndarray, lead: int) -> Tuple[jnp.ndarray, int, int]:
    size = int(np.prod(x.shape[lead:])) if x.ndim > lead else 1
    d = max(size, 1)
    dp = d + ((-d) % _BLOCK)
    flat = jnp.asarray(x).reshape((x.shape[0], d) if lead else (1, d))
    return _pad_cols(flat, dp), d, dp


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def _dot_reduce_f32(x: jnp.ndarray, wn: jnp.ndarray) -> jnp.ndarray:
    """f32 weighted reduce over the leading client axis as a dot —
    mirrors the kernels' MXU accumulation (`_acc_tile`'s ``jnp.dot``
    with f32 ``preferred_element_type``); off-TPU, XLA lowers it to the
    threaded gemv instead of materializing an f32 copy of the stacked
    leaf (2.3x the sum-of-products form on the CPU proxy)."""
    flat = x.reshape(x.shape[0], -1)
    acc = jnp.dot(wn, flat, preferred_element_type=jnp.float32)
    return acc.reshape(x.shape[1:])


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def weighted_reduce(stacked: Any, weights: jnp.ndarray, *,
                    interpret: Optional[bool] = None,
                    prefer_pallas: Optional[bool] = None) -> Any:
    """``agg_stacked``'s contract through the kernel family: weighted
    mean over the leading client axis, f32 accumulation, float leaves
    cast back to their dtype (non-float keep the f32 result)."""
    wn = _norm_weights(weights)
    use_pl = _use_pallas(prefer_pallas)
    if interpret is None:
        interpret = not _on_tpu()

    def _leaf(x: jnp.ndarray) -> jnp.ndarray:
        xa = jnp.asarray(x)
        out_dtype = xa.dtype if _is_float(xa) else jnp.float32
        if not use_pl:
            acc = _dot_reduce_f32(xa, wn)
            return acc.astype(out_dtype)
        c = xa.shape[0]
        flat, d, dp = _flatten_leaf(xa, 1)
        out = _leaf_pallas_call(
            functools.partial(_reduce_kernel, out_dtype=out_dtype),
            [(wn.reshape(1, c), None), (flat, c)],
            (out_dtype,), dp, interpret)
        return out.reshape(dp)[:d].reshape(xa.shape[1:])

    return jax.tree_util.tree_map(_leaf, stacked)


def fused_epilogue(global_tree: Any, stacked: Any, weights: jnp.ndarray,
                   server_lr: Any = 1.0, spec: EpilogueSpec = NONE_SPEC,
                   opt_state: Optional[Any] = None, *,
                   interpret: Optional[bool] = None,
                   prefer_pallas: Optional[bool] = None
                   ) -> Tuple[Any, Optional[Any]]:
    """The whole round epilogue in one pass per leaf: weighted reduce →
    ``server_lr`` mix / pseudo-gradient → optimizer channel → cast back.
    Returns ``(new_global, new_opt_state)`` (state is None for the
    stateless channels)."""
    wn = _norm_weights(weights)
    lr32 = jnp.asarray(server_lr, jnp.float32)
    use_pl = _use_pallas(prefer_pallas)
    if interpret is None:
        interpret = not _on_tpu()

    if spec.opt not in ("none", "sgd", "momentum", "adam"):
        raise ValueError(f"unknown epilogue optimizer {spec.opt!r}")

    t_new = None
    bc1 = bc2 = jnp.asarray(1.0, jnp.float32)
    if spec.opt == "adam":
        if opt_state is None:
            raise ValueError("adam epilogue needs opt_state "
                             "(init_opt_state)")
        t_new = opt_state["t"] + 1
        tf = t_new.astype(jnp.float32)
        bc1 = 1.0 - jnp.power(jnp.asarray(spec.b1, jnp.float32), tf)
        bc2 = 1.0 - jnp.power(jnp.asarray(spec.b2, jnp.float32), tf)
    if spec.opt == "momentum" and opt_state is None:
        raise ValueError("momentum epilogue needs opt_state "
                         "(init_opt_state)")
    p_row = _params_row(lr32, bc1, bc2)

    def _reduce_f32(x: jnp.ndarray) -> jnp.ndarray:
        return _dot_reduce_f32(x, wn)

    def _leaf(g, x, m=None, v=None):
        ga, xa = jnp.asarray(g), jnp.asarray(x)
        acc_dtype = xa.dtype if _is_float(xa) else jnp.float32
        if not _is_float(ga):
            # mix_global contract: non-float leaves take the aggregate
            # as-is; the optimizer channel never touches them
            acc = (_reduce_f32(xa).astype(acc_dtype)
                   if not use_pl else None)
            if acc is None:
                c = xa.shape[0]
                flat, d, dp = _flatten_leaf(xa, 1)
                acc = _leaf_pallas_call(
                    functools.partial(_reduce_kernel, out_dtype=acc_dtype),
                    [(wn.reshape(1, c), None), (flat, c)],
                    (acc_dtype,), dp, interpret
                ).reshape(dp)[:d].reshape(xa.shape[1:])
            new_m = m
            new_v = v
            return acc, new_m, new_v
        if not use_pl:
            acc = _reduce_f32(xa).astype(acc_dtype).astype(jnp.float32)
            gf = ga.astype(jnp.float32)
            if spec.opt == "none":
                return (gf + lr32 * (acc - gf)).astype(ga.dtype), m, v
            grad = lr32 * (gf - acc)
            if spec.opt == "sgd":
                return (gf - spec.lr * grad).astype(ga.dtype), m, v
            if spec.opt == "momentum":
                new_m = spec.momentum * m + grad
                return (gf - spec.lr * new_m).astype(ga.dtype), new_m, v
            new_m = spec.b1 * m + (1.0 - spec.b1) * grad
            new_v = spec.b2 * v + (1.0 - spec.b2) * grad * grad
            mhat = new_m / bc1
            vhat = new_v / bc2
            upd = spec.lr * mhat / (jnp.sqrt(vhat) + spec.eps)
            return (gf - upd).astype(ga.dtype), new_m, new_v
        # pallas path — one call per leaf, every channel's state rides
        # the same lane tiling as the model leaf
        c = xa.shape[0]
        flat, d, dp = _flatten_leaf(xa, 1)
        gflat, _, _ = _flatten_leaf(ga, 0)
        common = [(p_row, None), (wn.reshape(1, c), None),
                  (flat, c), (gflat, 1)]
        if spec.opt == "none":
            out = _leaf_pallas_call(
                functools.partial(_mix_kernel, acc_dtype=acc_dtype,
                                  out_dtype=ga.dtype),
                common, (ga.dtype,), dp, interpret)
            return out.reshape(dp)[:d].reshape(ga.shape), m, v
        if spec.opt == "sgd":
            out = _leaf_pallas_call(
                functools.partial(_sgd_kernel, lr=spec.lr,
                                  acc_dtype=acc_dtype, out_dtype=ga.dtype),
                common, (ga.dtype,), dp, interpret)
            return out.reshape(dp)[:d].reshape(ga.shape), m, v
        mflat, _, _ = _flatten_leaf(m, 0)
        if spec.opt == "momentum":
            out, om = _leaf_pallas_call(
                functools.partial(_momentum_kernel, lr=spec.lr,
                                  momentum=spec.momentum,
                                  acc_dtype=acc_dtype, out_dtype=ga.dtype),
                common + [(mflat, 1)], (ga.dtype, jnp.float32), dp,
                interpret)
            return (out.reshape(dp)[:d].reshape(ga.shape),
                    om.reshape(dp)[:d].reshape(ga.shape), v)
        vflat, _, _ = _flatten_leaf(v, 0)
        out, om, ov = _leaf_pallas_call(
            functools.partial(_adam_kernel, lr=spec.lr, b1=spec.b1,
                              b2=spec.b2, eps=spec.eps,
                              acc_dtype=acc_dtype, out_dtype=ga.dtype),
            common + [(mflat, 1), (vflat, 1)],
            (ga.dtype, jnp.float32, jnp.float32), dp, interpret)
        return (out.reshape(dp)[:d].reshape(ga.shape),
                om.reshape(dp)[:d].reshape(ga.shape),
                ov.reshape(dp)[:d].reshape(ga.shape))

    g_leaves, treedef = jax.tree_util.tree_flatten(global_tree)
    x_leaves = treedef.flatten_up_to(stacked)
    if spec.opt in ("none", "sgd"):
        outs = [_leaf(g, x)[0] for g, x in zip(g_leaves, x_leaves)]
        return jax.tree_util.tree_unflatten(treedef, outs), None
    m_leaves = treedef.flatten_up_to(opt_state["m"])
    if spec.opt == "momentum":
        res = [_leaf(g, x, m) for g, x, m in
               zip(g_leaves, x_leaves, m_leaves)]
        new_global = jax.tree_util.tree_unflatten(
            treedef, [r[0] for r in res])
        new_m = jax.tree_util.tree_unflatten(treedef, [r[1] for r in res])
        return new_global, {"m": new_m}
    v_leaves = treedef.flatten_up_to(opt_state["v"])
    res = [_leaf(g, x, m, v) for g, x, m, v in
           zip(g_leaves, x_leaves, m_leaves, v_leaves)]
    new_global = jax.tree_util.tree_unflatten(treedef, [r[0] for r in res])
    new_m = jax.tree_util.tree_unflatten(treedef, [r[1] for r in res])
    new_v = jax.tree_util.tree_unflatten(treedef, [r[2] for r in res])
    return new_global, {"m": new_m, "v": new_v, "t": t_new}


def fold_delta(tree: Any, delta: Any, server_lr: Any, *,
               interpret: Optional[bool] = None,
               prefer_pallas: Optional[bool] = None) -> Any:
    """``tree ← tree + server_lr · delta`` in one pass per leaf — the
    fed_llm adapter fold (f32 add, cast back to the adapter dtype; the
    ``agg_stacked``/``_add_delta_tree`` contract)."""
    lr32 = jnp.asarray(server_lr, jnp.float32)
    use_pl = _use_pallas(prefer_pallas)
    if interpret is None:
        interpret = not _on_tpu()
    p_row = _params_row(lr32)

    def _leaf(a, d):
        aa, da = jnp.asarray(a), jnp.asarray(d)
        if not use_pl:
            return (aa.astype(jnp.float32)
                    + lr32 * da.astype(jnp.float32)).astype(aa.dtype)
        aflat, dsz, dp = _flatten_leaf(aa, 0)
        dflat, _, _ = _flatten_leaf(da, 0)
        out = _leaf_pallas_call(
            functools.partial(_delta_kernel, out_dtype=aa.dtype),
            [(p_row, None), (aflat, 1), (dflat, 1)],
            (aa.dtype,), dp, interpret)
        return out.reshape(dp)[:dsz].reshape(aa.shape)

    return jax.tree_util.tree_map(_leaf, tree, delta)
