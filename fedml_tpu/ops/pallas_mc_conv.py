"""Multi-client 2-D convolution Pallas TPU kernels.

The federated north-star trains K clients' ResNet-56 replicas with
PER-CLIENT weights.  jax's conv batching rule lowers a vmapped conv with
batched kernels to a ``feature_group_count=K`` grouped convolution, which
measured ~40% SLOWER than running the K clients sequentially on v5e
(benchmarks/BENCH_NOTES.md round 3) — the one shape XLA handles badly on
this path.  These kernels implement the batched-clients conv directly:

* grid over ``(client, batch-tile)``; each cell builds the im2col patch
  matrix for its tile IN VMEM (9 static shifted copies — the patches never
  touch HBM, which is what sank the XLA-level im2col probe 7x) and runs
  ONE MXU matmul ``[M, kh*kw*Ci] @ [kh*kw*Ci, Co]``, the densest
  contraction available for small-channel convs (Ci=16 -> 144-deep);
* a weight-gradient kernel with the same structure (``xs^T @ g`` per
  kernel tap, accumulated over batch tiles);
* input gradients for stride-1 convs reuse the forward kernel with
  spatially flipped, transposed weights; strided convs fall back to XLA
  for the backward (3 of 57 convs in ResNet-56).

`mc_conv` is the custom-vjp'd entry point; `conv_for_clients` is the
module-level dispatcher (pallas on TPU, interpret in tests, XLA grouped
conv as the documented fallback).

Capability attribution: reference has no analog (CUDA/cuDNN handles small
convs with hand-tuned kernels; `fedml/simulation/sp/fedavg/fedavg_api.py`
trains clients strictly sequentially).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _on_tpu


def _shifted(x, dy: int, dx: int, oh: int, ow: int, sh: int, sw: int):
    """Static (dy, dx)-offset window of a padded [BT, Hp, Wp, C] tile →
    [BT, OH, OW, C].  Stride-2 uses the reshape trick (Mosaic has no
    strided vector loads): take every other row/col of an even-length
    slice."""
    if sh == 1 and sw == 1:
        return x[:, dy:dy + oh, dx:dx + ow, :]
    bt, hp, wp, c = x.shape
    xs = x[:, dy:dy + sh * oh, dx:dx + sw * ow, :]
    if sh > 1:
        xs = xs.reshape(bt, oh, sh, sw * ow, c)[:, :, 0]
    if sw > 1:
        xs = xs.reshape(bt, oh, ow, sw, c)[:, :, :, 0]
    return xs


def _fwd_kernel(x_ref, w_ref, o_ref, patches, *, kh, kw, oh, ow, sh, sw,
                ci, co, bt):
    x = x_ref[0]                                   # [BT, Hp, Wp, Ci]
    m = bt * oh * ow
    for dy in range(kh):
        for dx in range(kw):
            xs = _shifted(x, dy, dx, oh, ow, sh, sw)
            patches[:, (dy * kw + dx) * ci:(dy * kw + dx + 1) * ci] = \
                xs.reshape(m, ci)
    w2 = w_ref[0].reshape(kh * kw * ci, co)
    acc = jnp.dot(patches[:], w2, preferred_element_type=jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype).reshape(bt, oh, ow, co)


def _wgrad_kernel(x_ref, g_ref, o_ref, *, kh, kw, oh, ow, sh, sw, ci, co,
                  bt):
    x = x_ref[0]                                   # [BT, Hp, Wp, Ci]
    g = g_ref[0].reshape(bt * oh * ow, co)         # [M, Co]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    for dy in range(kh):
        for dx in range(kw):
            xs = _shifted(x, dy, dx, oh, ow, sh, sw).reshape(
                bt * oh * ow, ci)
            o_ref[0, dy, dx] += jnp.dot(
                xs.T, g, preferred_element_type=jnp.float32)


def _pick_bt(b: int, hp: int, wp: int, ci: int, kh: int, kw: int,
             oh: int, ow: int) -> int:
    """Largest batch tile whose VMEM working set (x tile + patches +
    f32 accumulator, with last-dim lane padding to 128) stays under a
    ~10 MB budget of the 16 MB VMEM."""
    def pad128(c):
        return ((c + 127) // 128) * 128

    for bt in (b, b // 2, b // 4, b // 8, 1):
        if bt < 1 or b % max(bt, 1):
            continue
        x_bytes = bt * hp * wp * pad128(ci) * 2
        p_bytes = bt * oh * ow * pad128(kh * kw * ci) * 2
        a_bytes = bt * oh * ow * 128 * 4
        if x_bytes + p_bytes + a_bytes < 10 * 2 ** 20:
            return bt
    return 1


@functools.partial(jax.jit, static_argnames=("stride", "interpret"))
def _mc_conv_fwd(x, w, stride: Tuple[int, int] = (1, 1),
                 interpret: bool = False):
    """[K, B, H, W, Ci] x [K, kh, kw, Ci, Co] → [K, B, OH, OW, Co],
    SAME padding."""
    k, b, h, wd, ci = x.shape
    _, kh, kw, _, co = w.shape
    sh, sw = stride
    oh = -(-h // sh)
    ow = -(-wd // sw)
    # SAME padding (matches lax.conv_general_dilated "SAME"); the extra
    # (s-1) rows/cols on the high side feed the strided reshape trick in
    # `_shifted` (sliced but never selected)
    ph = max((oh - 1) * sh + kh - h, 0)
    pw = max((ow - 1) * sw + kw - wd, 0)
    xp = jnp.pad(x, ((0, 0), (0, 0),
                     (ph // 2, ph - ph // 2 + sh - 1),
                     (pw // 2, pw - pw // 2 + sw - 1), (0, 0)))
    hp, wp = xp.shape[2], xp.shape[3]
    bt = _pick_bt(b, hp, wp, ci, kh, kw, oh, ow)
    grid = (k, b // bt)
    kern = functools.partial(_fwd_kernel, kh=kh, kw=kw, oh=oh, ow=ow,
                             sh=sh, sw=sw, ci=ci, co=co, bt=bt)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, hp, wp, ci),
                         lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((1, kh, kw, ci, co),
                         lambda i, j: (i, 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, oh, ow, co),
                               lambda i, j: (i, j, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, b, oh, ow, co), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bt * oh * ow, kh * kw * ci), x.dtype)],
        interpret=interpret,
    )(xp, w)


@functools.partial(jax.jit,
                   static_argnames=("kh", "kw", "stride", "interpret"))
def _mc_conv_wgrad(x, g, kh: int, kw: int,
                   stride: Tuple[int, int] = (1, 1),
                   interpret: bool = False):
    """d/dw of `_mc_conv_fwd`: x [K, B, H, W, Ci], cotangent
    g [K, B, OH, OW, Co] → [K, kh, kw, Ci, Co] (f32)."""
    k, b, h, wd, ci = x.shape
    _, _, oh, ow, co = g.shape
    sh, sw = stride
    ph = max((oh - 1) * sh + kh - h, 0)
    pw = max((ow - 1) * sw + kw - wd, 0)
    xp = jnp.pad(x, ((0, 0), (0, 0),
                     (ph // 2, ph - ph // 2 + sh - 1),
                     (pw // 2, pw - pw // 2 + sw - 1), (0, 0)))
    hp, wp = xp.shape[2], xp.shape[3]
    bt = _pick_bt(b, hp, wp, ci, kh, kw, oh, ow)
    grid = (k, b // bt)
    kern = functools.partial(_wgrad_kernel, kh=kh, kw=kw, oh=oh, ow=ow,
                             sh=sh, sw=sw, ci=ci, co=co, bt=bt)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, hp, wp, ci),
                         lambda i, j: (i, j, 0, 0, 0)),
            pl.BlockSpec((1, bt, oh, ow, co),
                         lambda i, j: (i, j, 0, 0, 0)),
        ],
        # every batch tile j revisits client i's block and accumulates
        out_specs=pl.BlockSpec((1, kh, kw, ci, co),
                               lambda i, j: (i, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, kh, kw, ci, co), jnp.float32),
        interpret=interpret,
    )(xp, g)


# ---------------------------------------------------------------------------
# custom-vjp entry point (the batched-clients conv the bucketed federated
# step calls; gradients stay on the pallas path where profitable)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def mc_conv(x, w, stride: Tuple[int, int] = (1, 1),
            interpret: bool = False):
    """Multi-client conv: x [K, B, H, W, Ci], per-client kernels
    w [K, kh, kw, Ci, Co], SAME padding → [K, B, OH, OW, Co]."""
    return _mc_conv_fwd(x, w, stride=stride, interpret=interpret)


def _mc_fwd_rule(x, w, stride, interpret):
    return _mc_conv_fwd(x, w, stride=stride, interpret=interpret), (x, w)


def _mc_bwd_rule(stride, interpret, res, g):
    x, w = res
    kh, kw = w.shape[1], w.shape[2]
    g = g.astype(x.dtype)
    dw = _mc_conv_wgrad(x, g, kh, kw, stride=stride,
                        interpret=interpret).astype(w.dtype)
    if stride == (1, 1) and kh % 2 == 1 and kw % 2 == 1:
        # dx = conv(g, flip(w)^T) — same kernel, flipped taps, Ci<->Co.
        # SAME forward/backward paddings only coincide for odd stride-1
        # kernels (3x3, 1x1 — all of the zoo's stride-1 convs)
        w_flip = jnp.flip(w, axis=(1, 2)).transpose(0, 1, 2, 4, 3)
        dx = _mc_conv_fwd(g, w_flip, stride=(1, 1),
                          interpret=interpret).astype(x.dtype)
    else:
        # strided or even-kernel transposed conv: let XLA derive it from
        # the equivalent per-client conv formulation (rare cases)
        dx = jax.vmap(
            lambda xk, wk, gk: jax.vjp(
                lambda xx: jax.lax.conv_general_dilated(
                    xx, wk, window_strides=stride, padding="SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC")),
                xk)[1](gk)[0])(x, w, g)
    return dx, dw


mc_conv.defvjp(_mc_fwd_rule, _mc_bwd_rule)


def conv_for_clients(x, w, stride: Tuple[int, int] = (1, 1),
                     impl: Optional[str] = None):
    """Dispatcher for the K-clients conv:

    * ``impl="pallas"`` (or None on TPU) → the pallas kernels;
    * ``impl="interpret"`` (tests off-TPU) → same kernels, interpreter;
    * ``impl="xla"`` → vmapped lax conv (XLA's grouped-conv lowering),
      kept as the measured baseline the kernel must beat.
    """
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    if impl in ("pallas", "interpret"):
        return mc_conv(x, w, stride, impl == "interpret")
    return jax.vmap(
        lambda xk, wk: jax.lax.conv_general_dilated(
            xk, wk, window_strides=stride, padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")))(x, w)
