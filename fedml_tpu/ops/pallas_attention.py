"""Flash-attention Pallas TPU kernel.

The long-context path (`parallel/ring_attention.py`, `parallel/ulysses.py`,
the transformer/ViT zoo and the LLM engine) computes attention per shard.
XLA materializes the full [T, T] score matrix in HBM for the naive einsum
formulation; this kernel runs the online-softmax (flash) recurrence with the
score block resident in VMEM, so HBM traffic stays O(T·D) — the standard
TPU treatment of the one genuinely bandwidth-bound matmul-adjacent op
(/opt/skills/guides/pallas_guide.md).

Semantics match `parallel.ring_attention.reference_attention` exactly
(same masking convention).  Dispatch:

* on TPU → the pallas kernel;
* off TPU with ``interpret=True`` (tests) → the same kernel through the
  pallas interpreter;
* otherwise → a jnp fallback with identical math.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _on_tpu

NEG_INF = -1e30


def _reference(q, k, v, causal):
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t = q.shape[2]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, o_acc, l_acc, m_acc, *,
                  block_q: int, block_k: int, t_valid: int, causal: bool,
                  scale: float, nk: int):
    """Grid (BH, nq, nk), k innermost: VMEM scratch carries the
    online-softmax accumulators across k steps, so only one [bq, D] q tile
    and one [bk, D] k/v tile are VMEM-resident at a time (scales to any T)."""
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        l_acc[:] = jnp.zeros_like(l_acc)
        m_acc[:] = jnp.full_like(m_acc, NEG_INF)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    # blocks fully above the causal diagonal contribute nothing — skip the
    # compute (their DMA still happens; grid steps can't be elided)
    live = (j * block_k <= qi * block_q + block_q - 1) if causal else (j >= 0)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale                # [bq, D]
        k_blk = k_ref[0].astype(jnp.float32)                    # [bk, D]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bq, bk]
        mask = k_pos < t_valid                                  # pad keys out
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m = m_acc[:]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - new_m)
        l_acc[:] = l_acc[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o_acc[:] = o_acc[:] * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_acc[:] = new_m

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (o_acc[:] / jnp.maximum(l_acc[:], 1e-12)).astype(
            o_ref.dtype)


def _flash_kernel_residuals(q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
                            o_acc, l_acc, m_acc, *, block_q: int,
                            block_k: int, t_valid: int, causal: bool,
                            scale: float, nk: int):
    """Same as `_flash_kernel` but also emits the softmax residuals
    (row sum l and row max m) so partial results over disjoint key sets can
    be merged exactly (`merge_attention_partials`) — the ring-attention
    building block."""
    j = pl.program_id(2)
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, o_acc, l_acc, m_acc,
                  block_q=block_q, block_k=block_k, t_valid=t_valid,
                  causal=causal, scale=scale, nk=nk)

    @pl.when(j == nk - 1)
    def _emit_residuals():
        l_ref[0] = l_acc[:]
        m_ref[0] = m_acc[:]


def _reference_residuals(q, k, v, causal, t_valid=None):
    """jnp fallback for `flash_attention_residuals` — identical math."""
    t, tk = q.shape[2], k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.ones((t, tk), bool)
    if t_valid is not None and t_valid < tk:
        mask = mask & (jnp.arange(tk)[None, :] < t_valid)
    if causal:
        mask = mask & (jnp.arange(t)[:, None] >= jnp.arange(tk)[None, :])
    s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    e = jnp.exp(s - m[..., None])
    e = jnp.where(mask[None, None], e, 0.0)
    l = jnp.sum(e, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", e, v.astype(jnp.float32))
    o = (o / jnp.maximum(l[..., None], 1e-12)).astype(q.dtype)
    return o, l, m


def merge_attention_partials(a, b):
    """Merge two attention partials (o, l, m) computed over DISJOINT key
    sets for the same queries (o normalized per-partial, l the softmax sum
    in the m-shifted frame, m the row max).  Exact — the flash combine."""
    o_a, l_a, m_a = a
    o_b, l_b, m_b = b
    new_m = jnp.maximum(m_a, m_b)
    w_a = l_a * jnp.exp(m_a - new_m)
    w_b = l_b * jnp.exp(m_b - new_m)
    l = w_a + w_b
    denom = jnp.maximum(l, 1e-12)[..., None]
    o = (o_a.astype(jnp.float32) * w_a[..., None]
         + o_b.astype(jnp.float32) * w_b[..., None]) / denom
    return o.astype(o_a.dtype), l, new_m


def flash_attention_residuals(q: jnp.ndarray, k: jnp.ndarray,
                              v: jnp.ndarray, causal: bool = True,
                              block_q: int = 128, block_k: int = 128,
                              interpret: Optional[bool] = None,
                              t_valid: Optional[int] = None):
    """Like `flash_attention` but also returns the softmax residuals
    (l, m) [B, H, T] so callers can merge partial attentions over disjoint
    key sets (`merge_attention_partials`) — the ring-attention block op.
    Requires block-aligned lengths (ring blocks are); the key length may
    differ from the query length for non-causal partials."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    if t_valid is None:
        t_valid = tk
    if interpret is None:
        if not _on_tpu():
            return _reference_residuals(q, k, v, causal, t_valid)
        interpret = False

    block_q = min(block_q, max(t, 1))
    block_k = min(block_k, max(tk, 1))
    if t % block_q or tk % block_k or (causal and tk != t):
        return _reference_residuals(q, k, v, causal, t_valid)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    nk = tk // block_k
    kernel = functools.partial(
        _flash_kernel_residuals, block_q=block_q, block_k=block_k,
        t_valid=t_valid, causal=causal, scale=1.0 / float(d) ** 0.5, nk=nk)
    out, l, m = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bi, i, j: (bi, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bi, i, j: (bi, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bi, i, j: (bi, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
        # the kernel's name in a device trace; the blockwise backward is
        # plain jnp and has none there
        name="flash_fwd",
    )(qf, kf, vf)
    return (out.reshape(b, h, t, d), l.reshape(b, h, t),
            m.reshape(b, h, t))


def flash_mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True) -> jnp.ndarray:
    """[B, T, H, D] (flax layout) convenience wrapper around
    `flash_attention` for dropping into `nn.MultiHeadDotProductAttention`-
    style call sites."""
    o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal)
    return o.transpose(0, 2, 1, 3)


def _flash_backward_blockwise(q, k, v, o, l, m, do, causal: bool,
                              t_valid: int, block_k: int):
    """Exact attention backward with O(T·block_k) score memory: lax.scan
    over key blocks recomputing p = exp(s − m)/l from the saved softmax
    residuals (FlashAttention-2 backward, jnp formulation — XLA fuses it;
    runs everywhere, no kernel needed for correctness)."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    scale = 1.0 / float(d) ** 0.5
    qf = q.astype(jnp.float32)
    do_f = do.astype(jnp.float32)
    delta = jnp.sum(do_f * o.astype(jnp.float32), axis=-1)      # [B,H,T]
    nk = tk // block_k
    kb = k.reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nk, block_k, d).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(t)[:, None]

    def body(carry, xs):
        dq, j = carry[0], carry[1]
        k_j, v_j = xs
        k_j = k_j.astype(jnp.float32)
        v_j = v_j.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_j) * scale
        k_pos = j * block_k + jnp.arange(block_k)[None, :]
        mask = (k_pos < t_valid)
        if causal:
            mask = mask & (q_pos >= k_pos)
        p = jnp.where(mask[None, None], jnp.exp(s - m[..., None]), 0.0)
        p = p / jnp.maximum(l[..., None], 1e-12)
        dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, do_f)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_f, v_j)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_j) * scale
        dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
        return (dq, j + 1), (dk_j, dv_j)

    (dq, _), (dk_b, dv_b) = jax.lax.scan(
        body, (jnp.zeros((b, h, t, d), jnp.float32), 0), (kb, vb))
    dk = dk_b.transpose(1, 2, 0, 3, 4).reshape(b, h, tk, d)
    dv = dv_b.transpose(1, 2, 0, 3, 4).reshape(b, h, tk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.lru_cache(maxsize=64)
def _flash_core(causal: bool, block_q: int, block_k: int,
                interpret: Optional[bool], t_valid: int):
    """custom_vjp-wrapped flash attention on block-aligned [B, H, T, D]:
    pallas kernel forward (saves softmax residuals), blockwise-jnp exact
    backward — so the kernel path is trainable (ulysses/ring local steps).
    lru-cached per config so long-lived servers with many distinct context
    lengths don't grow an unbounded closure cache (the jit traces behind
    each entry are evicted with it)."""

    @jax.custom_vjp
    def f(q, k, v):
        o, _, _ = flash_attention_residuals(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, t_valid=t_valid)
        return o

    def fwd(q, k, v):
        o, l, m = flash_attention_residuals(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, t_valid=t_valid)
        return o, (q, k, v, o, l, m)

    def bwd(res, do):
        q, k, v, o, l, m = res
        return _flash_backward_blockwise(
            q, k, v, o, l, m, do, causal=causal, t_valid=t_valid,
            block_k=min(block_k, k.shape[2]))

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Exact attention on [B, H, T, D] via the flash recurrence.

    T is padded internally to the block size; padded keys are masked out and
    padded query rows sliced off, so any T works.  Differentiable: the
    forward runs the pallas kernel, the backward is the exact blockwise
    recomputation (`_flash_backward_blockwise`).
    """
    b, h, t, d = q.shape
    if interpret is None:
        if not _on_tpu():
            return _reference(q, k, v, causal)
        interpret = False

    block_q = min(block_q, max(t, 1))
    block_k = min(block_k, max(t, 1))
    t_pad = -(-t // block_q) * block_q
    t_pad = -(-t_pad // block_k) * block_k
    pad = t_pad - t
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        qp, kp, vp = q, k, v

    core = _flash_core(causal, block_q, block_k, interpret, t_valid=t)
    out = core(qp, kp, vp)
    return out[:, :, :t, :] if pad else out
