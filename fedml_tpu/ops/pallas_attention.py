"""Flash-attention Pallas TPU kernel.

The long-context path (`parallel/ring_attention.py`, `parallel/ulysses.py`,
the transformer/ViT zoo and the LLM engine) computes attention per shard.
XLA materializes the full [T, T] score matrix in HBM for the naive einsum
formulation; this kernel runs the online-softmax (flash) recurrence with the
score block resident in VMEM, so HBM traffic stays O(T·D) — the standard
TPU treatment of the one genuinely bandwidth-bound matmul-adjacent op
(/opt/skills/guides/pallas_guide.md).

Schedule: a grid step takes one head's q tile (512 queries where T allows)
against the head's K and V, held in VMEM as one block that is fetched once
a head, and walks them `block_k` keys at a pass in a loop that ends at the
tile's causal diagonal: a block above it costs neither a fetch nor a step.
K and V too long for the VMEM budget go on the grid in large blocks.  The
scores of a pass are held [keys, queries], so the softmax state and the
residuals are lane-dense rows.  The two products take bfloat16 operands and
accumulate in float32 (XLA's default precision for the rest of a float32
program on a TPU); the softmax state, the residuals and what reaches HBM
keep float32 / the caller's dtypes.

Two further shapes of attention ride the same kernel.  A ``window`` (query i
sees key j iff 0 <= i - j < window, under ``causal``): the loop of a q tile
starts at the first sub-block that holds a key its first query may see, so
key blocks wholly outside the window cost neither a fetch nor a pass, forward
or backward.  Grouped heads (``k``/``v`` of fewer heads than ``q``): the q
heads of a group read the one K/V head through the block index, so it is
fetched once a group where it is resident.

The backward is a kernel beside it, `flash_bwd` (`_flash_bwd_kernel`): the
same tiles, the same walk and the same [keys, queries] scores, recomputed
from what the forward keeps (o, l, m, and q x scale, k and v rounded to
bfloat16 as every product takes them: `_rounded`) and spent in VMEM where
they are made, five products a tile (s, dp, dv, dk, dq) on bfloat16 operands
into float32.  dK and dV of a K/V head are float32 accumulators
that stay in VMEM over the q heads of its group and all their q tiles; dQ of
a q tile is summed over its walk and written once.

Masking convention as `parallel.ring_attention.reference_attention`; equal
to it up to the bfloat16 rounding of the products' operands.  Dispatch:

* on TPU → the pallas kernel;
* off TPU with ``interpret=True`` (tests) → the same kernel through the
  pallas interpreter;
* otherwise → a jnp fallback computed in float32.

`fedml_attention_traces_total` counts, as calls are traced, which of these
ran and with which tile (``path`` `kernel`, `reference`, and `kernel_bwd`
once a traced backward).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.mlops import metrics as _metrics
from ..core.mlops import tracing
from .pallas_ops import _on_tpu

NEG_INF = -1e30
_LANES = 128
#: default tile lengths, largest first, for both the queries of a grid step
#: and the keys of a pass of its loop
_TILES = (512, 256, 128)
#: VMEM a grid step may spend on its K and V blocks, both double-buffered by
#: the pipeline; a head's whole K and V are one block when they fit it
#: (16,384 float32 keys of 128 take 32 MiB of a v5e core's 128)
_KV_VMEM_BUDGET = 40 * 2 ** 20
#: K and V blocks up to this size leave the rest of a grid step room under
#: the compiler's own VMEM limit; larger ones raise it by their size
_KV_VMEM_DEFAULT = 8 * 2 ** 20


def _visible(t: int, tk: int, causal: bool, window: Optional[int] = None):
    """[t, tk] bool: which keys each query may see."""
    gap = jnp.arange(t)[:, None] - jnp.arange(tk)[None, :]
    mask = gap >= 0 if causal else jnp.ones((t, tk), bool)
    return mask if window is None else mask & (gap < window)


def _spread_heads(q, k, v):
    """K and V with each head repeated for the q heads of its group."""
    g = q.shape[1] // k.shape[1]
    if g == 1:
        return k, v
    return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)


def _reference(q, k, v, causal, window=None):
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    k, v = _spread_heads(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = _visible(q.shape[2], k.shape[2], True, window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _pass_bounds(i, block_q: int, block_k: int, t_valid: int, causal: bool,
                 window: Optional[int]):
    """Which key sub-blocks of ``block_k``, numbered over the whole key
    axis, q tile ``i`` walks, forward and backward: (n_dead, n_edge, n_full,
    n_live).  The walk is [n_dead, n_live): a sub-block before it holds no
    key the tile's first query sees under the window, one after it none
    that is valid and, under ``causal``, at or below the tile's last query;
    neither is ever visited.  Those in [n_edge, n_full) need no mask (every
    key valid and seen by every query of the tile); up to n_edge a
    sub-block holds keys the tile's last query no longer sees (without a
    window there are none, and n_dead and n_edge are None), from n_full keys
    above the diagonal or past ``t_valid``."""
    n_dead = n_edge = None
    n_full, n_live = t_valid // block_k, -(-t_valid // block_k)
    if causal:
        n_full = jnp.minimum(n_full, (i * block_q + 1) // block_k)
        n_live = jnp.minimum(n_live, (i * block_q + block_q - 1) // block_k + 1)
    if window is not None:
        n_dead = jnp.maximum(i * block_q - (window - 1), 0) // block_k
        n_edge = (jnp.maximum(i * block_q + block_q - window, 0)
                  + block_k - 1) // block_k
        n_edge = jnp.clip(n_edge, n_dead, n_live)
        n_full = jnp.clip(n_full, n_edge, n_live)
    return n_dead, n_edge, n_full, n_live


def _walk(step, carry, bounds, first, subs: int):
    """``step(masked, sub, carry)`` over the sub-blocks of `_pass_bounds`
    that lie in a K/V block of ``subs`` starting at sub-block ``first``,
    numbered within the block: the window's edge masked, the full ones not,
    the diagonal's masked."""
    n_dead, n_edge, n_full, n_live = (
        n if n is None else jnp.clip(n - first, 0, subs) for n in bounds)
    start = 0
    if n_edge is not None:
        start = n_edge
        carry = jax.lax.fori_loop(n_dead, n_edge,
                                  functools.partial(step, True), carry)
    carry = jax.lax.fori_loop(start, n_full,
                              functools.partial(step, False), carry)
    return jax.lax.fori_loop(n_full, n_live,
                             functools.partial(step, True), carry)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
                  o_acc, l_acc, m_acc, *, block_k: int, t_valid: int,
                  causal: bool, window: Optional[int], scale: float):
    """One grid step of grid (BH, q tiles, K/V blocks): a [block_q, D] q
    tile against the step's K/V block, ``block_k`` keys at a pass in a
    `fori_loop` that stops at the tile's own causal diagonal.

    The K/V block is the head's whole K and V when they fit the VMEM
    budget (`_kv_block`): its index then does not change over the q axis
    and it is fetched once a head.  Otherwise the K/V axis has several
    steps, the scratch carries the online-softmax state across them, and a
    step wholly above the diagonal finds its loop empty (its block index is
    clamped to the last live one, so it fetches nothing either); under a
    ``window`` the same holds for a step wholly below the tile's window.

    Scores are held transposed, [keys, queries]: the softmax state (row max
    m, row sum l) is then one lane-dense row [1, block_q], its reductions
    run down the sublanes, and the residuals leave as rows.  The two
    products take bfloat16 operands (q already scaled) and accumulate in
    float32; the state, the residuals and the output accumulator
    ([D, block_q], turned once at the end) are float32."""
    block_q, block_kv = q_ref.shape[1], k_ref.shape[1]
    subs = block_kv // block_k
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        l_acc[:] = jnp.zeros_like(l_acc)
        m_acc[:] = jnp.full_like(m_acc, NEG_INF)

    bounds = _pass_bounds(i, block_q, block_k, t_valid, causal, window)
    first = j * subs
    q = (q_ref[0].astype(jnp.float32) * scale).astype(jnp.bfloat16)
    q_pos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_q), 1)

    def step(masked, sub, carry):
        o, l, m = carry                         # [D, bq], [1, bq], [1, bq]
        rows = pl.ds(pl.multiple_of(sub * block_k, block_k), block_k)
        s = jax.lax.dot_general(
            k_ref[0, rows, :].astype(jnp.bfloat16), q,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, bq]
        if masked:
            k_pos = (first + sub) * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            mask = k_pos < t_valid                              # pad keys out
            if causal:
                mask = mask & (q_pos >= k_pos)
            if window is not None:
                mask = mask & (q_pos - k_pos < window)
            s = jnp.where(mask, s, NEG_INF)
        # key 0 is valid and visible to every query and its sub-block comes
        # first, so `new_m` is a real score and a masked p is exp(-1e30) = 0.
        # Under a window a query may find its first sub-blocks all masked:
        # what it sums there (p = 1 at `new_m` = -1e30) is wiped by `alpha`
        # = 0 at its first real score, and its own key is always one
        new_m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - new_m)
        alpha = jnp.exp(m - new_m)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        o = o * alpha + jax.lax.dot_general(
            v_ref[0, rows, :].astype(jnp.bfloat16), p.astype(jnp.bfloat16),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return o, l, new_m

    o_acc[:], l_acc[:], m_acc[:] = _walk(
        step, (o_acc[:], l_acc[:], m_acc[:]), bounds, first, subs)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o = o_acc[:] / jnp.maximum(l_acc[:], 1e-12)             # [D, bq]
        o_ref[0] = o.T.astype(o_ref.dtype)
        l_ref[0] = l_acc[:]
        m_ref[0] = m_acc[:]


def _reference_residuals(q, k, v, causal, t_valid=None, window=None):
    """jnp fallback for `flash_attention_residuals` — identical math."""
    t, tk = q.shape[2], k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    k, v = _spread_heads(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = _visible(t, tk, causal, window)
    if t_valid is not None and t_valid < tk:
        mask = mask & (jnp.arange(tk)[None, :] < t_valid)
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


def _pick_block(n: int, block: Optional[int]) -> int:
    """A block length for an axis of n: the caller's, else the largest of
    `_TILES` that divides n (one block of the whole axis where n is shorter
    than the smallest)."""
    if block is not None:
        return min(block, max(n, 1))
    return next((s for s in _TILES if n % s == 0), min(n, _TILES[-1]))


def _kv_bytes_per_key(d: int, itemsize: int) -> int:
    """VMEM one key takes in the K and V blocks, both double-buffered."""
    return 4 * -(-d // _LANES) * _LANES * itemsize


def _kv_block(tk: int, block_k: int, per_key: int, budget: int) -> int:
    """Keys a grid step holds in VMEM at ``per_key`` bytes each: the whole
    axis when that fits ``budget``, else the most whole sub-blocks that do
    and that divide the axis."""
    subs = tk // block_k
    fit = max(1, budget // (per_key * block_k))
    return block_k * max(n for n in range(1, subs + 1)
                         if subs % n == 0 and n <= fit)


def _note_trace(path: str, head_dim: int, block_q: int = 0, block_k: int = 0,
                kv_resident: bool = False) -> None:
    """Counts, as a call is traced, which path it took, at which head size
    and with which tile (docs/OBSERVABILITY.md)."""
    _metrics.counter(
        "fedml_attention_traces_total",
        "flash-attention calls traced, by the path, head size and tile they "
        "took",
        labels=("path", "block_q", "block_k", "kv_resident", "head_dim"),
    ).labels(path=path, block_q=block_q, block_k=block_k,
             kv_resident=str(kv_resident).lower(), head_dim=head_dim).inc()


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "block_kv", "t_valid",
    "interpret"))
def _flash_call(q, k, v, *, causal: bool, block_q: int, block_k: int,
                block_kv: int, t_valid: int, interpret: bool,
                window: Optional[int] = None):
    """`_flash_kernel` over [B, H, T, D] queries and [B, Hk, Tk, D] keys and
    values, H a multiple of Hk.  Under its own `jit`: a program that calls
    it once a layer, and again under remat and autodiff, traces and lowers
    the kernel once (PERF.md section 6, PRs 25 and 31)."""
    b, h, t, d = q.shape
    hk, tk = k.shape[1], k.shape[2]
    group = h // hk

    def q_map(bi, i, j):
        return bi, i, 0

    def row_map(bi, i, j):      # the residuals: one lane-dense row a head
        return bi, 0, i

    def kv_map(bi, i, j):
        if causal:      # a step above the diagonal keeps the last live block
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_kv)
        if window is not None:  # and one below the window the first
            j = jnp.maximum(
                j, jnp.maximum(i * block_q - (window - 1), 0) // block_kv)
        if group > 1:           # the q heads of a group read one K/V head
            bi = bi // h * hk + bi % h // group
        return bi, j, 0

    params = {}
    kv_vmem = _kv_bytes_per_key(d, k.dtype.itemsize) * block_kv
    if kv_vmem > _KV_VMEM_DEFAULT:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=kv_vmem + 16 * 2 ** 20)

    out, l, m = pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, t_valid=t_valid,
                          causal=causal, window=window,
                          scale=1.0 / float(d) ** 0.5),
        grid=(b * h, t // block_q, tk // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q), row_map),
            pl.BlockSpec((1, 1, block_q), row_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32),
            jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32),
                        pltpu.VMEM((1, block_q), jnp.float32),
                        pltpu.VMEM((1, block_q), jnp.float32)],
        interpret=interpret,
        name="flash_fwd",       # the kernel's name in a device trace
        **params,
    )(q.reshape(b * h, t, d), k.reshape(b * hk, tk, d),
      v.reshape(b * hk, tk, d))
    return (out.reshape(b, h, t, d), l.reshape(b, h, t),
            m.reshape(b, h, t))


def flash_attention_residuals(q: jnp.ndarray, k: jnp.ndarray,
                              v: jnp.ndarray, causal: bool = True,
                              block_q: Optional[int] = None,
                              block_k: Optional[int] = None,
                              interpret: Optional[bool] = None,
                              t_valid: Optional[int] = None,
                              window: Optional[int] = None):
    """Like `flash_attention` but also returns the softmax residuals
    (l, m) [B, H, T] so callers can merge partial attentions over disjoint
    key sets (`merge_attention_partials`) — the ring-attention block op.
    Requires block-aligned lengths (ring blocks are); the key length may
    differ from the query length for non-causal partials.

    `block_q` is the queries of a grid step and `block_k` the keys of one
    pass of its inner loop; left out, both follow the shape."""
    t, tk = q.shape[2], k.shape[2]
    if t_valid is None:
        t_valid = tk
    if interpret is None and _on_tpu():
        interpret = False
    block_q = _pick_block(t, block_q)
    block_k = _pick_block(tk, block_k)
    if window is not None and not causal:
        raise ValueError("a window is defined under causal attention only")
    if (interpret is None or t % block_q or tk % block_k
            or (causal and tk != t)):
        _note_trace("reference", q.shape[3])
        return _reference_residuals(q, k, v, causal, t_valid, window)
    block_kv = _kv_block(
        tk, block_k, _kv_bytes_per_key(q.shape[3], k.dtype.itemsize),
        _KV_VMEM_BUDGET)
    _note_trace("kernel", q.shape[3], block_q, block_k,
                kv_resident=block_kv == tk)
    return _flash_call(q, k, v, causal=causal, block_q=block_q,
                       block_k=block_k, block_kv=block_kv, t_valid=t_valid,
                       interpret=interpret, window=window)


def flash_mha(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True) -> jnp.ndarray:
    """[B, T, H, D] (flax layout) convenience wrapper around
    `flash_attention` for dropping into `nn.MultiHeadDotProductAttention`-
    style call sites."""
    o = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=causal)
    return o.transpose(0, 2, 1, 3)


def _flash_bwd_kernel(q_ref, do_ref, o_ref, l_ref, m_ref, k_ref, v_ref,
                      dq_ref, dk_ref, dv_ref, *, block_k: int, t_valid: int,
                      causal: bool, window: Optional[int], scale: float):
    """One grid step of grid (B x K/V heads, K/V blocks, q heads of the
    group, q tiles): the gradients a [block_q, D] q tile and the step's K/V
    block owe each other, ``block_k`` keys at a pass over the sub-blocks
    `_flash_kernel` walks for the same tile, and no others.

    q (times the scale), k and v arrive rounded to bfloat16 (`_rounded`).
    A pass recomputes its scores from them and the forward's l and m and
    spends them where they are: ``p = exp(s - m) / l`` (as ``exp(s - (m + log
    l))``), ``ds = p * (dp - delta)`` with ``delta = sum(do * o)`` a row of
    the tile, and the five products ``s = k q^T``, ``dp = v do^T``, ``dv +=
    p do``, ``dk += ds q``, ``dq^T += k^T ds`` on bfloat16 operands into
    float32.  Scores are held [keys, queries], so l, m and delta are
    lane-dense rows.  ``dk_ref`` / ``dv_ref`` are the K/V block's float32
    accumulators: output blocks whose index changes only with the K/V head
    and block, resident over the group's q heads and all their q tiles and
    written to HBM once.  dQ of the tile is summed over the walk ([D,
    block_q], turned once) and written once; with several K/V blocks each
    writes its own partial (`_flash_bwd_call` sums them)."""
    block_q, block_kv = q_ref.shape[1], k_ref.shape[1]
    subs = block_kv // block_k
    j, i = pl.program_id(1), pl.program_id(3)

    @pl.when((pl.program_id(2) == 0) & (i == 0))
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    bounds = _pass_bounds(i, block_q, block_k, t_valid, causal, window)
    first = j * subs
    q = q_ref[0]        # times the scale already: dk = ds^T q needs none
    do = do_ref[0].astype(jnp.float32)
    delta = jnp.sum((do * o_ref[0].astype(jnp.float32)).T, axis=0,
                    keepdims=True)                              # [1, bq]
    do = do.astype(jnp.bfloat16)
    lse = m_ref[0] + jnp.log(jnp.maximum(l_ref[0], 1e-12))      # [1, bq]
    q_pos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_q), 1)

    def step(masked, sub, dq):                                  # dq [D, bq]
        rows = pl.ds(pl.multiple_of(sub * block_k, block_k), block_k)
        k = k_ref[0, rows, :]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, bq]
        p = jnp.exp(s - lse)
        if masked:
            k_pos = (first + sub) * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            mask = k_pos < t_valid
            if causal:
                mask = mask & (q_pos >= k_pos)
            if window is not None:
                mask = mask & (q_pos - k_pos < window)
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            v_ref[0, rows, :], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(jnp.bfloat16)
        dv_ref[0, rows, :] += jnp.dot(p.astype(jnp.bfloat16), do,
                                      preferred_element_type=jnp.float32)
        dk_ref[0, rows, :] += jnp.dot(ds, q,
                                      preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(
            k, ds, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = _walk(step, jnp.zeros(q.shape[::-1], jnp.float32), bounds, first,
               subs)
    dq_ref[0, 0] = dq.T * scale


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "block_kv", "t_valid",
    "interpret"))
def _flash_bwd_call(q, k, v, o, l, m, do, *, causal: bool, block_q: int,
                    block_k: int, block_kv: int, t_valid: int,
                    interpret: bool, window: Optional[int] = None):
    """`_flash_bwd_kernel` over what `_flash_core` keeps of a `_flash_call`
    (q, k and v as `_rounded` leaves them, o, l, m) and the cotangent of
    its output: float32 (dq, dk, dv).  Under its own `jit`, as `_flash_call`
    is."""
    b, h, t, d = q.shape
    hk, tk = k.shape[1], k.shape[2]
    group, blocks = h // hk, tk // block_kv

    def q_head(bi, g):          # q head g of the group of K/V head bi
        return bi // hk * h + bi % hk * group + g

    def q_tile(bi, j, g, i):
        # a q tile no key of the K/V block reaches is not fetched: the
        # index stays on the block's first live tile, or its last
        if causal:
            i = jnp.maximum(i, j * block_kv // block_q)
        if window is not None:
            i = jnp.minimum(
                i, (j * block_kv + block_kv + window - 2) // block_q)
        return q_head(bi, g), i

    def q_map(bi, j, g, i):
        return (*q_tile(bi, j, g, i), 0)

    def row_map(bi, j, g, i):
        bi, i = q_tile(bi, j, g, i)
        return bi, 0, i

    def kv_map(bi, j, g, i):
        return bi, j, 0

    def dq_map(bi, j, g, i):
        return j, q_head(bi, g), i, 0

    params = {}
    kv_vmem = (_kv_bytes_per_key(d, k.dtype.itemsize)
               + _kv_bytes_per_key(d, 4)) * block_kv
    if kv_vmem > _KV_VMEM_DEFAULT:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=kv_vmem + 24 * 2 ** 20)

    rows = (b * h, t, d)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_k=block_k,
                          t_valid=t_valid, causal=causal, window=window,
                          scale=1.0 / float(d) ** 0.5),
        grid=(b * hk, blocks, group, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q), row_map),
            pl.BlockSpec((1, 1, block_q), row_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), dq_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((blocks,) + rows, jnp.float32),
            jax.ShapeDtypeStruct((b * hk, tk, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hk, tk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd",
        **params,
    )(q.reshape(rows), do.reshape(rows), o.reshape(rows),
      l.reshape(b * h, 1, t), m.reshape(b * h, 1, t),
      k.reshape(b * hk, tk, d), v.reshape(b * hk, tk, d))
    return (dq.sum(0).reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape))


def _rounded(q, k, v):
    """q times the softmax scale, k and v as the products of both kernels
    take them: rounded to bfloat16.  The backward reads nothing else of
    them, so this is what the forward keeps for it, at half the bytes."""
    scale = 1.0 / float(q.shape[-1]) ** 0.5
    return ((q.astype(jnp.float32) * scale).astype(jnp.bfloat16),
            k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))


@functools.lru_cache(maxsize=64)
def _flash_core(causal: bool, block_q: int, block_k: int,
                interpret: bool, t_valid: int, window: Optional[int] = None):
    """custom_vjp-wrapped flash attention on block-aligned [B, H, T, D]:
    the kernel `flash_fwd` forward (saves the softmax residuals), the kernel
    `flash_bwd` backward at the forward's tiles, exact from those residuals
    — so the kernel path is trainable (ulysses/ring local steps).
    lru-cached per config so long-lived servers with many distinct context
    lengths don't grow an unbounded closure cache (the jit traces behind
    each entry are evicted with it)."""

    @jax.custom_vjp
    def f(q, k, v):
        o, _, _ = flash_attention_residuals(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, t_valid=t_valid, window=window)
        return o

    def fwd(q, k, v):
        o, l, m = flash_attention_residuals(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, t_valid=t_valid, window=window)
        return o, (*_rounded(q, k, v), o, l, m)

    @tracing.scope("attn_bwd")
    def bwd(res, do):
        q, k, v, o, l, m = res
        d, tk = q.shape[3], k.shape[2]
        block_kv = _kv_block(
            tk, block_k,
            _kv_bytes_per_key(d, k.dtype.itemsize) + _kv_bytes_per_key(d, 4),
            2 * _KV_VMEM_BUDGET)
        _note_trace("kernel_bwd", d, block_q, block_k,
                    kv_resident=block_kv == tk)
        grads = _flash_bwd_call(
            q, k, v, o, l, m, do, causal=causal, block_q=block_q,
            block_k=block_k, block_kv=block_kv, t_valid=t_valid,
            interpret=interpret, window=window)
        # `flash_attention` hands q, k and v over in one type, which is o's
        return tuple(g.astype(o.dtype) for g in grads)

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Exact attention on [B, H, T, D] via the flash recurrence.

    T is padded internally to the block size (left out, the blocks follow
    the shape: `flash_attention_residuals`); padded keys are masked out and
    padded query rows sliced off, so any T works.  ``k`` and ``v`` may have
    fewer heads than ``q`` (a divisor of its count: grouped heads).  With a
    ``window``, under ``causal``, query i sees key j iff 0 <= i - j <
    window.  Differentiable: the forward runs the kernel `flash_fwd`, the
    backward the kernel `flash_bwd`, the exact recomputation from the
    forward's residuals over the same tiles (through the interpreter where
    the forward goes through it).
    """
    b, h, t, d = q.shape
    if h % k.shape[1]:
        raise ValueError(f"{h} query heads over {k.shape[1]} key/value heads")
    if window is not None and not causal:
        raise ValueError("a window is defined under causal attention only")
    if interpret is None:
        if not _on_tpu():
            _note_trace("reference", d)
            return _reference(q, k, v, causal, window)
        interpret = False

    # default blocks are chosen over whole 128-position tiles of a long T
    t_tiles = t if t <= _LANES else -(-t // _LANES) * _LANES
    block_q = _pick_block(t_tiles, block_q)
    block_k = _pick_block(t_tiles, block_k)
    t_pad = -(-t // block_q) * block_q
    t_pad = -(-t_pad // block_k) * block_k
    pad = t_pad - t
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        qp, kp, vp = q, k, v

    core = _flash_core(causal, block_q, block_k, interpret, t_valid=t,
                       window=window)
    out = core(qp, kp.astype(q.dtype), vp.astype(q.dtype))
    return out[:, :, :t, :] if pad else out
