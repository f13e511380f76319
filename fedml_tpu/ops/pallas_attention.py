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

Masking convention as `parallel.ring_attention.reference_attention`; equal
to it up to the bfloat16 rounding of the products' operands.  Dispatch:

* on TPU → the pallas kernel;
* off TPU with ``interpret=True`` (tests) → the same kernel through the
  pallas interpreter;
* otherwise → a jnp fallback computed in float32.

`fedml_attention_traces_total` counts, as calls are traced, which of these
ran and with which tile.
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

    # key sub-blocks are numbered over the whole key axis.  The first
    # `n_full` need no mask (every key valid and, under `causal`, at or
    # below the tile's first query); those up to `n_live` hold a key some
    # query of the tile attends to; the rest are never visited.
    n_full, n_live = t_valid // block_k, -(-t_valid // block_k)
    if causal:
        n_full = jnp.minimum(n_full, (i * block_q + 1) // block_k)
        n_live = jnp.minimum(n_live, (i * block_q + block_q - 1) // block_k + 1)
    if window is not None:
        # under a window the walk starts at `n_dead`, the first sub-block
        # with a key the tile's first query sees; up to `n_edge` a
        # sub-block holds keys the tile's last query no longer sees
        n_dead = jnp.maximum(i * block_q - (window - 1), 0) // block_k
        n_edge = (jnp.maximum(i * block_q + block_q - window, 0)
                  + block_k - 1) // block_k
        n_edge = jnp.clip(n_edge, n_dead, n_live)
        n_full = jnp.clip(n_full, n_edge, n_live)
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

    carry = (o_acc[:], l_acc[:], m_acc[:])
    full_end = jnp.clip(n_full - first, 0, subs)
    full_start = 0
    if window is not None:
        full_start = jnp.clip(n_edge - first, 0, subs)
        carry = jax.lax.fori_loop(
            jnp.clip(n_dead - first, 0, subs), full_start,
            functools.partial(step, True), carry)
    carry = jax.lax.fori_loop(
        full_start, full_end, functools.partial(step, False), carry)
    carry = jax.lax.fori_loop(
        full_end, jnp.clip(n_live - first, 0, subs),
        functools.partial(step, True), carry)
    o_acc[:], l_acc[:], m_acc[:] = carry

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


def _kv_block(tk: int, block_k: int, d: int, itemsize: int) -> int:
    """Keys a grid step holds in VMEM: the whole axis when K and V fit
    `_KV_VMEM_BUDGET`, else the most whole sub-blocks that do and that
    divide the axis."""
    per_key = _kv_bytes_per_key(d, itemsize)
    subs = tk // block_k
    fit = max(1, _KV_VMEM_BUDGET // (per_key * block_k))
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
        # the kernel's name in a device trace; the blockwise backward is
        # plain jnp and has none there
        name="flash_fwd",
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
    block_kv = _kv_block(tk, block_k, q.shape[3], k.dtype.itemsize)
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


#: queries and keys of a tile of the backward of a long sequence
#: (`_flash_backward_tiled`); a sequence of one such tile has nothing to
#: skip at that grain and takes `_flash_backward_blockwise`
_BWD_TILE = 1024


def _flash_backward_tiled(q, k, v, o, l, m, do, window: Optional[int],
                          t_valid: int, tile: int):
    """Exact causal attention backward over [tile x tile] score tiles that
    hold a visible pair, and no others: for each key tile (a `lax.scan`)
    only the query tiles from its diagonal to the end of its window are
    walked (a `fori_loop` with those bounds), so a long sequence pays for
    the half under the diagonal, and a windowed one for the band.  The
    same recomputation from the saved residuals as
    `_flash_backward_blockwise`; K and V may have fewer heads than q."""
    b, h, t, d = q.shape
    hk, group = k.shape[1], h // k.shape[1]
    scale = 1.0 / float(d) ** 0.5
    n = t // tile
    f32 = jnp.float32
    # [B, Hk, G, T, ...]: the q heads of a group beside their K/V head
    qg = q.reshape(b, hk, group, t, d)
    dog = do.reshape(b, hk, group, t, d)
    delta = jnp.sum(dog.astype(f32) * o.reshape(qg.shape).astype(f32), -1)
    lg = jnp.maximum(l, 1e-12).reshape(b, hk, group, t)
    mg = m.reshape(b, hk, group, t)
    last = n if window is None else (tile + window - 2) // tile + 1

    def rows(x, i, axis):
        return jax.lax.dynamic_slice_in_dim(x, i * tile, tile, axis)

    def key_tile(dq, j):
        k_j, v_j = rows(k, j, 2).astype(f32), rows(v, j, 2).astype(f32)
        k_pos = j * tile + jnp.arange(tile)[None, :]

        def query_tile(i, carry):
            dq, dk_j, dv_j = carry
            q_i, do_i = rows(qg, i, 3).astype(f32), rows(dog, i, 3).astype(f32)
            gap = i * tile + jnp.arange(tile)[:, None] - k_pos
            mask = (gap >= 0) & (k_pos < t_valid)
            if window is not None:
                mask = mask & (gap < window)
            s = jnp.einsum("bcgqd,bckd->bcgqk", q_i, k_j) * scale
            p = jnp.where(mask, jnp.exp(s - rows(mg, i, 3)[..., None]), 0.0)
            p = p / rows(lg, i, 3)[..., None]
            dv_j = dv_j + jnp.einsum("bcgqk,bcgqd->bckd", p, do_i)
            dp = jnp.einsum("bcgqd,bckd->bcgqk", do_i, v_j)
            ds = p * (dp - rows(delta, i, 3)[..., None])
            dq_i = jnp.einsum("bcgqk,bckd->bcgqd", ds, k_j) * scale
            dq = jax.lax.dynamic_update_slice_in_dim(
                dq, rows(dq, i, 3) + dq_i, i * tile, 3)
            dk_j = dk_j + jnp.einsum("bcgqk,bcgqd->bckd", ds, q_i) * scale
            return dq, dk_j, dv_j

        zero = jnp.zeros((b, hk, tile, d), f32)
        dq, dk_j, dv_j = jax.lax.fori_loop(
            j, jnp.minimum(j + last, n), query_tile, (dq, zero, zero))
        return dq, (dk_j, dv_j)

    dq, (dk_b, dv_b) = jax.lax.scan(
        key_tile, jnp.zeros(qg.shape, f32), jnp.arange(n))
    dk = dk_b.transpose(1, 2, 0, 3, 4).reshape(k.shape)
    dv = dv_b.transpose(1, 2, 0, 3, 4).reshape(v.shape)
    return (dq.reshape(q.shape).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


def _flash_backward_blockwise(q, k, v, o, l, m, do, causal: bool,
                              t_valid: int, block_k: int,
                              window: Optional[int] = None):
    """Exact attention backward with O(T·block_k) score memory: lax.scan
    over key blocks recomputing p = exp(s − m)/l from the saved softmax
    residuals (FlashAttention-2 backward, jnp formulation — XLA fuses it;
    runs everywhere, no kernel needed for correctness).  K and V of fewer
    heads than q are spread over their groups, and their gradients summed
    over them."""
    b, h, t, d = q.shape
    kv_shape = k.shape
    k, v = _spread_heads(q, k, v)
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
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
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
    if kv_shape[1] != h:
        dk, dv = (z.reshape(b, kv_shape[1], -1, tk, d).sum(2)
                  for z in (dk, dv))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.lru_cache(maxsize=64)
def _flash_core(causal: bool, block_q: int, block_k: int,
                interpret: bool, t_valid: int, window: Optional[int] = None):
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
            interpret=interpret, t_valid=t_valid, window=window)
        return o

    def fwd(q, k, v):
        o, l, m = flash_attention_residuals(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=interpret, t_valid=t_valid, window=window)
        return o, (q, k, v, o, l, m)

    @tracing.scope("attn_bwd")
    def bwd(res, do):
        q, k, v, o, l, m = res
        t = q.shape[2]
        if causal and t > _BWD_TILE and t % _BWD_TILE == 0:
            return _flash_backward_tiled(q, k, v, o, l, m, do, window,
                                         t_valid, _BWD_TILE)
        # the backward's scores are [B, H, T, block] arrays in HBM: it keeps
        # 128-key blocks whatever the forward's loop takes at a pass
        return _flash_backward_blockwise(
            q, k, v, o, l, m, do, causal=causal, t_valid=t_valid,
            block_k=_LANES if block_k % _LANES == 0 else block_k,
            window=window)

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
    window.  Differentiable: the forward runs the pallas kernel, the
    backward is the exact recomputation from its residuals, in blocks
    (`_flash_backward_blockwise`) or, for a long sequence, in the tiles
    that hold a visible pair (`_flash_backward_tiled`).
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
    out = core(qp, kp, vp)
    return out[:, :, :t, :] if pad else out
