"""`flash_attention` with a window and with grouped key/value heads, forward
and backward, against plain attention: the kernels `flash_fwd` and `flash_bwd`
through the interpreter, and the off-TPU fallback."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import pallas_attention as pa

#: what rounding q x scale, k, v and p to bfloat16 costs the kernel against
#: float32 attention on unit-normal inputs (tests/test_pallas_ops.py)
BF16_ATOL = 3e-2


def plain_attention(q, k, v, window=None, causal=True):
    """Softmax attention in float32 at `highest`, [B, H, T, D] queries over
    [B, Hk, T, D] keys and values: each q head reads head `h // group`;
    under ``causal`` query i sees key j iff 0 <= i - j (< window)."""
    b, h, t, d = q.shape
    g = h // k.shape[1]
    k, v = (jnp.repeat(z, g, axis=1) for z in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / np.sqrt(d)
    gap = np.arange(t)[:, None] - np.arange(t)[None, :]
    mask = gap >= 0 if causal else np.ones((t, t), bool)
    mask = mask if window is None else mask & (gap < window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _qkv(seed, t, d=16, b=2, h=6, hk=2):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, t, d), jnp.float32),
            jnp.asarray(rng.randn(b, hk, t, d), jnp.float32),
            jnp.asarray(rng.randn(b, hk, t, d), jnp.float32))


def _grads(fn, q, k, v):
    w = jnp.asarray(np.random.RandomState(9).randn(*q.shape), jnp.float32)
    return jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)


# a window shorter than, equal to and longer than the sequence, and none
WINDOWS = [5, 8, 23, 48, 64, None]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("hk", [6, 2, 1], ids=["mha", "gqa3", "mqa"])
def test_windowed_grouped_kernel_matches_plain_attention(window, hk):
    q, k, v = _qkv(1, 48, hk=hk)
    out = pa.flash_attention(q, k, v, causal=True, block_q=16, block_k=8,
                             interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(plain_attention(q, k, v, window)),
                               atol=BF16_ATOL, rtol=BF16_ATOL)


@pytest.mark.parametrize("window", [7, 40, None])
def test_kernel_with_kv_blocks_on_the_grid_skips_by_window(monkeypatch,
                                                           window):
    """K and V too long for the VMEM budget go on the grid; blocks wholly
    above the diagonal or below the window are not visited (poisoned here:
    a visit would show as NaN)."""
    monkeypatch.setattr(pa, "_KV_VMEM_BUDGET", 2 * 4 * 128 * 4 * 8)  # 2 passes
    q, k, v = _qkv(2, 64, hk=2)
    got = pa.flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                             interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(plain_attention(q, k, v, window)),
                               atol=BF16_ATOL, rtol=BF16_ATOL)
    if window is not None:
        # queries 48.. see no key below 48 - window + 1: poison the passes
        # wholly below it; a visit would put 0 x NaN into their output
        dead = (48 - window + 1) // 8 * 8
        o, _, _ = pa.flash_attention_residuals(
            q, k, v.at[:, :, :dead].set(jnp.nan), causal=True, block_q=8,
            block_k=8, interpret=True, window=window)
        assert np.isfinite(np.asarray(o[:, :, 48:])).all()


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("tiles", [(16, 8), (8, 24)], ids=["16x8", "8x24"])
def test_backward_matches_plain_attention(window, tiles):
    """The kernel `flash_bwd` under a window and grouped heads, against
    autodiff of plain attention: three q tiles of two key passes each, and
    six q tiles over passes longer than they are."""
    q, k, v = _qkv(3, 48)
    got = _grads(lambda *a: pa.flash_attention(
        *a, causal=True, block_q=tiles[0], block_k=tiles[1], interpret=True,
        window=window), q, k, v)
    want = _grads(lambda *a: plain_attention(*a, window=window), q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2 * BF16_ATOL, rtol=2 * BF16_ATOL)


def _backward(shape, hk, causal, window, t_valid, block_q, block_k, block_kv):
    """(`flash_bwd` through the interpreter, the jnp backward that rounds
    what it rounds) on [B, H, T, D] operands of a few bits each, q so that q
    x scale is one such number: every sum that feeds a rounding to bfloat16
    (s, dp, delta) is then exact on both sides, and the two differ by the
    order of the last products' float32 sums alone.  l and m are those of
    the operands; o, which the backward only multiplies with do, is cut to
    multiples of 1/64."""
    from conftest import few_bits, rounded_flash_backward

    b, h, t, d = shape
    q = few_bits(21, shape) * 2.0 ** -round(np.log2(d) / 2) * float(d) ** 0.5
    k, v = (few_bits(seed, (b, hk, t, d)) for seed in (22, 23))
    do = few_bits(24, shape)
    o, l, m = pa._reference_residuals(q, k, v, causal, t_valid, window)
    o = jnp.round(o * 64) / 64
    got = pa._flash_bwd_call(
        *pa._rounded(q, k, v), o, l, m, do, causal=causal, block_q=block_q,
        block_k=block_k, block_kv=block_kv, t_valid=t_valid, interpret=True,
        window=window)
    return got, rounded_flash_backward(q, k, v, o, l, m, do, causal, t_valid,
                                       window)


# window (none, shorter than a tile, crossing tiles), K/V heads under 6 (or
# 2) q heads, causal, T (43 pads to 48: padded keys and query rows), head
# size, K/V blocks of the grid; every case has 3 q tiles of 16 or more
BACKWARD_CASES = [
    (None, 6, True, 48, 8, 1), (None, 2, True, 43, 64, 3),
    (None, 6, False, 48, 8, 2), (None, 2, False, 43, 64, 1),
    (5, 6, True, 48, 64, 1), (5, 2, True, 43, 8, 3),
    (23, 6, True, 43, 8, 1), (23, 1, True, 64, 64, 2),
    (23, 2, True, 43, 192, 1), (None, 1, True, 64, 192, 2),
    (40, 2, True, 64, 8, 4),
]


@pytest.mark.parametrize("window,hk,causal,t,d,kv_blocks", BACKWARD_CASES)
def test_backward_kernel_matches_autodiff_and_its_rounded_form(
        window, hk, causal, t, d, kv_blocks):
    """`flash_bwd`'s gradients (a) within the bfloat16 tolerance of autodiff
    of plain float32 attention, through `flash_attention` as a caller
    reaches it, and (b) tightly against the jnp backward fed operands
    rounded as the kernel rounds them, over windows, groups, non-causal
    attention, padded keys, head sizes and K/V blocks on the grid."""
    h = 2 if d == 192 else 6
    q, k, v = _qkv(11, t, d=d, b=1, h=h, hk=min(hk, h))
    got = _grads(lambda *a: pa.flash_attention(
        *a, causal=causal, block_q=16, block_k=8, interpret=True,
        window=window), q, k, v)
    want = _grads(lambda *a: plain_attention(*a, window, causal), q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2 * BF16_ATOL, rtol=2 * BF16_ATOL)
    t_pad = -(-t // 16) * 16
    got, tight = _backward((q.shape[0], h, t_pad, d), k.shape[1], causal,
                           window, t, 16, 8, t_pad // kv_blocks)
    for g, w in zip(got, tight):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4,
                                   rtol=1e-4)


def test_backward_kernel_is_the_same_arithmetic_at_any_tiles():
    """Other tiles and K/V blocks walk the same pairs in another order:
    float32 rounding apart, the gradients agree."""
    q, k, v = _qkv(4, 64)
    o, l, m = pa._reference_residuals(q, k, v, True, window=20)
    do = jnp.asarray(np.random.RandomState(5).randn(*q.shape), jnp.float32)
    a, b = (pa._flash_bwd_call(
        *pa._rounded(q, k, v), o, l, m, do, causal=True, block_q=bq,
        block_k=bk, block_kv=bkv, t_valid=64, interpret=True, window=20)
        for bq, bk, bkv in [(16, 8, 64), (32, 16, 16)])
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_blocks", [1, 4], ids=["kv-resident",
                                                   "kv-on-grid"])
def test_backward_kernel_never_visits_a_dead_tile(kv_blocks):
    """Tiles wholly outside the window or above the diagonal cost neither a
    fetch nor a step (poisoned here: a visit would show as NaN).  Queries
    48.. see no key below 48 - 23 + 1 = 26: with the key passes wholly below
    it poisoned their dQ stays finite, and with those queries' cotangent
    poisoned, dK and dV of those passes do."""
    window, dead = 23, 24
    q, k, v = _qkv(12, 64)
    do = jnp.asarray(np.random.RandomState(6).randn(*q.shape), jnp.float32)
    o, l, m = pa._reference_residuals(q, k, v, True, window=window)
    call = functools.partial(
        pa._flash_bwd_call, causal=True, block_q=16, block_k=8,
        block_kv=64 // kv_blocks, t_valid=64, interpret=True, window=window)
    dq, _, _ = call(*pa._rounded(q, k.at[:, :, :dead].set(jnp.nan),
                                 v.at[:, :, :dead].set(jnp.nan)), o, l, m, do)
    assert np.isfinite(np.asarray(dq[:, :, 48:])).all()
    assert np.isnan(np.asarray(dq[:, :, :dead])).all()
    _, dk, dv = call(*pa._rounded(q, k, v), o, l, m,
                     do.at[:, :, 48:].set(jnp.nan))
    for g in (dk, dv):
        assert np.isfinite(np.asarray(g[:, :, :dead])).all()
        assert np.isnan(np.asarray(g[:, :, 48:])).all()


@pytest.mark.parametrize("window", [5, 48, None])
def test_off_tpu_fallback_takes_window_and_groups(window):
    q, k, v = _qkv(6, 48)
    np.testing.assert_allclose(
        np.asarray(pa.flash_attention(q, k, v, causal=True, window=window)),
        np.asarray(plain_attention(q, k, v, window)), atol=1e-5, rtol=1e-5)


def test_window_needs_causal_and_heads_must_divide():
    q, k, v = _qkv(7, 16)
    with pytest.raises(ValueError, match="causal"):
        pa.flash_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="heads"):
        pa.flash_attention(q, k[:, :1].repeat(4, 1), v, causal=True)
