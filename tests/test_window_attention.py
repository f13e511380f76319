"""`flash_attention` with a window and with grouped key/value heads, forward
and backward, against plain attention: the kernel through the interpreter,
both backward forms (key blocks; the tiles that hold a visible pair), and the
off-TPU fallback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import pallas_attention as pa

#: what rounding q x scale, k, v and p to bfloat16 costs the kernel against
#: float32 attention on unit-normal inputs (tests/test_pallas_ops.py)
BF16_ATOL = 3e-2


def plain_attention(q, k, v, window=None):
    """Causal softmax attention in float32 at `highest`, [B, H, T, D] queries
    over [B, Hk, T, D] keys and values: each q head reads head `h // group`;
    query i sees key j iff 0 <= i - j (< window)."""
    b, h, t, d = q.shape
    g = h // k.shape[1]
    k, v = (jnp.repeat(z, g, axis=1) for z in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / np.sqrt(d)
    gap = np.arange(t)[:, None] - np.arange(t)[None, :]
    mask = gap >= 0 if window is None else (gap >= 0) & (gap < window)
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
@pytest.mark.parametrize("tiled", [False, True], ids=["blocks", "tiles"])
def test_backward_matches_plain_attention(monkeypatch, window, tiled):
    """Both backward forms under a window and grouped heads: key blocks over
    all queries (a short sequence), and the tiles that hold a visible pair
    (a sequence of several `_BWD_TILE`s, 16 here)."""
    monkeypatch.setattr(pa, "_BWD_TILE", 16 if tiled else 1024)
    pa._flash_core.cache_clear()
    q, k, v = _qkv(3, 48)
    got = _grads(lambda *a: pa.flash_attention(
        *a, causal=True, block_q=16, block_k=8, interpret=True,
        window=window), q, k, v)
    want = _grads(lambda *a: plain_attention(*a, window=window), q, k, v)
    pa._flash_core.cache_clear()
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2 * BF16_ATOL, rtol=2 * BF16_ATOL)


def test_tiled_backward_equals_blockwise_backward():
    """The two forms are the same arithmetic in another order: float32
    rounding apart, they agree."""
    q, k, v = _qkv(4, 64)
    o, l, m = pa._reference_residuals(q, k, v, True, window=20)
    do = jnp.asarray(np.random.RandomState(5).randn(*q.shape), jnp.float32)
    a = pa._flash_backward_blockwise(q, k, v, o, l, m, do, causal=True,
                                     t_valid=64, block_k=8, window=20)
    b = pa._flash_backward_tiled(q, k, v, o, l, m, do, 20, 64, 16)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5, rtol=1e-5)


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
