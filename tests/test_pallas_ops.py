"""Pallas kernels (interpret mode on CPU): parity with the jnp math."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_weighted_average_flat_matches_einsum():
    from fedml_tpu.ops.pallas_ops import weighted_average_flat

    rng = np.random.RandomState(0)
    stacked = jnp.asarray(rng.randn(10, 3000), jnp.float32)  # non-multiple D
    w = jnp.asarray(rng.rand(10), jnp.float32)
    out = weighted_average_flat(stacked, w, interpret=True)
    expect = (w / w.sum()) @ stacked
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_agg_stacked_pallas_matches_tree_version():
    from fedml_tpu.ml.aggregator.agg_operator import agg_stacked
    from fedml_tpu.ops.pallas_ops import agg_stacked_pallas

    rng = np.random.RandomState(1)
    tree = {"w": jnp.asarray(rng.randn(6, 17, 5), jnp.float32),
            "b": jnp.asarray(rng.randn(6, 9), jnp.float32)}
    w = jnp.asarray(rng.rand(6) * 10, jnp.float32)
    a = agg_stacked(tree, w)
    b = agg_stacked_pallas(tree, w, interpret=True)
    for k in tree:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   atol=1e-5, rtol=1e-5)


def test_quantize_mask_fused_matches_two_step():
    from fedml_tpu.core.mpc.secagg import mask_model, quantize
    from fedml_tpu.ops.pallas_ops import quantize_mask

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(777), jnp.float32)
    mask = jnp.asarray(rng.randint(0, 2**32, size=777, dtype=np.uint32))
    fused = quantize_mask(x, mask, interpret=True)
    two_step = mask_model(quantize({"x": x})["x"], mask)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(two_step))


#: what rounding q x scale, k, v and p to bfloat16 (8 bits of mantissa, so
#: 2**-9 of each operand) costs against attention computed in float32, on
#: unit-normal inputs: the tolerance of every comparison with
#: `reference_attention`.  The tight comparisons (2e-5) are with
#: `conftest.rounded_flash_reference`, which rounds the same operands.
BF16_ATOL = 3e-2


def _qkv(seed, t, d, tk=None, b=2, h=3):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, t, d), jnp.float32),
            jnp.asarray(rng.randn(b, h, tk or t, d), jnp.float32),
            jnp.asarray(rng.randn(b, h, tk or t, d), jnp.float32))


def _traces(**labels):
    """`fedml_attention_traces_total` for one set of labels."""
    from fedml_tpu.core.mlops import metrics

    m = metrics.REGISTRY.collect().get("fedml_attention_traces_total")
    return m.labels(**labels).value if m else 0


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,d,bq,bk", [
    (32, 16, 8, 8),      # exact block fit
    (40, 16, 16, 8),     # T needs padding to block_q
    (17, 8, 8, 8),       # ragged T
])
def test_flash_attention_matches_reference(causal, t, d, bq, bk):
    from conftest import rounded_flash_reference
    from fedml_tpu.ops.pallas_attention import flash_attention
    from fedml_tpu.parallel.ring_attention import reference_attention

    q, k, v = _qkv(3, t, d)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    pad = [(0, 0), (0, 0), (0, -t % bk), (0, 0)]
    tight, _, _ = rounded_flash_reference(
        q, jnp.pad(k, pad), jnp.pad(v, pad), causal, bk, t_valid=t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(tight),
                               atol=2e-5, rtol=2e-5)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=BF16_ATOL, rtol=BF16_ATOL)


def test_flash_attention_off_tpu_fallback_matches():
    """interpret=None off-TPU routes to the jnp fallback, same math, and
    says so in the counter."""
    from fedml_tpu.ops.pallas_attention import flash_attention
    from fedml_tpu.parallel.ring_attention import reference_attention

    q, k, v = _qkv(4, 24, 8, b=1, h=2)
    before = _traces(path="reference", block_q=0, block_k=0,
                     kv_resident="false", head_dim=8)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert _traces(path="reference", block_q=0, block_k=0,
                   kv_resident="false", head_dim=8) == before + 1


def _assert_partial(got, want):
    """(o, l, m) of the kernel against the rounded reference's."""
    for a, b, name in zip(got, want, "olm"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=2e-5, err_msg=name)


def _budget_of_two_passes(monkeypatch):
    """Shrinks the kernel's VMEM budget to the K and V of two passes of 8
    keys (double-buffered, 128 lanes of float32 a key)."""
    from fedml_tpu.ops import pallas_attention as pa

    monkeypatch.setattr(pa, "_KV_VMEM_BUDGET", 2 * 8 * 4 * 128 * 4)


@pytest.mark.parametrize("resident", [True, False],
                         ids=["kv-resident", "kv-on-grid"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_schedule_kv_resident_or_on_the_grid(monkeypatch, causal,
                                                   resident):
    """64 keys, 8 a pass.  Resident: a head's K and V are one block, eight
    passes a grid step.  With a VMEM budget of two passes' keys the K/V axis
    goes on the grid in blocks of 16, its index clamped to the last live
    block, and under `causal` the first q tile has three dead steps."""
    from conftest import rounded_flash_reference
    from fedml_tpu.ops import pallas_attention as pa

    if not resident:
        _budget_of_two_passes(monkeypatch)
    q, k, v = _qkv(7, 64, 16)
    labels = dict(path="kernel", block_q=16, block_k=8,
                  kv_resident=str(resident).lower(), head_dim=16)
    before = _traces(**labels)
    got = pa.flash_attention_residuals(q, k, v, causal=causal, block_q=16,
                                       block_k=8, interpret=True)
    assert _traces(**labels) == before + 1
    _assert_partial(got, rounded_flash_reference(q, k, v, causal, 8))


@pytest.mark.parametrize("resident", [True, False],
                         ids=["kv-resident", "kv-on-grid"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_counts_its_kernel_and_its_kv_blocks(monkeypatch,
                                                            causal, resident):
    """A traced backward says `kernel_bwd` once, with the forward's tile.
    The budget that puts the forward's K and V on the grid in blocks of two
    passes holds, doubled, the backward's K, V, dK and dV of two passes too:
    four K/V blocks, each q tile's dQ the sum of their partials."""
    from fedml_tpu.ops import pallas_attention as pa
    from fedml_tpu.parallel.ring_attention import reference_attention

    if not resident:
        _budget_of_two_passes(monkeypatch)
    q, k, v = _qkv(12, 64, 16)
    labels = dict(path="kernel_bwd", block_q=16, block_k=8,
                  kv_resident=str(resident).lower(), head_dim=16)
    before = _traces(**labels)
    got = jax.grad(lambda *a: jnp.sum(pa.flash_attention(
        *a, causal=causal, block_q=16, block_k=8, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    assert _traces(**labels) == before + 1
    want = jax.grad(lambda *a: jnp.sum(reference_attention(
        *a, causal=causal) ** 2), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2 * BF16_ATOL, rtol=2 * BF16_ATOL)


@pytest.mark.parametrize("resident", [True, False],
                         ids=["kv-resident", "kv-on-grid"])
@pytest.mark.parametrize("tile", [0, 1, 3], ids=["first", "middle", "last"])
def test_flash_causal_tile_stops_at_its_diagonal(monkeypatch, tile,
                                                 resident):
    """A causal q tile visits no key beyond its own last query: V is NaN
    there (a visited pass would carry it into the tile through 0 x NaN), and
    the tile's rows come out finite and equal to the reference's."""
    from conftest import rounded_flash_reference
    from fedml_tpu.ops import pallas_attention as pa

    if not resident:
        _budget_of_two_passes(monkeypatch)
    q, k, v = _qkv(8, 64, 16)
    rows = slice(tile * 16, tile * 16 + 16)
    poisoned = v.at[:, :, rows.stop:].set(jnp.nan)
    got = pa.flash_attention_residuals(q, k, poisoned, causal=True,
                                       block_q=16, block_k=8, interpret=True)
    want = rounded_flash_reference(q, k, v, True, 8)
    _assert_partial([a[:, :, rows] for a in got],
                    [a[:, :, rows] for a in want])


def test_flash_residuals_non_causal_tk_longer_with_padded_keys():
    """16 queries over 48 keys of which 41 are valid: five passes need no
    mask, the sixth masks its padded keys, and l and m say so."""
    from conftest import rounded_flash_reference
    from fedml_tpu.ops.pallas_attention import (
        _reference_residuals, flash_attention_residuals)

    q, k, v = _qkv(9, 16, 8, tk=48)
    got = flash_attention_residuals(q, k, v, causal=False, block_q=8,
                                    block_k=8, interpret=True, t_valid=41)
    _assert_partial(got, rounded_flash_reference(q, k, v, False, 8,
                                                 t_valid=41))
    for a, b in zip(got, _reference_residuals(q, k, v, False, 41)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=BF16_ATOL, rtol=BF16_ATOL)


@pytest.mark.parametrize("t,bq,bk", [(500, 512, 512), (768, 256, 256),
                                     (384, 128, 128)])
def test_flash_attention_default_blocks_follow_the_shape(t, bq, bk):
    """No blocks given: 500 positions pad to four 128-position tiles and
    take all 512 queries in a grid step and all 512 keys a pass; 768 are
    three steps of 256 queries, 256 keys a pass; 384 divide by neither and
    keep 128 x 128.  The counter names the tile that ran."""
    from conftest import rounded_flash_reference
    from fedml_tpu.ops.pallas_attention import flash_attention
    from fedml_tpu.parallel.ring_attention import reference_attention

    q, k, v = _qkv(10, t, 16, b=1, h=2)
    labels = dict(path="kernel", block_q=bq, block_k=bk, kv_resident="true",
                  head_dim=16)
    before = _traces(**labels)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert _traces(**labels) == before + 1
    pad = [(0, 0), (0, 0), (0, -t % bk), (0, 0)]
    tight, _, _ = rounded_flash_reference(
        q, jnp.pad(k, pad), jnp.pad(v, pad), True, bk, t_valid=t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(tight),
                               atol=2e-5, rtol=2e-5)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=BF16_ATOL, rtol=BF16_ATOL)


def test_flash_residuals_merge_matches_full():
    """Splitting keys in two, computing partials, and merging equals full
    attention — the ring-attention combine.  The merged l and m are the
    whole's own (m exactly: a maximum; l a float32 sum in another order)."""
    from fedml_tpu.ops.pallas_attention import (
        flash_attention_residuals, merge_attention_partials)
    from fedml_tpu.parallel.ring_attention import reference_attention

    q, k, v = _qkv(5, 16, 8, tk=32, h=2)
    pa = flash_attention_residuals(q, k[:, :, :16], v[:, :, :16],
                                   causal=False, interpret=True)
    pb = flash_attention_residuals(q, k[:, :, 16:], v[:, :, 16:],
                                   causal=False, interpret=True)
    o, l, m = merge_attention_partials(pa, pb)
    whole_o, whole_l, whole_m = flash_attention_residuals(
        q, k, v, causal=False, block_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(whole_m))
    np.testing.assert_allclose(np.asarray(l), np.asarray(whole_l), rtol=2e-5)
    # o's p were rounded against another running max in the two halves
    np.testing.assert_allclose(np.asarray(o), np.asarray(whole_o),
                               atol=BF16_ATOL, rtol=BF16_ATOL)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=BF16_ATOL, rtol=BF16_ATOL)


def test_int8_matmul_matches_dequant_reference():
    from fedml_tpu.ops.pallas_ops import int8_matmul
    from fedml_tpu.serving.quantization import quantize_matrix_int8

    rng = np.random.RandomState(6)
    w = jnp.asarray(rng.randn(48, 700), jnp.float32)  # N not block-aligned
    x = jnp.asarray(rng.randn(4, 48), jnp.float32)
    qs = quantize_matrix_int8(w)
    out = int8_matmul(x, qs["q"], qs["s"], interpret=True)
    ref = (x @ (qs["q"].astype(jnp.float32) * qs["s"][None, :]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    # and the quantization itself tracks the dense matrix
    assert float(jnp.max(jnp.abs(w - qs["q"].astype(jnp.float32)
                                 * qs["s"][None, :]))) < 0.05
