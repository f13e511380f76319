"""The gated delta rule (`ops/delta_rule`): the chunked kernels through the
Pallas interpreter against the plain recurrence, which is the definition:
outputs and all five gradients, over one chunk, several and a row that is not
whole chunks, one and two value heads a key head, strong and weak decay, one
row and two; and which form a traced call took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.mlops import metrics
from fedml_tpu.ops import delta_rule as dr


def _data(seed, b, t, hk, hv, d, strong):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (b, t, hk, d))
    k = jax.random.normal(ks[1], (b, t, hk, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, hv, d))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, hv))) * (
        8.0 if strong else 0.05)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, t, hv, d))


def _gap(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.fixture
def operand(monkeypatch, request):
    """The type the kernels' products round their operands to: float32 shows
    the mathematics, bfloat16 is what runs.  Chunks of 16 here: the solve is
    unrolled over a chunk's rows, and the interpreter traces every case."""
    monkeypatch.setattr(dr, "_OPERAND", request.param)
    monkeypatch.setattr(dr, "_CHUNK", 16)
    return 2e-5 if request.param == "float32" else 2e-2


@pytest.mark.parametrize("operand", ["float32"], indirect=True)
@pytest.mark.parametrize("strong", [False, True], ids=["weak", "strong"])
@pytest.mark.parametrize("b,hk,hv", [(1, 2, 2), (2, 1, 2)],
                         ids=["ratio1", "ratio2_batch2"])
@pytest.mark.parametrize("t", [16, 40, 80],
                         ids=["one_chunk", "ragged", "blocks"])
def test_chunked_forward_is_the_recurrence(operand, t, b, hk, hv, strong):
    args, _ = _data(t, b, t, hk, hv, 16, strong)
    want = dr._recurrence(*args)
    got = dr.gated_delta_rule(*args, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert _gap(got, want) < operand


@pytest.mark.parametrize("operand,t,b,hk,hv,strong", [
    ("float32", 16, 1, 1, 1, False), ("float32", 40, 2, 1, 2, True),
    ("float32", 80, 1, 2, 4, False), ("bfloat16", 40, 1, 1, 2, False)],
    ids=["one_chunk", "ragged_ratio2_strong", "blocks_ratio2", "as_it_runs"],
    indirect=["operand"])
def test_chunked_gradients_are_autodiff_of_the_recurrence(operand, t, b, hk,
                                                          hv, strong):
    args, w = _data(t + 1, b, t, hk, hv, 16, strong)
    want = jax.grad(lambda *a: jnp.sum(dr._recurrence(*a) * w),
                    argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(lambda *a: jnp.sum(
        dr.gated_delta_rule(*a, interpret=True) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b_ in zip("q k v g beta".split(), got, want):
        assert a.shape == b_.shape, name
        assert _gap(a, b_) < operand, name


@pytest.mark.parametrize("strong", [False, True], ids=["weak", "strong"])
def test_chunks_of_64_rounded_as_the_chip_takes_them(strong):
    """The real chunk and the real rounding, over a chunk and a bit."""
    args, _ = _data(9, 1, 72, 1, 2, 16, strong)
    assert _gap(dr.gated_delta_rule(*args, interpret=True),
                dr._recurrence(*args)) < 2e-2


def test_a_state_that_is_never_written_reads_nothing(monkeypatch):
    monkeypatch.setattr(dr, "_CHUNK", 16)
    """beta = 0: nothing is written, so every output is zero, whatever the
    decay; and positions past a row's end neither write nor decay (a row of
    40 in blocks of 48 is padded)."""
    (q, k, v, g, beta), _ = _data(3, 1, 40, 1, 2, 16, False)
    out = dr.gated_delta_rule(q, k, v, g, jnp.zeros_like(beta),
                              interpret=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0
    whole = dr.gated_delta_rule(q, k, v, g, beta, interpret=True)
    head = dr.gated_delta_rule(q[:, :24], k[:, :24], v[:, :24], g[:, :24],
                               beta[:, :24], interpret=True)
    assert _gap(whole[:, :24], head) < 2e-2         # causal


def test_tiles_follow_the_shape():
    assert dr._tiles(16384, 128, 128) == (64, 4)
    assert dr._tiles(40, 16, 16) == (40, 1)
    assert dr._tiles(100, 16, 16) == (64, 2)
    chunk, subs = dr._tiles(16384, 512, 512)        # a budget, not an option
    assert chunk == 64 and subs < 4


def test_a_traced_call_counts_its_form():
    def count(path):
        m = metrics.REGISTRY.collect().get("fedml_delta_rule_traces_total")
        return 0 if m is None else sum(
            c.value for labels, c in m.children().items()
            if labels[m.label_names.index("path")] == path)

    (args, _) = _data(5, 1, 16, 1, 1, 16, False)
    before = count("recurrence"), count("interpret"), count("kernel_bwd")
    dr.gated_delta_rule(*args)                      # off the TPU: the plain
    jax.grad(lambda *a: dr.gated_delta_rule(*a, interpret=True).sum())(*args)
    after = count("recurrence"), count("interpret"), count("kernel_bwd")
    assert [a - b for a, b in zip(after, before)] == [1, 1, 1]


def test_key_heads_must_divide_value_heads():
    (q, k, v, g, beta), _ = _data(6, 1, 16, 2, 3, 16, False)
    with pytest.raises(ValueError, match="value heads"):
        dr.gated_delta_rule(q, k, v, g, beta)
