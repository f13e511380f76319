"""The delta-rule mixer's passes around the scan (`ops/delta_rule`: the
kernels `gdn_operands_fwd` / `_bwd` and `gdn_gate_fwd` / `_bwd`) through the
Pallas interpreter against the jnp that defines them (`plain_operands`,
`plain_gate`, the recurrence): q, k, v and the gated output; the gradients to
every column of the projection, to the convolution's weights, the gated
norm's scale, the decay and the writing strength; a row's start (the zero
halo), rows that are no whole block, two value heads a key head; and which
path a traced call took."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.mlops import metrics
from fedml_tpu.ops import delta_rule as dr

EPS = 1e-6


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 16 positions in slabs of 8 and chunks of 16, float32
    operands in the scan: every case crosses blocks, and what is compared is
    the mathematics (the scan's rounding is `test_delta_rule.py`'s)."""
    monkeypatch.setattr(dr, "_MIXER_ROWS", 16)
    monkeypatch.setattr(dr, "_SLAB", 8)
    monkeypatch.setattr(dr, "_CHUNK", 16)
    monkeypatch.setattr(dr, "_OPERAND", "float32")


def _data(seed, b, t, hk, hv, d, taps=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = 2 * hk * d + hv * d
    return dict(
        qkvz=jax.random.normal(ks[0], (b, t, n + hv * d)),
        g=-jax.nn.softplus(jax.random.normal(ks[1], (b, t, hv))) * 0.3,
        beta=jax.nn.sigmoid(jax.random.normal(ks[2], (b, t, hv))),
        conv=jax.random.normal(ks[3], (n, taps)) * 0.5,
        scale=1.0 + 0.1 * jax.random.normal(ks[4], (d,))), ks[5]


def _plain(qkvz, g, beta, conv, scale, heads):
    o = dr._recurrence(*dr.plain_operands(qkvz, conv, heads), g, beta)
    return dr.plain_gate(o, qkvz, scale, EPS).reshape(*qkvz.shape[:2], -1)


def _gap(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _tile(t, heads, d):
    lanes = dr._mixer_lanes(heads, (d, d))
    return dict(heads=heads, rows=dr._mixer_rows(t, 4 * lanes), lanes=lanes,
                interpret=True)


SHAPES = pytest.mark.parametrize("b,t,hk,hv,d", [
    (1, 16, 1, 1, 16), (2, 40, 1, 2, 16), (1, 48, 2, 4, 8), (1, 21, 2, 2, 8)],
    ids=["one_block", "ragged_ratio2_batch2", "blocks_ratio2", "odd_rows"])


@SHAPES
def test_operands_are_the_definition(b, t, hk, hv, d):
    data, _ = _data(t, b, t, hk, hv, d)
    want = dr.plain_operands(data["qkvz"], data["conv"], (hk, hv))
    got = dr._operands_fwd_call(data["qkvz"], data["conv"].T,
                                **_tile(t, (hk, hv), d))
    for name, a, w in zip("qkv", got, want):
        assert a.dtype == jnp.float32 and a.shape == (b, t, w.shape[2] * d)
        assert _gap(a, w.reshape(a.shape)) < 1e-5, name
    # a row's first positions see zeros, not the row before
    np.testing.assert_allclose(got[2][0, 0], np.asarray(jax.nn.silu(
        data["qkvz"][0, 0, 2 * hk * d:(2 * hk + hv) * d]
        * data["conv"][2 * hk * d:, -1])), rtol=1e-5, atol=1e-6)


@SHAPES
def test_gated_output_is_the_definition(b, t, hk, hv, d):
    data, key = _data(t + 1, b, t, hk, hv, d)
    o = jax.random.normal(key, (b, t, hv, d))
    want = dr.plain_gate(o, data["qkvz"], data["scale"], EPS)
    got = dr._gate_fwd_call(o.reshape(b, t, -1), data["qkvz"],
                            data["scale"].reshape(1, -1), eps=EPS,
                            **_tile(t, (hk, hv), d))
    assert got.dtype == jnp.float32
    assert _gap(got, want.reshape(got.shape)) < 1e-5


@SHAPES
def test_backward_kernels_are_autodiff_of_the_definition(b, t, hk, hv, d):
    """Each backward kernel alone: the projection's gradient is one array,
    its z columns written by the gate's kernel and the others, in place, by
    the operands' (which sums a key head's value heads as it reads)."""
    data, key = _data(t + 2, b, t, hk, hv, d)
    qkvz, conv, scale = data["qkvz"], data["conv"], data["scale"]
    ks = jax.random.split(key, 5)
    o, dy, dv = (jax.random.normal(k, (b, t, hv * d)) for k in ks[:3])
    dq, dk = (jax.random.normal(k, (b, t, hv * d)) for k in ks[3:])
    tile = _tile(t, (hk, hv), d)
    by = lambda z, h: z.reshape(b, t, h, -1)
    want_o, want_z = jax.vjp(lambda o_, x: dr.plain_gate(
        by(o_, hv), x, scale, EPS), o, qkvz)[1](by(dy, hv))
    do, d_qkvz = dr._gate_bwd_call(o, qkvz, scale.reshape(1, -1), dy,
                                   eps=EPS, **tile)
    assert _gap(do, want_o) < 1e-5
    of_key = lambda z: z.reshape(b, t, hk, hv // hk, -1).sum(3)
    want_x, = jax.vjp(lambda x: dr.plain_operands(x, conv, (hk, hv)), qkvz)[
        1]((of_key(dq), of_key(dk), by(dv, hv)))
    got = dr._operands_bwd_call(qkvz, conv.T, dq, dk, dv, d_qkvz, **tile)
    assert got.shape == qkvz.shape
    assert _gap(got, want_x + want_z) < 1e-5
    assert _gap(got[..., -hv * d:], want_z[..., -hv * d:]) < 1e-5


@pytest.mark.parametrize("b,t,hk,hv,d", [
    (1, 16, 1, 1, 16), (2, 40, 1, 2, 16), (1, 48, 2, 4, 8)],
    ids=["one_block", "ragged_ratio2_batch2", "blocks_ratio2"])
def test_mixer_and_its_gradients_are_the_definition(b, t, hk, hv, d):
    """The whole of it under its one `custom_vjp`, the scan's kernels
    between the mixer's: the gated output and the gradients to all five."""
    data, key = _data(t + 3, b, t, hk, hv, d)
    w = jax.random.normal(key, (b, t, hv * d))
    mixer = functools.partial(dr.gated_delta_mixer, heads=(hk, hv), eps=EPS,
                              interpret=True)
    got = mixer(**data)
    assert got.dtype == jnp.float32
    assert _gap(got, _plain(heads=(hk, hv), **data)) < 2e-5
    names = sorted(data)
    grads = lambda fn: jax.grad(lambda args: jnp.sum(fn(**args) * w))(data)
    got_g = grads(mixer)
    want_g = grads(functools.partial(_plain, heads=(hk, hv)))
    for name in names:
        assert got_g[name].shape == data[name].shape, name
        assert _gap(got_g[name], want_g[name]) < 5e-5, name
    z = slice(-hv * d, None)                # the gate's columns too
    assert _gap(got_g["qkvz"][..., z], want_g["qkvz"][..., z]) < 5e-5


def _count(part, path):
    m = metrics.REGISTRY.collect().get("fedml_delta_mixer_traces_total")
    return 0 if m is None else sum(
        c.value for labels, c in m.children().items()
        if labels == (part, path))


PARTS = ("operands", "gate", "operands_bwd", "gate_bwd")


def test_a_traced_call_counts_its_parts_and_path(monkeypatch):
    data, _ = _data(7, 1, 16, 1, 1, 16)
    mixer = functools.partial(dr.gated_delta_mixer, heads=(1, 1), eps=EPS)
    counts = lambda path: [_count(part, path) for part in PARTS]
    before = counts("interpret"), counts("jnp")
    assert mixer(**data) is None                    # off the TPU: the jnp
    jax.grad(lambda x: mixer(**dict(data, qkvz=x), interpret=True).sum())(
        data["qkvz"])
    after = counts("interpret"), counts("jnp")
    assert [a - b for a, b in zip(after[0], before[0])] == [1, 1, 1, 1]
    assert [a - b for a, b in zip(after[1], before[1])] == [1, 1, 0, 0]


@pytest.mark.parametrize("d,dtype,kernels", [
    (64, jnp.float32, False), (128, jnp.bfloat16, False),
    (128, jnp.float32, True)], ids=["head_of_64", "bfloat16", "head_of_128"])
def test_on_a_tpu_only_whole_lane_tiles_in_float32_take_the_kernels(
        monkeypatch, d, dtype, kernels):
    """Nothing but the shapes, the type and the backend chooses: a head of
    64 takes the jnp path on a TPU too, and the counter says so."""
    monkeypatch.setattr(dr, "_on_tpu", lambda: True)
    taken = []
    monkeypatch.setattr(dr, "_mixed", lambda *a: taken.append(a) or (
        lambda *operands: "kernels"))
    data, _ = _data(8, 1, 16, 1, 2, d)
    before = _count("operands", "jnp")
    out = dr.gated_delta_mixer(**dict(data, qkvz=data["qkvz"].astype(dtype)),
                               heads=(1, 2), eps=EPS)
    assert (out == "kernels") == kernels == bool(taken)
    assert _count("operands", "jnp") - before == (0 if kernels else 1)
    if kernels:
        assert taken[0][-1] is False                # compiled, not interpreted


def test_tiles_follow_the_shape(monkeypatch):
    lanes = dr._mixer_lanes
    assert lanes((16, 32), (128, 128)) == 512
    assert lanes((2, 2), (8, 8)) == 16
    assert lanes((1, 2), (1024, 1024)) is None      # no block holds a head
    assert lanes((2, 2), (128, 256)) == 256
    assert lanes((3, 3), (128, 256)) is None        # q's columns: 384
    monkeypatch.undo()                              # the real blocks
    # a budget, not an option: the operands' backward holds seven blocks
    assert dr._mixer_rows(16384, 4 * 512) == 512
    assert dr._mixer_rows(16384, 7 * 512) == 256
    assert dr._mixer_rows(21, 4 * 16) == 24 and dr._mixer_rows(5, 128) == 8


def test_key_heads_must_divide_value_heads():
    data, _ = _data(9, 1, 16, 2, 2, 8)
    with pytest.raises(ValueError, match="value heads"):
        dr.gated_delta_mixer(**data, heads=(2, 3), eps=EPS)
