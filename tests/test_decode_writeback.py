"""`decode_multi` stores a dispatch's k new positions into the donated cache
in place.  The store moves values and does no arithmetic, so the cache after
a dispatch and the tokens emitted are, bit for bit, those of the reference
kept here: the same scan, its chunk merged over the whole cache by a gather
and a select, as `decode_multi` itself did before PR 25.

That the compiled program really stores in place (no temporary of the
cache's shape, the output aliasing the donated input) is held by
`tests/test_chip_compile.py::test_decode_multi_stores_in_place_for_v5e`,
in the one file that describes a topology.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.serving import kv_cache_lm
from fedml_tpu.serving.kv_cache_lm import KVCacheLM

B, T, HEADS, DIM, VOCAB = 7, 40, 4, 32, 50


@functools.partial(jax.jit, static_argnames=("heads", "k"))
def _reference_decode_multi(params, cache, prompt_buf, prompt_n, pos0, temps,
                            top_k, top_p, rng, heads, k):
    """`_decode_multi`'s scan, then the write-back as it was: position
    ``iota`` of row i takes chunk slot ``iota - pos0[i]`` where that lies in
    [0, k), and keeps the cache's value elsewhere (so a position at or
    beyond the cache's end is dropped)."""
    b = prompt_buf.shape[0]
    nl = len(params["blocks"])
    dh = params["embed"].shape[1] // heads
    dt = cache[0]["k"].dtype
    kc0 = jnp.zeros((nl, b, k, heads, dh), dt)

    def step(carry, j):
        kc, vc, tok, rng = carry
        kc, vc, logits = kv_cache_lm._decode_core_chunked(
            params, cache, kc, vc, tok, pos0, j, heads)
        rng, sub = jax.random.split(rng)
        out_tok = kv_cache_lm._filter_sample(logits, temps, top_k, top_p, sub)
        nxt = jnp.where(j + 1 < prompt_n,
                        prompt_buf[jnp.arange(b), jnp.minimum(j + 1, k - 1)],
                        out_tok)
        return (kc, vc, nxt, rng), out_tok

    (kc, vc, _, _), emitted = jax.lax.scan(
        step, (kc0, kc0, prompt_buf[:, 0], rng), jnp.arange(k))
    iota = jnp.arange(cache[0]["k"].shape[-1])
    hit = ((iota[None] >= pos0[:, None])
           & (iota[None] < pos0[:, None] + k))[:, None, None, :]
    slot = jnp.clip(iota[None] - pos0[:, None], 0, k - 1)[:, None, None, :]

    def merge(chunk, full):         # chunk [B, k, H, Dh], full [B, H, Dh, T]
        return jnp.where(hit, jnp.take_along_axis(
            chunk.transpose(0, 2, 3, 1), slot, axis=3), full)

    merged = [{"k": merge(kc[li], layer["k"]), "v": merge(vc[li], layer["v"])}
              for li, layer in enumerate(cache)]
    return merged, emitted.T


def _lm(dtype):
    lm = KVCacheLM.create(jax.random.PRNGKey(5), vocab=VOCAB, dim=DIM,
                          layers=2, heads=HEADS, max_len=T)
    lm.params = jax.tree_util.tree_map(lambda a: a.astype(dtype), lm.params)
    return lm


def _filled_cache(lm):
    """A cache with something in every position, so that a store in the
    wrong place shows wherever it lands."""
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 64))
    return [{name: jax.random.normal(next(keys), a.shape, a.dtype)
             for name, a in layer.items()} for layer in lm.init_cache(B)]


def _operands(k, pos0, seed):
    """Rows as the engine builds them: 0 and 1 mid-generation, 2 teacher-
    forced through the whole chunk, 3 sampling at a temperature with top-k,
    4 and 5 idle (no request: zeros, a position left from an earlier one),
    6 part prompt and part its own samples."""
    r = np.random.default_rng(seed)
    buf = r.integers(0, VOCAB, (B, k)).astype(np.int32)
    n = np.ones((B,), np.int32)
    n[2], n[6] = k, (k + 1) // 2
    buf[4:6] = 0
    temps = np.zeros((B,), np.float32)
    temps[3] = 0.8
    top_k = np.zeros((B,), np.int32)
    top_k[3] = 5
    return tuple(jnp.asarray(a) for a in (
        buf, n, np.asarray(pos0, np.int32), temps, top_k,
        np.ones((B,), np.float32)))


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _run_both(lm, k, pos0, seed):
    ops = _operands(k, pos0, seed)
    rng = jax.random.PRNGKey(seed)
    want = _reference_decode_multi(lm.params, _filled_cache(lm), *ops, rng,
                                   heads=HEADS, k=k)
    # its own copy of the cache: `decode_multi` donates the one it is given
    got = lm.decode_multi(_filled_cache(lm), *ops, rng, k)
    return want, got


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("k", [1, 2, 8])
def test_decode_multi_cache_and_tokens_equal_the_select_reference(k, dtype):
    lm = _lm(dtype)
    pos0 = np.random.default_rng(k).integers(0, T - k + 1, B)
    pos0[0], pos0[1], pos0[4] = 0, T - k, T - k
    (want_cache, want_tokens), (got_cache, got_tokens) = _run_both(
        lm, k, pos0, seed=3 + k)
    np.testing.assert_array_equal(np.asarray(got_tokens),
                                  np.asarray(want_tokens))
    for li, (got, want) in enumerate(zip(got_cache, want_cache)):
        for name in ("k", "v"):
            assert got[name].dtype == dtype
            assert got[name].shape == (B, HEADS, DIM // HEADS, T)
            np.testing.assert_array_equal(_bits(got[name]), _bits(want[name]),
                                          err_msg=f"layer {li} {name}")
    # and the dispatch did store something: k positions of every row
    before = _filled_cache(lm)
    changed = np.any(_bits(got_cache[0]["k"]) != _bits(before[0]["k"]),
                     axis=(1, 2))
    want_changed = ((np.arange(T)[None] >= pos0[:, None])
                    & (np.arange(T)[None] < pos0[:, None] + k))
    np.testing.assert_array_equal(changed, want_changed)


def test_positions_beyond_the_cache_end_are_dropped():
    """The contract `store_positions` states for ``pos0 + k > T``, which is
    what the select did: such a row's positions below T are stored where
    they belong, those at or beyond T are dropped, and nothing is moved to
    fit (a clamped update would land on older positions).  The tokens are
    the scan's, which the store does not touch."""
    k = 8
    lm = _lm(jnp.bfloat16)
    pos0 = np.array([0, T - k, T - k + 1, T - 1, 5, T - 3, 11])
    (want_cache, want_tokens), (got_cache, got_tokens) = _run_both(
        lm, k, pos0, seed=9)
    np.testing.assert_array_equal(np.asarray(got_tokens),
                                  np.asarray(want_tokens))
    before = _filled_cache(lm)
    inside = ((np.arange(T)[None] >= pos0[:, None])
              & (np.arange(T)[None] < pos0[:, None] + k))
    for got, want, was in zip(got_cache, want_cache, before):
        for name in ("k", "v"):
            np.testing.assert_array_equal(_bits(got[name]), _bits(want[name]))
            changed = np.any(_bits(got[name]) != _bits(was[name]), axis=(1, 2))
            np.testing.assert_array_equal(changed, inside)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("k", [1, 2, 8, 130])
@pytest.mark.parametrize("t", [384, 300], ids=["whole-blocks", "ragged"])
def test_store_positions_over_lane_blocks(t, k, dtype):
    """At lengths of more than one 128-position block, the last block whole
    or ragged: windows inside one block, ending at a block's last position,
    straddling two, starting at a block's first, and running off the end;
    at k = 130 a dispatch longer than the 64 positions one call stores."""
    from fedml_tpu.ops.pallas_kv_store import store_positions

    b, h, dh = 8, 3, 8
    r = np.random.default_rng(k)
    arrays = [jnp.asarray(r.normal(size=(b, h, dh, t)), dtype)
              for _ in range(2)]
    chunks = [jnp.asarray(r.normal(size=(b, h, dh, k)), dtype)
              for _ in range(2)]
    pos0 = np.array([0, 128 - k, 127, 128, 250, t - k, t - 1, t - 84],
                    np.int32)
    pos0 = np.maximum(pos0, 0)
    got = jax.jit(store_positions)(arrays, chunks, jnp.asarray(pos0))
    for array, chunk, out in zip(arrays, chunks, got):
        want = np.asarray(array).copy()
        for i in range(b):
            n = min(k, t - pos0[i])
            want[i, :, :, pos0[i]:pos0[i] + n] = np.asarray(chunk)[i, :, :, :n]
        np.testing.assert_array_equal(_bits(out), _bits(want))
