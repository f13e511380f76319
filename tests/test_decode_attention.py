"""`decode_multi`'s attention reads the live blocks of each row's cache and
merges them with the dispatch's chunk by the softmax statistics.

The form it replaced is kept here as the reference: two contractions over
the whole cache length, masked afterwards, one softmax over cache and chunk
together (`_decode_core_chunked` until PR 28).  Blocks change the order of
summation, so results are close and not bit-equal: the tolerances below are
stated in float32 roundings of values of order 1.

That the compiled program holds no full-length score any more is held by
`tests/test_chip_compile.py::test_decode_multi_scores_no_dead_position_for_v5e`.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.mlops import metrics
from fedml_tpu.models import functional_lm
from fedml_tpu.ops.pallas_decode_attention import MASKED, decode_attention
from fedml_tpu.serving import kv_cache_lm
from fedml_tpu.serving.kv_cache_lm import KVCacheLM
from fedml_tpu.serving.llm_engine import CACHE_BLOCK, KVCacheLLMEngine

HEADS, DH = 4, 16
#: lengths mixed in one batch: none, one, around a block's edge, ragged, long
LENGTHS = (0, 1, 127, 128, 129, 300, 1000)


def _reference_attention(q, k, v, kc, vc, pos0, j):
    """The deleted jnp form: scores against all T positions, masked
    afterwards; softmax over cache and chunk together."""
    t = k.shape[-1]
    dh = q.shape[-1]
    valid_full = jnp.arange(t)[None] < pos0[:, None]
    valid_chunk = jnp.arange(kc.shape[1]) <= j
    s_full = jnp.einsum("bhd,bhdt->bht", q, k) / np.sqrt(dh)
    s_full = jnp.where(valid_full[:, None, :], s_full, -1e30)
    s_chunk = jnp.einsum("bhd,bkhd->bhk", q, kc) / np.sqrt(dh)
    s_chunk = jnp.where(valid_chunk[None, None, :], s_chunk, -1e30)
    w = jax.nn.softmax(jnp.concatenate([s_full, s_chunk], axis=-1), axis=-1)
    return (jnp.einsum("bht,bhdt->bhd", w[..., :t], v)
            + jnp.einsum("bhk,bkhd->bhd", w[..., t:], vc))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("t", [384, 300, 1024])
def test_kernel_and_chunk_merge_equal_the_full_length_form(t, k, dtype):
    lengths = np.minimum(LENGTHS, t).astype(np.int32)
    b = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(t + k), 5)
    q = jax.random.normal(keys[0], (b, HEADS, DH), jnp.float32)
    cache_k = jax.random.normal(keys[1], (b, HEADS, DH, t), dtype)
    cache_v = jax.random.normal(keys[2], (b, HEADS, DH, t), dtype)
    kc = jax.random.normal(keys[3], (b, k, HEADS, DH), dtype)
    vc = jax.random.normal(keys[4], (b, k, HEADS, DH), dtype)
    for j in sorted({0, k - 1}):
        want = _reference_attention(q, cache_k, cache_v, kc, vc,
                                    jnp.asarray(lengths), j)
        got = kv_cache_lm._attend_cache_and_chunk(
            q, {"k": cache_k, "v": cache_v}, kc, vc, jnp.asarray(lengths), j)
        assert got.dtype == want.dtype == jnp.float32
        # outputs are averages of unit normals: a few float32 roundings
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=2e-5)


def test_kernel_statistics_and_an_empty_row():
    """``(o, m, l)`` against numpy, row by row; a row of length 0 gives the
    statistics of no position, whatever its cache holds (NaN here)."""
    t = 300
    lengths = np.asarray([0, 5, 128, 300, 0, 257], np.int32)
    b = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = np.asarray(jax.random.normal(keys[0], (b, HEADS, DH)))
    k = np.array(jax.random.normal(keys[1], (b, HEADS, DH, t)))
    v = np.array(jax.random.normal(keys[2], (b, HEADS, DH, t)))
    k[0] = v[0] = np.nan
    o, m, l = (np.asarray(a) for a in decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(lengths),
        0.25))
    for i, n in enumerate(lengths):
        if n == 0:
            assert (m[i] == MASKED).all() and (l[i] == 0).all()
            assert (o[i] == 0).all()
            continue
        s = 0.25 * np.einsum("hd,hdt->ht", q[i], k[i, :, :, :n])
        np.testing.assert_allclose(m[i], s.max(-1), rtol=1e-6, atol=1e-6)
        p = np.exp(s - s.max(-1, keepdims=True))
        np.testing.assert_allclose(l[i], p.sum(-1), rtol=1e-5)
        np.testing.assert_allclose(
            o[i], np.einsum("ht,hdt->hd", p, v[i, :, :, :n]), atol=1e-4)


# -- `decode_multi` on a small LM ---------------------------------------------

B, T, DIM, VOCAB = 7, 300, 32, 50


@functools.partial(jax.jit, static_argnames=("heads", "k"))
def _reference_decode_multi(params, cache, prompt_buf, pos0, heads, k):
    """Greedy `decode_multi` with the full-length attention: the tokens it
    emits and the logits of every inner step."""
    b = prompt_buf.shape[0]
    dh = params["embed"].shape[1] // heads
    dt = cache[0]["k"].dtype
    chunk0 = jnp.zeros((len(params["blocks"]), b, k, heads, dh), dt)

    def step(carry, j):
        kc, vc, tok = carry
        h = functional_lm.embed(params, tok, pos0 + j)
        for li, (blk, layer) in enumerate(zip(params["blocks"], cache)):
            def attend(q, k_new, v_new, li=li, layer=layer):
                nonlocal kc, vc
                kc = kc.at[li, :, j].set(k_new.astype(dt))
                vc = vc.at[li, :, j].set(v_new.astype(dt))
                return _reference_attention(q, layer["k"], layer["v"],
                                            kc[li], vc[li], pos0, j)

            h = functional_lm.block(h, blk, heads, attend)
        logits = functional_lm.head(h, params)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (kc, vc, tok), (tok, logits)

    _, (toks, logits) = jax.lax.scan(
        step, (chunk0, chunk0, prompt_buf[:, 0]), jnp.arange(k))
    return toks.T, logits


def _small_lm(dtype):
    lm = KVCacheLM.create(jax.random.PRNGKey(5), vocab=VOCAB, dim=DIM,
                          layers=2, heads=HEADS, max_len=T)
    lm.params = jax.tree_util.tree_map(lambda a: a.astype(dtype), lm.params)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    cache = [{name: jax.random.normal(next(keys), a.shape, a.dtype)
              for name, a in layer.items()} for layer in lm.init_cache(B)]
    return lm, cache


#: how far apart a logit of the kernel's path may lie from the reference's
#: (logits here are under 0.5).  Past the first attention the activations
#: are float32 in both, so what differs is the order of float32 sums; with
#: bfloat16 weights one such difference may tip a rounding to bfloat16
#: (2**-9 of a value) on the way, once in a while.
LOGIT_TOL = {jnp.bfloat16: 2e-3, jnp.float32: 2e-5}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("k", [2, 8])
def test_decode_multi_tokens_equal_the_full_length_reference(k, dtype):
    """Mid-generation rows at mixed lengths (an idle one at 0): the tokens
    are the reference's wherever its two best logits lie further apart than
    twice the tolerance, and the first inner step's logits lie within it."""
    lm, cache = _small_lm(dtype)
    tol = LOGIT_TOL[dtype]
    pos0 = jnp.asarray([0, 1, 127, 128, 129, 255, T - k], jnp.int32)
    buf = jnp.asarray(np.random.default_rng(k).integers(0, VOCAB, (B, k)),
                      jnp.int32)
    want, logits = _reference_decode_multi(lm.params, cache, buf, pos0,
                                           heads=HEADS, k=k)
    chunk0 = jnp.zeros((2, B, k, HEADS, DIM // HEADS), dtype)
    _, _, first = jax.jit(kv_cache_lm._decode_core_chunked, static_argnums=7)(
        lm.params, cache, chunk0, chunk0, buf[:, 0], pos0, 0, HEADS)
    np.testing.assert_allclose(np.asarray(first, np.float32),
                               np.asarray(logits[0], np.float32),
                               rtol=0, atol=tol)
    zeros = jnp.zeros((B,), jnp.float32)
    _, got = lm.decode_multi(
        cache, buf, jnp.ones((B,), jnp.int32), pos0, zeros,
        jnp.zeros((B,), jnp.int32), zeros + 1, jax.random.PRNGKey(0), k)
    best = np.sort(np.asarray(logits, np.float32), axis=-1)
    clear = (best[..., -1] - best[..., -2] > 2 * tol).T         # [B, k]
    # a token that differs feeds the later steps of its row: compare a row
    # up to its first unclear step
    upto = np.where(clear.all(1), k, np.argmin(clear, axis=1))
    assert upto.sum() > B * k // 2, "the tolerance leaves too little to compare"
    for i in range(B):
        assert (np.asarray(got)[i, :upto[i]]
                == np.asarray(want)[i, :upto[i]]).all(), (i, got, want)


# -- the engine ---------------------------------------------------------------

def _counter(name):
    m = metrics.REGISTRY.collect().get(name)
    return 0.0 if m is None else sum(c.value for c in m.children().values())


def test_engine_sends_idle_slots_at_length_0_and_counts_blocks(monkeypatch):
    """A slot whose request has retired is sent with length 0 while another
    slot decodes on, though its position counter still stands where the
    request left it; and the two counters add up to what the dispatches'
    operands say."""
    lm = KVCacheLM.create(jax.random.PRNGKey(0), vocab=VOCAB, dim=DIM,
                          layers=1, heads=HEADS, max_len=T)
    seen = []
    inner = lm.decode_multi

    def spy(cache, prompt_buf, prompt_n, pos0, *rest, **kw):
        seen.append((np.asarray(pos0).copy(), int(prompt_buf.shape[1])))
        return inner(cache, prompt_buf, prompt_n, pos0, *rest, **kw)

    monkeypatch.setattr(lm, "decode_multi", spy)
    live0, total0 = (_counter("fedml_llm_cache_blocks_live_total"),
                     _counter("fedml_llm_cache_blocks_total"))
    engine = KVCacheLLMEngine(lm, max_batch=4, tokens_per_dispatch=4)
    try:
        # slot 0: beyond one block, done after 3 tokens; slot 1: decodes on
        short = engine.submit(list(range(1, 141)), max_new=3)
        long = engine.submit(list(range(1, 10)), max_new=40)
        short.result(timeout=120)
        long.result(timeout=120)
        deadline = time.monotonic() + 30
        while engine.active_count and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine._pos[0] > CACHE_BLOCK          # left where it stood
    finally:
        engine.stop()
    both = [pos0 for pos0, _ in seen if pos0[0] > 0 and pos0[1] > 0]
    after = [pos0 for pos0, _ in seen if pos0[0] == 0 and pos0[1] > 0]
    assert both and after, seen
    assert all((pos0[2:] == 0).all() for pos0, _ in seen)
    live = sum(k * int(np.sum(-(-pos0 // CACHE_BLOCK))) for pos0, k in seen)
    total = sum(k * 4 * -(-T // CACHE_BLOCK) for _, k in seen)
    assert _counter("fedml_llm_cache_blocks_live_total") - live0 == live
    assert _counter("fedml_llm_cache_blocks_total") - total0 == total
    assert 0 < live < total


def test_cache_read_share_reader(monkeypatch):
    """`chipbench/metrics/cache_read_share_pct.py`: 100 x live / total off
    the process registry, and nothing where the program keeps no such
    counters (the parent's)."""
    from chipbench.harness import runner

    read = runner.reader_of("cache_read_share_pct")
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    assert read(None) is None
    for name, n in (("fedml_llm_cache_blocks_live_total", 13),
                    ("fedml_llm_cache_blocks_total", 256)):
        metrics.counter(name, "", labels=("engine",)).labels(
            engine="kv").inc(n)
    assert read(None) == pytest.approx(100 * 13 / 256)
