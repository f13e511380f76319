"""Per-row KV-cache incremental decoding for the functional transformer LM.

The scalellm-equivalent engine (`llm_engine.py`) originally re-ran the full
window every token — O(T²) per sequence.  This module gives it the standard
TPU serving treatment (prefill/decode split, the vLLM/scalellm
architecture):

* ``prefill`` — one full forward over the prompt, returning the per-layer
  K/V cache rows and the next-token logits;
* ``decode_multi`` — k tokens per row per dispatch against the cache,
  sampled on the device, with a PER-ROW position vector, so continuously
  batched rows at different generation depths share one fixed-shape program
  (flax's built-in decode cache keys on a single scalar index and cannot do
  this); k = 1 is the one-token step;
* ``KVCacheLM`` — stateless convenience wrapper holding params/config.

Model = `models/functional_lm` (same params pytree, the same `block`, which
is handed an ``attend`` that keeps or reads the cache; parity-tested
token-for-token against the non-cached forward).  What a layer keeps in the
cache is made from its description (`layer_state`): K and V of its key/value
heads for an attention layer, the last inputs of its convolution for a
short-convolution layer; GPT-2's description is the default.
"""

from __future__ import annotations

import functools
from functools import lru_cache, partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mlops import tracing
from ..models.functional_lm import (
    GPT2,
    Layer,
    Rows,
    _add_stats,
    block,
    embed,
    head,
    init_lm_params,
    lm_forward,
)
from ..ops.delta_rule import causal_conv
from ..ops.pallas_decode_attention import MASKED, decode_attention
from ..ops.pallas_kv_store import store_positions
from ..ops.routed_experts import fit_tile


def _descriptions(params: Dict[str, Any],
                  layers: Optional[Sequence[Layer]]) -> Sequence[Layer]:
    """Each block's description; GPT-2's for all where the caller names
    none."""
    return tuple(layers) if layers else (GPT2,) * len(params["blocks"])


def layer_state(layer: Layer, batch: int, max_len: int, heads: int,
                dim: int, dt) -> Dict[str, jnp.ndarray]:
    """What one layer keeps of a batch of rows between dispatches, made from
    its description; every entry has the rows first.

    * an attention layer: ``k`` and ``v`` of its key/value heads, ``[B, Hk,
      Dh, T]``, positions last.  That is the order a v5e gives the array in
      memory whatever order is asked (a last dimension of Dh = 64 would be
      padded to the 128 lanes, so the chip's layout puts the positions
      there); saying it in the shape lets the two kernels of `decode_multi`
      address a row's block of positions as whole tiles:
      `ops.pallas_kv_store` writes the blocks a dispatch's new positions
      fall in, `ops.pallas_decode_attention` reads the blocks below a row's
      length and no others (PERF.md, PR 25 and 28).  Valid below a row's
      position, whatever lies beyond.
    * a short-convolution layer: ``conv``, the gated inputs of its last
      ``taps - 1`` positions, ``[B, taps - 1, D]``, oldest first.  Not by
      position: it is the state *before* the row's position and nothing
      else, so whoever sets a row's position sets it (`prefill`, or
      `decode_multi` for a row that starts at position 0).

    A layer whose state the cache cannot keep yet says so: a delta rule's
    ``[B, Hv, Dk, Dv]`` and its convolution's inputs would be two more
    entries of the second kind; a window's ring and a latent cache are other
    entries of the first."""
    for what, name in ((layer.delta, "a delta-rule layer's recurrent state"),
                       (layer.latent, "a latent cache"),
                       (layer.window, "a window's ring")):
        if what is not None:
            raise NotImplementedError(
                f"the serving cache keeps {name} not yet")
    if layer.conv is not None:
        return {"conv": jnp.zeros((batch, layer.conv.taps - 1, dim), dt)}
    shape = (batch, layer.kv_heads or heads, layer.head_dim or dim // heads,
             max_len)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_cache(params: Dict[str, Any], batch: int, max_len: int,
               heads: int, layers: Optional[Sequence[Layer]] = None
               ) -> List[Dict[str, jnp.ndarray]]:
    """The cache: per layer what `layer_state` makes of its description.
    `prefill`, `decode_multi` and the engine's `_scatter_cache_row` all take
    and return this one definition."""
    dim = params["embed"].shape[1]
    dt = params["embed"].dtype        # bf16 params -> bf16 cache (an fp32
    # zero cache would silently promote every where-update to fp32,
    # doubling decode HBM traffic)
    return [layer_state(layer, batch, max_len, heads, dim, dt)
            for layer in _descriptions(params, layers)]


def _expert_tile(layer: Layer, rows: int) -> Optional[int]:
    """The tile a routed layer's ``rows`` rows are laid out in."""
    ex = layer.experts
    return None if ex is None else fit_tile(rows * ex.top_k, ex.held)


@partial(jax.jit, static_argnames=("heads", "max_len", "layers"))
def prefill(params: Dict[str, Any], tokens: jnp.ndarray,
            length: jnp.ndarray, heads: int, max_len: int = 0,
            layers: Optional[Tuple[Layer, ...]] = None
            ) -> Tuple[List[Dict[str, jnp.ndarray]], jnp.ndarray]:
    """Full pass over padded prompts [B, T] (valid length per row) →
    (cache sized for ``max_len`` positions, logits at the last valid
    position).  ``max_len`` > T zero-pads the cache rows so decoding can
    keep writing past the prompt width; 0 keeps the prompt width (only safe
    when the caller re-scatters into a full-size cache itself).

    The cache is for a caller that resumes at the LAST valid position and
    feeds its token again (the engine's admission): that rewrites identical
    K and V, and a state that is not by position is handed over as it stood
    *before* that position, so that the token is not taken in twice."""
    b, t = tokens.shape
    if max_len and max_len < t:
        raise ValueError(f"prefill: max_len={max_len} < prompt width {t}")
    layers = _descriptions(params, layers)
    pad = ((0, 0), (0, 0), (0, 0), (0, max(max_len - t, 0)))
    pos_ids = jnp.arange(t)
    causal = (pos_ids[:, None] >= pos_ids[None, :])[None, None]
    live = (pos_ids[None, :] < length[:, None]).reshape(-1)
    cache = []

    def attend(q, k, v):
        """Causal softmax attention over the prompt, [B, T, H, Dh] (k and v
        [B, T, Hk, Dh] under grouped heads); the layer's K and V go to the
        cache on the way."""
        cache.append({"k": jnp.pad(k.transpose(0, 2, 3, 1), pad),
                      "v": jnp.pad(v.transpose(0, 2, 3, 1), pad)})
        if k.shape[2] != q.shape[2]:
            k, v = (jnp.repeat(z, q.shape[2] // z.shape[2], axis=2)
                    for z in (k, v))
        q, k, v = (z.transpose(0, 2, 1, 3) for z in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        s = jnp.where(causal, s, -1e30)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        return o.transpose(0, 2, 1, 3)

    def convolve(u, taps):
        """The causal convolution over the prompt from zeros; the inputs at
        the ``taps - 1`` positions before the last valid one go to the
        cache."""
        n = taps.shape[1] - 1
        at = length[:, None] - 1 - n + jnp.arange(n)[None, :]     # [B, n]
        with tracing.scope("state_write"):
            kept = jnp.take_along_axis(u, jnp.maximum(at, 0)[:, :, None], 1)
            cache.append({"conv": jnp.where(at[:, :, None] >= 0, kept, 0
                                            ).astype(params["embed"].dtype)})
        return causal_conv(u, taps)

    h = embed(params, tokens)
    for blk, layer in zip(params["blocks"], layers):
        h = block(h, blk, heads,
                  attend if layer.conv is None else convolve, layer,
                  rows=Rows(live=live, tile=_expert_tile(layer, b * t)))
    # the head over the last valid position alone: [B, T, V] logits of a
    # long prompt at a large vocabulary are gigabytes that nobody reads
    last = jnp.take_along_axis(h, (length - 1)[:, None, None], axis=1)
    return cache, head(last, params, layers[-1])[:, 0]


def _attend_cache_and_chunk(q: jnp.ndarray, layer: Dict[str, jnp.ndarray],
                            kc: jnp.ndarray, vc: jnp.ndarray,
                            pos0: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    """One layer's attention of ``q`` [B, H, Dh] over row i's cache positions
    below ``pos0[i]`` and the chunk's slots up to ``j`` (``kc``/``vc``
    [B, K, Hk, Dh]): the softmax over all of them, float32 [B, H, Dh].  The
    cache's half comes from `ops.pallas_decode_attention` unnormalised, with
    its scores' maximum and sum; the chunk's few positions are scored here
    and the two halves merged by those statistics (the flash-decoding
    merge)."""
    dh = q.shape[-1]
    o_full, m_full, l_full = decode_attention(
        q, layer["k"], layer["v"], pos0, 1.0 / np.sqrt(dh))
    if kc.shape[2] != q.shape[1]:       # grouped heads: the chunk is small
        kc, vc = (jnp.repeat(z, q.shape[1] // z.shape[2], axis=2)
                  for z in (kc, vc))
    s_chunk = jnp.einsum("bhd,bkhd->bhk", q, kc) / np.sqrt(dh)
    s_chunk = jnp.where((jnp.arange(kc.shape[1]) <= j)[None, None, :],
                        s_chunk, MASKED)
    m = jnp.maximum(m_full, jnp.max(s_chunk, axis=-1))    # [B, H]
    w_full = jnp.exp(m_full - m)
    w_chunk = jnp.exp(s_chunk - m[..., None])             # 0 where masked
    return (o_full * w_full[..., None]
            + jnp.einsum("bhk,bkhd->bhd", w_chunk, vc)) / (
                l_full * w_full + jnp.sum(w_chunk, axis=-1))[..., None]


def _decode_step(params: Dict[str, Any],
                 cache: List[Dict[str, jnp.ndarray]],
                 kc: jnp.ndarray, vc: jnp.ndarray,
                 states: Tuple[jnp.ndarray, ...],
                 token: jnp.ndarray, pos0: jnp.ndarray, j: jnp.ndarray,
                 heads: int, layers: Sequence[Layer],
                 live: Optional[jnp.ndarray] = None):
    """One token per row against a READ-ONLY full cache plus a small
    per-chunk K/V buffer (``kc``/``vc`` [La, B, K, Hk, Dh] over the model's
    La attention layers, written at inner step ``j``) — the flash-decoding
    split that lets `decode_multi` avoid rewriting the [B, T] cache every
    token.  Row i's absolute position is ``pos0[i] + j``, which is where a
    layer that rotates q and k turns them; full-cache entries are valid
    strictly below ``pos0`` (everything newer lives in the chunk buffer),
    and of the cache only the blocks of positions below ``pos0[i]`` are read
    (`_attend_cache_and_chunk`).  ``states``: each short-convolution layer's
    carried inputs [B, taps - 1, D] as they stand before this position.
    ``live`` [B] bool: the rows that hold a request (the picks of the others
    land on no expert).  Returns the updated chunk buffers, the logits, the
    states after this position, and how the step's picks fell (nothing for
    a model without routed layers)."""
    states, seen = list(states), []
    pos = pos0 + j
    h = embed(params, token, pos)                         # [B, D]
    ai = ci = 0
    for blk, layer, kept in zip(params["blocks"], layers, cache):
        if layer.conv is not None:
            def mixer(u, taps, ci=ci):
                # what the row carries and this position: the convolution's
                # window, oldest first
                window = jnp.concatenate([states[ci], u[:, None]], axis=1)
                with tracing.scope("state_write"):
                    states[ci] = window[:, 1:].astype(states[ci].dtype)
                return jnp.sum(window * taps.T.astype(u.dtype), axis=1)
            ci += 1
        else:
            def mixer(q, k_new, v_new, li=ai, layer=kept):
                nonlocal kc, vc
                # uniform-position write: every row writes chunk slot j
                # (cheap contiguous dynamic_update_slice, no per-row scatter)
                with tracing.scope("cache_write"):
                    kc = jax.lax.dynamic_update_slice(
                        kc, k_new[None, :, None].astype(kc.dtype),
                        (li, 0, j, 0, 0))
                    vc = jax.lax.dynamic_update_slice(
                        vc, v_new[None, :, None].astype(vc.dtype),
                        (li, 0, j, 0, 0))
                return _attend_cache_and_chunk(q, layer, kc[li], vc[li], pos0,
                                               j)
            ai += 1
        h = block(h, blk, heads, mixer, layer,
                  lambda stats, picks: seen.append(stats),
                  Rows(pos, live, _expert_tile(layer, h.shape[0])))
    return (kc, vc, head(h, params, layers[-1]), tuple(states),
            functools.reduce(_add_stats, seen, {}))


def _decode_core_chunked(params: Dict[str, Any],
                         cache: List[Dict[str, jnp.ndarray]],
                         kc: jnp.ndarray, vc: jnp.ndarray,
                         token: jnp.ndarray, pos0: jnp.ndarray,
                         j: jnp.ndarray, heads: int
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`_decode_step` of a model whose layers are all GPT-2's: the updated
    chunk buffers and the logits [B, V]."""
    return _decode_step(params, cache, kc, vc, (), token, pos0, j, heads,
                        _descriptions(params, None))[:3]


#: sampler candidate cap: top-k / nucleus filtering runs over the top
#: FILTER_CAP logits via `lax.top_k` instead of two full-vocab sorts (a
#: 50k-wide bitonic sort per token was a measurable share of the decode
#: step).  Vocabs <= the cap (all tests) are handled EXACTLY; for larger
#: vocabs, top_k is clamped to the cap and nucleus probabilities are
#: exact (full-vocab logsumexp) but the nucleus can keep at most the cap's
#: candidates — the same truncation every capped TPU sampler makes.
#: Rows with NO active filter (top_k=0, top_p>=1) bypass the cap entirely
#: and sample the full vocab.
FILTER_CAP = 128


@tracing.scope("sample")
def _filter_sample(logits: jnp.ndarray, temps: jnp.ndarray,
                   top_k: jnp.ndarray, top_p: jnp.ndarray,
                   key: jax.Array) -> jnp.ndarray:
    """Per-row greedy / temperature sampling with on-device top-k and
    nucleus filtering ([B, V] logits; top_k 0 = off, top_p 1 = off)."""
    b, v = logits.shape
    cap = min(FILTER_CAP, v)
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temps, 1e-6)[:, None]
    scaled = logits.astype(jnp.float32) / temp
    vals, idxs = jax.lax.top_k(scaled, cap)          # [B, cap] desc
    # exact per-candidate log-probs: normalize against the FULL vocab
    logz = jax.nn.logsumexp(scaled, axis=-1, keepdims=True)
    probs = jnp.exp(vals - logz)
    slot = jnp.arange(cap)[None]                     # [1, cap]
    # top-k: keep the first top_k slots (0 = off; clamped to the cap)
    k_active = top_k > 0
    kk = jnp.where(k_active, jnp.minimum(top_k, cap), cap)[:, None]
    keep = slot < kk
    # nucleus AFTER top-k, over the top-k-renormalized distribution (the
    # sequential-warper order of the host sampler / HF): a slot stays iff
    # the renormalized mass BEFORE it is < top_p; slot 0 always stays, so
    # top_p<=0 degenerates to keep-top-token exactly like _sample_token.
    # With top-k off, the below-cap tail mass still counts in the
    # denominator, so kept nucleus prefixes are exact (never too small).
    probs_k = probs * keep
    tail = jnp.where(k_active, 0.0,
                     jnp.maximum(1.0 - jnp.sum(probs, axis=-1), 0.0))
    z_k = jnp.sum(probs_k, axis=-1) + tail
    csum_before = (jnp.cumsum(probs_k, axis=-1) - probs_k) \
        / jnp.maximum(z_k, 1e-20)[:, None]
    p_active = (top_p < 1.0)[:, None]
    keep &= jnp.where(p_active,
                      (csum_before < jnp.minimum(top_p, 1.0)[:, None])
                      | (slot == 0),
                      True)
    masked = jnp.where(keep, vals, -jnp.inf)
    # ONE gumbel draw serves both paths (categorical == gumbel-argmax):
    # rows with BOTH filters off sample the FULL vocab (the cap only
    # applies when a filter is active — plain temperature sampling must
    # match the host sampler's distribution, tail included), filtered
    # rows argmax over the kept candidates using the SAME noise gathered
    # at their vocab positions
    gumbel = jax.random.gumbel(key, scaled.shape, scaled.dtype)
    plain = jnp.argmax(scaled + gumbel, axis=-1)
    g_at = jnp.take_along_axis(gumbel, idxs, axis=-1)        # [B, cap]
    choice = jnp.argmax(masked + g_at, axis=-1)              # [B] in slots
    filtered = jnp.take_along_axis(idxs, choice[:, None], axis=-1)[:, 0]
    filters_off = (~k_active) & (top_p >= 1.0)
    sampled = jnp.where(filters_off, plain, filtered)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


#: bisection depth for the exact sampler: threshold resolution is
#: (max-min scaled logit)/2^ITERS per row — 1e-6-ish for sane
#: temperatures, i.e. below float32 spacing of the log-probs involved
EXACT_FILTER_ITERS = 30


@tracing.scope("sample")
def _exact_filter_sample(logits: jnp.ndarray, temps: jnp.ndarray,
                         top_k: jnp.ndarray, top_p: jnp.ndarray,
                         key: jax.Array) -> jnp.ndarray:
    """EXACT full-vocab top-k / nucleus filtering (VERDICT r4 item 7).

    Instead of sorting the vocab (a 50k-wide bitonic sort per token) or
    truncating candidates at FILTER_CAP, find the per-row keep THRESHOLDS
    by bisection — each iteration is one [B, V] compare+reduce, so the
    cost is ~2*EXACT_FILTER_ITERS cheap passes and no sort at all:

    - top-k keeps ``logp >= t_k`` where t_k is the largest threshold with
      ``count(logp >= t_k) >= k`` (== the k-th largest value, exactly);
    - nucleus (after top-k renormalization, HF sequential-warper order)
      keeps ``logp >= t_p`` where t_p is the largest threshold whose kept
      mass reaches ``top_p`` — the minimal sorted prefix crossing top_p,
      i.e. the token that crosses the boundary is kept, like the capped
      path's ``csum_before < p`` rule.

    Deviation from a sorted implementation: EXACT float ties at either
    boundary are all kept (a sort would keep only the first by sort
    order) — measure-zero for real logits.  Rows with filters off sample
    the full vocab with the SAME gumbel draw as `_filter_sample`, so the
    two samplers are distribution-identical wherever both are exact.
    Tested against a numpy sorted-nucleus oracle at vocab 50257
    (tests/test_llm.py::test_exact_topp_*)."""
    keep, scaled, greedy = _exact_filter_keep(logits, temps, top_k, top_p)
    gumbel = jax.random.gumbel(key, scaled.shape, scaled.dtype)
    choice = jnp.argmax(jnp.where(keep, scaled + gumbel, -jnp.inf),
                        axis=-1)
    return jnp.where(temps > 0, choice, greedy).astype(jnp.int32)


def _exact_filter_keep(logits: jnp.ndarray, temps: jnp.ndarray,
                       top_k: jnp.ndarray, top_p: jnp.ndarray):
    """Bisected per-row keep mask for `_exact_filter_sample` (split out so
    tests can diff the SET against a numpy sorted-nucleus oracle)."""
    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    temp = jnp.maximum(temps, 1e-6)[:, None]
    scaled = logits.astype(jnp.float32) / temp
    logz = jax.nn.logsumexp(scaled, axis=-1, keepdims=True)
    logp = scaled - logz                                   # [B, V]
    hi0 = jnp.max(logp, axis=-1) + 1e-3
    lo0 = jnp.min(logp, axis=-1) - 1e-3

    k_active = top_k > 0
    kk = jnp.where(k_active, top_k, v).astype(jnp.float32)

    # invariant: count{>=lo} >= k >= count{>=hi} (hi above the max keeps
    # nothing; lo below the min keeps everything)
    def kbody(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum(logp >= mid[:, None], axis=-1).astype(jnp.float32)
        ge = cnt >= kk
        return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid)

    t_k, _ = jax.lax.fori_loop(0, EXACT_FILTER_ITERS, kbody, (lo0, hi0))
    keep = jnp.where(k_active[:, None], logp >= t_k[:, None], True)

    probs_k = jnp.where(keep, jnp.exp(logp), 0.0)          # [B, V]
    target = jnp.clip(top_p, 0.0, 1.0) * jnp.sum(probs_k, axis=-1)

    def pbody(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        mass = jnp.sum(jnp.where(logp >= mid[:, None], probs_k, 0.0),
                       axis=-1)
        ge = mass >= target
        return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid)

    t_p, _ = jax.lax.fori_loop(0, EXACT_FILTER_ITERS, pbody, (lo0, hi0))
    p_active = top_p < 1.0
    keep &= jnp.where(p_active[:, None], logp >= t_p[:, None], True)
    # the argmax token can never be filtered out (top_p <= 0 degenerates
    # to keep-top-token, matching _filter_sample's slot-0 rule)
    keep |= jax.nn.one_hot(greedy, v, dtype=bool)
    return keep, scaled, greedy


def _decode_multi(params: Dict[str, Any],
                  cache: List[Dict[str, jnp.ndarray]],
                  prompt_buf: jnp.ndarray, prompt_n: jnp.ndarray,
                  pos0: jnp.ndarray, temps: jnp.ndarray,
                  top_k: jnp.ndarray, top_p: jnp.ndarray, rng: jax.Array,
                  heads: int, k: int, exact_filters: bool = False,
                  layers: Optional[Tuple[Layer, ...]] = None):
    """k tokens per row in ONE dispatch, sampling on-device — the
    autoregressive loop never returns to the host mid-chunk, so there is
    one dispatch and no per-token host sync (``k`` = the engine's
    ``tokens_per_dispatch``; its value is not re-measured on a local chip).

    ``prompt_buf`` [B, k]: tokens to teacher-force (chunked prefill);
    row i consumes ``prompt_n[i]`` of them, then switches to its own
    samples (``prompt_n[i] == 0``: the row holds no request; it runs like a
    row with one token, and its picks land on no expert).  ``temps`` [B]:
    0 → greedy, else temperature sampling with
    per-row on-device top-k / nucleus filtering (`_filter_sample`).
    Returns (cache, emitted [B, k]) where emitted[i, j] is the model output
    after feeding inner token j — new tokens from j = prompt_n[i]-1 on.  For
    a model with routed layers ``emitted`` has `MOE_COUNTS` more rows, [B +
    3, k]: what the live rows' picks of token step j came to over the
    layers (`MOE_COUNTS`), so that the counts ride back in the one array a
    caller fetches anyway.

    The inner scan never writes the [B, T] cache: new K/V land in a
    [L, B, k] chunk buffer (`_decode_step`; a full-cache rewrite
    every token made the step ~3x slower than its HBM read floor,
    BENCH_NOTES r4), and each inner step reads of the cache what is alive:
    `ops.pallas_decode_attention` visits, per row, the blocks of positions
    below ``pos0[i]`` and merges them with the chunk by the softmax
    statistics.  Until PR 28 two contractions ran over all T positions of
    every row and masked afterwards: at GPT-2 large's 32 slots 6 GB read a
    token step, at 90% of the HBM's rate, some 5% of it alive in the serving
    cell (PERF.md, PR 28).  A row with ``pos0[i] == 0`` (the engine sends
    that for a slot that holds no request) reads nothing.

    A state that is not by position (`layer_state`: a short convolution's
    last inputs) rides the scan's carry and is written back whole behind
    it.  A row with ``pos0[i] == 0`` starts from zeros whatever the cache
    holds: position 0 is a row's start, so a slot never inherits the state
    of the request that held it before.

    After the scan `ops.pallas_kv_store` stores each row's k positions into
    the donated cache IN PLACE, in this same program: the tiles that hold
    positions ``pos0[i] .. pos0[i] + k - 1`` of each row are read and
    written, nothing else of the cache's size is (held by
    tests/test_chip_compile.py, as is the absence of a full-length score).
    Until PR 25 the write-back gathered the chunk to the cache's full shape
    and selected, which XLA turned into some nine passes over all 6 GB: 105
    ms of every dispatch, whatever its length; the store is 4.6 ms whatever
    k (my chip runs, PR 25, device time).

    A row with ``pos0 + k > T`` (a request about to fill its cache: the
    engine sends it like any other): its positions below T are stored, those
    at or beyond T are dropped, none is moved to fit
    (tests/test_decode_writeback.py).  An inner step whose position lies
    beyond the position table is fed the table's last row (`embed`), and
    what it emits is nobody's token: the engine's ``submit()`` holds prompt
    plus output to T, so the last token a request is owed comes from feeding
    position T - 2 at the latest, and `_stream` hands out no more than a
    request is owed.  Other rows see nothing of it.  An idle slot comes with
    ``pos0 == 0``: its positions 0 .. k-1 are written, and the row is never
    read before an admission writes it again."""
    b = prompt_buf.shape[0]
    layers = _descriptions(params, layers)
    kv = [c for c in cache if "k" in c]
    routed = any(layer.experts is not None for layer in layers)
    # [La, B, k, Hk, Dh]: the attention layers' new positions
    kc0 = jnp.zeros((len(kv), b, k) + kv[0]["k"].shape[1:3], kv[0]["k"].dtype)
    fresh = (pos0 == 0)[:, None, None]
    states0 = tuple(jnp.where(fresh, 0, c["conv"])
                    for c in cache if "conv" in c)
    live = prompt_n > 0

    # scan carries the "next token to feed" per row + the chunk buffers
    def step(carry, j):
        kc, vc, states, tok, rng = carry
        kc, vc, logits, states, stats = _decode_step(
            params, cache, kc, vc, states, tok, pos0, j, heads, layers, live)
        rng, sub = jax.random.split(rng)
        # static switch: exact_filters=True routes through the full-vocab
        # bisection sampler (needed only when vocab > FILTER_CAP and a
        # request's nucleus/top-k could exceed the cap; the engine picks
        # per dispatch, so unfiltered batches never pay for it)
        sampler = _exact_filter_sample if exact_filters else _filter_sample
        out_tok = sampler(logits, temps, top_k, top_p, sub)
        # next inner step feeds the prompt while any remains, else out_tok
        nxt = jnp.where(j + 1 < prompt_n,
                        prompt_buf[jnp.arange(b),
                                   jnp.minimum(j + 1, k - 1)],
                        out_tok)
        out = (out_tok, jnp.stack([stats[c] for c in MOE_COUNTS])
               ) if routed else out_tok
        return (kc, vc, states, nxt, rng), out

    carry0 = (kc0, kc0, states0, prompt_buf[:, 0], rng)
    (kc, vc, states, _, _), emitted = jax.lax.scan(step, carry0,
                                                   jnp.arange(k))
    if routed:
        emitted = jnp.concatenate([emitted[0], emitted[1].astype(
            emitted[0].dtype)], axis=1)                    # [k, B + 3]

    # chunk slot j of row i is position pos0[i] + j of the cache
    out_cache, states, li = [], iter(states), -1
    for layer in cache:
        if "conv" in layer:
            with tracing.scope("state_write"):
                out_cache.append({"conv": next(states)})
            continue
        li += 1
        with tracing.scope("cache_write"):
            new_k, new_v = store_positions(
                [layer["k"], layer["v"]],
                [kc[li].transpose(0, 2, 3, 1), vc[li].transpose(0, 2, 3, 1)],
                pos0)
        out_cache.append({"k": new_k, "v": new_v})
    return out_cache, emitted.T                            # [B, k]


#: what a routed model's `decode_multi` appends to ``emitted``, a row each,
#: a token step a column: the live rows' picks over the routed layers, the
#: heaviest expert's picks of any layer, and the experts that got a pick at
#: all, summed over the layers (the matrices the step had to fetch)
MOE_COUNTS = ("picks", "expert_picks_max", "experts_touched")


@lru_cache(maxsize=16)
def _decode_multi_jit(k: int):
    """`_decode_multi` jitted for one dispatch length under a name that
    carries it: a device trace's ``XLA Modules`` line then tells
    ``jit_decode_multi_k8`` from ``jit_decode_multi_k2`` (``k`` is static
    either way, so this compiles nothing more than one jit would)."""
    def named(params, cache, prompt_buf, prompt_n, pos0, temps, top_k,
              top_p, rng, heads, exact_filters=False, layers=None):
        return _decode_multi(params, cache, prompt_buf, prompt_n, pos0,
                             temps, top_k, top_p, rng, heads, k,
                             exact_filters, layers)

    named.__name__ = named.__qualname__ = f"decode_multi_k{k}"
    return jax.jit(named, static_argnames=("heads", "exact_filters",
                                           "layers"),
                   donate_argnums=(1,))


def decode_multi(params, cache, prompt_buf, prompt_n, pos0, temps, top_k,
                 top_p, rng, heads: int, k: int,
                 exact_filters: bool = False,
                 layers: Optional[Tuple[Layer, ...]] = None):
    """`_decode_multi` through the jitted program of its dispatch length;
    ``cache`` is donated."""
    return _decode_multi_jit(k)(params, cache, prompt_buf, prompt_n, pos0,
                                temps, top_k, top_p, rng, heads,
                                exact_filters, layers)


# what ``jax.jit`` gave the one program this used to be: the plain function,
# for a caller that jits it inside its own (`quantization`), and the
# lowering, for the compile rehearsals
decode_multi.__wrapped__ = _decode_multi
decode_multi.lower = lambda *args, k, **kw: _decode_multi_jit(k).lower(
    *args, **kw)


class KVCacheLM:
    """Decode-oriented LM handle for the batched engine: owns params and
    config, exposes prefill/decode_multi with per-row positions.
    ``layers``: each block's `functional_lm.Layer`, as the model hub makes
    them for training (`RoutedLMModule.layers`); the cache is made from
    them (`layer_state`).  Left out: GPT-2's for all."""

    def __init__(self, params: Dict[str, Any], heads: int,
                 max_len: int,
                 layers: Optional[Sequence[Layer]] = None) -> None:
        self.params = params
        self.heads = int(heads)
        self.max_len = int(max_len)
        self.vocab = int(params["embed"].shape[0])
        self.layers = tuple(layers) if layers else None

    @classmethod
    def create(cls, rng: jax.Array, vocab: int, dim: int = 64,
               layers: int = 2, heads: int = 4,
               max_len: int = 256) -> "KVCacheLM":
        return cls(init_lm_params(rng, vocab, dim=dim, layers=layers,
                                  heads=heads, max_len=max_len),
                   heads, max_len)

    def init_cache(self, batch: int):
        return init_cache(self.params, batch, self.max_len, self.heads,
                          self.layers)

    def prefill(self, tokens, length, max_len: int = -1):
        """max_len -1 → this LM's configured max_len (safe default: cache
        rows are sized so decode can continue past the prompt)."""
        ml = self.max_len if max_len == -1 else max_len
        return prefill(self.params, tokens, length, self.heads, ml,
                       self.layers)

    def decode_multi(self, cache, prompt_buf, prompt_n, pos0, temps,
                     top_k, top_p, rng, k: int,
                     exact_filters: bool = False):
        return decode_multi(self.params, cache, prompt_buf, prompt_n, pos0,
                            temps, top_k, top_p, rng, self.heads, k,
                            exact_filters, self.layers)

    def full_logits(self, tokens):
        """Non-cached forward (parity reference / tests)."""
        from ..parallel.ring_attention import reference_attention

        def attention(q, k, v):         # [B, H, T, Dh]; k, v [B, Hk, T, Dh]
            k, v = (jnp.repeat(z, q.shape[1] // z.shape[1], axis=1)
                    for z in (k, v))
            return reference_attention(q, k, v, causal=True)

        return lm_forward(self.params, tokens, self.heads, attention,
                          layers=self.layers)


def kv_lm_from_checkpoint(path: str, heads: int,
                          max_len: Optional[int] = None,
                          schema: str = "auto") -> "KVCacheLM":
    """Serve an imported checkpoint (npz/safetensors, native or GPT-2
    naming) through the KV-cache engine — the deploy half of the
    reference's fine-tune → checkpoint → serve path
    (`train/llm/train_utils.py:196-244` + `device_model_deployment.py`).
    Heads are validated against the checkpoint dims; ``max_len`` defaults
    to the checkpoint's position-table length."""
    from ..train.llm.weight_import import (
        import_lm_weights,
        validate_lm_shapes,
    )

    params, _report = import_lm_weights(path, schema=schema)
    validate_lm_shapes(params, heads=heads)
    if max_len is None:
        max_len = int(params["pos"].shape[0])
    return KVCacheLM(params, heads, int(max_len))
