"""Rehearsal 3 kept as code for the LFM2 cell: the engine's three programs
(the full and the short ``decode_multi`` dispatch, the largest admission
prefill) and the row's scatter, at the sizes written in the cell's file and
the configuration's published widths, compile for a v5e that is described and
not attached, and fit its 16 GB beside the resident weights and cache.
Nothing runs, so nothing here is a time.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_fits_lfm2.py -q -s

prints the figures that are written into the cell's ``sizing``.
"""

import functools
import json

import jax
import jax.numpy as jnp
import pytest

from test_fits_v5e import HBM, _abstract, _bytes, _load, one_chip  # noqa: F401


def serve_bytes(one_chip, cfg, t, monkeypatch):
    from chipbench.planes import serve_lfm2
    from chipbench.reference import lfm2
    from fedml_tpu.ops import routed_experts
    from fedml_tpu.serving import kv_cache_lm

    # the expert layer picks its kernels by the backend it sees, which here
    # is the CPU: steer it to the branch the chip takes
    monkeypatch.setattr(routed_experts, "_on_tpu", lambda: True)
    m, heads, max_len = (t["max_batch"], cfg["num_attention_heads"],
                         cfg["n_positions"])
    layers = serve_lfm2.layers_of(cfg)
    spec = functools.partial(_abstract, sharding=one_chip)
    params = jax.eval_shape(lambda: lfm2.init_params(cfg, 0, jnp.bfloat16))
    cache = jax.eval_shape(functools.partial(
        kv_cache_lm.init_cache, batch=m, max_len=max_len, heads=heads,
        layers=layers), params)
    resident = sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves((params, cache)))
    out = {"resident_weights_and_cache": {"total": resident}}
    for k in (8, 2):
        vec = lambda dt, *s: jax.ShapeDtypeStruct((m, *s), dt,
                                                  sharding=one_chip)
        compiled = kv_cache_lm.decode_multi.lower(
            spec(params), spec(cache), vec(jnp.int32, k), vec(jnp.int32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32),
            spec(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
            heads=heads, k=k, exact_filters=False, layers=layers).compile()
        text = compiled.as_text()
        for kernel in ("moe_experts", "moe_sum_picks", "decode_attention",
                       "kv_store_positions"):
            assert kernel in text, f"no {kernel} in decode_multi_k{k}"
        out[f"decode_multi_k{k}"] = _bytes(compiled)
    top = max(b[1] for b in t["prompt_tokens"])
    bucket = min(b for b in (32, 64, 128, 256, 512, 1024, 2048) if b >= top)
    compiled = kv_cache_lm.prefill.lower(
        spec(params),
        jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
        heads=heads, max_len=max_len, layers=layers).compile()
    assert "moe_experts" in compiled.as_text()
    out[f"prefill_{bucket}"] = _bytes(compiled)
    # the prefill's row beside the batch cache it is scattered into
    out[f"prefill_{bucket}"]["beside_the_cache"] = (
        out[f"prefill_{bucket}"]["total"] + resident
        - sum(a.size * a.dtype.itemsize
              for a in jax.tree_util.tree_leaves(params)))
    return out


def test_serve_programs_fit_beside_weights_and_cache(one_chip, monkeypatch):
    cell = _load("workloads", "serve.lfm2_chat_steady.json")
    cfg = _load("configs", cell["config"] + ".json")
    got = serve_bytes(one_chip, cfg, cell["traffic"], monkeypatch)
    print("serve.lfm2_chat_steady programs:", json.dumps(got))
    assert got["resident_weights_and_cache"]["total"] > 10e9
    # a program's arguments are the resident weights and cache themselves
    for name, b in got.items():
        assert b["total"] < HBM, (name, b)
    assert got["prefill_2048"]["beside_the_cache"] < HBM
