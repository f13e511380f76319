"""Rehearsal 3 kept as code: the programs of the two GPT-2-large cells, at
the sizes written in the cells' files, compile for a v5e that is described
and not attached, and fit its 16 GB.  Nothing runs, so nothing here is a time.

The topology is described inside a fixture, never at import (one process may
load the TPU's library, and every xdist worker imports every test file).

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_fits_v5e.py -q -s

prints the figures that are written into the cells' files.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _abstract(tree, sharding, dtype=None):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                       sharding=sharding), tree)


def _bytes(compiled):
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "aliased": m.alias_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "total": (m.argument_size_in_bytes + m.output_size_in_bytes
                      - m.alias_size_in_bytes + m.temp_size_in_bytes)}


def _gpt2_shapes(cfg, dtype):
    from chipbench.reference import gpt2

    return jax.eval_shape(lambda: gpt2.init_params(cfg, 0, dtype))


def sft_epoch_bytes(one_chip, cfg, t, monkeypatch):
    """The epoch program ``LLMTrainer.train()`` runs, lowered from the
    operands it passes."""
    import fedml_tpu
    import optax
    from fedml_tpu.ops import pallas_attention
    from fedml_tpu.train.llm.lora import init_lora
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    # the attention picks its kernel by the backend it sees, which here is
    # the CPU: steer it to the branch the chip takes
    monkeypatch.setattr(pallas_attention, "_on_tpu", lambda: True)
    args = fedml_tpu.Config(model="functional_lm", dataset="shakespeare",
                            lm_dim=cfg["n_embd"], lm_layers=cfg["n_layer"],
                            lm_heads=cfg["n_head"],
                            lm_max_len=cfg["n_positions"])
    bundle = fedml_tpu.model.create(args, cfg["vocab_size"])
    tcfg = LLMTrainConfig(seq_len=t["seq_len"], batch_size=t["batch_size"])
    trainer = LLMTrainer.__new__(LLMTrainer)      # no weights are made
    trainer.bundle, trainer.cfg, trainer.mesh = bundle, tcfg, None
    trainer.tx = optax.chain(optax.clip_by_global_norm(tcfg.grad_clip),
                             optax.adamw(tcfg.learning_rate))
    params = _gpt2_shapes(cfg, jnp.float32)
    lora = jax.eval_shape(functools.partial(
        init_lora, rank=tcfg.lora_rank, rng=jax.random.PRNGKey(0)), params)
    opt = jax.eval_shape(trainer.tx.init, lora)
    shape = (t["steps_per_call"], t["batch_size"], t["seq_len"])
    batches = {"x": jnp.zeros(shape, jnp.int32), "y": jnp.zeros(shape, jnp.int32),
               "mask": jnp.zeros(shape, jnp.float32)}
    spec = functools.partial(_abstract, sharding=one_chip)
    fn = jax.jit(trainer._build_epoch_fn(), donate_argnums=(0, 1))
    compiled = fn.lower(spec(lora), spec(opt), spec(params), {},
                        spec(jax.eval_shape(lambda: batches)),
                        spec(jax.eval_shape(lambda: jax.random.PRNGKey(1)))
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no flash kernel"
    return _bytes(compiled)


def serve_bytes(one_chip, cfg, t):
    """The engine's programs at ``max_batch``: a full ``decode_multi``
    dispatch and the largest admission prefill."""
    from fedml_tpu.serving import kv_cache_lm

    m, heads, max_len = t["max_batch"], cfg["n_head"], cfg["n_positions"]
    spec = functools.partial(_abstract, sharding=one_chip)
    params = _gpt2_shapes(cfg, jnp.bfloat16)
    cache = jax.eval_shape(functools.partial(
        kv_cache_lm.init_cache, batch=m, max_len=max_len, heads=heads), params)
    out = {}
    for k in (8, 2):
        vec = lambda dt, *s: jax.ShapeDtypeStruct((m, *s), dt, sharding=one_chip)
        compiled = kv_cache_lm.decode_multi.lower(
            spec(params), spec(cache), vec(jnp.int32, k), vec(jnp.int32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32),
            spec(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
            heads=heads, k=k, exact_filters=False).compile()
        out[f"decode_multi_k{k}"] = _bytes(compiled)
    top = max(b[1] for b in t["prompt_tokens"])
    bucket = min(b for b in (32, 64, 128, 256, 512, 1024) if b >= top)
    compiled = kv_cache_lm.prefill.lower(
        spec(params),
        jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
        heads=heads, max_len=max_len).compile()
    out[f"prefill_{bucket}"] = _bytes(compiled)
    return out


def test_sft_epoch_program_fits(one_chip, monkeypatch):
    cell = _load("workloads", "sft.lora_1k.json")
    cfg = _load("configs", cell["config"] + ".json")
    got = sft_epoch_bytes(one_chip, cfg, cell["traffic"], monkeypatch)
    print("sft.lora_1k epoch program:", json.dumps(got))
    assert got["total"] < HBM


def test_serve_programs_fit_beside_weights_and_cache(one_chip):
    cell = _load("workloads", "serve.chat_steady.json")
    cfg = _load("configs", cell["config"] + ".json")
    got = serve_bytes(one_chip, cfg, cell["traffic"])
    print("serve.chat_steady programs:", json.dumps(got))
    # a program's arguments are the resident weights and cache themselves
    for name, b in got.items():
        assert b["total"] < HBM, (name, b)
