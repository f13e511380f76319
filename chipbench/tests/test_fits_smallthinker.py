"""Rehearsal 3 for ``sft.smallthinker_lora_16k``, as ``test_fits_v5e.py`` has
it for the GPT-2 cells: the epoch program ``LLMTrainer.train()`` runs, at the
sizes in the cell's file and at twice its batch, compiled for a v5e that is
described and not attached.  Nothing runs, so nothing here is a time.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_fits_smallthinker.py -q -s

prints the figures that are written into the cell's ``sizing``.
"""

import functools
import json

import jax
import jax.numpy as jnp
import pytest

from test_fits_v5e import HBM, _abstract, _bytes, _load, one_chip  # noqa: F401


def epoch_bytes(one_chip, cfg, t, batch, monkeypatch):
    import fedml_tpu
    import optax
    from chipbench.planes.sft_routed import model_args
    from chipbench.reference import smallthinker
    from fedml_tpu.ops import pallas_attention, routed_experts
    from fedml_tpu.train.llm.lora import init_lora
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    # both kernels pick their path by the backend they see, which here is
    # the CPU: steer them to the branch the chip takes
    monkeypatch.setattr(pallas_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(routed_experts, "_on_tpu", lambda: True)
    bundle = fedml_tpu.model.create(fedml_tpu.Config(**model_args(cfg)),
                                    cfg["vocab_size"])
    tcfg = LLMTrainConfig(seq_len=t["seq_len"], batch_size=batch)
    trainer = LLMTrainer.__new__(LLMTrainer)      # no weights are made
    trainer.bundle, trainer.cfg, trainer.mesh = bundle, tcfg, None
    trainer.tx = optax.chain(optax.clip_by_global_norm(tcfg.grad_clip),
                             optax.adamw(tcfg.learning_rate))
    params = jax.eval_shape(lambda: smallthinker.init_params(cfg, 0))
    lora = jax.eval_shape(functools.partial(
        init_lora, rank=tcfg.lora_rank, rng=jax.random.PRNGKey(0)), params)
    assert len(lora) == 4 * cfg["num_hidden_layers"]
    opt = jax.eval_shape(trainer.tx.init, lora)
    shape = (t["steps_per_call"], batch, t["seq_len"])
    batches = {"x": jnp.zeros(shape, jnp.int32), "y": jnp.zeros(shape, jnp.int32),
               "mask": jnp.zeros(shape, jnp.float32)}
    spec = functools.partial(_abstract, sharding=one_chip)
    fn = jax.jit(trainer._build_epoch_fn(), donate_argnums=(0, 1))
    compiled = fn.lower(spec(lora), spec(opt), spec(params), {},
                        spec(jax.eval_shape(lambda: batches)),
                        spec(jax.eval_shape(lambda: jax.random.PRNGKey(1)))
                        ).compile()
    text = compiled.as_text()
    for kernel in ("flash_fwd", "moe_experts", "moe_experts_t"):
        assert kernel in text, f"no {kernel} kernel in the epoch program"
    # arguments + temporaries is no peak here (the compiler spends what
    # memory there is, and counts both sides of what it rematerialises):
    # its own peak is
    return dict(_bytes(compiled),
                peak=compiled.memory_analysis().peak_memory_in_bytes)


@pytest.mark.parametrize("batch", [1, 2])
def test_smallthinker_epoch_program(one_chip, monkeypatch, batch):
    """Batch 1, the cell's, fits 16 GB with room; what batch 2 compiles to is
    printed for the cell's file (it may not compile at all: then the
    compiler's refusal is the finding)."""
    cell = _load("workloads", "sft.smallthinker_lora_16k.json")
    cfg = _load("configs", cell["config"] + ".json")
    assert batch != 1 or cell["traffic"]["batch_size"] == 1
    try:
        got = epoch_bytes(one_chip, cfg, cell["traffic"], batch, monkeypatch)
    except Exception as e:                     # noqa: BLE001 (the compiler's)
        if batch == 1:
            raise
        print(f"sft.smallthinker_lora_16k batch {batch}: refused: "
              f"{str(e)[:300]}")
        return
    print(f"sft.smallthinker_lora_16k batch {batch}:", json.dumps(got))
    if batch == 1:
        assert got["peak"] < 0.9 * HBM
