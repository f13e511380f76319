"""Rehearsal 3 for ``sft.gigachat_lora_8k``, as ``test_fits_smallthinker.py``
has it for the routed cell: the epoch program ``LLMTrainer.train()`` runs, at
batch 1 x 8,192 tokens and at 1 x 4,096, compiled for a v5e that is described
and not attached.  Nothing runs, so nothing here is a time.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_fits_gigachat.py -q -s

prints the figures that are written into the cell's ``sizing``.  The cell
runs 8,192 tokens if that program's peak is at or under 15.0 GB, else 4,096.
"""

import functools
import json

import jax
import jax.numpy as jnp
import pytest

from test_fits_v5e import HBM, _abstract, _bytes, _load, one_chip  # noqa: F401

#: the peak at or under which the cell runs 8,192 tokens
ROOM = 15.0e9


def epoch_bytes(one_chip, cfg, t, seq_len, monkeypatch):
    import fedml_tpu
    import optax
    from chipbench.planes.sft_mla import model_args
    from chipbench.reference import gigachat3
    from fedml_tpu.ops import pallas_attention, routed_experts
    from fedml_tpu.train.llm.lora import init_lora
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    # both kernels pick their path by the backend they see, which here is
    # the CPU: steer them to the branch the chip takes
    monkeypatch.setattr(pallas_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(routed_experts, "_on_tpu", lambda: True)
    bundle = fedml_tpu.model.create(fedml_tpu.Config(**model_args(cfg)),
                                    cfg["vocab_size"])
    tcfg = LLMTrainConfig(seq_len=seq_len, batch_size=t["batch_size"])
    trainer = LLMTrainer.__new__(LLMTrainer)      # no weights are made
    trainer.bundle, trainer.cfg, trainer.mesh = bundle, tcfg, None
    trainer.tx = optax.chain(optax.clip_by_global_norm(tcfg.grad_clip),
                             optax.adamw(tcfg.learning_rate))
    params = jax.eval_shape(lambda: gigachat3.init_params(cfg, 0))
    lora = jax.eval_shape(functools.partial(
        init_lora, rank=tcfg.lora_rank, rng=jax.random.PRNGKey(0)), params)
    # the five attention matrices of six blocks: 3.39M trainable
    assert len(lora) == 5 * (cfg["num_hidden_layers"] + 1)
    assert sum(a.size for a in jax.tree_util.tree_leaves(lora)) == 3394560
    opt = jax.eval_shape(trainer.tx.init, lora)
    shape = (t["steps_per_call"], t["batch_size"], seq_len)
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
    return dict(_bytes(compiled),
                peak=compiled.memory_analysis().peak_memory_in_bytes)


@pytest.mark.parametrize("seq_len", [8192, 4096])
def test_gigachat_epoch_program(one_chip, monkeypatch, seq_len):
    """What each length compiles to is printed for the cell's file (the
    longer may not compile at all: then the compiler's refusal is the
    finding); the length the cell runs fits with the room its rule names."""
    cell = _load("workloads", "sft.gigachat_lora_8k.json")
    cfg = _load("configs", cell["config"] + ".json")
    runs = cell["traffic"]["seq_len"] == seq_len
    try:
        got = epoch_bytes(one_chip, cfg, cell["traffic"], seq_len, monkeypatch)
    except Exception as e:                     # noqa: BLE001 (the compiler's)
        if runs:
            raise
        print(f"sft.gigachat_lora_8k at {seq_len}: refused: {str(e)[:300]}")
        return
    print(f"sft.gigachat_lora_8k at {seq_len}:", json.dumps(got))
    if runs:
        assert got["peak"] <= (ROOM if seq_len == 8192 else 0.9 * HBM)
    elif seq_len == 8192:
        assert got["peak"] > ROOM, "8,192 fits: the cell should run it"
