"""Rehearsal 3 for ``sft.qwen3next_lora_16k``, as ``test_fits_gigachat.py``
has it for the latent-attention cell: the epoch program ``LLMTrainer.train()``
runs, at batch 1 x 16,384 tokens and depth 8, compiled for a v5e that is
described and not attached.  Nothing runs, so nothing here is a time.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_fits_qwen3next.py -q -s

prints the figures that are written into the cell's ``sizing``.  The cell
holds 8 layers if that program's peak is at or under 15.0 GB.
"""

import functools
import json

import jax
import jax.numpy as jnp

from test_fits_v5e import _abstract, _bytes, _load, one_chip  # noqa: F401

#: the peak at or under which the cell holds 8 layers
ROOM = 15.0e9


def epoch_bytes(one_chip, cfg, t, monkeypatch):
    import fedml_tpu
    import optax
    from chipbench.planes.sft_gdn import model_args
    from chipbench.reference import qwen3_next
    from fedml_tpu.ops import delta_rule, pallas_attention, routed_experts
    from fedml_tpu.train.llm.lora import init_lora
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    # the kernels pick their path by the backend they see, which here is
    # the CPU: steer them to the branch the chip takes
    for module in (delta_rule, pallas_attention, routed_experts):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    bundle = fedml_tpu.model.create(fedml_tpu.Config(**model_args(cfg)),
                                    cfg["vocab_size"])
    tcfg = LLMTrainConfig(seq_len=t["seq_len"], batch_size=t["batch_size"])
    trainer = LLMTrainer.__new__(LLMTrainer)      # no weights are made
    trainer.bundle, trainer.cfg, trainer.mesh = bundle, tcfg, None
    trainer.tx = optax.chain(optax.clip_by_global_norm(tcfg.grad_clip),
                             optax.adamw(tcfg.learning_rate))
    params = jax.eval_shape(lambda: qwen3_next.init_params(cfg, 0))
    held = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert held == cfg["parameters"]["held"] == 3667251328
    lora = jax.eval_shape(functools.partial(
        init_lora, rank=tcfg.lora_rank, rng=jax.random.PRNGKey(0)), params)
    # wq, wk, wv, wo of the two softmax layers, w_qkvz and wo of the six
    # delta-rule layers: 1.33M trainable
    assert len(lora) == 2 * 4 + 6 * 2
    assert sum(a.size for a in jax.tree_util.tree_leaves(lora)) == cfg[
        "parameters"]["trained"] == 1327104
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
    text = compiled.as_text()
    for kernel in ("gdn_fwd", "gdn_bwd", "flash_fwd", "flash_bwd",
                   "moe_experts", "moe_experts_t", "moe_sum_picks"):
        assert kernel in text, f"no {kernel} kernel in the epoch program"
    return dict(_bytes(compiled),
                peak=compiled.memory_analysis().peak_memory_in_bytes)


def test_qwen3next_epoch_program(one_chip, monkeypatch):
    """What the cell's size compiles to is printed for the cell's file, and
    fits with the room its rule names."""
    cell = _load("workloads", "sft.qwen3next_lora_16k.json")
    cfg = _load("configs", cell["config"] + ".json")
    assert cell["traffic"]["seq_len"] == 16384 and cfg["num_hidden_layers"] == 8
    got = epoch_bytes(one_chip, cfg, cell["traffic"], monkeypatch)
    print("sft.qwen3next_lora_16k:", json.dumps(got))
    assert got["peak"] <= ROOM
