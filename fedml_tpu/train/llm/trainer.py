"""LLM fine-tuning trainer (SFT) — flax + optax + optional LoRA + orbax.

Capability parity: reference `train/llm/` (HF-Trainer-based SFT with PEFT
LoRA, DeepSpeed ZeRO, prompt formatting, checkpointing) rebuilt TPU-native:

* model = any causal-LM flax bundle (ships with TinyTransformerLM; larger
  configs scale via the parallel layer's dp/fsdp/tp shardings)
* LoRA via the functional transform in `lora.py` (only LoRA leaves train)
* the epoch loop is `lax.scan` over packed fixed-length batches in one jit
* checkpoints through `utils/checkpoint.RoundCheckpointer`
* ZeRO-equivalent: pass ``strategy="fsdp"`` to shard base params over the
  `data` mesh axis (reference reached this only via DeepSpeed passthrough,
  `train/llm/distributed.py:20-58`)
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...core.mlops import metrics as _metrics
from ...core.mlops import tracing
from ...ml.engine.model_bundle import ModelBundle, masked_loss
from .lora import _path_str, apply_lora, count_trainable, init_lora


@dataclasses.dataclass
class LLMTrainConfig:
    """reference `train/llm/configurations.py` ExperimentArguments subset."""

    seq_len: int = 128
    batch_size: int = 8
    learning_rate: float = 1e-3
    epochs: int = 1
    use_lora: bool = True
    lora_rank: int = 8
    lora_alpha: float = 16.0
    #: regex list selecting the 2D kernels that get (A, B) factors;
    #: None → lora.DEFAULT_TARGETS (fed_llm passes a validated
    #: ``--lora-targets`` spec through here)
    lora_targets: Optional[Tuple[str, ...]] = None
    grad_clip: float = 1.0
    checkpoint_dir: Optional[str] = None
    #: "none" | "dp" | "fsdp" — ZeRO-equivalent sharding of the BASE params
    #: over the `data` mesh axis (reference reached this only via the
    #: DeepSpeed passthrough, `train/llm/distributed.py:20-58`); the batch
    #: axis shards over `data` in all sharded modes.
    strategy: str = "none"
    data_parallel: int = -1  # mesh size; -1 = all devices
    #: apply the optimizer every k batches, accumulating gradients in
    #: between (reference: TrainingArguments.gradient_accumulation_steps) —
    #: large effective batches without the activation memory.
    grad_accum_steps: int = 1
    #: "constant" | "cosine" | "linear" (ml/engine/optimizers.make_lr)
    lr_schedule: str = "constant"
    warmup_steps: int = 0
    lr_decay_steps: int = 1000
    #: npz/safetensors checkpoint to fine-tune FROM (reference
    #: `train/llm/train_utils.py:196-244` from_pretrained); schema
    #: auto-detected (native / gpt2) by weight_import
    pretrained_path: Optional[str] = None
    pretrained_schema: str = "auto"


def pack_sequences(token_ids: np.ndarray, seq_len: int,
                   batch_size: int) -> Dict[str, np.ndarray]:
    """Pack a token stream into [n_batches, B, T] next-token batches
    (reference `dataset_utils.py` packing)."""
    n_tokens = (len(token_ids) - 1) // seq_len * seq_len
    x = token_ids[:n_tokens].reshape(-1, seq_len)
    y = token_ids[1:n_tokens + 1].reshape(-1, seq_len)
    n_seq = len(x) // batch_size * batch_size
    x, y = x[:n_seq], y[:n_seq]
    return {
        "x": x.reshape(-1, batch_size, seq_len),
        "y": y.reshape(-1, batch_size, seq_len),
        "mask": np.ones((n_seq // batch_size, batch_size, seq_len),
                        np.float32),
    }


def format_prompt(instruction: str, response: str = "") -> str:
    """Alpaca-style template (reference `dataset_utils.py` prompt format)."""
    return (f"### Instruction:\n{instruction}\n\n### Response:\n{response}")


def _note_picks(counted: Dict[str, Any]) -> None:
    """A routed model's counts of one epoch program, onto the process's
    counters (docs/OBSERVABILITY.md)."""
    for key, name, what in (
            ("picks", "fedml_moe_picks_total",
             "expert picks routed, over tokens, layers and steps"),
            ("picks_held", "fedml_moe_picks_held_total",
             "expert picks that landed on an expert this chip holds"),
            ("rows_passed", "fedml_moe_rows_passed_total",
             "rows the expert layers' passes went over for the landed picks"),
            ("expert_picks_max", "fedml_moe_expert_picks_max",
             "picks of the heaviest held expert of each step, summed"),
            ("tokens_in_held_group", "fedml_moe_tokens_in_held_group_total",
             "tokens, over layers and steps, whose kept groups of experts "
             "include the group this chip's experts lie in"),
            ("mtp_positions", "fedml_sft_mtp_positions_total",
             "positions the second head's loss was taken over")):
        if key in counted:
            # on the host already: `train` fetched it with the loss
            _metrics.counter(name, what).inc(
                float(counted[key]))  # fedml: noqa[JAX003]


class LLMTrainer:
    def __init__(self, bundle: ModelBundle, config: LLMTrainConfig,
                 rng: Optional[jax.Array] = None) -> None:
        self.bundle = bundle
        self.cfg = config
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        # one consumer per split: base-param init and LoRA factors must not
        # draw from the same key (JAX002 — correlated init)
        init_rng, lora_rng = jax.random.split(rng)
        self.variables = bundle.init_variables(init_rng, batch_size=2)
        self.import_report: Optional[Dict[str, Any]] = None
        if config.pretrained_path:
            from .weight_import import load_pretrained_into

            self.variables, self.import_report = load_pretrained_into(
                self.variables, config.pretrained_path,
                schema=config.pretrained_schema,
                module=getattr(bundle, "module", None))
            logging.info(
                "loaded pretrained weights from %s: %d tensors mapped",
                config.pretrained_path,
                len(self.import_report["mapped"]))
        self.lora: Dict[str, Any] = {}
        if config.use_lora:
            self.lora = init_lora(self.variables["params"],
                                  rank=config.lora_rank,
                                  targets=config.lora_targets,
                                  rng=lora_rng)
            logging.info("LoRA: %d trainable params",
                         count_trainable(self.lora))
        from ...ml.engine.optimizers import make_lr

        tx = optax.chain(optax.clip_by_global_norm(config.grad_clip),
                         optax.adamw(make_lr(config)))
        if int(config.grad_accum_steps) > 1:
            tx = optax.MultiSteps(tx, int(config.grad_accum_steps))
        self.tx = tx
        self.mesh = None
        if config.strategy in ("dp", "fsdp"):
            from ...ml.engine.mesh import build_mesh

            self.mesh = build_mesh({"data": int(config.data_parallel)})
        elif config.strategy != "none":
            raise ValueError(f"unknown llm strategy {config.strategy!r}; "
                             f"known: none, dp, fsdp")
        # donate trainable+opt_state: train() rebinds both every epoch and
        # writes the final value back, so the epoch scan updates in place
        # instead of holding two copies of the trainable+optimizer state
        # at peak (PERF001).  Non-LoRA mode passes base_params as the SAME
        # buffers as `trainable` — donating there would overwrite a
        # still-read input, so it keeps the copy.
        self._train_epoch = jax.jit(
            self._build_epoch_fn(),
            donate_argnums=(0, 1) if config.use_lora else ())
        #: the length of the `train` call before this one, for
        #: `tracing.note_iteration`
        self._prev_train_s: Optional[float] = None

    def _trainables(self):
        return self.lora if self.cfg.use_lora else self.variables["params"]

    def _build_epoch_fn(self):
        bundle, cfg = self.bundle, self.cfg
        use_lora = cfg.use_lora
        tx = self.tx
        mesh = self.mesh

        # a model whose [B, T, V] logits are too large to make takes its own
        # loss (in row blocks), and hands back what it counted on the way
        own_loss = getattr(bundle.module, "loss", None)

        def loss_fn(trainable, base_params, model_state, batch, rng):
            params = (apply_lora(base_params, trainable, cfg.lora_alpha)
                      if use_lora else trainable)
            variables = dict(model_state, params=params)
            if own_loss is not None:
                return own_loss(variables, batch["x"], batch["y"],
                                batch["mask"])
            logits, _ = bundle.apply(variables, batch["x"], train=True,
                                     rng=rng)
            with tracing.scope("loss"):
                return masked_loss("lm", logits, batch["y"],
                                   batch["mask"]), {}

        # the name is the program's in a device trace: ``jit_sft_epoch``
        def sft_epoch(trainable, opt_state, base_params, model_state,
                      batches, rng):
            nb = batches["x"].shape[0]
            if use_lora and mesh is not None:
                # base params are FROZEN across the epoch scan, but the
                # per-step LoRA merge (base + B@A) is not loop-invariant,
                # so the SPMD partitioner re-gathers every fsdp-sharded
                # LoRA-TARGET kernel INSIDE each step (a cross-host
                # all-gather per target per iteration — SHARD005).  Pin
                # exactly those leaves replicated before the loop: each
                # gathers once per epoch at entry and the step body runs
                # collective-free on them.  Non-target leaves keep their
                # fsdp sharding (their hoisted gathers are already
                # loop-invariant), and base stays sharded at rest between
                # epochs (train() re-device_puts per strategy).
                from jax.sharding import NamedSharding, PartitionSpec as P

                repl = NamedSharding(mesh, P())
                targets = set(trainable)

                def _pin(path, leaf):
                    if _path_str(path) in targets:
                        return jax.lax.with_sharding_constraint(leaf, repl)
                    return leaf

                base_params = jax.tree_util.tree_map_with_path(
                    _pin, base_params)

            def step(carry, i):
                trainable, opt_state, rng = carry
                rng, sub = jax.random.split(rng)
                batch = jax.tree_util.tree_map(lambda b: b[i], batches)
                (loss, counted), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(
                        trainable, base_params, model_state, batch, sub)
                with tracing.scope("opt"):
                    updates, opt_state = tx.update(grads, opt_state,
                                                   trainable)
                    trainable = optax.apply_updates(trainable, updates)
                return (trainable, opt_state, rng), (loss, counted)

            (trainable, opt_state, _), (losses, counted) = jax.lax.scan(
                step, (trainable, opt_state, rng), jnp.arange(nb))
            loss = jnp.mean(losses)
            if counted:
                # what the model counted rides with the loss, summed over
                # the call's steps: one fetch, as for the loss alone
                loss = dict(jax.tree_util.tree_map(jnp.sum, counted),
                            loss=loss)
            return trainable, opt_state, loss

        return sft_epoch

    def train(self, token_ids: np.ndarray) -> Dict[str, float]:
        phases: List[tracing.Phase] = []
        with tracing.phase("fedml.sft.train") as call:
            out = self._train(token_ids, phases)
        tracing.note_iteration(
            "llm-trainer: train()", call.dur_s, self._prev_train_s,
            [(ph.name.split(".")[2], ph.dur_s) for ph in phases])
        self._prev_train_s = call.dur_s
        return out

    def _train(self, token_ids: np.ndarray,
               phases: List[tracing.Phase]) -> Dict[str, float]:
        """`train` proper; its phases are appended to ``phases`` in the
        order they open."""
        cfg = self.cfg

        def phase(name: str) -> tracing.Phase:
            phases.append(tracing.phase(name))
            return phases[-1]

        with phase("fedml.sft.pack"):
            batches_np = pack_sequences(np.asarray(token_ids), cfg.seq_len,
                                        cfg.batch_size)
            batches = jax.tree_util.tree_map(jnp.asarray, batches_np)
        trainable = self._trainables()
        with phase("fedml.sft.opt_init"):
            opt_state = self.tx.init(trainable)
        base_params = self.variables["params"]
        model_state = {k: v for k, v in self.variables.items()
                       if k != "params"}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ...parallel.sharding import make_param_shardings

            # batch dim (axis 1 of [nb, B, T]) shards over `data`; base
            # params shard per strategy (fsdp = ZeRO-style), LoRA/trainable
            # and optimizer state stay replicated (they're small)
            with phase("fedml.sft.place"):
                batches = jax.device_put(
                    batches, NamedSharding(self.mesh, P(None, "data")))
                base_params = jax.device_put(
                    base_params, make_param_shardings(
                        base_params, self.mesh, self.cfg.strategy))
                repl = NamedSharding(self.mesh, P())
                trainable = jax.device_put(trainable, repl)
                opt_state = jax.device_put(opt_state, repl)
        rng = jax.random.PRNGKey(1)
        history, terms = [], {}
        ckpt = None
        if cfg.checkpoint_dir:
            from ...utils.checkpoint import RoundCheckpointer

            ckpt = RoundCheckpointer(cfg.checkpoint_dir)
        ctx = self.mesh if self.mesh is not None else \
            contextlib.nullcontext()
        for ep in range(cfg.epochs):
            rng, sub = jax.random.split(rng)
            # an enqueue: the wait for the program is the loss fetch
            with phase("fedml.sft.epoch") as epoch, ctx:
                trainable, opt_state, loss = self._train_epoch(
                    trainable, opt_state, base_params, model_state, batches,
                    sub)
            if cfg.use_lora:
                # the donated call above deleted the buffers self.lora
                # still points at — rebind EVERY epoch so an abnormal
                # exit (checkpoint failure, KeyboardInterrupt) never
                # leaves the trainer holding dead arrays
                self.lora = trainable
            # one deliberate sync per EPOCH (not per step): the scalar gates
            # logging/checkpointing, and the scan above has already retired
            with phase("fedml.sft.loss_fetch") as fetch:
                got = jax.device_get(loss)  # fedml: noqa[JAX003] — epoch boundary
            if isinstance(got, dict):
                _note_picks(got)
                # a model's own loss terms were summed over the steps
                terms = {k: float(v) / batches["x"].shape[0]  # fedml: noqa[JAX003]
                         for k, v in got.items() if k.startswith("loss_")}
                got = got["loss"]
            loss_host = float(got)  # fedml: noqa[JAX003] — fetched above
            history.append(loss_host)
            logging.info("llm epoch %d: loss %.4f (%.1fs)", ep, loss_host,
                         epoch.dur_s + fetch.dur_s)
            if ckpt is not None:
                with phase("fedml.sft.checkpoint"):
                    ckpt.save(ep, {"round_idx": ep, "trainable": trainable})
        if cfg.use_lora:
            self.lora = trainable
        else:
            self.variables = dict(self.variables, params=trainable)
        # the last epoch's loss, and beside it the terms a model with more
        # than one makes it of (``loss_main``, ``loss_mtp``)
        return {"train_loss": history[-1] if history else float("nan"),
                "loss_history": history, **terms}

    def generate(self, prompt_ids: np.ndarray, max_new: int = 20,
                 temperature: float = 0.0) -> np.ndarray:
        """Greedy/temperature sampling with the (LoRA-merged) model."""
        params = (apply_lora(self.variables["params"], self.lora,
                             self.cfg.lora_alpha)
                  if self.cfg.use_lora else self.variables["params"])
        variables = dict(self.variables, params=params)
        ids = list(np.asarray(prompt_ids).tolist())
        rng = jax.random.PRNGKey(2)
        for _ in range(max_new):
            x = jnp.asarray([ids[-self.cfg.seq_len:]])
            logits, _ = self.bundle.apply(variables, x, train=False)
            last = logits[0, -1]
            if temperature > 0:
                rng, k = jax.random.split(rng)
                # token-by-token sampling is host-driven by design: the next
                # feed depends on this token, so the sync is the algorithm
                nxt = int(jax.random.categorical(  # fedml: noqa[JAX003]
                    k, last / temperature))
            else:
                nxt = int(jnp.argmax(last))  # fedml: noqa[JAX003] — as above
            ids.append(nxt)
        return np.asarray(ids)

