"""The fine-tuning plane: ``LLMTrainer`` over ``model="functional_lm"``, driven
by whole ``train()`` calls.

One ``train()`` call is one compiled epoch program (a scan over the call's
optimizer steps) plus what the host does around it: packing the token stream,
host-to-device copies, a fresh optimizer state, the loss fetched back.  The
window repeats such calls on fresh slices of a seeded token stream until its
time is used; the call that crosses the end is finished and counted.

Set-up makes two calls before the window, both compared afterwards with the
plain reference.  The first is a ``train()`` call like the window's own: same
trainer object, same compiled program, same feed.  Sixteen Adam steps from
zero-initialised factors are a chaotic map, though: two sound computations
part by tens of percent in any gradient norm after them, so that call can
only be held to what hardly moves (its loss, the norm of the factors'
change).  The second, the probe, is one more execution of the same compiled
epoch program on operands packed the same way, with the mask of every step
after the first set to zero.  Those steps then have a gradient of exactly
zero, so AdamW's first moment after the call is the *first* gradient, as the
optimizer got it, times a known constant: the one number here that shows the
precision of the forward and backward pass.
"""

import gc
import math
from typing import Any, Dict, List

import numpy as np

from ..harness import compare
from ..harness.record import Record, now
from ..traffic import tokens


class Plane:
    def __init__(self, cell: Dict, config: Dict, reference, seed: int,
                 rec: Record) -> None:
        self.cell, self.config, self.ref = cell, config, reference
        self.seed, self.rec = int(seed), rec
        self.t = cell["traffic"]
        self.attempted = self.failed = 0
        self.trainer = None
        self.first: Dict[str, Any] = {}
        from fedml_tpu.train.llm.trainer import LLMTrainConfig

        # LoRA and the optimizer as the trainer's defaults have them
        self.tcfg = LLMTrainConfig(seq_len=self.t["seq_len"],
                                   batch_size=self.t["batch_size"],
                                   use_lora=bool(self.t["use_lora"]))

    # -- what one call is fed -------------------------------------------------
    def _stream(self, call: int) -> np.ndarray:
        t = self.t
        return tokens.call_tokens(
            self.seed, call, t["steps_per_call"] * t["batch_size"],
            t["seq_len"], int(self.config["vocab_size"]))

    def _call_tokens(self) -> int:
        t = self.t
        return t["steps_per_call"] * t["batch_size"] * t["seq_len"]

    # -------------------------------------------------------------------------
    def setup(self) -> None:
        import fedml_tpu
        import jax
        import jax.numpy as jnp
        from fedml_tpu.train.llm.trainer import LLMTrainer

        cfg, tcfg, ref = self.config, self.tcfg, self.ref
        args = fedml_tpu.Config(
            model="functional_lm", dataset="shakespeare",
            lm_dim=cfg["n_embd"], lm_layers=cfg["n_layer"],
            lm_heads=cfg["n_head"], lm_max_len=cfg["n_positions"])
        bundle = fedml_tpu.model.create(args, int(cfg["vocab_size"]))
        with self.rec.span("chipbench.build_trainer"):
            trainer = LLMTrainer(bundle, tcfg, rng=ref.seed_key(self.seed))
            # the benchmark's weights in the program's layout, in place of
            # the constructor's own draw (which has no biases and cannot be
            # given any): made on the device, from the seed
            trainer.variables = {"params": ref.init_params(
                cfg, self.seed, jnp.float32)}
            lora0 = ref.init_lora(cfg, self.seed, tcfg.lora_rank)
            trainer.lora = {f"blocks/{i}/{name}": f
                            for (i, name), f in lora0.items()}
            jax.block_until_ready((trainer.variables, trainer.lora))
        self.trainer = trainer

        with self.rec.span("chipbench.probe_call"):
            probe = self._probe(trainer, lora0)

        # the first call: warms every shape the window uses, and is followed
        # by the reference.  Its optimizer state is read off the program's
        # own output
        epoch, seen = trainer._train_epoch, []

        def watched(*operands):
            out = epoch(*operands)
            seen.append(out)
            return out

        before = jax.tree_util.tree_map(jnp.copy, trainer.lora)
        trainer._train_epoch = watched
        try:
            with self.rec.span("chipbench.first_call"):
                out = trainer.train(self._stream(0))
        finally:
            trainer._train_epoch = epoch
        _, opt_state, _ = seen.pop()
        mu, nu = _moments(opt_state)
        self.first = {
            "loss": float(out["train_loss"]),
            "change": _leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, trainer.lora, before)),
            "moment": _leaf_norms(mu),
            "second": _leaf_norms(nu, squared=False),
            "probe": probe,
            # AdamW's moments of the first call; the weights and factors
            # are counted in after the window (``finish``)
            "not_float32": sum(str(leaf.dtype) != "float32" for leaf in
                               jax.tree_util.tree_leaves((mu, nu))),
        }
        del before, opt_state, mu, nu, seen
        self.rec.say("sft_setup", first_call_loss=self.first["loss"], **{
            sp["name"].split(".")[1] + "_s": sp["t1"] - sp["t0"]
            for sp in self.rec.spans})

    def _probe(self, trainer, lora0) -> Dict[str, Any]:
        """One execution of the trainer's compiled epoch program, fed as
        ``train()`` feeds it but for the mask, which is zero after the first
        step: the first step's loss and gradient, and the factors' change."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.train.llm.trainer import pack_sequences

        tcfg, steps = self.tcfg, self.t["steps_per_call"]
        batches = pack_sequences(self._stream(0), tcfg.seq_len,
                                 tcfg.batch_size)
        batches["mask"][1:] = 0.0
        batches = jax.tree_util.tree_map(jnp.asarray, batches)
        start = {f"blocks/{i}/{name}": f for (i, name), f in lora0.items()}
        trainable = jax.tree_util.tree_map(jnp.copy, start)
        _, sub = jax.random.split(jax.random.PRNGKey(1))
        trainable, opt_state, loss = trainer._train_epoch(
            trainable, trainer.tx.init(trainable),
            trainer.variables["params"], {}, batches, sub)
        mu, _ = _moments(opt_state)
        # untouched by later (zero) gradients but for the decay of the mean
        undo = 1.0 / (0.1 * 0.9 ** (steps - 1))
        return {
            "loss1": float(loss) * steps,
            "grad": {k: v * undo for k, v in _leaf_norms(mu).items()},
            "change": _leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, trainable, start)),
        }

    # -------------------------------------------------------------------------
    def window(self, seconds: float) -> None:
        rec, trainer = self.rec, self.trainer
        steps = self.t["steps_per_call"]
        call = 1
        t0 = now()
        while now() - t0 < seconds:
            rec.trace_tick(now() - t0, seconds)
            with rec.span("chipbench.pack"):
                stream = self._stream(call)
            with rec.span("chipbench.train_call", steps=steps,
                          tokens=self._call_tokens()) as sp:
                out = trainer.train(stream)
            sp["loss"] = float(out["train_loss"])
            self.attempted += steps
            if not math.isfinite(sp["loss"]):
                self.failed += steps
            call += 1
        rec.window = {"t0": t0, "t1": now()}
        rec.say("sft_window", call_s=[round(c["t1"] - c["t0"], 4) for c in
                                      rec.spans_named("chipbench.train_call")],
                pack_s=[round(c["t1"] - c["t0"], 4) for c in
                        rec.spans_named("chipbench.pack")])

    def finish(self) -> None:
        """Note the types the program's state is kept in, then free it: the
        reference runs after it."""
        import jax

        tr = self.trainer
        self.first["not_float32"] += sum(
            str(leaf.dtype) != "float32" for leaf in
            jax.tree_util.tree_leaves((tr.variables["params"], tr.lora)))
        self.trainer = None
        gc.collect()

    # -------------------------------------------------------------------------
    def reference_reading(self, mode: str = "float32",
                          follow: bool = True) -> Dict[str, Any]:
        """Set-up's two calls again, by the plain reference (or, with another
        ``mode``, by the control).  The probe: the first step's loss, the
        per-leaf norms of its gradient and of the factors' change.  The first
        ``train()`` call, unless ``follow`` is false: its loss, and per-leaf
        norms of the change and of AdamW's moments."""
        import jax
        import jax.numpy as jnp

        cfg, t, ref, tcfg = self.config, self.t, self.ref, self.tcfg
        params = ref.init_params(cfg, self.seed, jnp.float32)
        x, y = tokens.as_batches(self._stream(0), t["steps_per_call"],
                                 t["batch_size"], t["seq_len"])
        keep = ref.init_lora(cfg, self.seed, tcfg.lora_rank)
        name = "blocks/{}/{}".format

        def run(**kw):
            return ref.finetune(
                params, ref.init_lora(cfg, self.seed, tcfg.lora_rank), x, y,
                int(cfg["n_head"]), float(tcfg.lora_alpha),
                float(tcfg.learning_rate), float(tcfg.grad_clip),
                float(cfg["layer_norm_epsilon"]), mode, **kw)

        def norms(tree, **kw):
            return _leaf_norms({name(*k): v for k, v in tree.items()}, **kw)

        def change(lora):
            return norms({k: jax.tree_util.tree_map(
                lambda a, b: a - b, lora[k], keep[k]) for k in lora})

        losses, lora, mu, _ = run(steps_with_data=1)
        undo = 1.0 / (0.1 * 0.9 ** (t["steps_per_call"] - 1))
        out = {"probe": {"loss1": losses[0], "change": change(lora),
                         "grad": {k: v * undo for k, v in norms(mu).items()}}}
        if follow:
            losses, lora, mu, nu = run()
            out.update(loss=float(np.mean(losses)), change=change(lora),
                       moment=norms(mu), second=norms(nu, squared=False))
        return out

    @staticmethod
    def gaps(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        """The numbers compared, of one reading against the reference's.

        Norms are taken by the worst leaf over the ``b`` factors.  LoRA
        starts them at zero, so the first gradient lives in them alone (that
        of every ``a`` is exactly zero)."""
        rel = lambda a, b: abs(a - b) / abs(b)
        gp, wp = got["probe"], want["probe"]
        out = {
            # the probe: the first step alone, through the compiled program
            "first_loss_gap": rel(gp["loss1"], wp["loss1"]),
            "first_grad_gap": compare.worst_leaf_gap(_b(gp["grad"]),
                                                     _b(wp["grad"])),
            "probe_change_gap": compare.worst_leaf_gap(_b(gp["change"]),
                                                       _b(wp["change"])),
        }
        if "loss" in got and "loss" in want:
            # the first train() call, all its steps
            out["loss_gap"] = rel(got["loss"], want["loss"])
            out["change_norm_gap"] = compare.worst_leaf_gap(
                _b(got["change"]), _b(want["change"]))
        return out

    @staticmethod
    def after_sixteen(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        """Not compared, shown: the gradient moments after the whole first
        call, where two sound computations already part by tens of percent."""
        return {
            "grad_mean_gap_after_call": compare.worst_leaf_gap(
                _b(got["moment"]), _b(want["moment"])),
            "grad_rms_gap_after_call": compare.worst_leaf_gap(
                _b(got["second"]), _b(want["second"]))}

    def check(self) -> List[Dict]:
        t0 = now()
        want = self.reference_reading()
        self.rec.say("sft_check", reference_s=now() - t0,
                     reference_loss=want["loss"],
                     **self.after_sixteen(self.first, want))
        got = self.gaps(self.first, want)
        # the configuration states float32 weights, factors and optimizer
        # state: an exact count, since no norm compared tells a state kept
        # in bfloat16 from one kept in float32 (PERF.md, section 2)
        got["state_leaves_not_float32"] = float(self.first["not_float32"])
        return compare.against_limits(got, self.cell["limits"])


def _b(norms: Dict[str, float]) -> Dict[str, float]:
    return {k: v for k, v in norms.items() if k.endswith("/b")}


def _moments(opt_state):
    """AdamW's first and second moment, wherever optax keeps them."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer's "
                           f"state, found {len(found)}")
    return found[0].mu, found[0].nu


def _leaf_norms(tree, squared: bool = True) -> Dict[str, float]:
    """Norm of every array of ``{name: {"a": ..., "b": ...}}`` (of a tree of
    squares, ``squared=False``: the root of its sum), fetched in one
    transfer."""
    import jax
    import jax.numpy as jnp

    flat = {f"{k}/{ab}": v for k, f in tree.items() for ab, v in f.items()}
    norms = jax.device_get(jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))
                                   if squared else a.astype(jnp.float32))),
        t))(flat))
    return {k: float(v) for k, v in norms.items()}
