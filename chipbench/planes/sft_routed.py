"""The fine-tuning plane for the routed family: ``LLMTrainer`` over
``model="routed_lm"``, driven, timed and compared as ``planes/sft.py`` drives
the GPT-2 cells (its docstring says how the two calls of set-up are made and
why each is compared as it is; the window, the freeing of the program's state
and the numbers compared are that file's own code, imported here).

What differs, and is this file's: the model's sizes come from the
configuration's own keys (the source's), the token stream's cycle is the
cell's, the epoch program's third result carries the picks' counts beside
the loss, the reference takes the configuration and not a head count, and
one more number is compared: the share of (token, layer) picks on which the
program and the reference disagree, on the first row of the first call at
the starting weights.  The program's picks come from the bundle's own
``picks`` (the same blocks, one more program built in set-up).
"""

from typing import Any, Dict, List

import numpy as np

from ..harness import compare
from ..harness.record import now
from ..traffic import cycles, tokens
from . import sft
from .sft import _leaf_norms, _moments


def model_args(cfg: Dict) -> Dict[str, Any]:
    """The model hub's arguments for a configuration in the source's keys."""
    layers = int(cfg["num_hidden_layers"])
    return dict(
        model="routed_lm", dataset="shakespeare",
        lm_dim=cfg["hidden_size"], lm_heads=cfg["num_attention_heads"],
        lm_kv_heads=cfg["num_key_value_heads"], lm_head_dim=cfg["head_dim"],
        lm_ffn=cfg["moe_ffn_hidden_size"],
        lm_experts=cfg["published"]["moe_num_primary_experts"],
        lm_experts_held=cfg["moe_num_primary_experts"],
        lm_first_held=cfg["experts_first_held"],
        lm_top_k=cfg["moe_num_active_primary_experts"],
        lm_norm_eps=cfg["rms_norm_eps"], lm_rope_theta=cfg["rope_theta"],
        lm_window=cfg["sliding_window_size"],
        lm_rope_layout=cfg["rope_layout"][:layers],
        lm_window_layout=cfg["sliding_window_layout"][:layers])


class Plane(sft.Plane):
    def _stream(self, call: int) -> np.ndarray:
        t = self.t
        return cycles.call_tokens(
            self.seed, call, t["steps_per_call"] * t["batch_size"],
            t["seq_len"], int(self.config["vocab_size"]), int(t["cycle"]))

    def _first_row(self):
        return self._stream(0)[:self.t["seq_len"]]

    # -------------------------------------------------------------------------
    def setup(self) -> None:
        import fedml_tpu
        import jax
        import jax.numpy as jnp
        from fedml_tpu.train.llm.trainer import LLMTrainer

        cfg, tcfg, ref = self.config, self.tcfg, self.ref
        bundle = fedml_tpu.model.create(
            fedml_tpu.Config(**model_args(cfg)), int(cfg["vocab_size"]))
        with self.rec.span("chipbench.build_trainer"):
            trainer = LLMTrainer(bundle, tcfg, rng=ref.seed_key(self.seed))
            # the benchmark's weights in the program's layout, in place of
            # the constructor's own draw, which is freed first: two copies
            # of 5.6 GB and the program would not fit
            trainer.variables = trainer.lora = None
            trainer.variables = {"params": ref.init_params(
                cfg, self.seed, jnp.float32)}
            lora0 = ref.init_lora(cfg, self.seed, tcfg.lora_rank)
            trainer.lora = {f"blocks/{i}/{name}": f
                            for (i, name), f in lora0.items()}
            jax.block_until_ready((trainer.variables, trainer.lora))
        self.trainer = trainer

        with self.rec.span("chipbench.picks_call"):
            picks = np.asarray(jax.jit(bundle.module.picks)(
                trainer.variables, jnp.asarray(self._first_row()[None])))[:, 0]
        with self.rec.span("chipbench.probe_call"):
            probe = self._probe(trainer, lora0)

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
            "probe": probe, "picks": picks,
            "not_float32": sum(str(leaf.dtype) != "float32" for leaf in
                               jax.tree_util.tree_leaves((mu, nu))),
        }
        del before, opt_state, mu, nu, seen
        self.rec.say("sft_setup", first_call_loss=self.first["loss"], **{
            sp["name"].split(".")[1] + "_s": sp["t1"] - sp["t0"]
            for sp in self.rec.spans})

    def _probe(self, trainer, lora0) -> Dict[str, Any]:
        """``sft.Plane._probe`` over an epoch program whose third result is
        the loss with the picks' counts beside it: the counts are said, the
        loss goes on."""
        epoch = trainer._train_epoch

        def loss_alone(*operands):
            trainable, opt_state, got = epoch(*operands)
            self.rec.say("sft_probe_picks", **{
                k: int(v) for k, v in got.items() if k != "loss"})
            return trainable, opt_state, got["loss"]

        trainer._train_epoch = loss_alone
        try:
            return super()._probe(trainer, lora0)
        finally:
            trainer._train_epoch = epoch

    # -------------------------------------------------------------------------
    def reference_reading(self, mode: str = "float32",
                          follow: bool = True) -> Dict[str, Any]:
        """Set-up's two calls again, by the plain reference (or, with another
        ``mode``, by the control), and the picks of the first row."""
        import jax
        import jax.numpy as jnp

        cfg, t, ref, tcfg = self.config, self.t, self.ref, self.tcfg
        # stacked as the reference walks them: one 5.6 GB copy, not two
        params = ref.init_params(cfg, self.seed, jnp.float32, stacked=True)
        x, y = tokens.as_batches(self._stream(0), t["steps_per_call"],
                                 t["batch_size"], t["seq_len"])
        keep = ref.init_lora(cfg, self.seed, tcfg.lora_rank)
        name = "blocks/{}/{}".format

        def run(**kw):
            return ref.finetune(
                params, ref.init_lora(cfg, self.seed, tcfg.lora_rank), x, y,
                cfg, float(tcfg.lora_alpha), float(tcfg.learning_rate),
                float(tcfg.grad_clip), mode, **kw)

        def norms(tree, **kw):
            return _leaf_norms({name(*k): v for k, v in tree.items()}, **kw)

        def change(lora):
            return norms({k: jax.tree_util.tree_map(
                lambda a, b: a - b, lora[k], keep[k]) for k in lora})

        losses, lora, mu, _ = run(steps_with_data=1)
        undo = 1.0 / (0.1 * 0.9 ** (t["steps_per_call"] - 1))
        out = {"probe": {"loss1": losses[0], "change": change(lora),
                         "grad": {k: v * undo for k, v in norms(mu).items()}},
               "picks": np.asarray(ref.picks_one(
                   params, jnp.asarray(self._first_row()), cfg, mode))}
        if follow:
            losses, lora, mu, nu = run()
            out.update(loss=float(np.mean(losses)), change=change(lora),
                       moment=norms(mu), second=norms(nu, squared=False))
        return out

    @staticmethod
    def gaps(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        """``sft.Plane.gaps`` and, beside them, the share of (token, layer)
        pairs whose picks are not the reference's six."""
        out = sft.Plane.gaps(got, want)
        same = np.all(np.sort(got["picks"], -1) == np.sort(want["picks"], -1),
                      axis=-1)
        out["picks_disagree_share"] = float(1.0 - same.mean())
        return out

    def check(self) -> List[Dict]:
        t0 = now()
        want = self.reference_reading()
        got = self.gaps(self.first, want)
        self.rec.say("sft_check", reference_s=now() - t0,
                     reference_loss=want["loss"],
                     picks_agree_share=1.0 - got["picks_disagree_share"],
                     **self.after_sixteen(self.first, want))
        got["state_leaves_not_float32"] = float(self.first["not_float32"])
        return compare.against_limits(got, self.cell["limits"])
