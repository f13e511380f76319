"""The fine-tuning plane for the family with delta-rule layers: ``LLMTrainer``
over ``model="routed_lm"`` described with gated delta-rule mixers among gated
softmax-attention ones (q and k normed by head, a part of a head rotated, norm
scales centred on zero) and softmax-routed SwiGLU experts beside a gated shared
one, driven, timed and compared as ``planes/sft_routed.py`` drives its cell
(the token stream, the window, the probe through the compiled epoch program,
the picks compared, the gaps and the check are that file's and
``planes/sft.py``'s own code).

What differs, and is this file's: the model hub's arguments come from this
configuration's keys; the weights are made in the types the configuration
states (the frozen matrices in bfloat16, the rest float32) and the count of
state leaves kept in another type counts against those
(``planes/sft_mla.misplaced``); the reference takes the configuration's
stored type and has no second way of laying its blocks out; and
``first_loss_gap`` is shown and not compared, as ``planes/sft_mla.py`` shows it.
"""

from typing import Any, Dict

import numpy as np

from ..traffic import tokens
from . import sft_mla, sft_routed
from .sft import _leaf_norms, _moments
from .sft_mla import misplaced


def model_args(cfg: Dict) -> Dict[str, Any]:
    """The model hub's arguments for a configuration in the source's keys."""
    layers = int(cfg["num_hidden_layers"])
    softmax = [(i + 1) % int(cfg["full_attention_interval"]) == 0
               for i in range(layers)]
    return dict(
        model="routed_lm", dataset="shakespeare",
        lm_dim=cfg["hidden_size"], lm_heads=cfg["num_attention_heads"],
        lm_kv_heads=cfg["num_key_value_heads"], lm_head_dim=cfg["head_dim"],
        lm_norm_eps=cfg["rms_norm_eps"], lm_centred_norm=True,
        lm_rope_theta=cfg["rope_theta"],
        lm_rope_layout=[int(s) for s in softmax],
        lm_window_layout=[0] * layers,
        lm_attention=dict(
            rotary=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            qk_norm=True, out_gate=True),
        lm_delta=dict(key_heads=cfg["linear_num_key_heads"],
                      value_heads=cfg["linear_num_value_heads"],
                      key_dim=cfg["linear_key_head_dim"],
                      value_dim=cfg["linear_value_head_dim"],
                      conv=cfg["linear_conv_kernel_dim"]),
        lm_delta_layout=[int(not s) for s in softmax],
        lm_ffn=cfg["moe_intermediate_size"],
        lm_shared_ffn=cfg["shared_expert_intermediate_size"],
        lm_shared_gate=True,
        lm_experts=cfg["published"]["num_experts"],
        lm_experts_held=cfg["num_experts"],
        lm_first_held=cfg["experts_first_held"],
        lm_top_k=cfg["num_experts_per_tok"],
        lm_router=dict(act=cfg["hidden_act"], reads="normed"),
        lm_store=cfg["weights_stored"])


class Plane(sft_routed.Plane):
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
            # of 7.3 GB would not fit
            trainer.variables = trainer.lora = None
            trainer.variables = {"params": ref.init_params(cfg, self.seed)}
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
            "not_float32": misplaced((mu, nu), cfg["weights_stored"]),
        }
        del before, opt_state, mu, nu, seen
        self.rec.say("sft_setup", first_call_loss=self.first["loss"], **{
            sp["name"].split(".")[1] + "_s": sp["t1"] - sp["t0"]
            for sp in self.rec.spans})

    #: the types held against the configuration's, and ``first_loss_gap``
    #: shown and not compared (at this size the accepted cells' limit leaves
    #: the sound runs' largest reading under three times of room, and no
    #: precision separates on it; the loss stays held through ``loss_gap``:
    #: PERF.md, section 2), both as the latent-attention plane has them
    finish = sft_mla.Plane.finish
    check = sft_mla.Plane.check

    # -------------------------------------------------------------------------
    def reference_reading(self, mode: str = "float32",
                          follow: bool = True) -> Dict[str, Any]:
        """Set-up's two calls again, by the plain reference (or, with another
        ``mode``, by the control), and the picks of the first row."""
        import jax
        import jax.numpy as jnp

        cfg, t, ref, tcfg = self.config, self.t, self.ref, self.tcfg
        params = ref.init_params(cfg, self.seed)
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
