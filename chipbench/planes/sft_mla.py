"""The fine-tuning plane for the latent-attention family: ``LLMTrainer`` over
``model="routed_lm"`` described with latent attention, a group-limited sigmoid
router, a shared expert, leading dense layers and a second head, driven, timed
and compared as ``planes/sft_routed.py`` drives its cell (the token stream, the
window, the probe through the compiled epoch program and the picks compared
are that file's and ``planes/sft.py``'s own code).

What differs, and is this file's: the model's sizes come from this
configuration's keys; the weights are made in the types the configuration
states (the frozen matrices in bfloat16, the rest float32), and the count of
state leaves kept in another type counts against those; the second head's
block is the last of ``blocks``, so its factors go by the next block number
and its picks are compared with the trunk's; one number more is compared,
``mtp_loss_gap``: the second head's loss of the probe's first step against
the reference's (in the sum it is weighted 0.3 and a dropped or mis-shifted
second loss would hide behind the first); and one fewer: ``first_loss_gap``
is shown and not compared (at this size the accepted cells' limit leaves the
sound runs' largest reading less than twice the room, and no precision
separates on it; the loss stays held through ``loss_gap``, the whole first
call's, and ``mtp_loss_gap``: PERF.md, section 2).
"""

import gc
from typing import Any, Dict, List

import numpy as np

from ..harness import compare
from ..harness.record import now
from ..traffic import tokens
from . import sft_routed
from .sft import _leaf_norms, _moments


def model_args(cfg: Dict) -> Dict[str, Any]:
    """The model hub's arguments for a configuration in the source's keys."""
    yarn = cfg["rope_scaling"]
    return dict(
        model="routed_lm", dataset="shakespeare",
        lm_dim=cfg["hidden_size"], lm_heads=cfg["num_attention_heads"],
        lm_layers=cfg["num_hidden_layers"],
        lm_norm_eps=cfg["rms_norm_eps"],
        lm_latent=dict(
            q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
            nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
            v=cfg["v_head_dim"], theta=cfg["rope_theta"],
            factor=yarn["factor"],
            original=yarn["original_max_position_embeddings"],
            beta_fast=yarn["beta_fast"], beta_slow=yarn["beta_slow"],
            mscale=yarn["mscale"], mscale_all_dim=yarn["mscale_all_dim"]),
        lm_dense_layers=cfg["first_k_dense_replace"],
        lm_dense_ffn=cfg["intermediate_size"],
        lm_ffn=cfg["moe_intermediate_size"],
        lm_shared_ffn=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        lm_experts=cfg["published"]["n_routed_experts"],
        lm_experts_held=cfg["n_routed_experts"],
        lm_first_held=cfg["experts_first_held"],
        lm_top_k=cfg["num_experts_per_tok"],
        lm_router=dict(scores=cfg["scoring_func"], groups=cfg["n_group"],
                       kept_groups=cfg["topk_group"],
                       scale=cfg["routed_scaling_factor"],
                       act=cfg["hidden_act"], reads="normed"),
        lm_mtp=cfg["num_nextn_predict_layers"],
        lm_mtp_weight=cfg["mtp_loss_weight"], lm_store=cfg["weights_stored"])


def misplaced(tree, stored: str) -> int:
    """Leaves kept in another type than the configuration states: a frozen
    matrix (two axes or more, the router apart) in ``stored``, every other
    leaf (factors, optimizer state, norms' scales, routers and their biases)
    in float32."""
    import jax

    def wrong(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        frozen = leaf.ndim >= 2 and name not in ("router", "a", "b")
        return str(leaf.dtype) != (stored if frozen else "float32")

    return sum(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map_with_path(wrong, tree)))


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
            # of 7 GB would not fit
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
        self.rec.say("sft_setup", first_call_loss=self.first["loss"],
                     first_call_loss_main=out["loss_main"],
                     first_call_loss_mtp=out["loss_mtp"], **{
                         sp["name"].split(".")[1] + "_s": sp["t1"] - sp["t0"]
                         for sp in self.rec.spans})

    def _probe(self, trainer, lora0) -> Dict[str, Any]:
        """``sft_routed.Plane._probe``, keeping the second head's loss of the
        first step: the later steps are masked out and add nothing to the
        epoch program's sum of it."""
        epoch, kept = trainer._train_epoch, {}

        def keeping(*operands):
            out = epoch(*operands)
            kept["loss_mtp"] = float(out[2]["loss_mtp"])
            return out

        trainer._train_epoch = keeping
        try:
            return dict(super()._probe(trainer, lora0), **kept)
        finally:
            trainer._train_epoch = epoch

    def finish(self) -> None:
        """As ``sft.Plane.finish``, the types held against the
        configuration's: frozen matrices as it stores them, the rest
        float32."""
        tr = self.trainer
        self.first["not_float32"] += misplaced(
            (tr.variables["params"], tr.lora), self.config["weights_stored"])
        self.trainer = None
        gc.collect()

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

        losses, lora, mu, _, terms = run(steps_with_data=1)
        undo = 1.0 / (0.1 * 0.9 ** (t["steps_per_call"] - 1))
        out = {"probe": {"loss1": losses[0], "loss_mtp": terms[0][1],
                         "change": change(lora),
                         "grad": {k: v * undo for k, v in norms(mu).items()}},
               "picks": np.asarray(ref.picks_one(
                   params, jnp.asarray(self._first_row()), cfg, mode))}
        if follow:
            losses, lora, mu, nu, _ = run()
            out.update(loss=float(np.mean(losses)), change=change(lora),
                       moment=norms(mu), second=norms(nu, squared=False))
        return out

    @staticmethod
    def gaps(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
        """``sft_routed.Plane.gaps`` and, beside them, the second head's loss
        of the probe's first step against the reference's."""
        out = sft_routed.Plane.gaps(got, want)
        out["mtp_loss_gap"] = abs(
            got["probe"]["loss_mtp"] - want["probe"]["loss_mtp"]) / abs(
                want["probe"]["loss_mtp"])
        return out

    def check(self) -> List[Dict]:
        t0 = now()
        want = self.reference_reading()
        got = self.gaps(self.first, want)
        self.rec.say("sft_check", reference_s=now() - t0,
                     reference_loss=want["loss"],
                     picks_agree_share=1.0 - got["picks_disagree_share"],
                     first_loss_gap_shown=got.pop("first_loss_gap"),
                     **self.after_sixteen(self.first, want))
        got["state_leaves_not_float32"] = float(self.first["not_float32"])
        return compare.against_limits(got, self.cell["limits"])
