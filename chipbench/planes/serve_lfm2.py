"""The serving plane for the LFM2 family: ``KVCacheLLMEngine`` over a model
whose layers are gated short convolutions beside grouped-head attention, with
sigmoid-routed SwiGLU experts, under the open-loop load, the clocks, the drain
and the check of ``planes/serve.py`` (the window, the drain and the check are
that file's own code).

What differs, and is this file's: the engine's model is made from the
configuration's keys, in the source's names, through the model hub's
arguments (``model_args``): the per-layer descriptions it makes for training
are what ``KVCacheLM`` makes its cache from; the weights are the reference's,
in the program's layout; set-up also serves one prompt short enough to be fed
through decode from position 0 (no prefill), and the warm-up's requests are
compared with the window's; and the reference is walked a request at a time
(its experts are sorted tiles: nothing to batch over).
"""

from typing import Any, Dict, List

import numpy as np

from . import serve


def model_args(cfg: Dict) -> Dict[str, Any]:
    """The model hub's arguments for a configuration in the source's keys:
    the stage's held layers, each a short convolution or an attention layer
    as ``layer_types`` says, the leading one dense."""
    held = [int(i) for i in cfg["held_layers"]]
    conv = [cfg["layer_types"][i] == "conv" for i in held]
    dense = [i < int(cfg["num_dense_layers"]) for i in held]
    if dense != sorted(dense, reverse=True):
        raise ValueError("the dense layers must lead the held ones")
    heads = int(cfg["num_attention_heads"])
    return dict(
        model="routed_lm", dataset="shakespeare",
        lm_dim=cfg["hidden_size"], lm_heads=heads,
        lm_kv_heads=cfg["num_key_value_heads"],
        lm_head_dim=int(cfg["hidden_size"]) // heads,
        lm_norm_eps=cfg["norm_eps"],
        lm_rope_theta=cfg["rope_parameters"]["rope_theta"],
        lm_rope_layout=[int(not c) for c in conv],
        lm_window_layout=[0] * len(held),
        lm_attention=dict(qk_norm=True),
        lm_conv=dict(taps=cfg["conv_L_cache"]),
        lm_conv_layout=[int(c) for c in conv],
        lm_dense_layers=sum(dense), lm_dense_ffn=cfg["intermediate_size"],
        lm_ffn=cfg["moe_intermediate_size"],
        lm_experts=cfg["num_experts"], lm_experts_held=cfg["num_experts"],
        lm_top_k=cfg["num_experts_per_tok"],
        lm_router=dict(scores="sigmoid", act="silu", reads="normed",
                       scale=float(cfg["routed_scaling_factor"])))


def layers_of(cfg: Dict):
    """Each held layer's ``functional_lm.Layer``, as the model hub makes
    them."""
    import fedml_tpu

    return fedml_tpu.model.create(
        fedml_tpu.Config(**model_args(cfg)),
        int(cfg["vocab_size"])).module.layers


class Plane(serve.Plane):
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from fedml_tpu.serving.kv_cache_lm import KVCacheLM
        from fedml_tpu.serving.llm_engine import KVCacheLLMEngine

        cfg = self.config
        room = int(cfg["n_positions"]) - int(self.t["max_total_tokens"])
        if room < 24:
            raise ValueError("max_total_tokens must leave 24 positions of "
                             "the cache free")
        layers = layers_of(cfg)
        if not any(getattr(layer, "conv", None) for layer in layers):
            # before any weight is made: a program from before the mixer
            raise SystemExit("chipbench: this program's model hub describes "
                             "no short-convolution layer")
        with self.rec.span("chipbench.build_engine"):
            params = jax.block_until_ready(
                self.ref.init_params(cfg, self.seed, jnp.bfloat16))
            lm = KVCacheLM(params, int(cfg["num_attention_heads"]),
                           int(cfg["n_positions"]), layers)
            self.engine = KVCacheLLMEngine(
                lm, max_batch=int(self.t["max_batch"]))
            self.tokens_per_dispatch = self.engine.tokens_per_dispatch
            del params, lm
        self.warmed: List = []
        with self.rec.span("chipbench.warm"):
            self._warm()
        self.rec.say("serve_setup", **{
            sp["name"].split(".")[1] + "_s": sp["t1"] - sp["t0"]
            for sp in self.rec.spans})

    def _warm(self) -> None:
        """Every program the window can reach, as `serve.Plane._warm` warms
        them, each request kept for the check; and last one prompt no
        longer than a dispatch, into the slot the others just left: it is
        fed through decode from position 0, where a row's state starts from
        zeros whatever the slot holds."""
        eng = self.engine
        rng = np.random.default_rng([self.seed, 0x3b])
        vocab = int(self.config["vocab_size"])
        top = max(b[1] for b in self.t["prompt_tokens"])
        low = min(b[0] for b in self.t["prompt_tokens"])
        lengths, prev = [], 0
        for b in eng._PREFILL_BUCKETS:
            if min(b, top, eng.lm.max_len) >= max(prev + 1, low):
                lengths.append(min(b, top, eng.lm.max_len))
            prev = b
        lengths.append(max(min(eng.tokens_per_dispatch, 5), 1))
        for n in lengths:
            max_new = min(2 * eng.tokens_per_dispatch + 3,
                          int(self.t["max_total_tokens"]) - n)
            fut = eng.submit(rng.integers(0, vocab, n).tolist(),
                             max_new=max_new)
            self.warmed.append((np.asarray(fut.result(timeout=1100)), n))
        self.rec.say("serve_warm", prompt_lengths=lengths)

    def finished(self) -> List:
        """The window's finished requests, and set-up's."""
        return self.warmed + super().finished()

    def gaps_on(self, finished, mode: str = "float32") -> Dict[str, float]:
        """`serve.Plane.gaps_on` for this family, a request at a time (padded
        to the next power of two: one program a padded length): the gap by
        which a served token's logit lies under that position's best by the
        reference's one full pass over the request, the widest over every
        served token and the mean over them.  A pick that flips on rounding
        moves a logit more than rounding does, in any precision, so the
        widest gap says that no token is far off and the mean, over
        thousands of tokens, what precision they were computed in."""
        import functools

        import jax
        import jax.numpy as jnp

        cfg, ref = self.config, self.ref
        z = ref.sizes(cfg)
        params = ref.init_params(cfg, self.seed, jnp.bfloat16)

        @functools.partial(jax.jit, static_argnames=("mode",))
        def widest(params, x, tok, live, mode):
            want = ref.logits_one(params, x, z, "float32")
            if mode != "float32":
                tok = jnp.argmax(ref.logits_one(params, x, z, mode), -1)
            at = jnp.take_along_axis(want, tok[:, None], -1)[:, 0]
            gap = jnp.where(live, want.max(-1) - at, 0.0)
            return jnp.max(gap), jnp.sum(gap)

        gaps, total, served = [0.0], 0.0, 0
        for seq, p in finished:
            pad = min(max(1 << (len(seq) - 2).bit_length(), 64),
                      int(cfg["n_positions"]))
            n = len(seq) - 1
            x, tok = np.zeros((pad,), np.int32), np.zeros((pad,), np.int32)
            live = np.zeros((pad,), bool)
            x[:n], tok[:n], live[p - 1:n] = seq[:-1], seq[1:], True
            served += int(live.sum())
            worst, summed = widest(params, jnp.asarray(x), jnp.asarray(tok),
                                   jnp.asarray(live), mode=mode)
            gaps.append(worst)
            total = total + summed
        return {"served_logit_gap": float(max(float(g) for g in gaps)),
                "served_logit_gap_mean": float(total) / max(served, 1),
                "tokens_compared": served}
