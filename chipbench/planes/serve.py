"""The serving plane: ``KVCacheLLMEngine`` under open-loop load.

The generator is this process's main thread.  It sends each request at the
time it is *due*, whatever the engine is doing, and times everything from the
client's side: a request's first token is late by (first ``on_token``
callback - due time), so a stall of the engine, or of the generator itself,
is charged to the requests that waited for it.  How late the generator ran is
reported beside it.  Requests due inside the window are measured; they are
drained after it.
"""

import gc
import time
from typing import Any, Dict, List

import numpy as np

from ..harness import compare
from ..harness.record import Record, now
from ..traffic import arrivals

#: how often the generator samples the engine's occupancy
SAMPLE_S = 0.25
#: positions the reference takes in one call (its float32 logits are
#: 200 KB a position at GPT-2's vocabulary)
CHECK_TOKENS = 4096


class _Sent:
    """One request as its client saw it."""

    __slots__ = ("due", "sent", "first", "last", "n", "future", "prompt_n",
                 "max_new", "hole")

    def __init__(self, due: float, prompt_n: int, max_new: int,
                 hole: List[float]) -> None:
        self.due, self.prompt_n, self.max_new = due, prompt_n, max_new
        self.sent = self.first = self.last = None
        self.n = 0
        self.future = None
        #: shared by the window's requests: the time of the latest token of
        #: any of them, the longest stretch without one, and when it ended
        self.hole = hole

    def on_token(self, _token: int) -> None:      # the engine's thread
        t = now()
        if self.first is None:
            self.first = t
        self.last = t
        self.n += 1
        hole = self.hole
        if t - hole[0] > hole[1]:
            hole[1], hole[2] = t - hole[0], t
        hole[0] = t


class Plane:
    def __init__(self, cell: Dict, config: Dict, reference, seed: int,
                 rec: Record) -> None:
        self.cell, self.config, self.ref = cell, config, reference
        self.seed, self.rec = int(seed), rec
        self.t = cell["traffic"]
        self.attempted = self.failed = 0
        self.engine = None
        self.sent: List[_Sent] = []
        self.done: List[Dict[str, Any]] = []

    # -------------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from fedml_tpu.serving.kv_cache_lm import KVCacheLM
        from fedml_tpu.serving.llm_engine import KVCacheLLMEngine

        cfg = self.config
        # a row within a dispatch of the cache's end sends the whole batch
        # down the engine's one-token fallback, a program no cell warms
        room = int(cfg["n_positions"]) - int(self.t["max_total_tokens"])
        if room < 24:
            raise ValueError("max_total_tokens must leave 24 positions of "
                             "the cache free")
        with self.rec.span("chipbench.build_engine"):
            params = jax.block_until_ready(
                self.ref.init_params(cfg, self.seed, jnp.bfloat16))
            lm = KVCacheLM(params, int(cfg["n_head"]),
                           int(cfg["n_positions"]))
            self.engine = KVCacheLLMEngine(
                lm, max_batch=int(self.t["max_batch"]))
            self.tokens_per_dispatch = self.engine.tokens_per_dispatch
        with self.rec.span("chipbench.warm"):
            self._warm()
        self.rec.say("serve_setup", **{
            sp["name"].split(".")[1] + "_s": sp["t1"] - sp["t0"]
            for sp in self.rec.spans})

    def _warm(self) -> None:
        """Every program the window can reach: one prompt inside each
        prefill bucket the traffic's prompts fall in, each followed by the
        short dispatch that follows an admission and then by full ones."""
        eng = self.engine
        rng = np.random.default_rng([self.seed, 0x3a])
        vocab = int(self.config["vocab_size"])
        top = max(b[1] for b in self.t["prompt_tokens"])
        low = min(b[0] for b in self.t["prompt_tokens"])
        lengths, prev = [], 0
        for b in eng._PREFILL_BUCKETS:
            if min(b, top, eng.lm.max_len) >= max(prev + 1, low):
                lengths.append(min(b, top, eng.lm.max_len))
            prev = b
        for n in lengths:
            fut = eng.submit(rng.integers(0, vocab, n).tolist(),
                             max_new=min(2 * eng.tokens_per_dispatch + 3,
                                         int(self.t["max_total_tokens"]) - n))
            fut.result(timeout=1100)
        self.rec.say("serve_warm", prompt_lengths=lengths)

    # -------------------------------------------------------------------------
    def window(self, seconds: float) -> None:
        rec, eng = self.rec, self.engine
        plan = arrivals.requests(self.t, seconds, self.seed,
                                 int(self.config["vocab_size"]))
        cap = float(eng.max_batch)
        t0 = now() + 0.02
        self.hole = [t0 + plan[0]["due_s"], 0.0, 0.0]
        self.sent = [_Sent(r["due_s"], len(r["prompt"]), r["max_new"],
                           self.hole) for r in plan]
        next_sample = t0

        def idle_until(t_abs: float) -> None:
            nonlocal next_sample
            while True:
                t = now()
                if t >= next_sample:
                    rec.sample("occupancy", eng.stats()["active"] / cap)
                    next_sample += SAMPLE_S
                    rec.trace_tick(t - t0, seconds)
                if t >= t_abs:
                    return
                time.sleep(max(min(t_abs, next_sample) - t, 0.0))

        for r, s in zip(plan, self.sent):
            idle_until(t0 + s.due)
            with rec.span("chipbench.submit"):
                s.sent = now()
                s.future = eng.submit(r["prompt"], max_new=r["max_new"],
                                      on_token=s.on_token)
        idle_until(t0 + seconds)
        rec.window = {"t0": t0, "t1": now()}

    def finish(self) -> None:
        """Drain what the window left, then stop the engine and free its
        state: the reference runs after it."""
        self.drain()
        eng = self.engine
        eng.stop()
        if eng._worker.is_alive():
            raise RuntimeError("the engine's thread did not stop")
        self.engine = None
        gc.collect()

    def drain(self) -> None:
        """Wait, outside the window, for the requests it left unfinished, and
        read each request's record from the client's and the engine's side."""
        t0 = self.rec.window["t0"]
        self.done = []
        deadline = now() + float(self.t["drain_seconds"])
        self.attempted = len(self.sent)
        for s in self.sent:
            row = {"due": s.due, "late_s": s.sent - (t0 + s.due),
                   "prompt_n": s.prompt_n, "max_new": s.max_new, "ok": False}
            try:
                out = s.future.result(timeout=max(deadline - now(), 0.01))
                req = s.future.request
                row.update(
                    tokens=np.asarray(out), reason=req.finish_reason,
                    queue_wait_s=req.queue_wait_s(),
                    prefill_s=req.prefill_s(), n=s.n,
                    ok=(req.finish_reason == "stop" and s.n == s.max_new
                        and len(out) == s.prompt_n + s.max_new))
            except Exception as e:      # shed, errored, or not done in time
                row["reason"] = type(e).__name__
            if row["ok"]:
                row["ttft_s"] = s.first - (t0 + s.due)
                if s.n >= 2:
                    row["tbt_s"] = (s.last - s.first) / (s.n - 1)
            self.done.append(row)
        self.failed = sum(not r["ok"] for r in self.done)
        self.sent = []
        # where a stall of the machine would show: the generator late too
        # means the whole process stood still, the engine alone does not
        self.rec.say("serve_window",
                     late_max_ms=1e3 * max(r["late_s"] for r in self.done),
                     longest_time_without_a_token_ms=1e3 * self.hole[1],
                     which_ended_at_s=self.hole[2] - t0)

    # -------------------------------------------------------------------------
    def finished(self) -> List:
        """Every request the window finished: (sequence, prompt length)."""
        return [(r["tokens"], r["prompt_n"]) for r in self.done if r["ok"]]

    def gaps_on(self, finished, mode: str = "float32") -> Dict[str, float]:
        """Teacher-force the reference over every finished sequence.  With
        the reference's own mode: the widest gap by which a *served* token's
        logit lies under that position's best.  With a control's mode: the
        same for the token the control puts first, judged by the reference's
        logits.  Sequences are padded to the next power of two (padding sits
        after every position that is read, and attention is causal) and taken
        ``CHECK_TOKENS`` positions to a call: one program a padded length."""
        import functools

        import jax
        import jax.numpy as jnp

        cfg, ref = self.config, self.ref
        params = jax.jit(ref.stack_blocks)(
            ref.init_params(cfg, self.seed, jnp.bfloat16))
        one = functools.partial(ref.logits_one, n_head=int(cfg["n_head"]),
                                eps=float(cfg["layer_norm_epsilon"]))

        @functools.partial(jax.jit, static_argnames=("mode",))
        def widest(params, x, tok, live, mode):
            want = jax.vmap(lambda row: one(params, row, mode="float32"))(x)
            if mode != "float32":
                tok = jnp.argmax(jax.vmap(
                    lambda row: one(params, row, mode=mode))(x), -1)
            at = jnp.take_along_axis(want, tok[..., None], -1)[..., 0]
            return jnp.max(jnp.where(live, want.max(-1) - at, 0.0))

        by_len: Dict[int, List] = {}
        for seq, p in finished:
            pad = min(max(1 << (len(seq) - 2).bit_length(), 64),
                      int(cfg["n_positions"]))
            by_len.setdefault(pad, []).append((seq, p))
        gaps, served = [0.0], 0
        for pad, group in sorted(by_len.items()):
            rows = max(CHECK_TOKENS // pad, 1)
            for k in range(0, len(group), rows):
                x = np.zeros((rows, pad), np.int32)
                tok = np.zeros((rows, pad), np.int32)
                live = np.zeros((rows, pad), bool)
                for i, (seq, p) in enumerate(group[k:k + rows]):
                    n = len(seq) - 1
                    x[i, :n], tok[i, :n] = seq[:-1], seq[1:]
                    live[i, p - 1:n] = True
                served += int(live.sum())
                gaps.append(widest(params, jnp.asarray(x), jnp.asarray(tok),
                                   jnp.asarray(live), mode=mode))
        return {"served_logit_gap": float(max(float(g) for g in gaps)),
                "tokens_compared": served}

    def check(self) -> List[Dict]:
        sample = self.finished()
        if not sample:
            return [{"name": "served_logit_gap", "value": float("nan"),
                     "limit": float(self.cell["limits"]["served_logit_gap"]),
                     "ok": False}]
        t0 = now()
        got = self.gaps_on(sample)
        self.rec.say("serve_check", requests=len(sample),
                     tokens_compared=got.pop("tokens_compared"),
                     reference_s=now() - t0)
        return compare.against_limits(got, self.cell["limits"])
