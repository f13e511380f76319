"""Batched LLM serving engine — the scalellm-equivalent runtime.

Capability parity: reference `serving/scalellm/` (a prebuilt GPU serving
runtime wrapper exposing generate/complete).  TPU-era design: continuous
batching on top of one jit-compiled fixed-shape decode step —

* requests enter a queue; a worker admits up to ``max_batch`` sequences
  into the active set BETWEEN decode steps (new arrivals don't wait for
  the whole previous batch to finish — continuous batching);
* every step runs ONE forward over a fixed [max_batch, window] token
  buffer (inactive rows are padding), so XLA compiles exactly once and
  the MXU sees a full batch regardless of arrival pattern;
* greedy or temperature sampling per request; finished rows retire and
  their slots are re-admitted immediately.
"""

from __future__ import annotations

import itertools
import logging
import queue
import threading
import time
from concurrent.futures import Future, TimeoutError as FuturesTimeoutError
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..core.mlops import ledger, tracing
from ..core.mlops import metrics as _metrics
from ..core.mlops.lock_profiler import named_lock
from .admission import ServingAdmissionController, ShedError

#: request-id stream (one per process): every request carries ``rid``
#: through its lifecycle events so the anatomy correlator can join them
_rid_counter = itertools.count(1)


#: positions of one row in a cache block, the unit the cache counters count in
#: (a lane tile of the cache's ``[B, H, Dh, T]``)
CACHE_BLOCK = 128


class _EngineMetrics:
    """Per-engine cached label children — one label lookup at construction
    instead of one per decode step.  Metric objects resolve get-or-create
    at construction (the ledger idiom) so an engine built after a test's
    ``REGISTRY.reset()`` still lands on the exposition surface."""

    #: decode ledger sampling stride: per-step ledger writes on the token
    #: hot loop would be the overhead the self-measurement exists to
    #: catch, so decode_batch events aggregate this many steps
    DECODE_LEDGER_EVERY = 64

    def __init__(self, engine_label: str) -> None:
        self.label = engine_label
        self.ttft = _metrics.histogram(
            "fedml_llm_ttft_seconds", "Submit-to-first-token latency",
            labels=("engine",),
            buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                     15.0, 60.0)).labels(engine=engine_label)
        self.step = _metrics.histogram(
            "fedml_llm_decode_step_seconds",
            "Latency of one decode dispatch", labels=("engine",),
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 5.0)).labels(engine=engine_label)
        self.tokens = _metrics.counter(
            "fedml_llm_tokens_total", "Tokens generated",
            labels=("engine",)).labels(engine=engine_label)
        self.tps = _metrics.gauge(
            "fedml_llm_tokens_per_s",
            "Decode throughput since engine start",
            labels=("engine",)).labels(engine=engine_label)
        self.queue = _metrics.gauge(
            "fedml_llm_queue_depth", "Requests waiting for a batch slot",
            labels=("engine",)).labels(engine=engine_label)
        self.active = _metrics.gauge(
            "fedml_llm_active_requests", "Requests occupying batch slots",
            labels=("engine",)).labels(engine=engine_label)
        # TTFT decomposition (queue + prefill + first-decode): each leg
        # its own histogram so /metrics alone can check the identity
        self.queue_wait = _metrics.histogram(
            "fedml_llm_queue_wait_seconds",
            "Submit-to-admit wait for a batch slot (the queue leg of "
            "TTFT: ttft = queue_wait + prefill + first_decode)",
            labels=("engine",),
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5, 5.0, 15.0, 60.0)).labels(engine=engine_label)
        self.prefill = _metrics.histogram(
            "fedml_llm_prefill_seconds",
            "Admission-prefill latency (the prefill leg of TTFT)",
            labels=("engine",),
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 5.0)).labels(engine=engine_label)
        self.tbt = _metrics.histogram(
            "fedml_llm_tbt_seconds",
            "Per-request mean time-between-tokens, observed at FINISH "
            "only (cancelled requests never count)",
            labels=("engine",),
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 5.0)).labels(engine=engine_label)
        self._shed_total = _metrics.counter(
            "fedml_llm_shed_total",
            "Requests refused admission by the serving admission policy",
            labels=("engine", "reason"))
        self._requests_total = _metrics.counter(
            "fedml_llm_requests_total",
            "Requests by terminal lifecycle outcome",
            labels=("engine", "outcome"))
        self.occupancy = _metrics.gauge(
            "fedml_llm_batch_occupancy",
            "Active batch slots / max_batch, sampled on the engine loop",
            labels=("engine",)).labels(engine=engine_label)
        self.kv_tokens = _metrics.gauge(
            "fedml_llm_kv_cache_tokens",
            "KV-cache positions in use across active slots, sampled on "
            "the engine loop", labels=("engine",)).labels(
                engine=engine_label)
        # what `decode_multi`'s attention reads of the cache, in blocks of
        # CACHE_BLOCK positions a row, summed over token steps
        self.cache_blocks_live = _metrics.counter(
            "fedml_llm_cache_blocks_live_total",
            "Cache blocks (128 positions of one row) holding a position a "
            "query may attend to, summed over decode_multi's token steps",
            labels=("engine",)).labels(engine=engine_label)
        self.cache_blocks = _metrics.counter(
            "fedml_llm_cache_blocks_total",
            "Cache blocks there are (max_batch x ceil(T / 128)), summed "
            "over decode_multi's token steps",
            labels=("engine",)).labels(engine=engine_label)
        # a state that is not by position (a short convolution's last
        # inputs) is set at every admission, never inherited: from the
        # prefill, or to zeros by the row's first dispatch from position 0
        self.state_sets = _metrics.counter(
            "fedml_llm_state_sets_total",
            "Admissions that set a slot's fixed-size state, by how: from "
            "the prefill, or to zeros for a prompt fed through decode",
            labels=("engine", "how"))
        self._decode_lock = named_lock("_EngineMetrics._decode_lock")
        self._decode_steps = 0
        self._decode_secs = 0.0

    def note_moe(self, counts: "np.ndarray") -> None:
        """A routed model's counts of one dispatch (`kv_cache_lm.MOE_COUNTS`,
        a token step a column), onto the process's counters: the same
        ``fedml_moe_*`` the epoch program's counts land on, and the experts
        touched (docs/OBSERVABILITY.md)."""
        from .kv_cache_lm import MOE_COUNTS

        for key, row in zip(MOE_COUNTS, counts):
            name, what = self._MOE[key]
            # on the host already: `_step_multi` fetched it with the tokens
            _metrics.counter(name, what).inc(
                float(row.sum()))  # fedml: noqa[JAX003]

    _MOE = {
        "picks": ("fedml_moe_picks_total",
                  "expert picks routed, over tokens, layers and steps"),
        "expert_picks_max": (
            "fedml_moe_expert_picks_max",
            "picks of the heaviest held expert of each step, summed"),
        "experts_touched": (
            "fedml_moe_experts_touched_total",
            "held experts a token step picked at all, summed over layers "
            "and steps: the matrices decode had to fetch"),
    }

    # -- per-request lifecycle ----------------------------------------------
    # events: submit → (queue) → admit|shed → prefill → first_token →
    # (decode) → finish|cancel.  Every emission carries ``rid`` so
    # `loadgen.anatomy.request_anatomy` can join a request's lifecycle
    # back together; all ledger writes are one-dict-hit no-ops when the
    # run ledger is disarmed.

    def note_submit(self, req: "_Request") -> None:
        if ledger.enabled():
            ledger.event("serving", "submit", rid=req.rid,
                         engine=self.label, prompt_tokens=len(req.ids),
                         max_new=req.remaining)
            req.span = tracing.start_span(
                "serving.request", rid=req.rid, engine=self.label)

    def note_shed(self, req: "_Request", reason: str,
                  queue_depth: int) -> None:
        req.outcome = "shed"
        req.finish_reason = "shed"
        self._shed_total.labels(engine=self.label, reason=reason).inc()
        self._requests_total.labels(engine=self.label,
                                    outcome="shed").inc()
        if ledger.enabled():
            ledger.event("serving", "shed", rid=req.rid,
                         engine=self.label, reason=reason,
                         queue_depth=int(queue_depth))
        if req.span is not None:
            req.span.set_attr("reason", reason)
            req.span.end("shed")

    def note_admit(self, req: "_Request", slot: int) -> None:
        req.t_admit = time.monotonic()
        wait = req.t_admit - req.t_submit
        self.queue_wait.observe(wait)
        if ledger.enabled():
            ledger.event("serving", "admit", rid=req.rid,
                         engine=self.label, slot=int(slot),
                         queue_wait_s=round(wait, 6))

    def note_prefill(self, req: "_Request", secs: float) -> None:
        req.t_prefill_done = time.monotonic()
        self.prefill.observe(secs)
        if ledger.enabled():
            ledger.event("serving", "prefill", rid=req.rid,
                         engine=self.label, secs=round(secs, 6),
                         tokens=len(req.ids))

    def note_token(self, req: "_Request") -> None:
        now = time.monotonic()
        if req.t_first_token is None:
            req.t_first_token = now
            self.ttft.observe(now - req.t_submit)
            if ledger.enabled():
                ledger.event("serving", "first_token", rid=req.rid,
                             engine=self.label,
                             ttft_s=round(now - req.t_submit, 6),
                             queue_wait_s=round(req.queue_wait_s(), 6),
                             prefill_s=round(req.prefill_s(), 6),
                             first_decode_s=round(
                                 req.first_decode_s(now), 6))
        req.t_last_token = now
        req.n_generated += 1
        self.tokens.inc()

    def note_retire(self, req: "_Request", outcome: str) -> None:
        """Terminal lifecycle transition: ``finish`` or ``cancel``.
        Idempotent per request; TBT is observed on FINISH only so a
        cancelled stream's tokens never skew the TBT percentiles."""
        if req.outcome is not None:
            return
        req.outcome = outcome
        req.t_finish = time.monotonic()
        self._requests_total.labels(engine=self.label,
                                    outcome=outcome).inc()
        if outcome == "finish" and req.n_generated >= 2 \
                and req.t_first_token is not None \
                and req.t_last_token is not None:
            self.tbt.observe((req.t_last_token - req.t_first_token)
                             / (req.n_generated - 1))
        if ledger.enabled():
            ledger.event("serving", outcome, rid=req.rid,
                         engine=self.label, tokens=req.n_generated,
                         finish_reason=req.finish_reason,
                         service_s=round(req.t_finish - req.t_submit, 6))
        if req.span is not None:
            req.span.set_attr("tokens", req.n_generated)
            req.span.end(None if outcome == "finish" else outcome)

    def note_decode(self, dt: float, batch_size: int) -> None:
        """Sampled run-ledger attribution for the decode loop: one
        ``decode_batch`` event per DECODE_LEDGER_EVERY dispatches."""
        if not ledger.enabled():
            return
        with self._decode_lock:
            self._decode_steps += 1
            self._decode_secs += dt
            if self._decode_steps < self.DECODE_LEDGER_EVERY:
                return
            steps, secs = self._decode_steps, self._decode_secs
            self._decode_steps = 0
            self._decode_secs = 0.0
        ledger.event("serving", "decode_batch", engine=self.label,
                     steps=steps, secs=round(secs, 6), batch=batch_size)


_scatter_cache_row_jit = None


def _scatter_cache_row(cache, row_cache, slot):
    """Write a 1-row prefilled cache into row ``slot`` of the batch cache
    (one jitted donate-in-place dispatch for all layers, whatever each
    layer keeps: `kv_cache_lm.layer_state` puts the rows first in every
    entry) — the admission path of `KVCacheLLMEngine._prefill_admit`."""
    global _scatter_cache_row_jit
    if _scatter_cache_row_jit is None:
        import jax

        def scatter_cache_row(cache, row_cache, slot):
            return [
                {name: kept.at[slot].set(row[name][0])
                 for name, kept in layer.items()}
                for layer, row in zip(cache, row_cache)]

        _scatter_cache_row_jit = jax.jit(scatter_cache_row,
                                         donate_argnums=(0,))
    return _scatter_cache_row_jit(cache, row_cache, slot)


class _Request:
    def __init__(self, prompt_ids: List[int], max_new: int,
                 temperature: float, top_k: int = 0,
                 top_p: float = 1.0, on_token=None) -> None:
        self.ids = list(prompt_ids)
        self.remaining = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k or 0)
        self.top_p = float(top_p if top_p is not None else 1.0)
        self.on_token = on_token        # per-token streaming callback
        self.future: "Future[np.ndarray]" = Future()
        #: "stop" (ran to its token budget), "length" (the engine had to
        #: truncate: cache capacity < prompt+max_new), "cancelled", or
        #: "shed" — OpenAI semantics, surfaced to callers via
        #: future.request.finish_reason
        self.finish_reason = "stop"
        self.cancelled = threading.Event()
        # -- lifecycle telemetry (submit → admit|shed → prefill →
        #    first_token → finish|cancel); rid joins a request's ledger
        #    events + span back together in `loadgen.anatomy`
        self.rid = next(_rid_counter)
        self.t_submit = time.monotonic()
        self.t_admit: Optional[float] = None
        self.t_prefill_done: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.t_last_token: Optional[float] = None
        self.t_finish: Optional[float] = None
        self.n_generated = 0
        #: terminal lifecycle outcome ("finish" | "cancel" | "shed"),
        #: set exactly once by _EngineMetrics.note_retire / note_shed
        self.outcome: Optional[str] = None
        self.span: Optional[tracing.Span] = None
        self.future.request = self  # type: ignore[attr-defined]

    # -- TTFT decomposition legs (ttft = queue_wait + prefill +
    #    first_decode by construction; un-measured legs report 0.0)
    def queue_wait_s(self) -> float:
        if self.t_admit is None:
            return 0.0
        return self.t_admit - self.t_submit

    def prefill_s(self) -> float:
        if self.t_prefill_done is None or self.t_admit is None:
            return 0.0
        return self.t_prefill_done - self.t_admit

    def first_decode_s(self, t_first: float) -> float:
        base = self.t_prefill_done or self.t_admit or self.t_submit
        return t_first - base

    def cancel(self) -> None:
        """Ask the worker to retire this request at the next step (used by
        streaming consumers that disconnect mid-generation)."""
        self.cancelled.set()

    def emit(self, token: int) -> None:
        if self.on_token is not None:
            try:
                self.on_token(int(token))
            except Exception:  # noqa: BLE001 — consumer bugs can't kill the worker
                pass


def _sample_token(row: np.ndarray, req: "_Request", rng: np.random.Generator
                  ) -> int:
    """Greedy / temperature with optional top-k then nucleus (top-p)
    filtering (reference serving templates' sampling controls)."""
    if req.temperature <= 0:
        return int(np.argmax(row))
    logits = row.astype(np.float64) / req.temperature
    if req.top_k > 0 and req.top_k < len(logits):
        kth = np.partition(logits, -req.top_k)[-req.top_k]
        logits = np.where(logits < kth, -np.inf, logits)
    p = np.exp(logits - np.max(logits))
    p = p / p.sum()
    if req.top_p < 1.0:
        # top_p<=0 degenerates to keep-top-token (HF convention)
        order = np.argsort(-p)
        csum = np.cumsum(p[order])
        cut = max(int(np.searchsorted(csum, max(req.top_p, 0.0))) + 1, 1)
        mask = np.zeros_like(p)
        mask[order[:cut]] = 1.0
        p = p * mask
        p = p / p.sum()
    return int(rng.choice(len(p), p=p))


class BatchedLLMEngine:
    def __init__(self, bundle: Any, variables: Dict[str, Any],
                 max_batch: int = 8, window: Optional[int] = None,
                 max_wait_s: float = 0.005,
                 admission: Optional[ServingAdmissionController] = None
                 ) -> None:
        import jax
        import jax.numpy as jnp

        self.bundle = bundle
        self.variables = variables
        self.max_batch = int(max_batch)
        self.window = int(window or getattr(bundle, "input_shape",
                                            (64,))[0] or 64)
        self.max_wait_s = float(max_wait_s)
        self.admission = admission
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        self._active: List[Optional[_Request]] = [None] * self.max_batch
        self._stop = threading.Event()
        self._np_rng = np.random.default_rng(7)
        self._metrics = _EngineMetrics("batched")
        #: guards loop-mutated counters that stats() snapshots from other
        #: threads (the autoscaler + load report read while the loop writes)
        self._state_lock = named_lock("BatchedLLMEngine._state_lock")
        self._tokens_done = 0
        self._t_start = time.monotonic()

        def step(variables, x, pos):
            # sequences are LEFT-aligned with zero right-padding; under
            # causal attention logits at index pos[i]-1 are EXACTLY the
            # unpadded next-token logits (padding can't attend backward),
            # so no attention mask is needed
            logits, _ = bundle.apply(variables, x, train=False)
            idx = jnp.clip(pos - 1, 0, x.shape[1] - 1)
            return jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0, :]  # [B, V]

        self._step = jax.jit(step)
        self._jnp = jnp
        self._jax = jax
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="llm-engine")
        self._worker.start()

    # -- public API ---------------------------------------------------------
    def submit(self, prompt_ids, max_new: int = 20,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, on_token=None) -> "Future[np.ndarray]":
        req = _Request(list(np.asarray(prompt_ids).tolist()), max_new,
                       temperature, top_k, top_p, on_token)
        if self._stop.is_set():
            req.future.set_exception(RuntimeError("engine stopped"))
            return req.future
        self._metrics.note_submit(req)
        if req.remaining <= 0:  # zero-budget: resolve without a decode step
            self._metrics.note_retire(req, "finish")
            req.future.set_result(np.asarray(req.ids))
            return req.future
        if self.admission is not None:
            depth = self._pending.qsize()
            ok, reason = self.admission.admit(depth)
            if not ok:
                self._metrics.note_shed(req, reason, depth)
                req.future.set_exception(
                    ShedError(reason, f"request shed ({reason}); "
                                      f"queue_depth={depth}"))
                return req.future
        self._pending.put(req)
        return req.future

    def generate(self, prompt_ids, max_new: int = 20,
                 temperature: float = 0.0, timeout: float = 120.0,
                 top_k: int = 0, top_p: float = 1.0) -> np.ndarray:
        fut = self.submit(prompt_ids, max_new, temperature, top_k, top_p)
        try:
            return fut.result(timeout)
        except (TimeoutError, FuturesTimeoutError):
            # free the slot: a timed-out request must not keep generating
            # into an orphaned future
            req = getattr(fut, "request", None)
            if req is not None:
                req.cancel()
            raise

    def stop(self) -> None:
        self._stop.set()
        self._worker.join(timeout=5.0)
        # a submit() racing stop() may have put() after the worker's final
        # drain — resolve any such stragglers here
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if not req.future.done():
                self._metrics.note_retire(req, "cancel")
                req.future.set_exception(RuntimeError("engine stopped"))

    @property
    def active_count(self) -> int:
        return sum(1 for r in self._active if r is not None)

    @property
    def alive(self) -> bool:
        """True while the engine can serve: not stopped AND the worker
        thread hasn't died (e.g. from a step exception)."""
        return not self._stop.is_set() and self._worker.is_alive()

    # -- worker -------------------------------------------------------------
    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self._active[slot] is None:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    return
                self._active[slot] = req
                self._metrics.note_admit(req, slot)

    def _retire(self, req: "_Request", outcome: str) -> None:
        self._metrics.note_retire(req, outcome)
        if self.admission is not None:
            self.admission.note_finish()

    def _loop(self) -> None:
        jnp = self._jnp
        while not self._stop.is_set():
            self._admit()
            if self.active_count == 0:
                try:
                    # idle: block on a coarse stop-aware wait (max_wait_s
                    # only bounds BATCHING latency, not idle polling)
                    req = self._pending.get(timeout=0.5)
                    self._active[0] = req
                    self._metrics.note_admit(req, 0)
                except queue.Empty:
                    continue
            x = np.zeros((self.max_batch, self.window), np.int32)
            pos = np.ones((self.max_batch,), np.int32)
            for slot, req in enumerate(self._active):
                if req is not None:
                    tail = req.ids[-self.window:]
                    x[slot, :len(tail)] = tail  # left-aligned window
                    pos[slot] = len(tail)
            t_step = time.monotonic()
            with self._metrics.step.time():
                logits = np.asarray(self._step(self.variables,
                                               jnp.asarray(x),
                                               jnp.asarray(pos)))
            self._metrics.note_decode(time.monotonic() - t_step,
                                      self.active_count)
            produced = 0
            for slot, req in enumerate(self._active):
                if req is None:
                    continue
                if req.cancelled.is_set():
                    req.finish_reason = "cancelled"
                    self._retire(req, "cancel")
                    if not req.future.done():
                        req.future.set_result(np.asarray(req.ids))
                    self._active[slot] = None
                    continue
                nxt = _sample_token(logits[slot], req, self._np_rng)
                req.ids.append(nxt)
                self._metrics.note_token(req)
                produced += 1
                req.emit(nxt)
                req.remaining -= 1
                if req.remaining <= 0:
                    self._retire(req, "finish")
                    req.future.set_result(np.asarray(req.ids))
                    self._active[slot] = None  # slot freed mid-flight
            with self._state_lock:
                self._tokens_done += produced
                tokens_done = self._tokens_done
            self._metrics.queue.set(self._pending.qsize())
            self._metrics.active.set(self.active_count)
            self._metrics.occupancy.set(self.active_count / self.max_batch)
            self._metrics.tps.set(tokens_done / max(
                time.monotonic() - self._t_start, 1e-9))
        # drain on shutdown: active AND still-pending requests must resolve
        for req in self._active:
            if req is not None and not req.future.done():
                self._retire(req, "cancel")
                req.future.set_result(np.asarray(req.ids))
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if not req.future.done():
                self._metrics.note_retire(req, "cancel")
                req.future.set_exception(RuntimeError("engine stopped"))

    def stats(self) -> Dict[str, float]:
        """Live metrics in the autoscaler's `observe` shape.  The counter
        snapshot happens under ``_state_lock`` (the loop batches its
        updates under the same lock) and the SAME values are pushed to the
        Prometheus gauges, so the load report and /metrics can't disagree."""
        with self._state_lock:
            tokens_done = self._tokens_done
        dt = max(time.monotonic() - self._t_start, 1e-9)
        tps = tokens_done / dt
        depth = self._pending.qsize()
        active = self.active_count
        self._metrics.tps.set(tps)
        self._metrics.queue.set(depth)
        self._metrics.active.set(active)
        self._metrics.occupancy.set(active / self.max_batch)
        return {"tokens_per_s": tps, "queue_depth": depth,
                "active": active, "capacity": self.max_batch}


class LLMEnginePredictor:
    """FedMLPredictor-shaped adapter: plugs a BatchedLLMEngine into the
    HTTP inference runner and the OpenAI-compatible chat API (reference
    serving/templates/hf_template — generation backend behind /predict and
    /v1/chat/completions).  ``encode``/``decode`` map text ↔ token ids;
    defaults to the char-level codec of the shakespeare-vocab models."""

    def __init__(self, engine: BatchedLLMEngine, encode=None,
                 decode=None) -> None:
        self.engine = engine
        self.encode = encode or (lambda s: [
            min(max(ord(c) - 32, 0), 89) for c in s] or [0])
        self.decode = decode or (lambda ids: "".join(
            chr(int(i) + 32) for i in ids))

    def predict(self, request: Any) -> str:
        r = self.predict_full(request)
        return r["stream"] if "stream" in r else r["text"]

    def predict_full(self, request: Any) -> Dict[str, Any]:
        """predict + OpenAI metadata.  Non-streaming → {"text",
        "finish_reason"} ("length" when the engine truncated the token
        budget); streaming → {"stream": generator, "finish": callable
        returning the final reason once the stream ends}."""
        if isinstance(request, str):
            request = {"prompt": request}
        prompt = str(request.get("prompt", ""))
        raw_max = request.get("max_tokens")
        max_tokens = 20 if raw_max is None else int(raw_max)
        temperature = float(request.get("temperature", 0.0) or 0.0)
        raw_k, raw_p = request.get("top_k"), request.get("top_p")
        top_k = 0 if raw_k is None else int(raw_k)
        top_p = 1.0 if raw_p is None else float(raw_p)
        ids = self.encode(prompt)
        timeout = float(request.get("timeout", 300.0) or 300.0)
        if request.get("stream"):
            holder: Dict[str, str] = {}
            gen = self._stream_tokens(ids, max_tokens, temperature,
                                      top_k, top_p, timeout, holder)
            return {"stream": gen,
                    "finish": lambda: holder.get("finish", "stop")}
        fut = self.engine.submit(ids, max_new=max_tokens,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p)
        req = getattr(fut, "request", None)
        try:
            out = fut.result(timeout)
        except (TimeoutError, FuturesTimeoutError):
            # free the slot — otherwise timed-out requests keep generating
            # into orphaned futures until they starve live traffic.  Both
            # names: futures.TimeoutError only aliases the builtin on 3.11+
            if req is not None:
                req.cancel()
            raise
        return {"text": self.decode(out[len(ids):]),
                "finish_reason": getattr(req, "finish_reason", "stop")}

    def _stream_tokens(self, ids, max_tokens, temperature, top_k, top_p,
                       timeout: float = 300.0, holder: Optional[dict] = None):
        """Generator yielding decoded tokens AS the engine produces them —
        the lazy iterable the SSE path consumes incrementally.  ``timeout``
        bounds the inter-token gap (from the request, not hardcoded); a
        consumer that disconnects (GeneratorExit) or times out CANCELS the
        underlying engine request so the slot stops generating into an
        orphaned queue."""
        q: "queue.Queue" = queue.Queue()
        fut = self.engine.submit(ids, max_new=max_tokens,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p, on_token=q.put)
        fut.add_done_callback(lambda _f: q.put(None))
        req = getattr(fut, "request", None)
        try:
            while True:
                try:
                    tok = q.get(timeout=timeout)
                except queue.Empty:
                    if req is not None:
                        req.cancel()
                    if holder is not None:
                        holder["finish"] = "timeout"
                    raise TimeoutError(
                        f"no token for {timeout:.0f}s; request cancelled")
                if tok is None:
                    break
                yield self.decode([tok])
            if holder is not None and req is not None:
                holder["finish"] = req.finish_reason
        except GeneratorExit:
            if req is not None:
                req.cancel()
            raise

    def ready(self) -> bool:
        return self.engine.alive


class KVCacheLLMEngine:
    """Continuous batching over a per-row KV cache (`kv_cache_lm.KVCacheLM`)
    — the prefill/decode architecture of scalellm/vLLM.  An iteration of
    the loop admits what is pending (a prompt longer than a dispatch is
    prefilled whole, a shorter one is teacher-forced through the decode
    program), picks the dispatch length and runs `decode_multi` once for
    every slot: the ONE decode path, whatever the length and however near a
    row is to the end of its cache.  Each generated token costs
    O(cache_len) attention instead of the full-window O(T²) re-forward of
    `BatchedLLMEngine`."""

    def __init__(self, lm: Any, max_batch: int = 8,
                 tokens_per_dispatch: int = 8,
                 admission: Optional[ServingAdmissionController] = None
                 ) -> None:
        import jax
        import jax.numpy as jnp

        self.lm = lm
        self.max_batch = int(max_batch)
        #: inner on-device loop length: decode_multi samples k tokens per
        #: dispatch (greedy, temperature, top-k and nucleus filtering all
        #: run on-device) with NO host round trip in between — a ~k x
        #: dispatch-latency win; 1 is one token a dispatch, the same program
        self.tokens_per_dispatch = max(int(tokens_per_dispatch), 1)
        self.admission = admission
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        self._active: List[Optional[_Request]] = [None] * self.max_batch
        # per-slot decode state: position only (prefill progress is
        # _pos vs len(req.ids))
        self._pos = np.zeros((self.max_batch,), np.int32)
        self._cache = lm.init_cache(self.max_batch)
        #: some layer keeps a state that is not by position
        self._stateful = any("k" not in layer for layer in self._cache)
        self._stop = threading.Event()
        self._rng_key = jax.random.PRNGKey(13)
        #: guards loop-mutated counters that stats() snapshots from other
        #: threads (the autoscaler + load report read while the loop writes)
        self._state_lock = named_lock("KVCacheLLMEngine._state_lock")
        self._tokens_done = 0
        self._t_start = time.monotonic()
        self._metrics = _EngineMetrics("kv")
        #: the length of the iteration (admit to end of stream) before the
        #: one under way, for `tracing.note_iteration`
        self._prev_iter_s: Optional[float] = None
        self._jax, self._jnp = jax, jnp
        self._worker = threading.Thread(target=self._loop, daemon=True,
                                        name="kv-llm-engine")
        self._worker.start()

    # -- public API (mirrors BatchedLLMEngine) ------------------------------
    def submit(self, prompt_ids, max_new: int = 20,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, on_token=None) -> "Future[np.ndarray]":
        req = _Request(list(np.asarray(prompt_ids).tolist()), max_new,
                       temperature, top_k, top_p, on_token)
        if self._stop.is_set():
            req.future.set_exception(RuntimeError("engine stopped"))
            return req.future
        self._metrics.note_submit(req)
        cap = self.lm.max_len
        req.prefix = []
        if len(req.ids) + req.remaining > cap:
            # cache capacity split: generation gets what it asked for up to
            # half the cache; the prompt TAIL keeps the rest (the full
            # sequence is still returned) — so a long prompt is never cut
            # to a single token just because max_new was large
            gen = min(req.remaining,
                      max(cap - len(req.ids), cap // 2))
            keep = cap - gen
            if len(req.ids) > keep:
                req.prefix = req.ids[:-keep]
                req.ids = req.ids[-keep:]
            if gen < req.remaining:
                # fewer tokens than asked for: surface it, don't hide it
                req.finish_reason = "length"
            req.remaining = gen
        if req.remaining <= 0 or len(req.ids) == 0:
            self._metrics.note_retire(req, "finish")
            req.future.set_result(np.asarray(req.prefix + req.ids))
            return req.future
        if self.admission is not None:
            depth = self._pending.qsize()
            ok, reason = self.admission.admit(depth)
            if not ok:
                self._metrics.note_shed(req, reason, depth)
                req.future.set_exception(
                    ShedError(reason, f"request shed ({reason}); "
                                      f"queue_depth={depth}"))
                return req.future
        self._pending.put(req)
        return req.future

    def generate(self, prompt_ids, max_new: int = 20,
                 temperature: float = 0.0, timeout: float = 120.0,
                 top_k: int = 0, top_p: float = 1.0) -> np.ndarray:
        fut = self.submit(prompt_ids, max_new, temperature, top_k, top_p)
        try:
            return fut.result(timeout)
        except (TimeoutError, FuturesTimeoutError):
            # free the slot: a timed-out request must not keep generating
            # into an orphaned future
            req = getattr(fut, "request", None)
            if req is not None:
                req.cancel()
            raise

    def stop(self) -> None:
        self._stop.set()
        self._worker.join(timeout=5.0)
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if not req.future.done():
                req.future.set_exception(RuntimeError("engine stopped"))

    @property
    def active_count(self) -> int:
        return sum(1 for r in self._active if r is not None)

    @property
    def alive(self) -> bool:
        return not self._stop.is_set() and self._worker.is_alive()

    # -- worker -------------------------------------------------------------
    def _admit(self) -> Tuple[bool, float]:
        """Admit pending requests into free slots; returns True iff any
        admitted request was ADMISSION-PREFILLED (its first token is one
        short dispatch away — the turbo-dispatch precondition), and the
        seconds the admissions took."""
        any_prefilled, admit_s = False, 0.0
        for slot in range(self.max_batch):
            if self._active[slot] is None:
                try:
                    req = self._pending.get_nowait()
                except queue.Empty:
                    break
                prefilled, secs = self._admit_one(slot, req)
                any_prefilled |= prefilled
                admit_s += secs
        return any_prefilled, admit_s

    def _admit_one(self, slot: int, req: "_Request") -> Tuple[bool, float]:
        """One request just popped off ``_pending`` into ``slot``; True iff
        it was admission-prefilled, and the seconds it took."""
        with tracing.phase("fedml.serve.admit") as admit:
            self._active[slot] = req
            self._pos[slot] = 0
            self._metrics.note_admit(req, slot)
            prefilled = self._prefill_admit(slot, req)
            if self._stateful:
                self._metrics.state_sets.labels(
                    engine=self._metrics.label,
                    how="prefill" if prefilled else "zero").inc()
        return prefilled, admit.dur_s

    def _end_iteration(self, admit_s: float, *parts: tracing.Phase) -> None:
        """Close the iteration that its admissions and these phases made
        up; say so when it stood still."""
        total = admit_s + sum(ph.dur_s for ph in parts)
        tracing.note_iteration(
            "kv-engine: iteration", total, self._prev_iter_s,
            [("admit", admit_s)] + [
                (ph.name.split(".")[2], ph.dur_s) for ph in parts])
        self._prev_iter_s = total

    def _retire(self, req: "_Request", outcome: str) -> None:
        self._metrics.note_retire(req, outcome)
        if self.admission is not None:
            self.admission.note_finish()

    #: admission prefill length buckets (prompt padded up to the next
    #: bucket): one compiled prefill variant per bucket actually seen,
    #: instead of one per prompt length
    _PREFILL_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048)

    def _prefill_admit(self, slot: int, req: "_Request") -> bool:
        """TTFT path: run the REAL prefill over the admitted prompt in one
        dispatch and scatter its cache row into the batch cache, instead
        of teacher-forcing the prompt through ceil(P/k) decode dispatches.
        Measured on v5e (GPT-2 geometry, 45-token prompt, k=16): served
        TTFT 1075 ms → one prefill + one decode dispatch.  Decode resumes
        at the LAST prompt position: feeding ids[P-1] at pos P-1 rewrites
        identical K/V and yields the logits that sample token P; a state
        that is not by position comes from `prefill` as it stood before
        that position.  A prompt that is not prefilled starts at position
        0, where `decode_multi` starts every such state from zeros."""
        p = len(req.ids)
        k = self.tokens_per_dispatch
        # short prompts: chunked prefill already reaches generation in one
        # dispatch, and the scatter would cost more than it saves
        if p <= max(k, 2):
            return False
        tp = next((b for b in self._PREFILL_BUCKETS
                   if b >= p and b <= self.lm.max_len), None)
        if tp is None:
            tp = self.lm.max_len
        jnp = self._jnp
        # both are enqueues: neither waits for the device
        with tracing.phase(f"fedml.serve.prefill.t{tp}") as prefill:
            toks = np.zeros((1, tp), np.int32)
            toks[0, :p] = req.ids
            try:
                row_cache, _ = self.lm.prefill(jnp.asarray(toks),
                                               jnp.asarray([p], np.int32))
            except Exception:  # noqa: BLE001 — no donation yet: safe fallback
                logging.exception("kv-engine: admission prefill failed; "
                                  "falling back to chunked prefill")
                return False
        try:
            with tracing.phase("fedml.serve.scatter") as scatter:
                self._cache = _scatter_cache_row(
                    self._cache, row_cache, jnp.asarray(slot, np.int32))
        except Exception:  # noqa: BLE001
            # the scatter DONATES self._cache; an execution-time failure
            # (e.g. OOM) may have consumed it.  Rebuild an empty cache and
            # restart every active row's prefill from position 0 — req.ids
            # holds prompt + generated tokens, so chunked re-prefill
            # resumes each request correctly (slower, never wrong)
            logging.exception("kv-engine: admission scatter failed; "
                              "rebuilding cache and re-prefilling")
            dead = any(
                getattr(leaf, "is_deleted", lambda: False)()
                for layer in self._cache for leaf in layer.values())
            if dead:
                self._cache = self.lm.init_cache(self.max_batch)
                self._pos[:] = 0
            return False
        self._pos[slot] = p - 1
        self._metrics.note_prefill(req, prefill.dur_s + scatter.dur_s)
        return True

    #: admission-turbo dispatch length: the FIRST dispatch after an
    #: admission-PREFILLED request joins runs this many tokens instead of
    #: tokens_per_dispatch, so its first token lands after a 2-token
    #: dispatch rather than a full one.  Applies ONLY when the prompt was
    #: actually prefilled at admission (a chunk-prefilling short prompt
    #: would otherwise pay an extra dispatch RTT before its first token).
    #: Not re-measured on a local chip.  Set to 0 to disable.
    ADMIT_TURBO_K = 2

    def _loop(self) -> None:
        while not self._stop.is_set():
            turbo, admit_s = self._admit()
            if self.active_count == 0:
                try:
                    # the one wait of the loop with no work offered
                    with tracing.phase("fedml.serve.empty"):
                        req = self._pending.get(timeout=0.5)
                except queue.Empty:
                    continue
                turbo, admit_s = self._admit_one(0, req)
            self._metrics.queue.set(self._pending.qsize())
            self._metrics.active.set(self.active_count)
            self._metrics.occupancy.set(self.active_count / self.max_batch)
            self._metrics.kv_tokens.set(int(sum(
                int(self._pos[s]) for s, r in enumerate(self._active)
                if r is not None)))
            with self._state_lock:
                tokens_done = self._tokens_done
            self._metrics.tps.set(tokens_done / max(
                time.monotonic() - self._t_start, 1e-9))
            k = self.tokens_per_dispatch
            if turbo and self.ADMIT_TURBO_K and self.ADMIT_TURBO_K < k:
                k = self.ADMIT_TURBO_K
            self._step_multi(k, admit_s)
        for req in self._active:
            if req is not None and not req.future.done():
                self._retire(req, "cancel")
                req.future.set_result(
                    np.asarray(getattr(req, "prefix", []) + req.ids))
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if not req.future.done():
                self._metrics.note_retire(req, "cancel")
                req.future.set_exception(RuntimeError("engine stopped"))

    def stats(self) -> Dict[str, float]:
        """Live metrics in the shape `scheduler.autoscaler.ReplicaAutoscaler
        .observe` consumes: decode throughput since start, queue depth, and
        active batch occupancy.  The counter snapshot happens under
        ``_state_lock`` (the worker loop batches its updates under the
        same lock) and the SAME values are pushed to the Prometheus
        gauges, so the load report and /metrics can't disagree."""
        with self._state_lock:
            tokens_done = self._tokens_done
        dt = max(time.monotonic() - self._t_start, 1e-9)
        tps = tokens_done / dt
        depth = self._pending.qsize()
        active = self.active_count
        self._metrics.tps.set(tps)
        self._metrics.queue.set(depth)
        self._metrics.active.set(active)
        self._metrics.occupancy.set(active / self.max_batch)
        return {"tokens_per_s": tps, "queue_depth": depth,
                "active": active, "capacity": self.max_batch}

    def _step_multi(self, k: int, admit_s: float) -> None:
        import jax

        from .kv_cache_lm import FILTER_CAP

        jnp = self._jnp
        b = self.max_batch
        with tracing.phase("fedml.serve.build") as build:
            prompt_buf = np.zeros((b, k), np.int32)
            # a slot that holds no request is sent with no token to feed and
            # length 0: its picks then land on no expert, and the attention
            # reads nothing of its row
            prompt_n = np.zeros((b,), np.int32)
            pos0 = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            top_k = np.zeros((b,), np.int32)
            top_p = np.ones((b,), np.float32)
            for slot, req in enumerate(self._active):
                if req is None:
                    continue
                pos = pos0[slot] = int(self._pos[slot])
                upcoming = req.ids[pos:pos + k]
                if not upcoming:       # mid-generation: feed last sample
                    upcoming = [req.ids[-1]]
                prompt_buf[slot, :len(upcoming)] = upcoming
                prompt_n[slot] = len(upcoming)
                temps[slot] = req.temperature
                top_k[slot] = req.top_k
                top_p[slot] = req.top_p
            self._rng_key, sub = jax.random.split(self._rng_key)
            # exact-filter dispatch (VERDICT r4 item 7): on a big vocab any
            # filtered row routes the dispatch through the full-vocab
            # bisection sampler — it is EXACT for every top_k/top_p (no
            # 128-candidate truncation) and measured FASTER than the capped
            # path at GPT-2 geometry (331 vs 373 ms/dispatch, bs128 k16,
            # vocab 50257 on v5e: the bisection's ~60 compare+reduce passes
            # cost less than one 50k-wide lax.top_k per token).  Unfiltered
            # batches keep the plain path.  The flag is static per jit — at
            # most two compiled variants.
            exact = bool(self.lm.vocab > FILTER_CAP and np.any(
                (temps > 0) & ((top_k > 0) | (top_p < 1.0))))
            operands = (jnp.asarray(prompt_buf), jnp.asarray(prompt_n),
                        jnp.asarray(pos0), jnp.asarray(temps),
                        jnp.asarray(top_k), jnp.asarray(top_p))
            self._metrics.cache_blocks_live.inc(
                k * int(np.sum(-(-pos0 // CACHE_BLOCK))))
            self._metrics.cache_blocks.inc(
                k * b * -(-self.lm.max_len // CACHE_BLOCK))
        # an enqueue: the wait for the device is the fetch
        with tracing.phase(f"fedml.serve.dispatch.k{k}") as dispatch:
            self._cache, emitted = self.lm.decode_multi(
                self._cache, *operands, sub, k, exact_filters=exact)
        with tracing.phase("fedml.serve.fetch") as fetch:
            emitted = np.asarray(emitted)
        if emitted.shape[0] > b:        # a routed model's counts ride along
            self._metrics.note_moe(emitted[b:])
        dt_dispatch = dispatch.dur_s + fetch.dur_s
        self._metrics.step.observe(dt_dispatch)
        self._metrics.note_decode(dt_dispatch, self.active_count)
        with tracing.phase("fedml.serve.stream") as stream:
            self._stream(emitted, k)
        self._end_iteration(admit_s, build, dispatch, fetch, stream)

    def _stream(self, emitted: np.ndarray, k: int) -> None:
        """Hand a dispatch's tokens to their requests (the clients'
        ``on_token`` callbacks run here) and retire what finished.  A
        request is handed no more than ``remaining``, which ``submit()``
        held to the cache's length less the prompt: what a row emitted
        past that (the tail of its last dispatch, its positions beyond the
        cache included) is dropped here."""
        produced = 0
        for slot, req in enumerate(self._active):
            if req is None:
                continue
            # R = prompt-ish tokens that were still unfed at dispatch time;
            # emitted[slot, j] (output after feeding inner token j) is NEW
            # from j = R-1 on — and not at all when the chunk was entirely
            # prefill (R > k: emitted[k-1] predicts a KNOWN prompt token)
            r = len(req.ids) - int(self._pos[slot])
            self._pos[slot] += k
            start = r - 1 if r <= k else k
            # one host conversion per slot — the loop below touches only
            # Python ints, never the (already np.asarray'd) batch array
            row = emitted[slot].tolist()
            for j in range(start, k):
                if req.remaining <= 0:
                    break
                req.ids.append(row[j])
                self._metrics.note_token(req)
                req.emit(row[j])
                req.remaining -= 1
                produced += 1
            if req.cancelled.is_set():
                req.finish_reason = "cancelled"
            if (req.remaining <= 0 or req.cancelled.is_set()
                    or self._pos[slot] + 1 >= self.lm.max_len):
                if req.remaining > 0 and not req.cancelled.is_set():
                    req.finish_reason = "length"
                self._retire(req, "cancel" if req.cancelled.is_set()
                             else "finish")
                if not req.future.done():
                    req.future.set_result(
                        np.asarray(getattr(req, "prefix", []) + req.ids))
                self._active[slot] = None
        with self._state_lock:
            self._tokens_done += produced
