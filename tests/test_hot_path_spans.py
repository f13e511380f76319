"""The light span tier (`tracing.phase`), the spans the serving engine and
`LLMTrainer.train()` open with it, the compile counters and the
slow-iteration line.  On the CPU: what is checked is which host events a
profiler session sees and in what order, never how long anything took."""

import glob
import logging
import os

import jax
import numpy as np
import pytest

from chipbench.harness import scopes
from fedml_tpu.core.mlops import metrics, tracing


def _span_count(name):
    hist = metrics.REGISTRY.collect().get("fedml_span_seconds")
    child = hist.children().get((name,)) if hist is not None else None
    return 0 if child is None else child.count


def _host_events(trace_dir):
    """(name, start ns, end ns) of every host event a trace holds."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return sorted(
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events)


class _Profiler:
    """A profiler session without the Python tracer, as the benchmark
    opens it."""

    def __init__(self, trace_dir):
        self.dir = os.fspath(trace_dir)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


# -- the light span itself ----------------------------------------------------

def test_phase_observes_the_histogram_and_writes_no_record(monkeypatch):
    from fedml_tpu.core import mlops

    emitted = []
    monkeypatch.setattr(mlops, "_emit", lambda *a, **kw: emitted.append(a))
    tracing.reset_sink()
    before = _span_count("test.light")
    with tracing.phase("test.light") as ph:
        pass
    assert _span_count("test.light") == before + 1
    assert ph.dur_s >= 0.0
    assert emitted == []                      # no spans.jsonl record
    assert tracing._sink["written"] == 0      # no sink budget
    with tracing.span("test.heavy"):
        pass
    assert [a[0] for a in emitted] == ["spans"]
    assert tracing._sink["written"] == 1


@pytest.fixture
def registry(monkeypatch):
    """A process registry of the test's own: resetting the real one would
    leave other tests' module-level handles unexported."""
    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


def test_phase_survives_a_registry_reset(registry):
    with tracing.phase("test.reset"):
        pass
    with tracing.phase("test.reset"):
        pass
    assert _span_count("test.reset") == 2
    registry.reset()
    with tracing.phase("test.reset"):
        pass
    assert _span_count("test.reset") == 1     # exported again, counted anew


def test_phase_nests_inside_and_beside_span_without_touching_the_stack():
    assert tracing.current() is None
    with tracing.phase("test.outer"):
        assert tracing.current() is None
        with tracing.span("test.round") as sp:
            with tracing.phase("test.inner"):
                # a span opened under a phase still parents under the span
                assert tracing.current() is sp.ctx
                with tracing.span("test.child") as child:
                    assert child.parent_span_id == sp.ctx.span_id
            assert tracing.current() is sp.ctx
        assert tracing.current() is None
    with tracing.span("test.beside") as sp, tracing.phase("test.beside"):
        assert tracing.current() is sp.ctx
    assert tracing.current() is None


def test_phase_still_times_where_annotations_are_off(monkeypatch):
    monkeypatch.setattr(tracing, "_jax_annotations", "0")
    with tracing.phase("test.plain") as ph:
        assert ph._annotation is None
    assert ph.dur_s >= 0.0


# -- the spans of the two hot paths -------------------------------------------

def test_engine_iteration_leaves_its_spans_in_a_profiler_trace(tmp_path):
    from fedml_tpu.serving.kv_cache_lm import KVCacheLM
    from fedml_tpu.serving.llm_engine import KVCacheLLMEngine

    lm = KVCacheLM.create(jax.random.PRNGKey(3), vocab=40, dim=32, layers=2,
                          heads=4, max_len=64)
    rng = np.random.RandomState(5)
    # the 20-token prompt is prefilled at admission (bucket 32) and followed
    # by the short dispatch; the others stream in through full dispatches
    prompts = [list(rng.randint(0, 40, size=n)) for n in (20, 3, 2)]
    eng = KVCacheLLMEngine(lm, max_batch=4, tokens_per_dispatch=4)
    try:
        with _Profiler(tmp_path):
            futs = [eng.submit(p, max_new=9) for p in prompts]
            for f in futs:
                f.result(timeout=120)
    finally:
        eng.stop()
    events = [e for e in _host_events(tmp_path)
              if e[0].startswith("fedml.serve.")]
    names = {e[0] for e in events}
    assert names == {
        "fedml.serve.admit", "fedml.serve.prefill.t32",
        "fedml.serve.scatter", "fedml.serve.build",
        "fedml.serve.dispatch.k2", "fedml.serve.dispatch.k4",
        "fedml.serve.fetch", "fedml.serve.stream"}

    def of(name):
        return sorted(e[1:] for e in events if e[0].startswith(name))

    dispatch, fetch, stream = (of("fedml.serve." + n)
                               for n in ("dispatch.", "fetch", "stream"))
    assert len(dispatch) == len(fetch) == len(stream) == len(
        of("fedml.serve.build"))
    for d, f, s in zip(dispatch, fetch, stream):
        assert d[1] <= f[0] and f[1] <= s[0]
    # prefill and scatter lie inside their admission
    admits = of("fedml.serve.admit")
    for inner in of("fedml.serve.prefill.") + of("fedml.serve.scatter"):
        assert any(a[0] <= inner[0] and inner[1] <= a[1] for a in admits)


def test_train_call_holds_its_phases_in_order(tmp_path):
    import fedml_tpu
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    bundle = fedml_tpu.model.create(fedml_tpu.Config(
        model="transformer", dataset="shakespeare",
        compute_dtype="float32"), 90)
    trainer = LLMTrainer(bundle, LLMTrainConfig(seq_len=16, batch_size=2,
                                                lora_rank=2))
    tokens = np.random.RandomState(0).randint(0, 90, size=200)
    assert trainer._train_epoch.__name__ == "sft_epoch"
    with _Profiler(tmp_path):
        trainer.train(tokens)
    events = [e for e in _host_events(tmp_path)
              if e[0].startswith("fedml.sft.")]
    (_, t0, t1), = [e for e in events if e[0] == "fedml.sft.train"]
    inner = sorted((e for e in events if e[0] != "fedml.sft.train"),
                   key=lambda e: e[1])
    assert [e[0] for e in inner] == [
        "fedml.sft.pack", "fedml.sft.opt_init", "fedml.sft.epoch",
        "fedml.sft.loss_fetch"]
    assert all(t0 <= e[1] and e[2] <= t1 for e in inner)
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def test_device_programs_carry_names_a_trace_can_tell_apart():
    import jax.numpy as jnp
    from fedml_tpu.serving import kv_cache_lm, llm_engine

    lm = kv_cache_lm.KVCacheLM.create(jax.random.PRNGKey(0), vocab=40,
                                      dim=32, layers=1, heads=2, max_len=32)
    b = 2
    cache = lm.init_cache(b)
    for k in (2, 8):
        vec = lambda dt, *s: jax.ShapeDtypeStruct((b, *s), dt)
        text = kv_cache_lm.decode_multi.lower(
            lm.params, cache, vec(jnp.int32, k), vec(jnp.int32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32), jax.random.PRNGKey(1), heads=2, k=k,
            exact_filters=False).as_text()
        assert f"@jit_decode_multi_k{k} " in text
    row, _ = lm.prefill(jnp.zeros((1, 32), jnp.int32),
                        jnp.asarray([5], jnp.int32))
    llm_engine._scatter_cache_row(cache, row, jnp.asarray(0, jnp.int32))
    assert llm_engine._scatter_cache_row_jit.__name__ == "scatter_cache_row"


# -- compile counters ---------------------------------------------------------

def _built():
    m = metrics.REGISTRY.collect().get("fedml_programs_built_total")
    return 0 if m is None else sum(c.value for c in m.children().values())


def _build_seconds():
    m = metrics.REGISTRY.collect().get("fedml_program_build_seconds_total")
    return {} if m is None else {k[0]: c.value
                                 for k, c in m.children().items()}


def test_compile_counters_count_a_program_once():
    from fedml_tpu.utils import compile_cache

    compile_cache._count_program_builds()
    compile_cache._count_program_builds()     # registers once a process
    x = np.ones((3, 5), np.float32)
    fn = jax.jit(lambda a: a * 3.0 + 1.0)
    before, secs0 = _built(), _build_seconds()
    fn(x)
    assert _built() == before + 1
    secs1 = _build_seconds()
    assert set(secs1) >= {"trace", "lower", "backend"}
    assert all(secs1[s] > secs0.get(s, 0.0)
               for s in ("trace", "lower", "backend"))
    fn(x)
    assert _built() == before + 1 and _build_seconds() == secs1


def test_compile_counters_file_a_program_under_its_source(registry):
    """Driven by the events JAX records, in JAX's order: the cache's event
    falls inside the backend-compile event, whose end closes the program."""
    from fedml_tpu.utils import compile_cache as cc

    def by_source():
        m = metrics.REGISTRY.collect().get("fedml_programs_built_total")
        return {} if m is None else {k[0]: c.value
                                     for k, c in m.children().items()}

    backend = "/jax/core/compile/backend_compile_duration"
    cc._on_event("/jax/compilation_cache/cache_hits")
    cc._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    cc._on_duration(backend, 0.75)
    cc._on_event("/jax/compilation_cache/cache_misses")
    cc._on_duration(backend, 2.0)
    cc._on_duration(backend, 0.5)
    cc._on_duration("/jax/some/other_duration", 9.0)
    assert by_source() == {"cache": 1, "compiled": 1, "small": 1}
    assert _build_seconds() == {"cache_fetch": 0.25, "backend": 3.0}


# -- the slow-iteration line --------------------------------------------------

@pytest.mark.parametrize("total, prev, logged", [
    (3.21, 0.2, True),
    (3.21, None, False),      # nothing to compare with
    (3.21, 1.0, False),       # long, but so was the one before
    (0.9, 0.01, False),       # many times the one before, but short
])
def test_note_iteration_speaks_only_for_a_long_and_unusual_one(
        caplog, total, prev, logged):
    with caplog.at_level(logging.WARNING):
        tracing.note_iteration("kv-engine: iteration", total, prev,
                               [("admit", 0.0), ("fetch", 3.19)])
    lines = [r.getMessage() for r in caplog.records]
    assert lines == (["kv-engine: iteration took 3.21 s: admit 0.00 "
                      "fetch 3.19"] if logged else [])


def test_engine_logs_the_iteration_whose_fetch_stood_still(
        caplog, monkeypatch):
    from fedml_tpu.serving.kv_cache_lm import KVCacheLM
    from fedml_tpu.serving.llm_engine import KVCacheLLMEngine

    lm = KVCacheLM.create(jax.random.PRNGKey(3), vocab=40, dim=32, layers=2,
                          heads=4, max_len=64)
    eng = KVCacheLLMEngine(lm, max_batch=2, tokens_per_dispatch=4)
    exit_ = tracing.Phase.__exit__
    stalled = []

    def slow_third_fetch(self, *exc):
        out = exit_(self, *exc)
        if self.name == "fedml.serve.fetch":
            stalled.append(self)
            if len(stalled) == 3:
                self.dur_s += 1000.0
        return out

    try:
        # every program compiled before anything is listened for
        eng.generate([1, 2, 3], max_new=12, timeout=120)
        with caplog.at_level(logging.WARNING):
            eng.generate([1, 2, 3], max_new=12, timeout=120)
            assert not [r for r in caplog.records
                        if "iteration took" in r.getMessage()]
            monkeypatch.setattr(tracing.Phase, "__exit__", slow_third_fetch)
            eng.generate([1, 2, 3], max_new=12, timeout=120)
    finally:
        eng.stop()
    lines = [r.getMessage() for r in caplog.records
             if "iteration took" in r.getMessage()]
    assert len(lines) == 1 and lines[0].startswith(
        "kv-engine: iteration took 10")
    assert " fetch 10" in lines[0] and " build 0.00" in lines[0]


def test_trainer_logs_the_call_that_stood_still(caplog, monkeypatch):
    import fedml_tpu
    from fedml_tpu.train.llm.trainer import LLMTrainConfig, LLMTrainer

    bundle = fedml_tpu.model.create(fedml_tpu.Config(
        model="transformer", dataset="shakespeare",
        compute_dtype="float32"), 90)
    trainer = LLMTrainer(bundle, LLMTrainConfig(seq_len=16, batch_size=2,
                                                lora_rank=2))
    tokens = np.random.RandomState(0).randint(0, 90, size=200)
    trainer.train(tokens)                     # compiles
    exit_ = tracing.Phase.__exit__

    def slow_fetch(self, *exc):
        out = exit_(self, *exc)
        if self.name in ("fedml.sft.loss_fetch", "fedml.sft.train"):
            self.dur_s += 1000.0
        return out

    with caplog.at_level(logging.WARNING):
        trainer.train(tokens)
        assert not [r for r in caplog.records
                    if "train() took" in r.getMessage()]
        monkeypatch.setattr(tracing.Phase, "__exit__", slow_fetch)
        trainer.train(tokens)
    line, = [r.getMessage() for r in caplog.records
             if "train() took" in r.getMessage()]
    assert line.startswith("llm-trainer: train() took 10")
    assert "pack 0.0" in line and "loss_fetch 10" in line


# -- the device-side tier: `tracing.scope` ------------------------------------

def test_scope_is_a_name_and_nothing_at_run_time(registry):
    import jax.numpy as jnp

    @tracing.scope("test.decorated")
    def twice(x):
        return x * 2.0

    def fn(x):
        with tracing.scope("test.block"):
            return jnp.tanh(twice(x))

    text = jax.jit(fn).lower(np.ones(3, np.float32)).compile().as_text()
    assert "fedml.test.block/fedml.test.decorated/mul" in text
    fn(np.ones(3, np.float32))
    # no clock and no histogram: `phase` observes one sample a use
    assert registry.collect().get("fedml_span_seconds") is None
    with tracing.phase("test.scope_beside"):
        pass
    assert _span_count("test.scope_beside") == 1


def _held_scopes(compiled):
    """What `chipbench.harness.scopes` reads out of a compiled program's own
    HLO: the scopes of the instructions it holds, by direction."""
    module = compiled.runtime_executable().hlo_modules()[0]
    whole = scopes.instructions(module.as_serialized_hlo_module_proto())[
        scopes.ENTRY].holds
    by = {}
    for (scope, direction), n in whole.items():
        by.setdefault(scope, set()).add(direction)
    return by


_BLOCK = {"fedml.embed", "fedml.norm", "fedml.attn.qkv", "fedml.attn",
          "fedml.attn_bwd", "fedml.attn.out", "fedml.head", "fedml.loss",
          "fedml.lora", "fedml.opt"}
_ROUTED = _BLOCK | {"fedml.router", "fedml.experts.plan",
                    "fedml.experts.layout", "fedml.experts.products",
                    "fedml.experts.combine"}


def _gpt2_args():
    return dict(model="functional_lm", dataset="shakespeare", lm_dim=32,
                lm_layers=2, lm_heads=4, lm_max_len=32), 90, 2


def _routed_args():
    from chipbench.planes.sft_routed import model_args

    return model_args({
        "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 4,
        "moe_ffn_hidden_size": 24, "moe_num_primary_experts": 4,
        "moe_num_active_primary_experts": 3, "experts_first_held": 8,
        "published": {"moe_num_primary_experts": 16}, "rms_norm_eps": 1e-6,
        "rope_theta": 1500000, "sliding_window_size": 12,
        "rope_layout": [0, 1, 1, 1],
        "sliding_window_layout": [0, 1, 1, 1]}), 211, 1


def _mla_args():
    from chipbench.planes.sft_mla import model_args
    from mla_tiny import CFG

    # a dense layer, a routed one, the second head's block
    return model_args(dict(CFG, num_hidden_layers=2)), CFG["vocab_size"], 1


@pytest.mark.parametrize("family, wanted", [
    (_gpt2_args, _BLOCK | {"fedml.mlp"}),
    (_routed_args, _ROUTED),
    (_mla_args, _ROUTED | {"fedml.mlp", "fedml.mlp.shared",
                           "fedml.mtp.join"})])
def test_every_scope_reaches_the_epoch_program(monkeypatch, family, wanted):
    """The epoch programs of the three families, compiled here, name every
    scope of docs/OBSERVABILITY.md's table in their optimized HLO.  The
    attention goes through the kernel's interpreter, so that its backward is
    the `custom_vjp`'s, as on the chip."""
    import functools

    import fedml_tpu
    import jax.numpy as jnp
    from fedml_tpu.ops import pallas_attention
    from fedml_tpu.train.llm.trainer import (LLMTrainConfig, LLMTrainer,
                                             pack_sequences)

    monkeypatch.setattr(pallas_attention, "flash_attention", functools.partial(
        pallas_attention.flash_attention, interpret=True))
    args, vocab, batch = family()
    trainer = LLMTrainer(fedml_tpu.model.create(fedml_tpu.Config(**args),
                                                vocab),
                         LLMTrainConfig(seq_len=16, batch_size=batch,
                                        lora_rank=2))
    batches = jax.tree_util.tree_map(jnp.asarray, pack_sequences(
        np.arange(16 * batch * 2 + 1) % vocab, 16, batch))
    by = _held_scopes(trainer._train_epoch.lower(
        trainer.lora, trainer.tx.init(trainer.lora),
        trainer.variables["params"], {}, batches,
        jax.random.PRNGKey(1)).compile())
    assert set(by) - set(scopes.LOST) == wanted
    assert by["fedml.attn_bwd"] == {"bwd"} and by["fedml.opt"] == {"fwd"}
    assert {"fwd", "bwd"} <= by["fedml.norm"] and "bwd" in by["fedml.lora"]
    # the loss's row blocks are made again in the backward pass
    if "fedml.router" in wanted:
        assert "remat" in by["fedml.loss"] and "remat" in by["fedml.head"]


def test_every_scope_reaches_the_serving_programs():
    import jax.numpy as jnp
    from fedml_tpu.serving import kv_cache_lm

    lm = kv_cache_lm.KVCacheLM.create(jax.random.PRNGKey(0), vocab=40,
                                      dim=32, layers=1, heads=2, max_len=32)
    b, k = 2, 2
    vec = lambda dt, *s: jax.ShapeDtypeStruct((b, *s), dt)
    decode = _held_scopes(kv_cache_lm.decode_multi.lower(
        lm.params, lm.init_cache(b), vec(jnp.int32, k), vec(jnp.int32),
        vec(jnp.int32), vec(jnp.float32), vec(jnp.int32), vec(jnp.float32),
        jax.random.PRNGKey(1), heads=2, k=k, exact_filters=False).compile())
    dense = {"fedml.embed", "fedml.norm", "fedml.attn.qkv", "fedml.attn",
             "fedml.attn.out", "fedml.mlp", "fedml.head"}
    assert set(decode) - set(scopes.LOST) == dense | {"fedml.sample",
                                                      "fedml.cache_write"}
    prefill = _held_scopes(kv_cache_lm.prefill.lower(
        lm.params, vec(jnp.int32, 32), vec(jnp.int32), heads=2,
        max_len=32).compile())
    assert set(prefill) - set(scopes.LOST) == dense
    assert all(d == {"fwd"} for d in {**decode, **prefill}.values())


def test_direction_comes_from_the_path_jax_writes():
    """A `custom_vjp` under `scan` and `checkpoint`: its forward is fwd in
    the forward pass and remat in the backward's, its backward bwd under a
    scope of its own, and what autodiff transposes bwd under the scope the
    forward had."""
    import jax.numpy as jnp

    @jax.custom_vjp
    def wave(x):
        return jnp.sin(x)

    def wave_bwd(x, g):
        with tracing.scope("test.wave_bwd"):
            return (g * jnp.cos(x),)

    wave.defvjp(lambda x: (jnp.sin(x), x), wave_bwd)

    @jax.checkpoint
    def blk(c):
        with tracing.scope("test.wave"):
            c = wave(c)
        with tracing.scope("test.square"):
            return c * c

    def loss(x):
        return jnp.sum(jax.lax.scan(lambda c, _: (blk(c), None), x, None,
                                    length=3)[0])

    by = _held_scopes(jax.jit(jax.grad(loss)).lower(
        np.ones((4, 4), np.float32)).compile())
    assert by["fedml.test.wave"] == {"fwd", "remat"}
    assert by["fedml.test.wave_bwd"] == {"bwd"}
    # nothing reads the block's last product again: it is not made again
    assert by["fedml.test.square"] == {"fwd", "bwd"}


def test_an_engine_that_stands_empty_says_so_in_a_profiler_trace(tmp_path):
    import time

    from fedml_tpu.serving.kv_cache_lm import KVCacheLM
    from fedml_tpu.serving.llm_engine import KVCacheLLMEngine

    lm = KVCacheLM.create(jax.random.PRNGKey(3), vocab=40, dim=32, layers=1,
                          heads=2, max_len=32)
    eng = KVCacheLLMEngine(lm, max_batch=2, tokens_per_dispatch=2)
    try:
        with _Profiler(tmp_path):
            # the wait for a request turns every 0.5 s: one wait at least
            # both starts and ends inside the session
            time.sleep(1.2)
    finally:
        eng.stop()
    waits = [e for e in _host_events(tmp_path)
             if e[0] == "fedml.serve.empty"]
    assert waits and not [e for e in _host_events(tmp_path)
                          if e[0].startswith("fedml.serve.")
                          and e[0] != "fedml.serve.empty"]
    assert _span_count("fedml.serve.empty") >= len(waits)
