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
