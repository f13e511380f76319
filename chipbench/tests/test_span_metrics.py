"""The readers of the program's own spans, program names and counters, on
events written out by hand."""

import os

import pytest

from chipbench.harness import runner, xplane
from chipbench.harness.xplane import Event

BENCH = runner.load_json(os.path.join(runner.ROOT, "BENCHMARK.json"))

SFT = ("opt_init_ms_per_call", "pack_ms_per_call", "small_programs_per_call",
       "epoch_device_ms_per_step")
SERVE = ("loop_host_ms_per_dispatch", "turbo_dispatch_share_pct",
         "prefill_device_ms_per_admit")
SETUP = ("program_build_s", "setup_cache_misses")


class _Plane:
    tokens_per_dispatch = 8


def _run(host=(), modules=(), **kw):
    kw.setdefault("cell", {"traffic": {"steps_per_call": 4}})
    return runner.Run(trace=xplane.Trace({"d": []}, {"d": list(modules)},
                                         list(host)), plane=_Plane(), **kw)


def _read(name, run):
    return runner.reader_of(name)(run)


# -- nothing to read ----------------------------------------------------------

@pytest.mark.parametrize("name", SFT + SERVE)
def test_a_run_without_a_trace_or_without_the_spans_reads_nothing(name):
    assert _read(name, runner.Run(trace=None, plane=_Plane(),
                                  cell={"traffic": {}})) is None
    # the parent's trace: the benchmark's own spans and the runtime's events,
    # programs under their old names, none of the program's spans
    old = _run(host=[Event("chipbench.train_call", 0, 1000),
                     Event("PjitFunction(epoch)", 10, 50)],
               modules=[Event("jit_epoch(1)", 100, 800),
                        Event("jit_decode_multi(2)", 100, 800),
                        Event("jit_prefill(3)", 0, 50)])
    assert _read(name, old) is None


# -- the training call --------------------------------------------------------

def _sft_run():
    host = [
        # a call the trace holds whole ...
        Event("fedml.sft.train", 1_000, 10_000),
        Event("fedml.sft.pack", 1_100, 300),
        Event("fedml.sft.opt_init", 1_500, 2_000),
        Event("fedml.sft.epoch", 3_600, 100),
        Event("fedml.sft.loss_fetch", 3_800, 7_000),
        # ... a second, with another length ...
        Event("fedml.sft.train", 20_000, 10_000),
        Event("fedml.sft.pack", 20_100, 500),
        Event("fedml.sft.opt_init", 20_700, 1_000),
        # ... and the tail of one the trace cut: its inner spans are there,
        # the call's own is not
        Event("fedml.sft.opt_init", 100, 700),
        Event("fedml.sft.pack", 0, 90),
    ]
    modules = (
        [Event(f"jit_broadcast_in_dim({i})", 1_600 + 10 * i, 5)
         for i in range(6)]
        + [Event("jit_sft_epoch(77)", 3_700, 6_400)]
        + [Event(f"jit_broadcast_in_dim({i})", 20_800 + 10 * i, 5)
           for i in range(4)]
        + [Event("jit_sft_epoch(77)", 21_900, 8_000)]
        # inside no whole call
        + [Event("jit_broadcast_in_dim(9)", 200, 5),
           Event("jit_sft_epoch(77)", 40_000, 4_000)])
    return _run(host, modules)


def test_training_spans_are_read_over_whole_calls_only():
    run = _sft_run()
    assert _read("opt_init_ms_per_call", run) == pytest.approx(1_500 / 1e6)
    assert _read("pack_ms_per_call", run) == pytest.approx(400 / 1e6)
    assert _read("small_programs_per_call", run) == 5.0
    # 6400 and 8000 ns over 4 steps
    assert _read("epoch_device_ms_per_step", run) == pytest.approx(1_800 / 1e6)


def test_a_cut_call_alone_reads_nothing():
    cut = _run(host=[Event("fedml.sft.opt_init", 100, 700),
                     Event("fedml.sft.pack", 0, 90)],
               modules=[Event("jit_sft_epoch(77)", 900, 4_000)])
    assert all(_read(name, cut) is None for name in SFT)


def test_a_cell_without_steps_reads_no_epoch_time():
    run = _sft_run()
    run.cell = {"traffic": {}}
    assert _read("epoch_device_ms_per_step", run) is None


# -- the engine's loop --------------------------------------------------------

def _serve_run():
    host, mods, t = [], [], 0
    for k in (2, 8, 8, 2, 8):
        if k == 2:      # an admission goes before the short dispatch
            host += [Event("fedml.serve.admit", t, 90),
                     Event("fedml.serve.prefill.t128", t + 10, 40),
                     Event("fedml.serve.scatter", t + 55, 20)]
            mods += [Event("jit_prefill(5)", t + 30, 700),
                     Event("jit_scatter_cache_row(6)", t + 740, 300)]
            t += 100
        host += [Event("fedml.serve.build", t, 30),
                 Event(f"fedml.serve.dispatch.k{k}", t + 30, 10),
                 Event("fedml.serve.fetch", t + 40, 5_000),
                 Event("fedml.serve.stream", t + 5_040, 60)]
        mods.append(Event(f"jit_decode_multi_k{k}(7)", t + 1_100, 3_900))
        t += 5_100
    return _run(host, mods)


def test_the_loops_host_share_and_short_dispatches():
    run = _serve_run()
    # build 30 + dispatch 10 + stream 60 an iteration; the fetch is a wait
    assert _read("loop_host_ms_per_dispatch", run) == pytest.approx(100 / 1e6)
    assert _read("turbo_dispatch_share_pct", run) == pytest.approx(40.0)
    # two admissions: (700 + 300) ns of prefill and scatter each
    assert _read("prefill_device_ms_per_admit", run) == pytest.approx(1e-3)


def test_no_dispatch_or_no_admission_divides_nothing():
    run = _run(host=[Event("fedml.serve.build", 0, 30),
                     Event("fedml.serve.stream", 100, 60)],
               modules=[Event("jit_prefill(5)", 0, 700)])
    assert all(_read(name, run) is None for name in SERVE)
    # dispatches but no admission in the trace
    run = _run(host=[Event("fedml.serve.dispatch.k8", 0, 10)])
    assert _read("prefill_device_ms_per_admit", run) is None
    assert _read("turbo_dispatch_share_pct", run) == 0.0


# -- the compile counters -----------------------------------------------------

def test_build_counters_are_read_off_the_programs_registry(monkeypatch):
    from fedml_tpu.core.mlops import metrics

    run = runner.Run(trace=None)
    # a registry of the test's own, as on the parent's program: no counters
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    assert all(_read(name, run) is None for name in SETUP)
    secs = metrics.counter("fedml_program_build_seconds_total", "",
                           labels=("stage",))
    secs.labels(stage="trace").inc(1.5)
    secs.labels(stage="backend").inc(2.25)
    built = metrics.counter("fedml_programs_built_total", "",
                            labels=("source",))
    built.labels(source="cache").inc(7)
    assert _read("program_build_s", run) == 3.75
    assert _read("setup_cache_misses", run) == 0.0      # a warm run
    built.labels(source="compiled").inc(3)
    assert _read("setup_cache_misses", run) == 3.0


# -- which cell reports which -------------------------------------------------

@pytest.mark.parametrize("workload, mine, not_mine", [
    ("sft.lora_1k", SFT, SERVE), ("serve.chat_steady", SERVE, SFT)])
def test_each_cell_reports_setups_two_and_its_own_others(workload, mine,
                                                         not_mine):
    names = {m["name"] for m in runner.metrics_of(BENCH, workload,
                                                  "per_layer")}
    assert names >= set(SETUP) | set(mine)
    assert not names & set(not_mine)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert all(by_name[n]["moves"] == "setup_s" for n in SETUP)
