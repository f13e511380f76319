"""Observability plane: distributed tracing (span propagation across the
cross-silo hop), the typed metrics registry + Prometheus exposition, the
control-plane /metrics endpoint, mlops lifecycle isolation, perf-stats
monotonic timestamps, and log-daemon crash-resume."""

import json
import re
import threading
import time
import urllib.request

import pytest

from fedml_tpu.core.mlops import metrics as metrics_mod
from fedml_tpu.core.mlops import tracing


# -- tracing unit behavior ---------------------------------------------------

def test_span_nesting_and_ids():
    with tracing.span("outer", round=1) as outer:
        assert tracing.current() is outer.ctx
        with tracing.span("inner") as inner:
            assert inner.ctx.trace_id == outer.ctx.trace_id
            assert inner.parent_span_id == outer.ctx.span_id
    assert tracing.current() is None
    # fresh root gets a fresh trace
    with tracing.span("other") as other:
        assert other.ctx.trace_id != outer.ctx.trace_id
        assert other.parent_span_id is None


def test_trace_ctx_wire_roundtrip():
    with tracing.span("root") as sp:
        wire = tracing.inject()
        assert wire == {"trace_id": sp.ctx.trace_id,
                        "span_id": sp.ctx.span_id}
    ctx = tracing.extract(wire)
    assert ctx.trace_id == sp.ctx.trace_id
    # remote attachment parents new spans under the extracted context
    with tracing.use_ctx(ctx):
        with tracing.span("child") as child:
            assert child.ctx.trace_id == sp.ctx.trace_id
            assert child.parent_span_id == sp.ctx.span_id
    # tolerant of peers that predate tracing
    assert tracing.extract(None) is None
    assert tracing.extract("garbage") is None
    assert tracing.extract({"trace_id": ""}) is None
    assert tracing.inject(None) is not None or tracing.current() is None


def test_manual_span_end_idempotent():
    sp = tracing.start_span("held", phase="x")
    dur = sp.end()
    assert dur >= 0.0
    assert sp.end() == 0.0  # double end keeps the first record


# -- metrics registry --------------------------------------------------------

def test_histogram_bucketing_and_timer():
    r = metrics_mod.MetricsRegistry()
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    child = h.labels()
    cum = dict(child.cumulative())
    assert cum[0.1] == 1
    assert cum[1.0] == 3          # cumulative, not per-bucket
    assert cum[10.0] == 4
    assert cum[float("inf")] == 5
    assert child.count == 5
    assert child.sum == pytest.approx(56.05)
    with h.time():
        time.sleep(0.01)
    assert h.labels().count == 6

    c = r.counter("reqs_total", "requests", labels=("route",))
    c.labels(route="/a").inc()
    c.labels(route="/a").inc(2.5)
    with pytest.raises(ValueError):
        c.labels(route="/a").inc(-1)
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    g = r.gauge("depth", "queue depth")
    g.set(7)
    g.dec(3)
    assert g.labels().value == 4
    # type collision on an existing name is an error, same-type is get-or-create
    assert r.counter("reqs_total", labels=("route",)) is c
    with pytest.raises(ValueError):
        r.gauge("reqs_total")


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? (\+Inf|-?[0-9.eE+-]+)$")


def test_prometheus_exposition_format():
    r = metrics_mod.MetricsRegistry()
    r.counter("c_total", "a counter").inc(3)
    r.gauge("g_now", "a gauge", labels=("node",)).labels(
        node='weird"\\name\n').set(1.5)
    r.histogram("h_seconds", "a histogram", buckets=(0.5,)).observe(0.2)
    text = r.render_prometheus()
    assert text.endswith("\n")
    lines = text.splitlines()
    for name, kind in (("c_total", "counter"), ("g_now", "gauge"),
                       ("h_seconds", "histogram")):
        assert f"# TYPE {name} {kind}" in lines
    for line in lines:
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"
    # histogram completeness: buckets are cumulative and end at +Inf
    assert 'h_seconds_bucket{le="0.5"} 1' in lines
    assert 'h_seconds_bucket{le="+Inf"} 1' in lines
    assert "h_seconds_sum 0.2" in lines
    assert "h_seconds_count 1" in lines
    # label values escaped, not mangled
    assert r'node="weird\"\\name\n"' in text


# -- the acceptance-criteria run: two clients, one stitched trace ------------

_RUN_SEQ = iter(range(10_000))


@pytest.fixture
def cross_silo_run(args_factory, tmp_path):
    """Run a 2-client, 2-round cross-silo federation with tracking on;
    returns (spans, run_id).  The run_id is unique per invocation so
    run-labelled series in the process-global registry stay exact."""
    import fedml_tpu
    from fedml_tpu.cross_silo.runner import init_client, init_server

    run_id = f"obs-accept-{next(_RUN_SEQ)}"
    args = fedml_tpu.init(args_factory(
        training_type="cross_silo", client_num_in_total=2,
        client_num_per_round=2, comm_round=2, data_scale=0.2,
        run_id=run_id, enable_tracking=True,
        log_file_dir=str(tmp_path)))
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    server = init_server(args, dataset, bundle)
    clients = [init_client(args, dataset, bundle, rank) for rank in (1, 2)]
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    server.run()
    for t in threads:
        t.join(timeout=30)
    spans = tracing.load_spans(str(tmp_path))
    return spans, run_id


def test_cross_silo_trace_stitching(cross_silo_run):
    spans, _ = cross_silo_run
    assert spans, "no spans emitted"
    # ONE trace id across server, clients and aggregator
    assert len({s["trace_id"] for s in spans}) == 1
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    rounds = {s["attrs"]["round"]: s for s in by_name["train_round"]}
    assert set(rounds) == {0, 1}
    root = by_name["fed_run"][0]
    round_ids = {s["span_id"] for s in by_name["train_round"]}
    for s in by_name["train_round"]:
        assert s["parent_span_id"] == root["span_id"]
    # every client training nests under ITS round's parent span
    assert len(by_name["client.train"]) == 4  # 2 clients x 2 rounds
    for s in by_name["client.train"]:
        assert s["parent_span_id"] == rounds[s["attrs"]["round"]]["span_id"]
    # aggregation and eval nest under the round parents too
    for s in by_name["server.aggregate"] + by_name["server.eval"]:
        assert s["parent_span_id"] in round_ids
    # trainer spans nest under the client spans (grandchildren of the round)
    client_ids = {s["span_id"] for s in by_name["client.train"]}
    for s in by_name["trainer.local_update"]:
        assert s["parent_span_id"] in client_ids

    summary = tracing.summarize(spans)
    assert "train_round" in summary and "client.train" in summary
    assert summary.count("trainer.local_update") == 4


def test_control_plane_metrics_endpoint(cross_silo_run):
    """GET /metrics returns valid Prometheus text with a Counter, Gauge and
    Histogram populated by the federated run."""
    from fedml_tpu.scheduler.control_plane import ControlPlaneServer

    _, run_id = cross_silo_run
    srv = ControlPlaneServer(master=None).start()
    try:
        with urllib.request.urlopen(srv.url + "/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
    finally:
        srv.stop()
    lines = text.splitlines()
    for line in lines:
        if line and not line.startswith("#"):
            assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"
    assert "# TYPE fedml_rounds_completed_total counter" in lines
    assert "# TYPE fedml_current_round gauge" in lines
    assert "# TYPE fedml_round_seconds histogram" in lines
    assert f'fedml_rounds_completed_total{{run_id="{run_id}"}} 2' in lines
    assert f'fedml_round_seconds_count{{run_id="{run_id}"}} 2' in lines
    # trainer histogram populated by the run's local updates (the model
    # label is shared across tests in this process, so >=, not ==)
    counts = [int(l.rsplit(" ", 1)[1]) for l in lines if l.startswith(
        'fedml_trainer_local_update_seconds_count{model="lr"}')]
    assert counts and counts[0] >= 4


# -- mlops lifecycle isolation ----------------------------------------------

def test_mlops_reset_isolation(tmp_path, args_factory):
    from fedml_tpu.core import mlops

    dir_a, dir_b = tmp_path / "a", tmp_path / "b"
    seen_a = []
    mlops.init(args_factory(enable_tracking=True, run_id="runA",
                            log_file_dir=str(dir_a)))
    mlops.add_sink(lambda kind, rec: seen_a.append(rec))
    mlops.log({"x": 1})
    handle_a = mlops._state["files"]["metrics"]
    assert not handle_a.closed

    # back-to-back init: files from run A are closed, sinks cleared
    mlops.init(args_factory(enable_tracking=True, run_id="runB",
                            log_file_dir=str(dir_b)))
    assert handle_a.closed, "init() must close the previous run's files"
    mlops.log({"y": 2})
    assert len(seen_a) == 1, "run A's sink must not see run B's records"
    recs_a = [json.loads(l) for l in open(dir_a / "metrics.jsonl")]
    recs_b = [json.loads(l) for l in open(dir_b / "metrics.jsonl")]
    assert [r["run_id"] for r in recs_a] == ["runA"]
    assert [r["run_id"] for r in recs_b] == ["runB"]

    # shutdown() disables emission and releases files; double call is safe
    mlops.shutdown()
    mlops.shutdown()
    mlops.log({"z": 3})
    assert len([json.loads(l) for l in open(dir_b / "metrics.jsonl")]) == 1
    assert mlops._state["files"] == {} and mlops._state["sinks"] == []


# -- perf stats --------------------------------------------------------------

def test_perf_stats_ts_mono_and_priming(monkeypatch):
    from fedml_tpu.core.mlops import perf_stats

    s1 = perf_stats.system_snapshot()
    s2 = perf_stats.system_snapshot()
    assert "ts_mono" in s1 and s2["ts_mono"] >= s1["ts_mono"]

    import psutil

    calls = []
    real = psutil.cpu_percent
    monkeypatch.setattr(psutil, "cpu_percent",
                        lambda interval=None: calls.append(1) or
                        real(interval=interval))
    d = perf_stats.PerfStatsDaemon(interval_s=0.05).start()
    time.sleep(0.4)
    d.stop()
    assert d.samples, "no samples collected"
    # the sampler primed the counter BEFORE the first snapshot: at least
    # one more cpu_percent call than samples taken
    assert len(calls) >= len(d.samples) + 1
    assert all("ts_mono" in s for s in d.samples)
    mono = [s["ts_mono"] for s in d.samples]
    assert mono == sorted(mono)


# -- log daemon crash-resume -------------------------------------------------

def test_log_daemon_killed_mid_file_resumes_exactly(tmp_path):
    """A daemon that dies between chunk uploads must resume at the first
    unshipped chunk: the consolidated upload ends up with every line
    exactly once — none duplicated, none dropped."""
    from fedml_tpu.core.mlops.log_daemon import MLOpsRuntimeLogDaemon

    src = tmp_path / "run.log"
    n = 23
    src.write_text("".join(f"line {i}\n" for i in range(n)))
    updir = tmp_path / "uploaded"
    updir.mkdir()

    def uploader_for(crash_after):
        state = {"chunks": 0}

        def upload(run_id, lines):
            if state["chunks"] == crash_after:
                raise RuntimeError("killed mid-file")
            state["chunks"] += 1
            with open(updir / f"{run_id}.log", "a") as f:
                f.writelines(lines)

        return upload

    d = MLOpsRuntimeLogDaemon("rx", str(src),
                              uploader=uploader_for(crash_after=2),
                              chunk_lines=4)
    with pytest.raises(RuntimeError):
        d.ship_once()  # dies after shipping 2 chunks (8 lines)
    shipped = (updir / "rx.log").read_text().splitlines()
    assert shipped == [f"line {i}" for i in range(8)]

    # a NEW daemon (fresh process) resumes from the persisted cursor
    d2 = MLOpsRuntimeLogDaemon("rx", str(src),
                               uploader=uploader_for(crash_after=99),
                               chunk_lines=4)
    assert d2.ship_once() == n - 8
    shipped = (updir / "rx.log").read_text().splitlines()
    assert shipped == [f"line {i}" for i in range(n)]


# -- llm engine metrics ------------------------------------------------------

class _StubBundle:
    """Minimal bundle: uniform logits — enough to drive the decode loop."""

    input_shape = (16,)

    def apply(self, variables, x, train=False):
        import jax.numpy as jnp

        b, t = x.shape
        return jnp.zeros((b, t, 11)), None


def test_llm_engine_populates_metrics():
    from fedml_tpu.serving.llm_engine import BatchedLLMEngine

    # the engine registers its metrics when it is built (get-or-create),
    # so read the registry after that — this test must not depend on an
    # earlier one having built an engine
    eng = BatchedLLMEngine(_StubBundle(), {}, max_batch=2, window=16)
    try:
        reg = metrics_mod.REGISTRY.collect()
        ttft = reg["fedml_llm_ttft_seconds"].labels(engine="batched")
        tokens = reg["fedml_llm_tokens_total"].labels(engine="batched")
        ttft_before, tokens_before = ttft.count, tokens.value
        out = eng.generate([1, 2, 3], max_new=5, timeout=60.0)
        assert len(out) == 8
    finally:
        eng.stop()
    assert ttft.count == ttft_before + 1
    assert tokens.value == tokens_before + 5
    steps = reg["fedml_llm_decode_step_seconds"].labels(engine="batched")
    assert steps.count >= 5


# -- trace summarize CLI -----------------------------------------------------

def test_trace_summarize_cli(tmp_path, cross_silo_run):
    from click.testing import CliRunner

    from fedml_tpu.cli.cli import cli

    res = CliRunner().invoke(
        cli, ["trace", "summarize", "--log-dir", str(tmp_path)])
    assert res.exit_code == 0, res.output
    assert "train_round" in res.output and "fed_run" in res.output
    res = CliRunner().invoke(cli, ["metrics"])
    assert res.exit_code == 0, res.output
    assert "# TYPE fedml_rounds_completed_total counter" in res.output


# -- jax profiler hook -------------------------------------------------------

def test_trainer_jax_profile_capture(tmp_path):
    """profile_trace_dir: the first N local updates run inside
    jax.profiler.trace and land a capture on disk."""
    import os

    from fedml_tpu.ml.trainer.default_trainer import _maybe_jax_profile

    class _Args:
        profile_trace_dir = str(tmp_path / "prof")
        profile_trace_steps = 1

    import jax.numpy as jnp

    state = {}
    with _maybe_jax_profile(_Args(), state):
        jnp.ones(8).sum().block_until_ready()
    assert state["captured"] == 1
    captured = [f for r, _, fs in os.walk(_Args.profile_trace_dir)
                for f in fs]
    assert any(f.endswith(".xplane.pb") for f in captured), captured
    # budget exhausted: the next update is NOT captured
    with _maybe_jax_profile(_Args(), state):
        pass
    assert state["captured"] == 1


# -- metrics plane under concurrency (PR 9 satellite) ------------------------

def test_histogram_concurrent_observe_consistency():
    """A scrape racing multi-threaded observe() must stay internally
    consistent: bucket counts cumulative and monotone, and the implicit
    +Inf bucket exactly equal to the snapshot's count."""
    r = metrics_mod.MetricsRegistry()
    h = r.histogram("race_seconds", "x", buckets=(0.1, 1.0, 10.0))
    stop = threading.Event()
    errors = []

    def writer(seed):
        vals = (0.05, 0.5, 5.0, 50.0)
        i = seed
        while not stop.is_set():
            h.observe(vals[i % 4])
            i += 1

    def scraper():
        while not stop.is_set():
            try:
                pairs, _s, count = h.labels().snapshot()
                cums = [c for _b, c in pairs]
                assert cums == sorted(cums), f"non-monotone: {cums}"
                assert pairs[-1][0] == float("inf")
                assert pairs[-1][1] == count, \
                    f"+Inf {pairs[-1][1]} != count {count}"
                # exposition renders from one locked snapshot too
                text = r.render_prometheus()
                m = re.search(
                    r'race_seconds_bucket\{le="\+Inf"\} (\d+)', text)
                c = re.search(r"race_seconds_count (\d+)", text)
                assert m and c and m.group(1) == c.group(1)
            except AssertionError as e:  # noqa: PERF203
                errors.append(e)
                return
    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    threads += [threading.Thread(target=scraper) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors[0]
    # quiescent cross-check: totals add up after the race
    pairs, _s, count = h.labels().snapshot()
    assert pairs[-1][1] == count > 0


def test_counter_concurrent_increments_exact():
    r = metrics_mod.MetricsRegistry()
    c = r.counter("c_race_total", "x", labels=("w",))
    N, T = 2000, 8

    def worker(k):
        child = c.labels(w=str(k % 2))
        for _ in range(N):
            child.inc()
    threads = [threading.Thread(target=worker, args=(k,)) for k in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    total = sum(c.labels(w=str(i)).value for i in (0, 1))
    assert total == N * T


def test_registry_reset_mid_scrape_safe():
    """reset() racing scrapes and writers must never raise or wedge —
    cached handles keep working, fresh get-or-create re-registers."""
    r = metrics_mod.MetricsRegistry()
    stop = threading.Event()
    errors = []

    def writer():
        while not stop.is_set():
            try:
                r.counter("reset_race_total").inc()
                r.histogram("reset_race_seconds",
                            buckets=(1.0,)).observe(0.5)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    def scraper():
        while not stop.is_set():
            try:
                text = r.render_prometheus()
                assert text == "" or text.endswith("\n")
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    def resetter():
        while not stop.is_set():
            r.reset()
            time.sleep(0.005)
    threads = ([threading.Thread(target=writer) for _ in range(3)]
               + [threading.Thread(target=scraper) for _ in range(2)]
               + [threading.Thread(target=resetter)])
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors[0]
    # the registry still works after the churn
    r.counter("reset_race_total").inc()
    assert "reset_race_total" in r.render_prometheus()
