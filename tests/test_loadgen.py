"""Serving observatory (docs/OBSERVABILITY.md "Serving observatory"):
open-loop arrival processes, the per-request lifecycle ledger/metrics
telemetry on both engines, SLO-aware shedding end to end through the
OpenAI API, the degradation-curve knee, and the `fedml load` CLI."""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest
from click.testing import CliRunner

from fedml_tpu.core.mlops import ledger, metrics as metrics_mod


class _StubBundle:
    """Uniform logits — drives the batched decode loop with a trivial
    compile, so lifecycle tests don't pay a model forward."""

    input_shape = (16,)

    def apply(self, variables, x, train=False):
        import jax.numpy as jnp

        b, t = x.shape
        return jnp.zeros((b, t, 11)), None


def _stub_engine(max_batch=2, window=16, admission=None):
    from fedml_tpu.serving.llm_engine import BatchedLLMEngine

    return BatchedLLMEngine(_StubBundle(), {}, max_batch=max_batch,
                            window=window, admission=admission)


def _tiny_kv_engine(max_batch=2, tokens_per_dispatch=1, max_len=64,
                    admission=None):
    import jax

    from fedml_tpu.serving.kv_cache_lm import KVCacheLM
    from fedml_tpu.serving.llm_engine import KVCacheLLMEngine

    lm = KVCacheLM.create(jax.random.PRNGKey(0), vocab=90, dim=16,
                          layers=1, heads=2, max_len=max_len)
    return KVCacheLLMEngine(lm, max_batch=max_batch,
                            tokens_per_dispatch=tokens_per_dispatch,
                            admission=admission)


# -- arrival processes -------------------------------------------------------

def test_poisson_schedule_statistics():
    from fedml_tpu.serving.loadgen import PoissonProcess

    sched = PoissonProcess(50.0, seed=3).schedule(20.0)
    assert np.all(np.diff(sched) >= 0)           # sorted
    assert sched[0] >= 0 and sched[-1] < 20.0
    # mean count 1000, sd ~32 — 5 sd tolerance
    assert 840 <= sched.size <= 1160
    gaps = np.diff(sched)
    assert abs(float(gaps.mean()) - 1 / 50.0) < 0.004


def test_mmpp_bursty_schedule():
    from fedml_tpu.serving.loadgen import MarkovModulatedProcess

    proc = MarkovModulatedProcess(5.0, 80.0, switch_p=0.02, seed=7)
    sched = proc.schedule(60.0)
    mean_qps = sched.size / 60.0
    assert 5.0 < mean_qps < 80.0                 # between the two states
    # burstiness: squared coeff of variation of gaps well above the
    # Poisson value of 1
    gaps = np.diff(sched)
    cv2 = float(gaps.var() / gaps.mean() ** 2)
    assert cv2 > 1.5


def test_trace_replay_and_scale(tmp_path):
    from fedml_tpu.serving.loadgen import TraceProcess

    trace = tmp_path / "arrivals.jsonl"
    trace.write_text("".join(
        json.dumps({"ts": 100.0 + t}) + "\n" for t in (0, 1, 2, 4, 8)))
    proc = TraceProcess.from_jsonl(str(trace))
    np.testing.assert_allclose(proc.schedule(100.0), [0, 1, 2, 4, 8])
    fast = TraceProcess.from_jsonl(str(trace), scale=2.0)
    np.testing.assert_allclose(fast.schedule(100.0), [0, 0.5, 1, 2, 4])
    # horizon clips
    assert TraceProcess.from_jsonl(str(trace)).schedule(3.0).size == 3


def test_trace_from_ledger_submit_events(tmp_path):
    from fedml_tpu.serving.loadgen import TraceProcess, parse_arrivals

    led = tmp_path / "ledger.jsonl"
    recs = ([{"actor": "serving", "event": "submit", "ts_mono": 50.0 + t}
             for t in (0, 0.5, 1.5)]
            + [{"actor": "serving", "event": "admit", "ts_mono": 51.0},
               {"actor": "server", "event": "solicit", "ts_mono": 50.2}])
    led.write_text("".join(json.dumps(r) + "\n" for r in recs))
    proc = TraceProcess.from_ledger(str(led))
    np.testing.assert_allclose(proc.schedule(10.0), [0, 0.5, 1.5])
    # the dir form of the spec resolves through the same loader
    proc2 = parse_arrivals(f"trace:{tmp_path}")
    assert proc2.schedule(10.0).size == 3


def test_parse_arrivals_specs():
    from fedml_tpu.serving.loadgen import (MarkovModulatedProcess,
                                           PoissonProcess, parse_arrivals)

    assert isinstance(parse_arrivals("poisson:8"), PoissonProcess)
    mm = parse_arrivals("mmpp:2:40:0.2")
    assert isinstance(mm, MarkovModulatedProcess)
    assert mm.switch_p == 0.2
    for bad in ("", "poisson", "poisson:0", "mmpp:1", "warp:9", "poisson:x"):
        with pytest.raises(ValueError):
            parse_arrivals(bad)


def test_length_sampler_committed_hist():
    from fedml_tpu.serving.loadgen import LengthSampler

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks",
        "serving_length_hist.json")
    sampler = LengthSampler.from_file(path, seed=5)
    with open(path) as f:
        payload = json.load(f)
    prompts = {v for v, _ in payload["prompt"]}
    outputs = {v for v, _ in payload["output"]}
    for _ in range(50):
        s = sampler.sample()
        assert s["prompt_tokens"] in prompts
        assert s["output_tokens"] in outputs
    fixed = LengthSampler.fixed(7, 3)
    assert fixed.sample() == {"prompt_tokens": 7, "output_tokens": 3}


# -- engine lifecycle telemetry ----------------------------------------------

def test_lifecycle_coverage_and_ttft_decomposition(tmp_path):
    """Every submitted request reaches exactly one terminal ledger event,
    and ttft == queue_wait + prefill + first_decode at every first_token
    (the decomposition holds by construction)."""
    from fedml_tpu.serving.loadgen import request_anatomy

    ledger.enable(True, log_dir=str(tmp_path), run_id="lifecycle")
    eng = _tiny_kv_engine(max_batch=2, tokens_per_dispatch=2)
    try:
        futs = [eng.submit(list(range(1, 5 + i)), max_new=4)
                for i in range(5)]        # 5 reqs > 2 slots → queueing
        for f in futs:
            f.result(120.0)
    finally:
        eng.stop()
        ledger.reset()
    anatomy = request_anatomy(ledger.load_ledger(str(tmp_path)))
    assert anatomy["submitted"] == 5
    assert anatomy["coverage"] == 1.0
    assert anatomy["outcomes"] == {"finish": 5}
    firsts = [e for r in anatomy["requests"].values()
              for e in r["events"] if e["event"] == "first_token"]
    assert len(firsts) == 5
    for e in firsts:
        a = e["attrs"]
        lhs = a["queue_wait_s"] + a["prefill_s"] + a["first_decode_s"]
        assert abs(lhs - a["ttft_s"]) < 2e-3
    # satellite: admit-time queue-wait histogram is populated
    qw = metrics_mod.REGISTRY.collect()[
        "fedml_llm_queue_wait_seconds"].labels(engine="kv")
    assert qw.count >= 5


def test_admission_sheds_with_reason_and_metrics(tmp_path):
    """Past the queue bound the engine sheds: the future raises
    ShedError, the ledger records the shed with its reason, and the
    shed/requests counters agree."""
    from fedml_tpu.serving.admission import (ServingAdmissionController,
                                             ShedError)
    from fedml_tpu.serving.loadgen import request_anatomy

    shed_c = metrics_mod.counter(
        "fedml_llm_shed_total", "Requests shed by admission control",
        labels=("engine", "reason")).labels(engine="batched",
                                            reason="queue_full")
    shed_before = shed_c.value
    ledger.enable(True, log_dir=str(tmp_path), run_id="shed")
    eng = _stub_engine(max_batch=1,
                       admission=ServingAdmissionController(
                           max_queue_depth=0))
    try:
        # depth >= 0 → every request sheds before entering the queue
        futs = [eng.submit([1, 2], max_new=3) for _ in range(4)]
        for f in futs:
            with pytest.raises(ShedError) as ei:
                f.result(30.0)
            assert ei.value.reason == "queue_full"
    finally:
        eng.stop()
        ledger.reset()
    anatomy = request_anatomy(ledger.load_ledger(str(tmp_path)))
    assert anatomy["outcomes"] == {"shed": 4}
    assert anatomy["coverage"] == 1.0
    sheds = [e for r in anatomy["requests"].values()
             for e in r["events"] if e["event"] == "shed"]
    assert all(e["attrs"]["reason"] == "queue_full" for e in sheds)
    assert shed_c.value == shed_before + 4


def test_stats_snapshot_matches_gauges():
    """stats() is the single source: the dict it returns and the
    Prometheus gauges it refreshes carry the same values."""
    eng = _stub_engine(max_batch=2)
    try:
        s = eng.stats()
        reg = metrics_mod.REGISTRY.collect()
        assert reg["fedml_llm_queue_depth"].labels(
            engine="batched").value == s["queue_depth"]
        assert reg["fedml_llm_active_requests"].labels(
            engine="batched").value == s["active"]
        assert reg["fedml_llm_batch_occupancy"].labels(
            engine="batched").value == pytest.approx(
                s["active"] / s["capacity"])
    finally:
        eng.stop()


# -- OpenAI API: shed → 429, client disconnect → cancel ----------------------

def test_openai_shed_returns_429():
    from fedml_tpu.serving.admission import ServingAdmissionController
    from fedml_tpu.serving.llm_engine import LLMEnginePredictor
    from fedml_tpu.serving.openai_api import OpenAIServer
    import urllib.error
    import urllib.request

    eng = _stub_engine(max_batch=1,
                       admission=ServingAdmissionController(
                           max_queue_depth=0))
    srv = OpenAIServer(LLMEnginePredictor(eng), model_name="tiny", port=0)
    try:
        srv.run(block=False)
        body = json.dumps({"model": "tiny", "max_tokens": 3,
                           "messages": [{"role": "user",
                                         "content": "hi"}]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/chat/completions",
            data=body, headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=60)
        assert ei.value.code == 429
        payload = json.loads(ei.value.read())
        assert payload["error"]["code"] == "queue_full"
        assert payload["error"]["type"] == "overloaded"
    finally:
        srv.stop()
        eng.stop()


def test_client_disconnect_mid_decode_emits_cancel(tmp_path):
    """A streaming client that drops its socket mid-decode frees the
    slot, lands a `cancel` (never `finish`) lifecycle event, and leaves
    the TBT percentiles untouched."""
    from fedml_tpu.serving.llm_engine import LLMEnginePredictor
    from fedml_tpu.serving.loadgen import request_anatomy
    from fedml_tpu.serving.openai_api import OpenAIServer

    ledger.enable(True, log_dir=str(tmp_path), run_id="disconnect")
    eng = _tiny_kv_engine(max_batch=2, tokens_per_dispatch=1, max_len=256)
    reg = metrics_mod.REGISTRY.collect()
    tbt = reg["fedml_llm_tbt_seconds"].labels(engine="kv")
    cancels = reg["fedml_llm_requests_total"].labels(engine="kv",
                                                     outcome="cancel")
    tbt_before, cancels_before = tbt.count, cancels.value
    srv = OpenAIServer(LLMEnginePredictor(eng), model_name="tiny", port=0)
    try:
        srv.run(block=False)
        body = json.dumps({"model": "tiny", "max_tokens": 200,
                           "stream": True,
                           "messages": [{"role": "user",
                                         "content": "hello"}]}).encode()
        raw = (b"POST /v1/chat/completions HTTP/1.1\r\n"
               b"Host: x\r\nContent-Type: application/json\r\n"
               + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        sock = socket.create_connection(("127.0.0.1", srv.port),
                                        timeout=60)
        sock.sendall(raw)
        got = b""
        while b"data:" not in got:          # first token reached the wire
            got += sock.recv(4096)
        sock.close()                        # client vanishes mid-decode
        deadline = time.time() + 60
        while eng.active_count and time.time() < deadline:
            time.sleep(0.05)
        assert eng.active_count == 0        # slot freed
    finally:
        srv.stop()
        eng.stop()
        ledger.reset()
    anatomy = request_anatomy(ledger.load_ledger(str(tmp_path)))
    assert anatomy["outcomes"].get("cancel", 0) >= 1
    assert "finish" not in anatomy["outcomes"]
    assert cancels.value >= cancels_before + 1
    assert tbt.count == tbt_before          # cancels never observe TBT


# -- open-loop driver --------------------------------------------------------

def test_open_loop_driver_end_to_end(tmp_path):
    from fedml_tpu.serving.loadgen import (LengthSampler, OpenLoopDriver,
                                           PoissonProcess, request_anatomy,
                                           summarize_requests)

    ledger.enable(True, log_dir=str(tmp_path), run_id="driver")
    eng = _stub_engine(max_batch=2)
    try:
        driver = OpenLoopDriver(
            eng, PoissonProcess(30.0, seed=2),
            LengthSampler.fixed(4, 6), duration_s=1.5, vocab=10,
            cancel_fraction=0.3, cancel_after_tokens=2,
            gauge_period_s=0.1, seed=2)
        result = driver.run(drain_timeout_s=120.0)
    finally:
        eng.stop()
        ledger.reset()
    assert result.offered == len(result.rows) > 10
    outcomes = {r["outcome"] for r in result.rows}
    assert "finish" in outcomes and "cancel" in outcomes
    assert len(result.gauges) >= 5          # sampled during the soak
    assert all(g["queue_depth"] >= 0 for g in result.gauges)
    # full lifecycle coverage in the ledger
    anatomy = request_anatomy(ledger.load_ledger(str(tmp_path)))
    assert anatomy["submitted"] == result.offered
    assert anatomy["coverage"] == 1.0
    summary = summarize_requests(result.rows, result.duration_s,
                                 wall_s=result.wall_s,
                                 overhead_s=result.overhead_s)
    assert summary["finished"] + summary["cancelled"] == result.offered
    assert summary["ttft_p99"] is not None
    # cancelled streams are excluded from TBT rows
    assert all(r["tbt_s"] is None for r in result.rows
               if r["outcome"] == "cancel")
    # observability + driver bookkeeping stays a small fraction of wall
    # (the strict <2% budget is asserted on the longer CI soak)
    assert summary["overhead_frac"] < 0.2


# -- report / curve ----------------------------------------------------------

def _mk_rows(n_finish, n_shed=0, n_cancel=0, ttft=0.05, tbt=0.01):
    rows = []
    for i in range(n_finish):
        rows.append({"rid": i, "outcome": "finish", "tokens": 8,
                     "ttft_s": ttft, "queue_wait_s": ttft / 2,
                     "prefill_s": ttft / 4, "tbt_s": tbt})
    for i in range(n_shed):
        rows.append({"rid": 1000 + i, "outcome": "shed", "tokens": 0,
                     "ttft_s": None, "queue_wait_s": 0.0,
                     "prefill_s": 0.0, "tbt_s": None})
    for i in range(2000, 2000 + n_cancel):
        rows.append({"rid": i, "outcome": "cancel", "tokens": 2,
                     "ttft_s": ttft, "queue_wait_s": ttft / 2,
                     "prefill_s": ttft / 4, "tbt_s": None})
    return rows


def test_summarize_requests_partitions_outcomes():
    from fedml_tpu.serving.loadgen import summarize_requests

    s = summarize_requests(_mk_rows(8, n_shed=2, n_cancel=1), 10.0)
    assert s["offered"] == 11 and s["finished"] == 8
    assert s["shed"] == 2 and s["cancelled"] == 1
    assert s["shed_rate"] == pytest.approx(2 / 11)
    assert s["goodput_qps"] == pytest.approx(0.8)
    assert s["tbt_p99"] == pytest.approx(0.01)   # finish-only
    assert s["tokens"] == 8 * 8 + 2


def test_find_knee_and_graceful_verdict():
    from fedml_tpu.serving.loadgen import (find_knee, render_curve,
                                           summarize_requests)

    def point(qps, n_finish, n_shed, ttft):
        s = summarize_requests(
            _mk_rows(n_finish, n_shed=n_shed, ttft=ttft), 10.0)
        return s

    # graceful: past-knee point sheds, admitted p99 stays bounded
    graceful = [point(2, 20, 0, 0.02), point(8, 80, 0, 0.05),
                point(20, 150, 50, 0.2)]
    knee = find_knee(graceful, slo_ttft_p99_s=0.5)
    assert knee is graceful[1]        # last point fails goodput floor
    out = render_curve(graceful, 0.5)
    assert "<- knee" in out and "GRACEFUL" in out
    # collapsing: no shedding, p99 through the SLO
    collapsing = [point(2, 20, 0, 0.02), point(8, 80, 0, 0.05),
                  point(20, 190, 0, 3.0)]
    out2 = render_curve(collapsing, 0.5)
    assert "COLLAPSING" in out2 and "--admission" in out2
    # undersized: every point breaches
    assert find_knee([point(2, 20, 0, 3.0)], 0.5) is None


def test_request_anatomy_renders_exemplars():
    from fedml_tpu.serving.loadgen import (render_exemplars,
                                           render_request_timeline,
                                           request_anatomy)

    recs = [
        {"actor": "serving", "event": "submit", "ts_mono": 1.0,
         "attrs": {"rid": 1, "engine": "kv", "prompt_tokens": 4,
                   "max_new": 8}},
        {"actor": "serving", "event": "admit", "ts_mono": 1.01,
         "attrs": {"rid": 1, "slot": 0, "queue_wait_s": 0.01}},
        {"actor": "serving", "event": "first_token", "ts_mono": 1.02,
         "attrs": {"rid": 1, "ttft_s": 0.02, "queue_wait_s": 0.01,
                   "prefill_s": 0.005, "first_decode_s": 0.005}},
        {"actor": "serving", "event": "finish", "ts_mono": 1.05,
         "attrs": {"rid": 1, "tokens": 8, "service_s": 0.05,
                   "finish_reason": "stop"}},
        {"actor": "serving", "event": "submit", "ts_mono": 1.1,
         "attrs": {"rid": 2, "engine": "kv", "prompt_tokens": 4,
                   "max_new": 8}},
        {"actor": "serving", "event": "shed", "ts_mono": 1.1,
         "attrs": {"rid": 2, "reason": "queue_full", "queue_depth": 9}},
        {"actor": "serving", "event": "decode_batch", "ts_mono": 1.2,
         "attrs": {"active": 1}},        # aggregate event: no rid, skipped
    ]
    spans = [{"attrs": {"rid": 1}, "dur_s": 0.05, "status": None,
              "trace_id": "t1"}]
    anatomy = request_anatomy(recs, spans)
    assert anatomy["submitted"] == 2 and anatomy["coverage"] == 1.0
    assert anatomy["requests"][1]["span"]["dur_s"] == 0.05
    tl = render_request_timeline(anatomy, 1)
    assert "first_token" in tl and "ttft 20.0 ms" in tl
    ex = render_exemplars(anatomy)
    assert "lifecycle coverage 100.0%" in ex
    assert "a shed request" in ex and "queue_full" in ex


# -- SLO indicators ----------------------------------------------------------

def test_serving_slo_indicators_from_metrics():
    from fedml_tpu.core.mlops import slo as slo_mod

    metrics_mod.histogram(
        "fedml_llm_queue_wait_seconds", "Submit -> admit wait",
        labels=("engine",)).labels(engine="kv").observe(0.02)
    metrics_mod.histogram(
        "fedml_llm_tbt_seconds", "Mean inter-token gap",
        labels=("engine",)).labels(engine="kv").observe(0.004)
    metrics_mod.counter(
        "fedml_llm_shed_total", "Requests shed by admission control",
        labels=("engine", "reason")).labels(
            engine="kv", reason="queue_full").inc(2)
    metrics_mod.counter(
        "fedml_llm_requests_total", "Requests retired, by outcome",
        labels=("engine", "outcome")).labels(
            engine="kv", outcome="finish").inc(6)

    rules = [slo_mod.SLORule(name="qw", indicator="queue_wait_p99",
                             max=10.0),
             slo_mod.SLORule(name="tbt", indicator="decode_tbt_p99",
                             max=10.0)]
    results = slo_mod.evaluate(rules, slo_mod.SLOContext.live())
    by_name = {r["rule"]: r for r in results}
    assert by_name["qw"]["ok"] is True
    assert by_name["qw"]["value"] > 0
    assert by_name["tbt"]["ok"] is True
    # shed-rate over the live counters: shed / all requests
    rate = slo_mod.INDICATORS["serving_shed_rate"](
        slo_mod.SLOContext.live(),
        slo_mod.SLORule(name="s", indicator="serving_shed_rate",
                        max=1.0))
    assert rate is not None and 0.0 < rate <= 1.0


def test_serving_shed_rate_ledger_fallback(tmp_path):
    from fedml_tpu.core.mlops import slo as slo_mod

    recs = ([{"actor": "serving", "event": "submit", "ts_mono": t,
              "attrs": {"rid": t}} for t in range(10)]
            + [{"actor": "serving", "event": "shed", "ts_mono": 20 + t,
                "attrs": {"rid": t, "reason": "queue_full"}}
               for t in range(3)])
    (tmp_path / "ledger.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    ctx = slo_mod.SLOContext.from_artifacts(log_dir=str(tmp_path))
    rule = slo_mod.SLORule(name="shed", indicator="serving_shed_rate",
                           max=0.5)
    assert slo_mod.INDICATORS["serving_shed_rate"](ctx, rule) \
        == pytest.approx(0.3)
    results = slo_mod.evaluate([rule], ctx)
    assert results[0]["ok"] is True


# -- perf history ------------------------------------------------------------

def test_perf_history_serving_headline_regression(tmp_path):
    from fedml_tpu.core.mlops import perf_history

    assert "serving_sustained_qps" in perf_history.HEADLINE_METRICS
    assert "serving_tokens_per_s" in perf_history.HEADLINE_METRICS
    path = str(tmp_path / "hist.jsonl")
    perf_history.append_entry(
        path, platform="cpu", source="fedml load run",
        metrics={"serving_sustained_qps": 10.0,
                 "serving_tokens_per_s": 100.0}, ts=1.0, rev="aaa")
    perf_history.append_entry(
        path, platform="cpu", source="fedml load run",
        metrics={"serving_sustained_qps": 4.0,
                 "serving_tokens_per_s": 99.0}, ts=2.0, rev="bbb")
    findings = perf_history.detect(perf_history.load_history(path))
    regressed = {r["metric"] for r in findings["regressions"]}
    assert "serving_sustained_qps" in regressed
    assert "serving_tokens_per_s" not in regressed     # 1% < threshold


# -- CLI ---------------------------------------------------------------------

def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def keep_registry():
    """``fedml load run`` starts from an empty process-wide registry.
    Put back what it dropped, so modules that took their metric handles at
    import (the pod scheduler) still export them to tests that run later
    in this process."""
    before = metrics_mod.REGISTRY.collect()
    yield
    with metrics_mod.REGISTRY._lock:
        metrics_mod.REGISTRY._metrics.update(before)


def test_cli_load_run_report_and_slo_gate(tmp_path, keep_registry):
    from fedml_tpu.cli.cli import cli

    out = str(tmp_path / "soak")
    hist = str(tmp_path / "hist.jsonl")
    res = CliRunner().invoke(cli, [
        "load", "run", "--arrivals", "poisson:20", "--duration-s", "1.5",
        "--dim", "16", "--layers", "1", "--heads", "2", "--max-len", "48",
        "--max-batch", "2", "--lengths", "fixed:4:4",
        "--cancel-fraction", "0.2", "--out", out, "--history", hist,
        "--platform", "cpu-test"])
    assert res.exit_code == 0, res.output
    assert "lifecycle" not in res.output      # report, not anatomy
    for name in ("requests.jsonl", "gauges.jsonl", "summary.json",
                 "metrics.prom", "ledger.jsonl", "spans.jsonl"):
        assert os.path.exists(os.path.join(out, name)), name
    with open(os.path.join(out, "summary.json")) as f:
        summary = json.load(f)
    assert summary["finished"] > 0
    # provenance-stamped history row
    with open(hist) as f:
        entry = json.loads(f.readlines()[-1])
    assert entry["platform"] == "cpu-test" and entry["measured"]
    assert entry["metrics"]["serving_sustained_qps"] > 0
    assert "offered" in entry["notes"] and "ttft_p99" in entry["notes"]

    res2 = CliRunner().invoke(cli, ["load", "report", "--out", out,
                                    "--anatomy"])
    assert res2.exit_code == 0, res2.output
    assert "lifecycle coverage" in res2.output
    assert "slowest completed request" in res2.output
    assert "first_token" in res2.output

    res3 = CliRunner().invoke(cli, [
        "slo", "check",
        "--rules", os.path.join(_repo_root(), "examples",
                                "slo_serving.yaml"),
        "--log-dir", out, "--metrics", os.path.join(out, "metrics.prom")])
    assert res3.exit_code == 0, res3.output
    assert "decode_ttft_p99" in res3.output


@pytest.mark.slow
def test_cli_load_curve_finds_knee(tmp_path, keep_registry):
    """Acceptance: the CPU-proxy sweep locates a saturation knee and the
    engine degrades gracefully past it (shedding engaged, admitted p99
    bounded)."""
    from fedml_tpu.cli.cli import cli

    curve_path = str(tmp_path / "curve.json")
    res = CliRunner().invoke(cli, [
        "load", "curve", "--qps", "8,64,256", "--duration-s", "4",
        "--max-batch", "2", "--lengths", "fixed:16:32",
        "--admission", "queue:8", "--slo-ttft-p99", "1.0",
        "--out", curve_path])
    assert res.exit_code == 0, res.output
    assert "<- knee" in res.output
    with open(curve_path) as f:
        curve = json.load(f)
    assert curve["knee"] is not None
    past = [p for p in curve["points"]
            if p["offered_qps"] > curve["knee"]["offered_qps"]]
    assert past, "sweep never exceeded the knee"
    assert any(p["shed_rate"] > 0 for p in past)          # shedding engaged
    assert all(p["ttft_p99"] <= 1.0 for p in past)        # bounded p99


def test_serving_scaler_scales_up_under_open_loop_burst(tmp_path):
    """Policy loop under the load plane (docs/SCHEDULER.md "Elastic
    resize"): an MMPP burst through the open-loop driver feeds the real
    decode-step histogram the engine exports, and the replica scaler
    answers with an IN-PLACE resize request on the elastic RUNNING
    serving job — no drain, no preemption."""
    from fedml_tpu.scheduler.autoscaler import AutoscalePolicy
    from fedml_tpu.scheduler.pod import JobQueue, JobSpec, JobState
    from fedml_tpu.scheduler.pod.serving_scaler import ServingReplicaScaler
    from fedml_tpu.serving.loadgen import (LengthSampler,
                                           MarkovModulatedProcess,
                                           OpenLoopDriver)

    q = JobQueue(str(tmp_path / "pod"))
    jid = q.submit(JobSpec(name="svc", kind="serving", n_slots=2,
                           min_slots=1, max_slots=8, command="serve"))
    q.mark_dispatched(jid, "runS", [0, 1], "/tmp/l")
    scaler = ServingReplicaScaler(
        q, policy=AutoscalePolicy(min_replicas=1, max_replicas=8,
                                  target_latency_s=1e-6,
                                  target_qps_per_replica=1.0))
    assert scaler.tick() == {}               # baseline decode window
    eng = _stub_engine(max_batch=2)
    try:
        driver = OpenLoopDriver(
            eng, MarkovModulatedProcess(5.0, 80.0, switch_p=0.02, seed=7),
            LengthSampler.fixed(4, 6), duration_s=1.5, vocab=10,
            gauge_period_s=0.2, seed=7)
        result = driver.run(drain_timeout_s=120.0)
    finally:
        eng.stop()
    assert result.offered > 0
    decisions = scaler.tick()                # window saw the burst
    assert decisions.get(jid, 2) > 2
    row = q.get(jid)
    # elastic + RUNNING → the scaler latched an in-place resize
    assert row["state"] == JobState.RUNNING
    assert not row["preempt_requested"]
    assert row["resize_requested"] == decisions[jid]
    q.close()
