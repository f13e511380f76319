"""The LFM2 cell's readers on events and counters written out by hand, in
``test_span_metrics.py``'s manner: what they read, and that a program or a
trace without what they read gives nothing and raises nothing."""

import json
import os

import pytest

from chipbench.harness import runner, xplane
from chipbench.harness.xplane import Event
from chipbench.kernels import lfm2_decode

BENCH = runner.load_json(os.path.join(runner.ROOT, "BENCHMARK.json"))
CONFIG = runner.load_json(os.path.join(runner.HERE, "configs",
                                       "lfm2_24b_a2b.json"))
CELL = runner.load_json(os.path.join(runner.HERE, "workloads",
                                     "serve.lfm2_chat_steady.json"))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MINE = [m["name"] for m in BENCH["per_layer"]
        if m.get("workloads") == ["serve.lfm2_chat_steady"]]
#: the serving loop's clocks as the plane's rows have them: no trace needed
CLOCKS = ("lfm2_ttft_p50_ms", "lfm2_ttft_p95_ms")
#: an accepted reader under this cell's name: reads any serving program
ADMIT = "lfm2_prefill_device_ms_per_admit"
NEW = [n for n in MINE if n not in CLOCKS + (ADMIT,)]
COUNTERS = ("lfm2_experts_touched_pct", "lfm2_expert_load_max_over_mean")


class _Rec:
    trace_dir = None

    def say(self, *a, **kw):
        pass


def _read(name, run):
    return runner.reader_of(name)(run)


def _run(trace=None):
    return runner.Run(trace=trace, cell=CELL, config=CONFIG, peaks=PEAKS,
                      rec=_Rec(), plane=None)


def test_the_cell_lists_eleven_metrics_of_its_own_and_the_serving_ones():
    assert len(MINE) == 11 and all(n.startswith("lfm2_") for n in MINE)
    assert set(CLOCKS) < set(MINE)
    mine = {m["name"] for m in runner.metrics_of(
        BENCH, "serve.lfm2_chat_steady", "per_layer")}
    assert mine >= set(MINE) | {"decode_device_ms", "batch_occupancy_pct",
                                "loop_host_ms_per_dispatch"}
    # the tail of the time to first token spreads too widely over seeds to
    # be this cell's end to end: it, and what moves it, are per-layer here
    e2e = [m["name"] for m in runner.metrics_of(
        BENCH, "serve.lfm2_chat_steady", "end_to_end")]
    assert e2e == ["setup_s", "tbt_p50_ms"]
    assert all(m["moves"] == "tbt_p50_ms" for m in BENCH["per_layer"]
               if m["name"] in MINE)
    assert not mine & {"queue_wait_p95_ms", "prefill_device_ms_per_admit"}
    # counts by gpt2_decode's keys: not this cell's
    assert "decode_multi_roofline" not in mine
    other = {m["name"] for m in runner.metrics_of(
        BENCH, "serve.chat_steady", "per_layer")}
    assert "decode_multi_roofline" in other and not other & set(MINE)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_or_a_run_without_a_trace_reads_nothing(
        name, monkeypatch):
    from fedml_tpu.core.mlops import metrics

    # the parent's program: no such counter, no such scope
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    assert _read(name, _run()) is None
    old = xplane.Trace({"d": [Event("%fusion.1 = bf16[32,65536]", 10, 5)]},
                       {"d": [Event("jit_decode_multi_k8(1)", 0, 100),
                              Event("jit_prefill(2)", 200, 50)]},
                       [Event("fedml.serve.prefill.t64", 190, 5)])
    assert _read(name, _run(old)) is None
    # one admission, a prefill of 50 ns on the device
    assert _read(ADMIT, _run()) is None
    assert _read(ADMIT, _run(old)) == pytest.approx(50 / 1e6)


def test_the_time_to_first_token_is_read_from_the_planes_rows():
    class _Plane:
        done = [{"ttft_s": 0.001 * i} for i in range(1, 301)] + [{}]

    run = runner.Run(plane=_Plane())
    assert _read("lfm2_ttft_p50_ms", run) == pytest.approx(150.5)
    # a request that failed counts as never: 301 requests, rank 286
    assert _read("lfm2_ttft_p95_ms", run) == pytest.approx(286.0)
    _Plane.done = _Plane.done[:100]      # fewer than ten beyond it
    assert _read("lfm2_ttft_p95_ms", run) is None


def _count(monkeypatch, steps, touched, picks, heaviest, live):
    from fedml_tpu.core.mlops import metrics

    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    per_step = 32 * (4096 // 128)
    for name, value in (
            ("fedml_moe_experts_touched_total", touched * steps),
            ("fedml_moe_picks_total", picks * steps),
            ("fedml_moe_expert_picks_max", heaviest * steps)):
        metrics.counter(name, "").inc(value)
    for name, value in (("fedml_llm_cache_blocks_total", per_step * steps),
                        ("fedml_llm_cache_blocks_live_total", live * steps)):
        metrics.counter(name, "", labels=("engine",)).labels(
            engine="kv").inc(value)


def test_counters_are_read_a_token_step(monkeypatch):
    # 10 live rows: 40 picks a routed layer, 8 layers; 256 experts touched
    _count(monkeypatch, steps=1000, touched=256, picks=320, heaviest=5,
           live=40)
    run = _run()
    assert _read("lfm2_experts_touched_pct", run) == pytest.approx(50.0)
    # the heaviest expert's 5 picks over 40 / 64 an expert of a layer
    assert _read("lfm2_expert_load_max_over_mean", run) == pytest.approx(8.0)


def test_the_dispatchs_roofline_from_bytes_counted_and_device_time(
        monkeypatch):
    _count(monkeypatch, steps=1000, touched=256, picks=320, heaviest=5,
           live=40)
    step = lfm2_decode.token_step_bytes(CONFIG, 256, 40, 2)
    assert step["outside_experts"] == 2 * (
        CONFIG["parameters"]["held"] - 8 * 64 * 9437184)
    assert step["experts"] == 256 * 9437184 * 2
    assert step["cache"] == 40 * 2 * 2 * 8 * 64 * 128 * 2
    least_ns = 8 * step["total"] / 819e9 * 1e9
    ops, mods, t = [], [], 0
    for k, dur in ((8, 2 * least_ns), (2, least_ns), (8, 2 * least_ns)):
        mods.append(Event(f"jit_decode_multi_k{k}(7)", t, dur))
        for j in range(k):      # a token step's logits, its expert kernels
            at = t + j * dur / k
            ops.append(Event("%fusion.9 = f32[32,65536]{1,0} fusion()", at, 10))
            ops += [Event("%moe_experts.3 = f32[1104,3072] custom-call()",
                          at + 20 + 30 * i, 25) for i in range(16)]
        t += dur + 100
    run = _run(xplane.Trace({"d": ops}, {"d": mods}, []))
    assert _read("lfm2_decode_roofline", run) == pytest.approx(50.0)
    # 16 events of 25 ns a token step against the touched matrices' bytes
    least = lfm2_decode.experts_least_seconds(CONFIG, 256, 320, 2, PEAKS)
    assert least["bound"] == "memory"
    assert _read("lfm2_experts_roofline", run) == pytest.approx(
        100 * least["seconds"] / (16 * 25e-9))
    # even a prefill of 2,048 positions fetches its experts (9.7 GB, 11.8
    # ms) for longer than it multiplies by them (1.24 TFLOP, 6.3 ms); four
    # such rows would be bound by the products
    assert lfm2_decode.experts_least_seconds(
        CONFIG, 512, 8 * 4 * 2048, 2, PEAKS)["bound"] == "memory"
    assert lfm2_decode.experts_least_seconds(
        CONFIG, 512, 8 * 4 * 8192, 2, PEAKS)["bound"] == "compute"
