"""The harness's own arithmetic and bookkeeping, on the CPU."""

import json
import os
import re

import numpy as np
import pytest

from chipbench.harness import compare, runner, stats, xplane
from chipbench.harness.xplane import Event
from chipbench.traffic import arrivals, tokens

ROOT = runner.ROOT
HERE = runner.HERE
BENCH = runner.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- percentile rule ---------------------------------------------------------

@pytest.mark.parametrize("n, q, want", [
    (200, 95, 190.0),      # ten beyond the 190th
    (199, 95, None),       # nine beyond: not reported
    (1000, 95, 950.0),
    (1000, 99, 990.0),
    (999, 99, None),
    (0, 95, None),
])
def test_percentile_needs_ten_samples_beyond_it(n, q, want):
    assert stats.percentile([float(i) for i in range(1, n + 1)], q) == want


def test_percentile_counts_a_failed_request_as_never():
    values = [1.0] * 189 + [float("inf")] * 11
    assert stats.percentile(values, 95) == float("inf")


def test_iqr_share_is_the_contracts_spread():
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0.0
    assert stats.iqr_share([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)


# -- traffic -------------------------------------------------------------------

TRAFFIC = runner.load_json(os.path.join(
    HERE, "workloads", "serve.chat_steady.json"))["traffic"]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_requests_are_a_pure_function_of_the_seed(seed):
    a = arrivals.requests(TRAFFIC, 30.0, seed, 50257)
    b = arrivals.requests(TRAFFIC, 30.0, seed, 50257)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"]) for x, y in zip(a, b))
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]


def test_every_seed_offers_the_same_work_in_another_order():
    a = arrivals.requests(TRAFFIC, 30.0, 1, 50257)
    b = arrivals.requests(TRAFFIC, 30.0, 2, 50257)
    size = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    gaps = lambda rs: np.sort(np.diff([0.0] + [r["due_s"] for r in rs]))
    assert size(a) == size(b)
    assert np.allclose(gaps(a), gaps(b))
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    assert len(a) == round(TRAFFIC["arrivals"]["rate_qps"] * 30.0)


def test_the_order_is_free_so_long_answers_can_come_together():
    """Nothing deals sizes or gaps evenly over the window: over a few seeds,
    the work asked for in a run of 16 consecutive requests swings by more than
    half its mean, as it would in a sampled stream."""
    swings = []
    for seed in range(4):
        rs = arrivals.requests(TRAFFIC, 40.0, seed, 50257)
        out = np.array([r["max_new"] for r in rs])
        runs = out[:len(out) // 16 * 16].reshape(-1, 16).sum(1)
        swings.append(np.ptp(runs) / runs.mean())
    assert min(swings) > 0.5


def test_requests_stay_inside_the_window_and_the_cache():
    rs = arrivals.requests(TRAFFIC, 30.0, 3, 50257)
    due = [r["due_s"] for r in rs]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 30.0
    lo = min(b[0] for b in TRAFFIC["prompt_tokens"])
    hi = max(b[1] for b in TRAFFIC["prompt_tokens"])
    assert all(lo <= len(r["prompt"]) <= hi for r in rs)
    assert all(1 <= r["max_new"] and len(r["prompt"]) + r["max_new"]
               <= TRAFFIC["max_total_tokens"] for r in rs)


def test_an_unknown_arrival_process_is_an_error():
    with pytest.raises(ValueError):
        arrivals.due_times({"process": "poisson", "rate_qps": 1.0}, 1.0,
                           np.random.default_rng(0))


def test_training_rows_all_differ_and_repeat_by_seed():
    a = tokens.call_tokens(5, 0, 8, 128, 50257)
    assert np.array_equal(a, tokens.call_tokens(5, 0, 8, 128, 50257))
    assert not np.array_equal(a, tokens.call_tokens(5, 1, 8, 128, 50257))
    x, y = tokens.as_batches(a, 2, 4, 128)
    rows = x.reshape(8, 128)
    assert len({r.tobytes() for r in rows}) == 8
    assert np.array_equal(x.reshape(-1)[1:], y.reshape(-1)[:-1])


# -- comparison ------------------------------------------------------------------

def test_worst_leaf_gap_is_held_against_the_median_leaf():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.0, "b": 2.2, "c": 2e-9}
    # b: 0.2 / 2.0; c's tiny norm is held against the median leaf (1.0)
    assert compare.worst_leaf_gap(got, want) == pytest.approx(0.1)
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"a": 1.0}, want)


def test_a_number_without_a_limit_or_not_a_number_never_passes():
    rows = compare.against_limits({"x": 0.5, "y": float("nan")},
                                  {"x": 1.0, "y": 1.0})
    assert [r["ok"] for r in rows] == [True, False]
    with pytest.raises(KeyError):
        compare.against_limits({"z": 0.0}, {})


# -- the trace's reduction, on events written out by hand --------------------

def _ops():
    # a loop 0-100 holding two fusions, then a gap of 50, then a kernel
    return [Event("%while.1 = (s32[], f32[8,16]{1,0}) while(%tuple.3)", 0, 100),
            Event("%fusion.7 = f32[4,1024,5120]{2,1,0:T(8,128)} fusion(f32[4,1024,1280]{2,1,0:T(8,128)S(1)} %p)", 10, 30),
            Event("%fusion.8 = f32[4,1024,1280]{2,1,0:T(8,128)} fusion(%q)", 50, 40),
            Event('%jvp__.2 = (f32[80,1024,64]{2,1,0}, f32[80,1024,1]{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"', 150, 25)]


def test_busy_time_counts_nested_operations_once():
    assert xplane.busy_ns(_ops()) == 125.0
    assert xplane.gaps(_ops()) == [(100.0, 150.0)]


def test_self_time_takes_the_body_out_of_the_loop():
    by = xplane.time_by_kind(_ops())
    assert by == {"while s32[]": 30.0, "fusion f32[4,1024,5120]": 30.0,
                  "fusion f32[4,1024,1280]": 40.0,
                  "jvp__ f32[80,1024,64] tpu_custom_call": 25.0}
    assert xplane.top(by, 2) == [["fusion f32[4,1024,1280]", 4e-8],
                                 ["while s32[]", 3e-8]]
    assert len(xplane.matching(_ops(), r'custom_call_target="tpu_custom_call"')) == 1


def test_idle_goes_to_the_finest_host_event_over_the_gaps_middle():
    ops = [Event("a", 0, 10_000), Event("b", 30_000, 10_000),
           Event("c", 41_000, 1_000), Event("d", 100_000, 1_000)]
    host = [Event("chipbench.train_call", 0, 90_000),
            Event("PjitFunction(epoch)", 5_000, 30_000),
            Event("chipbench.pack", 95_000, 4_000)]
    by = xplane.idle_by_host_activity(ops, host)
    assert by == {"PjitFunction(epoch)": 20_000.0, xplane.SHORT: 1_000.0,
                  "chipbench.train_call": 58_000.0}
    assert xplane.busy_within_ns(ops, host[1]) == 10_000.0


def test_decode_variants_are_told_apart_by_their_token_steps():
    """Two ``decode_multi`` programs: the one whose executions hold more
    operations over the ``[max_batch, vocab]`` logits is the full dispatch,
    however often each ran."""
    step = "%fusion.9 = (f32[4,128]{1,0}) fusion(f32[4,211]{1,0} %logits)"
    ops, mods = [], []
    for i in range(5):                       # the short one runs more often
        mods.append(Event("jit_decode_multi(111)", 1000 * i, 200))
        ops += [Event(step, 1000 * i + 50 * j, 10) for j in range(2)]
    for i in range(2):
        mods.append(Event("jit_decode_multi(222)", 10_000 + 1000 * i, 700))
        ops += [Event(step, 10_000 + 1000 * i + 50 * j, 10) for j in range(8)]
    mods.append(Event("jit_prefill(333)", 20_000, 50))
    run = runner.Run(trace=xplane.Trace({"d": ops}, {"d": mods}, []),
                     cell={"traffic": {"max_batch": 4}},
                     config={"vocab_size": 211})
    assert runner.reader_of("decode_device_ms")(run) == pytest.approx(700 / 1e6)
    assert runner.reader_of("admit_dispatch_device_ms")(run) == pytest.approx(200 / 1e6)
    run.trace = xplane.Trace({"d": []}, {"d": mods}, [])   # nothing to tell by
    assert runner.reader_of("decode_device_ms")(run) is None


# -- a trace recorded on the chip ------------------------------------------------

SMALL = os.path.join(HERE, "tests", "data", "small_trace.xplane.pb")


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_recorded_trace_reduces_to_what_was_run():
    """Three rounds of a jitted matmul chain under ``chipbench.work``, each
    followed by 20 ms asleep under ``chipbench.sleep`` (tools/
    record_small_trace.py, one TPU v5 lite)."""
    tr = xplane.load(SMALL)
    ops = xplane.first_device(tr)
    runs = xplane.matching(xplane.first_device_modules(tr),
                           "small_matmul_chain")
    assert len(runs) == 3 and ops
    busy = xplane.busy_ns(ops)
    span = max(e.end for e in ops) - min(e.start for e in ops)
    assert 0 < busy < span
    # busy time is the three executions, near enough
    assert busy == pytest.approx(sum(r.dur for r in runs), rel=0.1)
    idle = xplane.idle_by_host_activity(ops, tr.host)
    # the device waited out two sleeps between the three rounds
    assert 0.035e9 < idle["chipbench.sleep"] < 0.06e9
    work = xplane.host_spans(tr, "chipbench.work")
    assert len(work) == 3
    # the device's clock and the host's differ by about a millisecond in
    # this trace (an execution shows 0.9 ms before the span that launched
    # it), so a span is widened by two before it is asked what ran inside
    wide = [Event(w.name, w.start - 2e6, w.dur + 2e6) for w in work]
    assert all(xplane.busy_within_ns(ops, w) == pytest.approx(r.dur, rel=0.01)
               for w, r in zip(wide, runs))
    s = xplane.summarize(tr)
    assert s["busy_s"] == pytest.approx(busy / 1e9)
    assert len(s["device_ops"]) <= 10 and s["idle_gaps"][0][0] == "chipbench.sleep"


# -- every name resolves, every name and unit is well formed ----------------------

def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_name_and_unit_passes_the_character_rules():
    names = ([c["name"] for c in BENCH["configs"]]
             + [w[k] for w in BENCH["workloads"]
                for k in ("name", "config", "traffic")]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    for text in ([w["why"] for w in BENCH["workloads"]]
                 + [c["why"] for c in BENCH["configs"]]
                 + [c["source"] for c in BENCH["configs"]]
                 + [m["layer"] for m in BENCH["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_end_to_end_metrics_carry_bounds_and_a_setup_time():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    assert all(set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"} for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_file_a_cell_names_is_there(workload):
    found = runner.resolve(workload, BENCH)
    cell, config = found["cell"], found["config"]
    assert cell["name"] == workload and cell["config"] == found["entry"]["config"]
    assert cell["chips"] == found["entry"]["chips"]
    assert os.path.exists(os.path.join(HERE, "planes", cell["plane"] + ".py"))
    assert os.path.exists(os.path.join(HERE, "reference",
                                       config["reference"] + ".py"))
    for kind in ("end_to_end", "per_layer"):
        mine = runner.metrics_of(BENCH, workload, kind)
        assert len(mine) >= (2 if kind == "end_to_end" else 1)
        for m in mine:
            assert callable(runner.reader_of(m["name"]))
    assert set(cell["limits"]) and all(v >= 0 for v in cell["limits"].values())


def test_every_configuration_is_its_source_unreduced():
    for c in BENCH["configs"]:
        cfg = runner.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    g = runner.load_json(os.path.join(HERE, "configs", "gpt2_large.json"))
    assert (g["n_embd"], g["n_layer"], g["n_head"], g["n_positions"],
            g["vocab_size"]) == (1280, 36, 20, 1024, 50257)


def test_peaks_are_listed_by_device_kind_with_their_source():
    from chipbench.harness import device

    peaks = device.load_peaks()
    v5e = peaks["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"],
            v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert all(p["source"] for p in peaks.values())


def test_a_run_without_a_tpu_is_refused():
    from chipbench.harness import device

    with pytest.raises(SystemExit) as e:
        device.require(1)
    assert e.value.code not in (0, None)
