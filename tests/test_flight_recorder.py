"""Performance flight recorder: phase attribution with the residual
host_gap bucket, bounded JSONL flight log, report/diff rendering,
cost-analysis FLOPs + measured-MFU helpers, the device-phase spans it
shares with `fedml trace summarize`, and the instrumented Parrot fused
path's end-to-end coverage + overhead budget."""

import json
import os
import time

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.core.mlops import flight_recorder as fr
from fedml_tpu.core.mlops import metrics as metrics_mod
from fedml_tpu.core.mlops import tracing
from fedml_tpu.runner import FedMLRunner

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture
def armed(tmp_path):
    fr.enable(True, log_dir=str(tmp_path), run_id="fr-test")
    yield str(tmp_path)
    fr.reset()


# -- phase / record primitives -----------------------------------------------

def test_round_decomposition_covers_wall(armed):
    with fr.record_round("unit_round", rounds=2, program="test/prog") as rec:
        with rec.phase("device_compute"):
            time.sleep(0.02)
        with rec.phase("h2d"):
            time.sleep(0.005)
        time.sleep(0.01)               # unattributed host work
    records = fr.load_flight_log(armed)
    assert len(records) == 1
    r = records[0]
    assert r["kind"] == "unit_round"
    assert r["rounds"] == 2
    assert r["program"] == "test/prog"
    phases = r["phases_s"]
    assert phases["device_compute"] >= 0.02
    assert phases["h2d"] >= 0.005
    # host_gap is the residual: decomposition sums to the wall by
    # construction, and here it must carry the un-phased sleep
    assert phases["host_gap"] >= 0.008
    assert sum(phases.values()) == pytest.approx(r["wall_s"], rel=1e-3)


def test_nested_phase_attributes_to_innermost_record(armed):
    with fr.record_round("outer") as outer:
        with fr.record_round("inner") as inner:
            with fr.phase("device_compute"):   # module-level helper
                time.sleep(0.01)
        assert inner.phase_seconds("device_compute") >= 0.01
        assert outer.phase_seconds("device_compute") == 0.0


def test_standalone_phase_has_no_residual(armed):
    with fr.phase("compile", program="test/prog"):
        time.sleep(0.01)
    records = fr.load_flight_log(armed)
    assert len(records) == 1
    r = records[0]
    assert r["kind"] == "phase"
    assert r["phases_s"]["compile"] >= 0.01
    # a standalone phase IS its record's wall — no residual bucket
    assert "host_gap" not in r["phases_s"]


def test_flight_log_is_bounded(tmp_path):
    fr.enable(True, log_dir=str(tmp_path), run_id="b", max_records=3)
    try:
        for _ in range(5):
            with fr.record_round("r"):
                pass
        with open(os.path.join(str(tmp_path), "flight.jsonl")) as f:
            assert len(f.readlines()) == 3
    finally:
        fr.reset()


def test_disarmed_is_noop(tmp_path):
    fr.reset()
    with fr.record_round("r") as rec:
        with rec.phase("device_compute"):
            pass
        rec.note(mfu=0.5)
        assert rec.phase_seconds("device_compute") == 0.0
    with fr.phase("compile"):
        pass
    fr.observe_phase("device_compute", 0.1)
    fr.note_transfer("h2d", 100)
    assert not os.path.exists(os.path.join(str(tmp_path), "flight.jsonl"))


def test_phase_histogram_and_transfer_counter(armed):
    with fr.record_round("r", rounds=4) as rec:
        with rec.phase("device_compute"):
            time.sleep(0.004)
    fr.note_transfer("h2d", 1024)
    fr.note_transfer("h2d", 1024)
    text = metrics_mod.render_prometheus()
    assert "fedml_round_phase_seconds" in text
    assert 'phase="device_compute"' in text
    assert 'phase="host_gap"' in text
    assert ('fedml_transfer_bytes_total{direction="h2d"} 2048' in text)


def test_tree_nbytes():
    tree = {"a": np.zeros((4, 4), np.float32), "b": [np.zeros(8, np.int8)]}
    assert fr.tree_nbytes(tree) == 4 * 4 * 4 + 8
    assert fr.tree_nbytes({"x": 3}) == 0   # scalar leaves have no nbytes


# -- cost analysis / measured MFU ---------------------------------------------

def test_program_cost_memory_and_mfu(armed):
    import jax
    import jax.numpy as jnp

    n = 64
    compiled = jax.jit(lambda a, b: a @ b).trace(
        jnp.zeros((n, n), jnp.float32),
        jnp.zeros((n, n), jnp.float32)).lower().compile()
    cost = fr.program_cost(compiled)
    assert cost is not None
    # XLA counts 2*n^3 (+/- fusion noise) for a matmul on CPU
    assert cost["flops"] == pytest.approx(2 * n ** 3, rel=0.2)
    mem = fr.program_memory(compiled)
    assert mem is not None and mem["argument"] >= 2 * n * n * 4

    info = fr.note_program("test/matmul", compiled, chunk_rounds=1)
    assert info is not None and info["flops"] == cost["flops"]
    assert fr.programs()["test/matmul"]["hbm_bytes"] == mem
    # a kind="program" flight record lands in the log
    kinds = [r.get("kind") for r in fr.load_flight_log(armed)]
    assert "program" in kinds

    # a stand-in device whose kind the peak table lists: the CPU these
    # tests run on has no peak, and so no MFU (asserted below)
    from types import SimpleNamespace

    from fedml_tpu.constants import TPU_PEAK_BF16_FLOPS

    v5e = SimpleNamespace(device_kind="TPU v5 lite")
    assert fr.chip_peak_flops(v5e) == TPU_PEAK_BF16_FLOPS["TPU v5 lite"]
    mfu = fr.measured_mfu("test/matmul", flops=cost["flops"],
                          device_seconds=0.001, device=v5e)
    assert 0.0 < mfu == pytest.approx(
        cost["flops"] / 0.001 / fr.chip_peak_flops(v5e))
    assert fr.measured_mfu("test/matmul", 1e9, 0.0, device=v5e) == 0.0
    text = metrics_mod.render_prometheus()
    assert 'fedml_measured_mfu{program="test/matmul"}' in text
    # no assumed peak: an unlisted kind (this CPU) gives no number at all
    assert fr.chip_peak_flops() is None
    assert fr.measured_mfu("test/unknown", 1e9, 0.001) is None
    assert 'program="test/unknown"' not in metrics_mod.render_prometheus()


# -- summarize / report / diff ------------------------------------------------

def _fake_log(dev=0.8, gap=0.2, rounds=10):
    return [{"kind": "fused", "rounds": rounds, "wall_s": dev + gap,
             "phases_s": {"device_compute": dev, "host_gap": gap},
             "overhead_s": 0.001, "program": "p",
             "meta": {"mfu": 0.41}},
            {"kind": "program", "program": "p", "flops": 1e12,
             "hbm_bytes": {"temp": 1 << 20}}]


def test_summarize_schema_and_report():
    s = fr.summarize(_fake_log())
    assert s["records"] == 1 and s["rounds"] == 10
    assert s["coverage"] == pytest.approx(1.0)
    assert s["measured_share"] == pytest.approx(0.8)
    assert s["overhead_frac"] == pytest.approx(0.001)
    assert s["kinds"]["fused"]["phases_s"]["device_compute"] == 0.8
    assert s["programs"]["p"]["last_mfu"] == 0.41
    assert s["programs"]["p"]["flops"] == 1e12
    text = fr.report(_fake_log())
    assert "device_compute" in text and "host_gap" in text
    assert "coverage: 100.0%" in text
    assert "mfu=0.4100" in text
    assert fr.report([]) == "(no flight records)"


def test_diff_renders_per_round_delta():
    a = _fake_log(dev=1.0, gap=0.2, rounds=10)     # 0.10 s/round device
    b = _fake_log(dev=0.5, gap=0.2, rounds=10)     # 0.05 s/round device
    text = fr.diff(a, b, label_a="before", label_b="after")
    assert "before" in text and "after" in text
    assert "device_compute" in text
    assert "0.50" in text      # device ratio after/before
    assert fr.diff([], b) == "(one of the flight logs is empty)"


# -- device-phase spans in the trace timeline ---------------------------------

def test_trace_summarize_renders_flight_spans():
    """Regression on a recorded fixture: `fedml trace summarize` must show
    the flight parent with its device phases nested under it."""
    records = tracing.load_spans(os.path.join(FIXTURES, "flight_trace"))
    assert records, "fixture flight_trace/spans.jsonl missing"
    text = tracing.summarize(records)
    lines = text.splitlines()
    parent = next(i for i, ln in enumerate(lines)
                  if "flight.parrot_fused" in ln)
    child_dc = next(i for i, ln in enumerate(lines)
                    if "phase.device_compute" in ln)
    child_h2d = next(i for i, ln in enumerate(lines)
                     if "phase.h2d" in ln)
    assert child_dc > parent and child_h2d > parent
    # children render INDENTED under the flight parent
    parent_indent = len(lines[parent]) - len(lines[parent].lstrip())
    for i in (child_dc, child_h2d):
        assert (len(lines[i]) - len(lines[i].lstrip())) > parent_indent
    assert "rounds=64" in lines[parent]


def test_live_run_emits_flight_spans(args_factory, tmp_path):
    """The recorder's spans reach the run's spans.jsonl alongside the
    host-side ones, so one timeline shows both."""
    args = fedml_tpu.init(args_factory(
        backend="parrot", comm_round=2, fused_rounds=True,
        frequency_of_the_test=2, flight_recorder=True,
        enable_tracking=True, log_file_dir=str(tmp_path)))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    FedMLRunner(args, device, dataset, bundle).run()
    names = {r.get("name") for r in tracing.load_spans(str(tmp_path))}
    assert "flight.parrot_fused" in names
    assert "phase.device_compute" in names


# -- end-to-end: instrumented parrot path --------------------------------------

def test_parrot_fused_coverage_and_overhead(args_factory, tmp_path):
    """Acceptance: the flight log decomposes >=95% of round wall time into
    named phases, the recorder's self-measured bookkeeping stays under the
    2% CI budget, and the compiled fused scan's cost analysis + MFU are
    captured."""
    args = fedml_tpu.init(args_factory(
        backend="parrot", comm_round=4, fused_rounds=True,
        frequency_of_the_test=4, flight_recorder=True,
        log_file_dir=str(tmp_path)))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    m = FedMLRunner(args, device, dataset, bundle).run()
    assert np.isfinite(m["test_loss"])

    s = fr.summarize(fr.load_flight_log(str(tmp_path)))
    assert s["records"] > 0
    assert s["coverage"] >= 0.95
    assert s["overhead_frac"] < 0.02
    assert "compile" in s["phases_s"]
    assert s["kinds"]["parrot_fused"]["phases_s"]["device_compute"] > 0
    prog = s["programs"].get("parrot/fused_round_scan")
    assert prog is not None and prog.get("flops", 0) > 0
    # this run is on the CPU, which has no peak in the table: no MFU
    assert "last_mfu" not in prog


def test_unfused_parrot_round_records(args_factory, tmp_path):
    args = fedml_tpu.init(args_factory(
        backend="parrot", comm_round=2, frequency_of_the_test=2,
        flight_recorder=True, log_file_dir=str(tmp_path)))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    FedMLRunner(args, device, dataset, bundle).run()
    s = fr.summarize(fr.load_flight_log(str(tmp_path)))
    assert s["kinds"]["parrot_round"]["records"] == 2
    assert s["coverage"] >= 0.95


def test_perf_cli_report_and_diff(args_factory, tmp_path):
    from click.testing import CliRunner

    from fedml_tpu.cli.cli import cli

    args = fedml_tpu.init(args_factory(
        backend="parrot", comm_round=2, fused_rounds=True,
        frequency_of_the_test=2, flight_recorder=True,
        log_file_dir=str(tmp_path)))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    FedMLRunner(args, device, dataset, bundle).run()

    runner = CliRunner()
    r = runner.invoke(cli, ["perf", "report", str(tmp_path)])
    assert r.exit_code == 0, r.output
    assert "device_compute" in r.output and "coverage" in r.output
    r = runner.invoke(cli, ["perf", "report", str(tmp_path), "--json"])
    assert r.exit_code == 0
    s = json.loads(r.output)
    assert s["coverage"] >= 0.95
    r = runner.invoke(cli, ["perf", "diff", str(tmp_path), str(tmp_path)])
    assert r.exit_code == 0 and "ratio" in r.output
    r = runner.invoke(cli, ["perf", "report", str(tmp_path / "missing")])
    assert r.exit_code != 0
