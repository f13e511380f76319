"""One run of one cell: resolve its files by name, set up, measure, check,
and build the result line.  Knows no cell, configuration or metric by name."""

import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

from . import device as device_mod
from . import xplane
from .record import Record, now

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "chipbench")


class Run:
    """What a metric reader is given."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, bench: Optional[Dict] = None) -> Dict[str, Any]:
    """BENCHMARK.json's entry of a cell, the cell's own file, and its
    configuration's file."""
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    cell = load_json(os.path.join(HERE, "workloads", workload + ".json"))
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    return {"bench": bench, "entry": entry, "cell": cell, "config": config}


def open_cell(workload: str) -> Dict[str, Any]:
    """``resolve`` a cell, refuse unless its chips are attached, and put the
    compilation cache where the program's own rule puts it: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.  Adds
    the device, the cache directory, the configuration's reference module and
    the cell's plane module to what ``resolve`` found."""
    found = resolve(workload)
    found["device"] = device_mod.require(int(found["entry"]["chips"]))
    from fedml_tpu.utils.compile_cache import configure_compile_cache

    found["cache_dir"] = configure_compile_cache()
    found["reference"] = importlib.import_module(
        "chipbench.reference." + found["config"]["reference"])
    found["plane"] = importlib.import_module(
        "chipbench.planes." + found["cell"]["plane"])
    return found


def metrics_of(bench: Dict, workload: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    that list it, and those that list no cell and belong to an end-to-end
    metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def reader_of(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(cell: Dict, config: Dict, metrics: List[Dict], seed: int,
             seconds: float, trace: bool, device: Dict[str, Any],
             t_process: float, out_dir: str) -> Dict[str, Any]:
    """Drive one cell once.  ``device`` is what ``device.require`` returned
    (tests hand in a stand-in); ``t_process`` is the process's start on
    ``record.now``'s clock, which set-up is counted from."""
    reference = importlib.import_module(
        "chipbench.reference." + config["reference"])
    plane_mod = importlib.import_module("chipbench.planes." + cell["plane"])
    rec = Record(os.path.join(out_dir, "trace") if trace else None,
                 cell.get("trace"))
    plane = plane_mod.Plane(cell, config, reference, seed, rec)
    compiles = device_mod.CompileCounter()

    plane.setup()
    setup_s = now() - t_process
    compiles.open()
    try:
        plane.window(float(seconds))
    finally:
        built = compiles.close()
        rec.trace_stop()
    plane.finish()
    peak = device_mod.memory_peak_bytes() if device["platform"] == "tpu" else 0

    tr = None
    if trace:
        path = xplane.newest_xplane(rec.trace_dir)
        if path is None or rec.traced is None:
            raise RuntimeError("the traced run left no trace")
        tr = xplane.load(path)
    rows = [{"name": "programs_built_in_window", "value": float(built),
             "limit": 0.0, "ok": built == 0}] + plane.check()
    for row in rows:
        rec.say("compared", **row)

    run = Run(rec=rec, plane=plane, cell=cell, config=config, device=device,
              peaks=device["peaks"], trace=tr, setup_s=setup_s,
              seconds=float(seconds))
    values = {}
    for m in metrics:
        v = reader_of(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": peak}
    result = {"correct": all(r["ok"] for r in rows),
              "attempted": int(plane.attempted), "failed": int(plane.failed),
              "metrics": values, "device": dev}
    if tr is not None:
        summary = xplane.summarize(tr)
        dev["busy_s"] = summary.pop("busy_s")
        dev["window_s"] = rec.traced["t1"] - rec.traced["t0"]
        result["breakdown"] = summary
    return result
