"""Performance flight recorder — round-phase time attribution, measured
MFU, and device telemetry.

The tracing plane (`tracing.py`) answers "what happened when" with
host-side spans; this module answers the question that directs TPU
optimization work: *where does the round wall-clock go*.  Hot paths wrap
their work in a per-round record whose phases carry paired host
timestamps with device-completion sync points, so each round decomposes
into the canonical buckets

    compile         trace/lower/compile (or AOT-cache load) of a program
    h2d             host→device transfer (dataset upload, batch feed)
    device_compute  dispatch→``block_until_ready`` of the jitted program
    comm            cross-silo wire time (broadcast/upload legs)
    host_gap        RESIDUAL: wall − Σ measured phases (host-side python,
                    sampling, logging, dispatch gaps)

``host_gap`` being the residual makes the decomposition sum to 100% of
the record's wall time by construction; the interesting signal is how
small the *measured* share leaves it.  Every record also carries the
recorder's own bookkeeping time (``overhead_s``) so the instrument can
prove it is not perturbing the measurement (CI budget: <2% of wall).

Three consumption surfaces share the data:

* Prometheus — ``fedml_round_phase_seconds{phase=...}`` histograms,
  ``fedml_measured_mfu{program=...}`` gauges, transfer-byte counters and
  per-program HBM gauges, all in the process registry (`metrics.py`);
* a bounded JSONL flight log (``<log_dir>/flight.jsonl``) rendered by
  ``fedml perf report`` / ``fedml perf diff``;
* tracing spans (``flight.<kind>`` / ``phase.<name>``) so `fedml trace
  summarize` shows host and device time side by side in one timeline.

Measured MFU replaces bench.py's hand-derived FLOPs constant: a compiled
program's executed FLOPs come from XLA's own ``cost_analysis()``
(captured by ``note_program`` at AOT-compile time, or re-derived for any
registered perf-lint entrypoint via ``entrypoint_costs``), divided by the
measured device seconds and the detected chip's peak from
`constants.TPU_PEAK_BF16_FLOPS`.

The recorder is opt-in (``flight_recorder: true`` config key or
``FEDML_TPU_FLIGHT_RECORDER=1``) and always-cheap when off: every
entrypoint returns a shared no-op object without allocating.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

from . import metrics as _metrics

#: canonical phase buckets (free-form extras like "d2h" are allowed; the
#: report renders whatever the log contains)
PHASES = ("compile", "h2d", "device_compute", "host_gap", "comm")

#: flight-log records kept per run before dropping (each record is one
#: round/chunk — ~300 bytes — so the default bounds the log near 1 MiB)
DEFAULT_MAX_RECORDS = 4096

_lock = threading.Lock()
_tls = threading.local()
_state: Dict[str, Any] = {
    "enabled": False,
    "log_dir": None,
    "run_id": "0",
    "file": None,
    "written": 0,
    "dropped": 0,
    "max_records": DEFAULT_MAX_RECORDS,
    "programs": {},          # name -> note_program() info dict
}

_PHASE_BUCKETS = (0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                  5.0, 15.0, 60.0, 300.0)


# metric handles are get-or-create per call (one dict hit) so a test's
# REGISTRY.reset() can't leave this module holding unexported handles
def _phase_seconds() -> Any:
    return _metrics.histogram(
        "fedml_round_phase_seconds",
        "Per-round seconds attributed to one flight-recorder phase",
        labels=("phase",), buckets=_PHASE_BUCKETS)


def _measured_mfu() -> Any:
    return _metrics.gauge(
        "fedml_measured_mfu",
        "Measured model FLOPs utilization: XLA cost-analysis FLOPs / "
        "measured device seconds / chip peak", labels=("program",))


def _transfer_bytes() -> Any:
    return _metrics.counter(
        "fedml_transfer_bytes_total",
        "Bytes crossing the host<->device or cross-silo wire boundary",
        labels=("direction",))


def _program_hbm() -> Any:
    return _metrics.gauge(
        "fedml_program_hbm_bytes",
        "Compiled-program HBM footprint from XLA memory_analysis",
        labels=("program", "kind"))


def _overhead_total() -> Any:
    return _metrics.counter(
        "fedml_flight_recorder_overhead_seconds_total",
        "Recorder bookkeeping time, self-measured (CI budget: <2% of "
        "attributed wall)")


# -- lifecycle ---------------------------------------------------------------

def configure(args: Any, log_dir: Optional[str] = None) -> None:
    """Arm (or disarm) the recorder for a run — called by ``mlops.init``.
    Opt-in via the ``flight_recorder`` config key or the
    ``FEDML_TPU_FLIGHT_RECORDER`` env toggle."""
    env = os.environ.get("FEDML_TPU_FLIGHT_RECORDER", "")
    on = bool(getattr(args, "flight_recorder", False)) \
        or env.lower() in ("1", "true", "yes", "on")
    enable(on, log_dir=log_dir,
           run_id=str(getattr(args, "run_id", "0")),
           max_records=int(getattr(args, "flight_max_records", 0)
                           or DEFAULT_MAX_RECORDS))


def enable(on: bool = True, log_dir: Optional[str] = None,
           run_id: str = "0",
           max_records: int = DEFAULT_MAX_RECORDS) -> None:
    """Programmatic arm/disarm (tests, bench).  Re-enabling resets the
    per-run counters but appends to an existing flight log."""
    reset()
    with _lock:
        _state["enabled"] = bool(on)
        _state["log_dir"] = log_dir
        _state["run_id"] = run_id
        _state["max_records"] = int(max_records)


def reset() -> None:
    """Close the flight log and disarm — safe to call repeatedly."""
    with _lock:
        f = _state["file"]
        if f is not None:
            try:
                f.flush()
                f.close()
            except Exception:  # noqa: BLE001 — a wedged fd can't block reset
                pass
        _state.update(enabled=False, file=None, written=0, dropped=0,
                      programs={})


def enabled() -> bool:
    return _state["enabled"]


def log_path() -> Optional[str]:
    d = _state["log_dir"]
    return os.path.join(d, "flight.jsonl") if d else None


def _write(record: Dict[str, Any]) -> None:
    """Bounded append — past ``max_records`` the record is counted as
    dropped instead of growing the log without limit."""
    if not _state["enabled"]:
        return
    record = dict(record, ts=time.time(), run_id=_state["run_id"])
    with _lock:
        if _state["written"] >= _state["max_records"]:
            _state["dropped"] += 1
            return
        path = log_path()
        if path is None:
            return
        f = _state["file"]
        if f is None or f.closed:
            try:
                os.makedirs(_state["log_dir"], exist_ok=True)
                # one-time lazy open; _lock IS the appender's serializer
                f = _state["file"] = open(path, "a")  # fedml: noqa[CONC004]
            except OSError:
                return            # unwritable log dir degrades, never aborts
        f.write(json.dumps(record, default=str) + "\n")
        f.flush()
        _state["written"] += 1


# -- phase / round primitives ------------------------------------------------

class _Null:
    """Shared no-op stand-in for every context manager when disarmed."""

    __slots__ = ()

    def __enter__(self) -> "_Null":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def phase(self, name: str, program: Optional[str] = None) -> "_Null":
        return self

    def note(self, **kv: Any) -> None:
        pass

    def phase_seconds(self, name: str) -> float:
        return 0.0


_NULL = _Null()


class _PhaseTimer:
    """One measured phase inside a RoundRecord.  Span open/close and
    bucket bookkeeping are timed separately and charged to the record's
    ``overhead_s``, never to the phase itself."""

    def __init__(self, record: "RoundRecord", name: str,
                 program: Optional[str]) -> None:
        self._record = record
        self._name = name
        self._program = program

    def __enter__(self) -> "_PhaseTimer":
        b0 = time.perf_counter()
        self._span = None
        try:
            from . import tracing

            attrs = {"phase": self._name}
            if self._program:
                attrs["program"] = self._program
            self._span = tracing.Span(f"phase.{self._name}", attrs=attrs)
            self._span.__enter__()
        except Exception:  # noqa: BLE001 — recording must never kill work
            self._span = None
        self._t0 = time.perf_counter()
        self._enter_overhead = self._t0 - b0
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        dur = t1 - self._t0
        rec = self._record
        rec.phases[self._name] = rec.phases.get(self._name, 0.0) + dur
        if self._span is not None:
            try:
                self._span.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001
                pass
        rec.overhead_s += self._enter_overhead + (time.perf_counter() - t1)
        return False


class RoundRecord:
    """One attributed unit of work (a round, a fused chunk, one local
    update).  Phases accumulate measured seconds; on exit the residual
    becomes ``host_gap`` so the decomposition covers the whole wall."""

    def __init__(self, kind: str, rounds: int = 1,
                 program: Optional[str] = None, residual: bool = True,
                 **meta: Any) -> None:
        self.kind = kind
        self.rounds = max(1, int(rounds))
        self.program = program
        self.meta = dict(meta)
        self.phases: Dict[str, float] = {}
        self.overhead_s = 0.0
        #: standalone phases ARE their record's wall — no residual bucket
        self._residual = residual

    def phase(self, name: str, program: Optional[str] = None) -> _PhaseTimer:
        return _PhaseTimer(self, name, program or self.program)

    def note(self, **kv: Any) -> None:
        self.meta.update(kv)

    def phase_seconds(self, name: str) -> float:
        return self.phases.get(name, 0.0)

    def __enter__(self) -> "RoundRecord":
        b0 = time.perf_counter()
        stack = getattr(_tls, "records", None)
        if stack is None:
            stack = _tls.records = []
        stack.append(self)
        self._span = None
        try:
            from . import tracing

            attrs = {"rounds": self.rounds}
            if self.program:
                attrs["program"] = self.program
            self._span = tracing.Span(f"flight.{self.kind}", attrs=attrs)
            self._span.__enter__()
        except Exception:  # noqa: BLE001
            self._span = None
        self._t0 = time.perf_counter()
        self.overhead_s += self._t0 - b0
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        wall = t1 - self._t0
        stack = getattr(_tls, "records", [])
        if stack and stack[-1] is self:
            stack.pop()
        if self._residual:
            measured = sum(self.phases.values())
            self.phases["host_gap"] = max(0.0, wall - measured)
        hist = _phase_seconds()
        for name, secs in self.phases.items():
            hist.labels(phase=name).observe(secs / self.rounds)
        record = {
            "kind": self.kind,
            "rounds": self.rounds,
            "wall_s": wall,
            "phases_s": {k: round(v, 6) for k, v in self.phases.items()},
            "overhead_s": round(self.overhead_s, 6),
        }
        if self.program:
            record["program"] = self.program
        if self.meta:
            record["meta"] = self.meta
        _write(record)
        if self._span is not None:
            try:
                self._span.__exit__(exc_type, exc, tb)
            except Exception:  # noqa: BLE001
                pass
        self.overhead_s += time.perf_counter() - t1
        _overhead_total().inc(self.overhead_s)
        return False


def record_round(kind: str, rounds: int = 1,
                 program: Optional[str] = None, **meta: Any):
    """``with record_round("parrot_fused", rounds=64, ...) as fr:`` —
    no-op singleton when disarmed."""
    if not _state["enabled"]:
        return _NULL
    return RoundRecord(kind, rounds=rounds, program=program, **meta)


class _StandalonePhase:
    """A phase with no enclosing round (e.g. the one-off compile): still
    observed into the histogram and written as a ``kind="phase"`` flight
    record so the report can account for it."""

    def __init__(self, name: str, program: Optional[str]) -> None:
        self._rec = RoundRecord("phase", rounds=1, program=program,
                                residual=False)
        self._timer = self._rec.phase(name)

    def __enter__(self) -> "_StandalonePhase":
        self._rec.__enter__()
        self._timer.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._timer.__exit__(exc_type, exc, tb)
        self._rec.__exit__(exc_type, exc, tb)
        return False


def phase(name: str, program: Optional[str] = None):
    """Scoped phase: attributes to the innermost active ``record_round``
    on this thread, or stands alone as its own flight record."""
    if not _state["enabled"]:
        return _NULL
    stack = getattr(_tls, "records", None)
    if stack:
        return stack[-1].phase(name, program)
    return _StandalonePhase(name, program)


def observe_phase(name: str, seconds: float,
                  program: Optional[str] = None) -> None:
    """Histogram-only attribution for already-measured durations on very
    hot paths (e.g. the serving decode step — per-token flight-log writes
    would be the overhead the recorder exists to catch)."""
    if not _state["enabled"]:
        return
    _phase_seconds().labels(phase=name).observe(float(seconds))


def note_transfer(direction: str, nbytes: int) -> None:
    """Count bytes crossing the host<->device (``h2d``/``d2h``) or wire
    (``comm``) boundary."""
    if not _state["enabled"]:
        return
    _transfer_bytes().labels(direction=direction).inc(float(max(0, nbytes)))


def tree_nbytes(tree: Any) -> int:
    """Total bytes of a pytree of arrays (0 for leaves without nbytes)."""
    try:
        import jax

        return int(sum(int(getattr(leaf, "nbytes", 0) or 0)
                       for leaf in jax.tree_util.tree_leaves(tree)))
    except Exception:  # noqa: BLE001
        return 0


# -- measured MFU + per-program telemetry ------------------------------------

def chip_peak_flops(device: Any = None) -> Optional[float]:
    """Peak bf16 FLOP/s of ``device`` (default: the first attached one)
    from the single-source table in `constants`; None for a kind the
    table does not list (a CPU, an unknown chip) — never an assumed peak."""
    from ...constants import TPU_PEAK_BF16_FLOPS

    if device is None:
        import jax

        device = jax.devices()[0]
    return TPU_PEAK_BF16_FLOPS.get(str(getattr(device, "device_kind", "")))


def program_cost(compiled: Any) -> Optional[Dict[str, float]]:
    """Executed-FLOPs (and bytes-accessed, when reported) of a compiled
    program from XLA's own ``cost_analysis()`` — None when the backend
    doesn't report (e.g. some remote-plugin paths)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not ca:
            return None
        out: Dict[str, float] = {}
        if ca.get("flops"):
            out["flops"] = float(ca["flops"])
        if ca.get("bytes accessed"):
            out["bytes_accessed"] = float(ca["bytes accessed"])
        return out or None
    except Exception:  # noqa: BLE001
        return None


def program_memory(compiled: Any) -> Optional[Dict[str, int]]:
    """HBM footprint of a compiled program from ``memory_analysis()``."""
    try:
        ma = compiled.memory_analysis()
        if isinstance(ma, (list, tuple)):
            ma = ma[0] if ma else None
        if ma is None:
            return None
        out = {}
        for kind, attr in (("argument", "argument_size_in_bytes"),
                           ("output", "output_size_in_bytes"),
                           ("temp", "temp_size_in_bytes"),
                           ("generated_code", "generated_code_size_in_bytes")):
            v = getattr(ma, attr, None)
            if v is not None:
                out[kind] = int(v)
        return out or None
    except Exception:  # noqa: BLE001
        return None


def note_program(name: str, compiled: Any,
                 **meta: Any) -> Optional[Dict[str, Any]]:
    """Capture a compiled program's analytic cost + HBM footprint at AOT
    time: sets the per-program gauges, writes a ``kind="program"`` flight
    record, and returns the info dict (None when XLA reports nothing).
    Runs even when the recorder is disarmed — the caller (bench) may want
    the numbers without the flight log."""
    info: Dict[str, Any] = {"program": name}
    cost = program_cost(compiled)
    if cost:
        info.update(cost)
    mem = program_memory(compiled)
    if mem:
        info["hbm_bytes"] = mem
        for kind, v in mem.items():
            _program_hbm().labels(program=name, kind=kind).set(float(v))
    if meta:
        info.update(meta)
    if len(info) <= 1:
        return None
    with _lock:
        _state["programs"][name] = info
    _write(dict(info, kind="program"))
    return info


def measured_mfu(program: str, flops: float, device_seconds: float,
                 device: Any = None) -> Optional[float]:
    """MFU from measured device time: ``flops / seconds / chip_peak``.
    Sets the per-program gauge and returns the value; None (and no gauge)
    where the device has no known peak."""
    peak = chip_peak_flops(device)
    if peak is None:
        return None
    if device_seconds <= 0:
        return 0.0
    mfu = float(flops) / float(device_seconds) / peak
    _measured_mfu().labels(program=program).set(mfu)
    return mfu


def entrypoint_costs(names: Optional[Iterable[str]] = None,
                     root: Optional[str] = None) -> Dict[str, Dict[str, Any]]:
    """Per-entrypoint analytic FLOPs + HBM for the perf-lint registry's
    programs (PR-7's `EntrypointRegistry`): trace+lower+compile each
    registered entry abstractly and read its cost/memory analysis.
    Expensive (compiles) — CLI/bench surface, never a hot path."""
    from ...analysis.perf.registry import load_default_entrypoints
    from ...analysis.perf.tracing import TracedEntrypoint

    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
    registry = load_default_entrypoints()
    want = set(names) if names else None
    out: Dict[str, Dict[str, Any]] = {}
    for spec in registry.entries():
        if want is not None and spec.name not in want:
            continue
        try:
            traced = TracedEntrypoint(spec, root)
            info: Dict[str, Any] = {}
            ca = traced.cost_analysis()
            if ca and ca.get("flops"):
                info["flops"] = float(ca["flops"])
            ma = traced.memory_analysis()
            if ma is not None:
                mem = {}
                for kind, attr in (
                        ("argument", "argument_size_in_bytes"),
                        ("output", "output_size_in_bytes"),
                        ("temp", "temp_size_in_bytes"),
                        ("generated_code", "generated_code_size_in_bytes")):
                    v = getattr(ma, attr, None)
                    if v is not None:
                        mem[kind] = int(v)
                if mem:
                    info["hbm_bytes"] = mem
            out[spec.name] = info or {"error": "no cost/memory analysis"}
        except Exception as e:  # noqa: BLE001 — one bad entry can't stop the scan
            out[spec.name] = {"error": str(e)}
    return out


def programs() -> Dict[str, Dict[str, Any]]:
    """Programs captured by ``note_program`` this run."""
    with _lock:
        return dict(_state["programs"])


# -- flight-log analysis (the `fedml perf report` / `diff` backend) ----------

def locate_flight_log(path: str) -> Optional[str]:
    """Resolve a flight-log path from a file OR a run/log directory.
    A directory without a direct ``flight.jsonl`` is searched one and
    two levels down (``.bench_flight/<ts>/flight.jsonl``,
    ``logs/<job>/<run>/flight.jsonl``), newest mtime winning."""
    if not os.path.isdir(path):
        return path if os.path.exists(path) else None
    direct = os.path.join(path, "flight.jsonl")
    if os.path.exists(direct):
        return direct
    import glob

    candidates = (glob.glob(os.path.join(path, "*", "flight.jsonl"))
                  + glob.glob(os.path.join(path, "*", "*", "flight.jsonl")))
    if not candidates:
        return None
    return max(candidates, key=os.path.getmtime)


def load_flight_log(path: str) -> List[Dict[str, Any]]:
    """Parse a flight log — accepts the jsonl file or a run log dir
    (auto-located via ``locate_flight_log``)."""
    located = locate_flight_log(path)
    if located is None:
        return []
    path = located
    if not os.path.exists(path):
        return []
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                continue
    return records


def summarize(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Aggregate flight records into the report schema: per-phase seconds
    and shares, coverage (named-phase share of wall — 1.0 by construction
    when every record came from ``record_round``), measured (non-residual)
    share, recorder overhead fraction, per-kind and per-program detail."""
    phase_s: Dict[str, float] = {}
    kinds: Dict[str, Dict[str, Any]] = {}
    progs: Dict[str, Dict[str, Any]] = {}
    wall = 0.0
    rounds = 0
    overhead = 0.0
    n = 0
    for r in records:
        if r.get("kind") == "program":
            # merge, don't assign — a round record's mfu note may already
            # have seeded this program's entry (log order isn't fixed)
            progs.setdefault(str(r.get("program")), {}).update(
                {k: v for k, v in r.items()
                 if k not in ("kind", "ts", "run_id")})
            continue
        phases = r.get("phases_s")
        if not isinstance(phases, dict):
            continue
        n += 1
        w = float(r.get("wall_s", 0.0))
        wall += w
        rounds += int(r.get("rounds", 1))
        overhead += float(r.get("overhead_s", 0.0))
        k = kinds.setdefault(str(r.get("kind")), {
            "records": 0, "rounds": 0, "wall_s": 0.0, "phases_s": {}})
        k["records"] += 1
        k["rounds"] += int(r.get("rounds", 1))
        k["wall_s"] += w
        for name, secs in phases.items():
            phase_s[name] = phase_s.get(name, 0.0) + float(secs)
            k["phases_s"][name] = k["phases_s"].get(name, 0.0) + float(secs)
        mfu = (r.get("meta") or {}).get("mfu")
        if mfu is not None and r.get("program"):
            p = progs.setdefault(str(r["program"]), {})
            p["last_mfu"] = float(mfu)
    attributed = sum(phase_s.values())
    measured = attributed - phase_s.get("host_gap", 0.0)
    return {
        "records": n,
        "rounds": rounds,
        "wall_s": round(wall, 6),
        "phases_s": {k: round(v, 6) for k, v in sorted(
            phase_s.items(), key=lambda kv: -kv[1])},
        "coverage": round(attributed / wall, 4) if wall > 0 else 0.0,
        "measured_share": round(measured / wall, 4) if wall > 0 else 0.0,
        "overhead_s": round(overhead, 6),
        "overhead_frac": round(overhead / wall, 6) if wall > 0 else 0.0,
        "kinds": {k: {"records": v["records"], "rounds": v["rounds"],
                      "wall_s": round(v["wall_s"], 6),
                      "phases_s": {p: round(s, 6)
                                   for p, s in v["phases_s"].items()}}
                  for k, v in kinds.items()},
        "programs": progs,
    }


def report(records: List[Dict[str, Any]]) -> str:
    """Human phase-breakdown table with top time sinks."""
    s = summarize(records)
    if not s["records"]:
        return "(no flight records)"
    out = [f"flight report: {s['records']} records, {s['rounds']} rounds, "
           f"wall {s['wall_s']:.3f}s"]
    out.append(f"{'phase':<16}{'seconds':>10}{'share':>8}{'per-round':>12}")
    for name, secs in s["phases_s"].items():
        share = secs / s["wall_s"] if s["wall_s"] else 0.0
        out.append(f"{name:<16}{secs:>10.3f}{share:>7.1%}"
                   f"{secs / max(1, s['rounds']):>12.5f}")
    out.append(f"coverage: {s['coverage']:.1%} of wall in named phases "
               f"({s['measured_share']:.1%} measured, rest residual "
               f"host_gap)")
    out.append(f"recorder overhead: {s['overhead_s']:.4f}s "
               f"({s['overhead_frac']:.2%} of wall)")
    sinks = [(k, v["wall_s"]) for k, v in s["kinds"].items()]
    sinks.sort(key=lambda kv: -kv[1])
    for k, w in sinks[:5]:
        kv = s["kinds"][k]
        top = max(kv["phases_s"].items(), key=lambda p: p[1],
                  default=("-", 0.0))
        out.append(f"  sink {k}: {w:.3f}s over {kv['rounds']} rounds "
                   f"(dominant: {top[0]} {top[1]:.3f}s)")
    for name, info in s["programs"].items():
        bits = []
        if info.get("flops"):
            bits.append(f"flops={info['flops']:.3e}")
        if info.get("last_mfu") is not None:
            bits.append(f"mfu={info['last_mfu']:.4f}")
        hbm = info.get("hbm_bytes") or {}
        if hbm:
            bits.append("hbm(temp)=%.1fMiB" % (hbm.get("temp", 0) / 2**20))
        if bits:
            out.append(f"  program {name}: {' '.join(bits)}")
    return "\n".join(out)


def diff(a: List[Dict[str, Any]], b: List[Dict[str, Any]],
         label_a: str = "A", label_b: str = "B") -> str:
    """Per-phase per-round delta between two flight logs (e.g. two BENCH
    runs) — the regression-hunting view."""
    sa, sb = summarize(a), summarize(b)
    if not sa["records"] or not sb["records"]:
        return "(one of the flight logs is empty)"

    def per_round(s: Dict[str, Any], name: str) -> float:
        return s["phases_s"].get(name, 0.0) / max(1, s["rounds"])

    names = sorted(set(sa["phases_s"]) | set(sb["phases_s"]),
                   key=lambda nm: -(per_round(sb, nm)))
    out = [f"flight diff ({label_a}: {sa['rounds']} rounds, "
           f"{label_b}: {sb['rounds']} rounds; per-round seconds)"]
    out.append(f"{'phase':<16}{label_a:>12}{label_b:>12}{'delta':>12}"
               f"{'ratio':>8}")
    for name in names:
        va, vb = per_round(sa, name), per_round(sb, name)
        ratio = (vb / va) if va > 0 else float("inf") if vb > 0 else 1.0
        out.append(f"{name:<16}{va:>12.5f}{vb:>12.5f}{vb - va:>+12.5f}"
                   f"{ratio:>8.2f}")
    wa = sa["wall_s"] / max(1, sa["rounds"])
    wb = sb["wall_s"] / max(1, sb["rounds"])
    out.append(f"{'wall':<16}{wa:>12.5f}{wb:>12.5f}{wb - wa:>+12.5f}"
               f"{(wb / wa if wa else 1.0):>8.2f}")
    return "\n".join(out)
