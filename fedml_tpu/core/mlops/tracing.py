"""Round-scoped distributed tracing — one stitched trace per federated run.

Capability parity: reference `MLOpsProfilerEvent` emits flat started/ended
events with no identity, so a round's server wait, N client trainings and
the aggregation can never be re-joined into one timeline.  This module adds
OpenTelemetry-shaped identity on top of the existing mlops JSONL pipeline:

* every span carries ``trace_id`` / ``span_id`` / ``parent_span_id``;
* the current span is tracked per-thread, so nested ``with span(...)``
  blocks parent automatically;
* ``inject()`` / ``extract()`` move a context across process (or thread)
  boundaries as a plain dict — the cross-silo managers put it on the wire
  as the ``MyMessage.MSG_ARG_KEY_TRACE_CTX`` message arg, which is how one
  round's spans from server, clients and aggregator end up sharing a single
  trace id;
* span ends are emitted through ``mlops._emit("spans", ...)`` so every
  registered remote sink ships them on, and durations feed the
  ``fedml_span_seconds`` histogram in `metrics.py`;
* when `jax.profiler` is importable and annotations are enabled, every span
  also opens a ``jax.profiler.TraceAnnotation`` so host-side spans line up
  with XLA events in a captured profiler trace;
* `phase()` is the light tier for hot loops (an engine iteration, a
  ``train()`` call): a clock pair, the same annotation and the same
  histogram, and none of the identity above;
* `scope()` is the device-side tier: a name on the instructions traced under
  it, which a profiler trace's own HLO carries back.

Everything is stdlib; JAX involvement is strictly optional.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import metrics as _metrics

_tls = threading.local()

#: jax.profiler.TraceAnnotation wrapping: "auto" opens annotations whenever
#: jax is importable (they are ~free when no profiler trace is being
#: captured); "1"/"0" force on/off.  Toggled via enable_jax_annotations().
_jax_annotations = os.environ.get("FEDML_TPU_JAX_TRACE_ANNOTATIONS", "auto")

#: spans.jsonl sink cap, the flight log's `flight_max_records` idiom
#: applied here: spans past the cap still observe the duration histogram
#: (and nest/propagate normally) but stop being written to the file
DEFAULT_MAX_SPANS = 16384

_sink_lock = threading.Lock()
_sink = {"written": 0, "dropped": 0, "max_spans": DEFAULT_MAX_SPANS}


def configure(args: Any) -> None:
    """Per-run sink bounds (``trace_max_spans`` config key) — called by
    ``mlops.init``; 0/absent keeps the module default."""
    reset_sink(max_spans=int(getattr(args, "trace_max_spans", 0)
                             or DEFAULT_MAX_SPANS))


def reset_sink(max_spans: int = DEFAULT_MAX_SPANS) -> None:
    with _sink_lock:
        _sink.update(written=0, dropped=0, max_spans=int(max_spans))


def dropped_spans() -> int:
    return int(_sink["dropped"])


def _dropped_total() -> Any:
    return _metrics.counter(
        "fedml_trace_dropped_spans_total",
        "Span records dropped past the trace_max_spans sink cap")


def _sink_admit() -> bool:
    """One span's write budget check — False past the cap."""
    with _sink_lock:
        if _sink["written"] >= _sink["max_spans"]:
            _sink["dropped"] += 1
            _dropped_total().inc()
            return False
        _sink["written"] += 1
        return True


def _span_seconds() -> Any:
    # get-or-create each time (one dict hit) so a test's REGISTRY.reset()
    # can't leave this module holding an unexported handle
    return _metrics.histogram(
        "fedml_span_seconds", "Duration of tracing spans by span name",
        labels=("name",),
        buckets=(0.001, 0.005, 0.02, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0))


def enable_jax_annotations(on: bool) -> None:
    global _jax_annotations
    _jax_annotations = "1" if on else "0"


#: ``jax.profiler.TraceAnnotation``, looked up at the first span: the class,
#: or False where JAX cannot be imported
_annotation_cls: Any = None


def _open_annotation(name: str) -> Any:
    """An entered ``TraceAnnotation`` (a host event on the profiler's own
    clock whenever a profiler session is open, next to nothing when none
    is), or None: annotations off, JAX absent, or the profiler unusable."""
    global _annotation_cls
    if _jax_annotations == "0":
        return None
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation

            _annotation_cls = TraceAnnotation
        except ImportError:
            _annotation_cls = False
    if not _annotation_cls:
        return None
    try:
        ann = _annotation_cls(name)
        ann.__enter__()
        return ann
    except Exception:  # noqa: BLE001 — profiler unusable
        return None


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


class TraceContext:
    """Immutable (trace_id, span_id) pair — the propagatable identity."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str) -> None:
        self.trace_id = trace_id
        self.span_id = span_id

    def to_wire(self) -> Dict[str, str]:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def __repr__(self) -> str:
        return f"TraceContext({self.trace_id[:8]}…/{self.span_id[:8]}…)"


def inject(ctx: Optional["TraceContext"] = None) -> Optional[Dict[str, str]]:
    """Serialize ``ctx`` (default: the current span's context) for a
    message arg; None when there is nothing to propagate."""
    ctx = ctx or current()
    return ctx.to_wire() if ctx is not None else None


def extract(wire: Any) -> Optional[TraceContext]:
    """Rebuild a TraceContext from a message arg produced by `inject`.
    Tolerant of None/garbage — remote peers may predate tracing."""
    if not isinstance(wire, dict):
        return None
    tid, sid = wire.get("trace_id"), wire.get("span_id")
    if not tid or not sid:
        return None
    return TraceContext(str(tid), str(sid))


def _stack() -> List[TraceContext]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current() -> Optional[TraceContext]:
    """The innermost active context on THIS thread (span or use_ctx)."""
    st = _stack()
    return st[-1] if st else None


class _CtxAttachment:
    """Context manager attaching a remote parent context to this thread —
    the receive-side half of cross-process propagation."""

    def __init__(self, ctx: Optional[TraceContext]) -> None:
        self._ctx = ctx

    def __enter__(self) -> Optional[TraceContext]:
        if self._ctx is not None:
            _stack().append(self._ctx)
        return self._ctx

    def __exit__(self, *exc) -> bool:
        if self._ctx is not None:
            st = _stack()
            if st and st[-1] is self._ctx:
                st.pop()
        return False


def use_ctx(ctx: Optional[TraceContext]) -> _CtxAttachment:
    """``with use_ctx(extract(msg.get(TRACE_CTX))): ...`` — spans opened in
    the body become children of the remote span.  No-op on None."""
    return _CtxAttachment(ctx)


class Span:
    """A started span.  Use the `span()` context manager for scoped spans;
    `start_span()`/`.end()` for spans held open across handler callbacks
    (e.g. the server's per-round parent)."""

    def __init__(self, name: str, parent: Optional[TraceContext] = None,
                 attrs: Optional[Dict[str, Any]] = None,
                 annotate: bool = True) -> None:
        parent = parent or current()
        trace_id = parent.trace_id if parent else _new_id(16)
        self.name = name
        self.ctx = TraceContext(trace_id, _new_id(8))
        self.parent_span_id = parent.span_id if parent else None
        self.attrs: Dict[str, Any] = dict(attrs or {})
        self.status = "ok"
        self.t_start = time.time()
        self._t0 = time.monotonic()
        self._ended = False
        # jax TraceAnnotation (TraceMe) is same-thread scoped; only scoped
        # `with span(...)` use can guarantee that, so manually-ended spans
        # (which e.g. a timer thread may close) pass annotate=False
        self._annotation = _open_annotation(name) if annotate else None

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def end(self, status: Optional[str] = None) -> float:
        """Close the span, emit its record, return the duration (s).
        Idempotent — a double end keeps the first record."""
        if self._ended:
            return 0.0
        self._ended = True
        dur = time.monotonic() - self._t0
        if self._annotation is not None:
            try:
                self._annotation.__exit__(None, None, None)
            except Exception:  # noqa: BLE001
                pass
        if status:
            self.status = status
        _span_seconds().labels(name=self.name).observe(dur)
        if not _sink_admit():
            return dur
        from . import _emit

        _emit("spans", {
            "name": self.name,
            "trace_id": self.ctx.trace_id,
            "span_id": self.ctx.span_id,
            "parent_span_id": self.parent_span_id,
            "t_start": self.t_start,
            "dur_s": dur,
            "status": self.status,
            "attrs": self.attrs,
        })
        return dur

    # -- scoped use ----------------------------------------------------------
    def __enter__(self) -> "Span":
        _stack().append(self.ctx)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        st = _stack()
        if st and st[-1] is self.ctx:
            st.pop()
        self.end("error" if exc_type is not None else None)
        return False


def start_span(name: str, parent: Optional[TraceContext] = None,
               **attrs: Any) -> Span:
    """Start a manually-ended span (NOT pushed on the thread-local stack —
    pass ``parent=span.ctx`` or wrap with `use_ctx` to nest under it).
    No jax annotation: `.end()` may legitimately run on another thread."""
    return Span(name, parent=parent, attrs=attrs, annotate=False)


def span(name: str, parent: Optional[TraceContext] = None,
         **attrs: Any) -> Span:
    """``with span("train_round", round=7): ...`` — child of the current
    thread-local span (or of ``parent``), auto-ended on exit."""
    return Span(name, parent=parent, attrs=attrs)


# -- the light tier: hot loops ------------------------------------------------

_phase_children: Dict[str, Any] = {}
_phase_generation = -1


def _phase_child(name: str) -> Any:
    """``fedml_span_seconds{name}``'s child, cached per name; the cache is
    dropped when the registry has been reset since (test isolation), so it
    never holds an unexported handle."""
    global _phase_generation
    gen = _metrics.REGISTRY.generation
    if gen != _phase_generation:
        _phase_children.clear()
        _phase_generation = gen
    child = _phase_children.get(name)
    if child is None:
        child = _phase_children[name] = _span_seconds().labels(name=name)
    return child


class Phase:
    """One timed stretch of a hot loop.  Use `phase()`."""

    __slots__ = ("name", "dur_s", "_t0", "_annotation")

    def __init__(self, name: str) -> None:
        self.name = name
        #: seconds on ``time.perf_counter``, once exited
        self.dur_s = 0.0

    def __enter__(self) -> "Phase":
        self._annotation = _open_annotation(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_s = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        _phase_child(self.name).observe(self.dur_s)
        return False


def phase(name: str) -> Phase:
    """``with phase("fedml.serve.fetch") as ph: ...`` then ``ph.dur_s``.

    For a loop that turns many times a second, where `span()` is too heavy:
    no ids, no thread-local stack, no ``spans.jsonl`` record and no sink
    budget.  It times itself, shows in a profiler trace under ``name`` while
    a profiler session is open (the annotation is thread-scoped: enter and
    exit on one thread), and observes ``fedml_span_seconds{name}``.  Use
    `span()` for what belongs to a round's stitched trace, and this for
    what the profiler or a histogram is to see.  ``name`` is a fixed string,
    or has one suffix from a small set (a dispatch length, a prefill
    bucket): readers match names exactly and every name is a label."""
    return Phase(name)


def scope(name: str) -> Any:
    """``with scope("attn"): ...`` (or ``@scope("norm")`` on a function)
    around JAX code as it is traced: the device-side tier.  It is
    ``jax.named_scope("fedml." + name)`` and nothing else: no clock, no
    histogram, nothing at run time.  The name reaches the ``op_name`` of
    every instruction traced under it, and so the optimized HLO a profiler
    trace carries (docs/OBSERVABILITY.md, "Device time by scope").  Metadata
    only: the compiled program and its compilation-cache key do not change."""
    import jax

    return jax.named_scope("fedml." + name)


#: `note_iteration` speaks up for an iteration longer than this many
#: seconds that is also this many times the one before it
SLOW_ITERATION_S = 1.0
SLOW_ITERATION_RATIO = 4.0


def note_iteration(what: str, total_s: float, prev_s: Optional[float],
                   parts: Sequence[Tuple[str, float]]) -> None:
    """One warning line when an iteration of a hot loop stood still: which
    of its phases held the time says whether the process waited for the
    device or stalled in host code.  ``prev_s`` is the iteration before it
    (None: there was none, nothing is logged)."""
    if (prev_s is not None and total_s > SLOW_ITERATION_S
            and total_s > SLOW_ITERATION_RATIO * prev_s):
        logging.warning("%s took %.2f s: %s", what, total_s, " ".join(
            f"{name} {secs:.2f}" for name, secs in parts))


# -- trace summarization (the `fedml trace summarize` renderer) --------------

def summarize(records: List[Dict[str, Any]],
              trace_id: Optional[str] = None) -> str:
    """Render span records (parsed spans.jsonl lines) as an indented
    per-round timeline.  ``trace_id`` narrows to one trace; default is the
    trace with the most spans."""
    spans = [r for r in records if r.get("span_id")]
    if not spans:
        return "(no spans)"
    by_trace: Dict[str, List[Dict[str, Any]]] = {}
    for r in spans:
        by_trace.setdefault(str(r.get("trace_id")), []).append(r)
    if trace_id is None:
        trace_id = max(by_trace, key=lambda t: len(by_trace[t]))
    chosen = by_trace.get(trace_id, [])
    if not chosen:
        return f"(no spans for trace {trace_id})"
    children: Dict[Optional[str], List[Dict[str, Any]]] = {}
    ids = {r["span_id"] for r in chosen}
    for r in chosen:
        parent = r.get("parent_span_id")
        children.setdefault(parent if parent in ids else None, []).append(r)
    for v in children.values():
        v.sort(key=lambda r: r.get("t_start", 0.0))
    t0 = min(r.get("t_start", 0.0) for r in chosen)
    out = [f"trace {trace_id}  ({len(chosen)} spans)"]

    def _walk(parent_id: Optional[str], depth: int) -> None:
        for r in children.get(parent_id, []):
            attrs = r.get("attrs") or {}
            extra = "".join(f" {k}={v}" for k, v in sorted(attrs.items()))
            out.append(
                f"  {'  ' * depth}+{r.get('t_start', 0.0) - t0:7.3f}s "
                f"[{r.get('dur_s', 0.0):7.3f}s] {r.get('name')}{extra}")
            _walk(r["span_id"], depth + 1)

    _walk(None, 0)
    return "\n".join(out)


def load_spans(log_dir: str) -> List[Dict[str, Any]]:
    """Parse ``<log_dir>/spans.jsonl`` (tolerates a missing file)."""
    import json

    path = os.path.join(log_dir, "spans.jsonl")
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
