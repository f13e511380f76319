"""Typed metrics plane — Counter/Gauge/Histogram with Prometheus exposition.

The serving engine, trainers, round managers and the scheduler all need
queryable numeric state ("tokens/s now", "p95 round time"), not just JSONL
event logs.  This module is a small, stdlib-only, thread-safe metrics
registry in the Prometheus data model:

* ``Counter`` — monotonically increasing totals;
* ``Gauge``   — set/inc/dec instantaneous values;
* ``Histogram`` — cumulative buckets + sum + count, with a ``time()``
  context manager for latency measurement;
* labels via ``metric.labels(key=value)`` returning a cached child;
* ``render_prometheus()`` — text exposition format v0.0.4, served from the
  scheduler control plane at ``GET /metrics`` and dumped by
  ``fedml metrics``.

A process-wide default ``REGISTRY`` backs the module-level ``counter`` /
``gauge`` / ``histogram`` get-or-create helpers; tests build private
``MetricsRegistry`` instances.
"""

from __future__ import annotations

import itertools
import re
import threading
import time
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                   2.5, 5.0, 10.0)

#: per-metric cap on distinct label sets.  A label fed from an unbounded
#: domain (a per-client id, a request id) would otherwise grow the
#: exporter without limit — the cardinality explosion PRIV002 hunts
#: statically; this is the runtime backstop.  Writes past the cap land in
#: a shared overflow child (never exported) and count into
#: ``fedml_metrics_dropped_labels_total{metric=...}``.
MAX_LABEL_SETS = 512

#: the drop counter is exempt from the cap (its own label domain is the
#: set of metric NAMES, bounded) — exempting it also breaks the
#: would-be recursion of a drop incrementing the drop counter.
DROPPED_METRIC = "fedml_metrics_dropped_labels_total"


def _fmt(v: float) -> str:
    """Prometheus sample value: integers render bare, +Inf as +Inf."""
    if v == float("inf"):
        return "+Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def _escape_label(v: Any) -> str:
    return (str(v).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


class _Child:
    """One labelset's sample storage (lock shared with the parent)."""

    def __init__(self, metric: "_Metric") -> None:
        self._metric = metric
        self._lock = metric._lock


class _CounterChild(_Child):
    def __init__(self, metric: "_Metric") -> None:
        super().__init__(metric)
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters can only increase")
        with self._lock:
            self.value += amount


class _GaugeChild(_Child):
    def __init__(self, metric: "_Metric") -> None:
        super().__init__(metric)
        self.value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self.value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)


class _Timer:
    def __init__(self, child: "_HistogramChild") -> None:
        self._child = child

    def __enter__(self) -> "_Timer":
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self._child.observe(time.monotonic() - self._t0)
        return False


class _HistogramChild(_Child):
    def __init__(self, metric: "_Metric") -> None:
        super().__init__(metric)
        self.buckets = metric.buckets
        self.bucket_counts = [0] * len(self.buckets)
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self.sum += value
            self.count += 1
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    self.bucket_counts[i] += 1
                    break

    def time(self) -> _Timer:
        return _Timer(self)

    def snapshot(self) -> Tuple[Iterable[Tuple[float, int]], float, int]:
        """One LOCKED snapshot of (cumulative bucket pairs, sum, count) —
        exposition must render all three from the same snapshot or a
        concurrent observe() can break count == the +Inf bucket."""
        with self._lock:
            counts = list(self.bucket_counts)
            total = self.count
            s = self.sum
        acc = 0
        out = []
        for bound, c in zip(self.buckets, counts):
            acc += c
            out.append((bound, acc))
        out.append((float("inf"), total))
        return out, s, total

    def cumulative(self) -> Iterable[Tuple[float, int]]:
        """(upper_bound, cumulative_count) pairs ending with (+Inf, count)."""
        return self.snapshot()[0]


_CHILD_TYPES = {"counter": _CounterChild, "gauge": _GaugeChild,
                "histogram": _HistogramChild}


class _Metric:
    def __init__(self, name: str, help: str, kind: str,
                 label_names: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS) -> None:
        self.name = name
        self.help = help
        self.kind = kind
        self.label_names = tuple(label_names)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], _Child] = {}
        #: shared sink for label sets past MAX_LABEL_SETS: absorbs writes
        #: (callers keep working) but is never exported
        self._overflow: Optional[_Child] = None
        #: owning registry, for routing drop counts (set by _get_or_create)
        self._registry: Optional["MetricsRegistry"] = None

    def labels(self, **labels: Any) -> Any:
        if set(labels) != set(self.label_names):
            raise ValueError(
                f"{self.name}: expected labels {self.label_names}, "
                f"got {tuple(labels)}")
        key = tuple(str(labels[n]) for n in self.label_names)
        dropped = False
        with self._lock:
            child = self._children.get(key)
            if child is None:
                if (self.name != DROPPED_METRIC
                        and len(self._children) >= MAX_LABEL_SETS):
                    if self._overflow is None:
                        self._overflow = _CHILD_TYPES[self.kind](self)
                    child = self._overflow
                    dropped = True
                else:
                    child = self._children[key] = \
                        _CHILD_TYPES[self.kind](self)
        if dropped:
            # incremented AFTER releasing this metric's lock: the drop
            # counter is a sibling metric with its own lock — nesting the
            # two would add a metric→metric edge to the lock-order DAG
            reg = self._registry
            if reg is not None:
                reg.counter(
                    DROPPED_METRIC,
                    "Label-set writes dropped by the per-metric "
                    "cardinality cap (MAX_LABEL_SETS)",
                    labels=("metric",)).labels(metric=self.name).inc()
        return child

    def children(self) -> Dict[Tuple[str, ...], _Child]:
        """Snapshot of label-key → child, for programmatic consumers
        (e.g. the pod serving scaler reading decode histograms)."""
        with self._lock:
            return dict(self._children)

    def _default_child(self) -> Any:
        """The no-labels child, for unlabelled metrics' direct methods."""
        if self.label_names:
            raise ValueError(f"{self.name} has labels "
                             f"{self.label_names}; use .labels(...)")
        return self.labels()

    # unlabelled convenience: counter.inc(), gauge.set(v), hist.observe(v)
    def inc(self, amount: float = 1.0) -> None:
        self._default_child().inc(amount)

    def dec(self, amount: float = 1.0) -> None:
        self._default_child().dec(amount)

    def set(self, value: float) -> None:
        self._default_child().set(value)

    def observe(self, value: float) -> None:
        self._default_child().observe(value)

    def time(self) -> _Timer:
        return self._default_child().time()

    # -- exposition ----------------------------------------------------------
    def _label_str(self, key: Tuple[str, ...],
                   extra: Tuple[Tuple[str, str], ...] = ()) -> str:
        pairs = [f'{n}="{_escape_label(v)}"'
                 for n, v in zip(self.label_names, key)]
        pairs += [f'{n}="{_escape_label(v)}"' for n, v in extra]
        return "{" + ",".join(pairs) + "}" if pairs else ""

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {self.kind}"]
        with self._lock:
            children = dict(self._children)
        for key in sorted(children):
            child = children[key]
            if self.kind == "histogram":
                pairs, h_sum, h_count = child.snapshot()
                for bound, cum in pairs:
                    lines.append(
                        f"{self.name}_bucket"
                        f"{self._label_str(key, (('le', _fmt(bound)),))}"
                        f" {cum}")
                lines.append(f"{self.name}_sum{self._label_str(key)} "
                             f"{_fmt(h_sum)}")
                lines.append(f"{self.name}_count{self._label_str(key)} "
                             f"{h_count}")
            else:
                lines.append(f"{self.name}{self._label_str(key)} "
                             f"{_fmt(child.value)}")
        return "\n".join(lines)


_generations = itertools.count()


class MetricsRegistry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}
        #: renewed by `reset`, and no two registries share one: a holder
        #: of cached children compares it to know that its handles are no
        #: longer exported
        self.generation = next(_generations)

    def _get_or_create(self, name: str, help: str, kind: str,
                       labels: Sequence[str],
                       buckets: Sequence[float]) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if m.kind != kind or m.label_names != tuple(labels):
                    raise ValueError(
                        f"metric {name} already registered as {m.kind}"
                        f"{m.label_names}")
                return m
            m = _Metric(name, help, kind, labels, buckets)
            m._registry = self
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = ()) -> _Metric:
        return self._get_or_create(name, help, "counter", labels,
                                   DEFAULT_BUCKETS)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = ()) -> _Metric:
        return self._get_or_create(name, help, "gauge", labels,
                                   DEFAULT_BUCKETS)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> _Metric:
        return self._get_or_create(name, help, "histogram", labels, buckets)

    def render_prometheus(self) -> str:
        with self._lock:
            metrics = [self._metrics[n] for n in sorted(self._metrics)]
        body = "\n".join(m.render() for m in metrics)
        return body + "\n" if body else ""

    def collect(self) -> Dict[str, _Metric]:
        with self._lock:
            return dict(self._metrics)

    def reset(self) -> None:
        """Drop all metrics — test isolation only; cached metric handles in
        long-lived objects keep working but stop being exported."""
        with self._lock:
            self._metrics.clear()
            self.generation = next(_generations)


#: process-wide default registry (what the control plane exports)
REGISTRY = MetricsRegistry()


def counter(name: str, help: str = "",
            labels: Sequence[str] = ()) -> _Metric:
    return REGISTRY.counter(name, help, labels)


def gauge(name: str, help: str = "", labels: Sequence[str] = ()) -> _Metric:
    return REGISTRY.gauge(name, help, labels)


def histogram(name: str, help: str = "", labels: Sequence[str] = (),
              buckets: Sequence[float] = DEFAULT_BUCKETS) -> _Metric:
    return REGISTRY.histogram(name, help, labels, buckets)


def render_prometheus(registry: Optional[MetricsRegistry] = None) -> str:
    return (registry or REGISTRY).render_prometheus()


# -- exposition parsing (the inverse of render_prometheus) -------------------
#
# `fedml metrics --json` and the SLO engine consume scrapes as data, not
# text; parsing our own v0.0.4 output (plus anything prometheus_client
# renders) keeps CI assertions and rule evaluation regex-free.

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)(?:\s+\d+)?$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape_label(v: str) -> str:
    return v.replace(r'\"', '"').replace(r"\n", "\n").replace("\\\\", "\\")


def _parse_value(v: str) -> float:
    if v == "+Inf":
        return float("inf")
    if v == "-Inf":
        return float("-inf")
    return float(v)


def parse_prometheus(text: str) -> Dict[str, Dict[str, Any]]:
    """Parse exposition-format text into::

        {metric: {"type", "help", "samples": [{"labels", "value"}],
                  "series": [...]}}  # histograms only

    Histogram ``_bucket`` / ``_sum`` / ``_count`` samples are regrouped
    under the base metric: each ``series`` entry is one labelset with
    ``buckets`` ([upper_bound, cumulative_count] pairs, +Inf last),
    ``sum`` and ``count`` — the shape ``histogram_quantile`` takes.
    """
    out: Dict[str, Dict[str, Any]] = {}

    def _metric(name: str) -> Dict[str, Any]:
        return out.setdefault(name, {"type": "untyped", "help": "",
                                     "samples": []})

    hist_names = set()
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_text = rest.partition(" ")
            _metric(name)["help"] = help_text
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            _metric(name)["type"] = kind.strip()
            if kind.strip() == "histogram":
                hist_names.add(name)
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, label_str, value = m.group(1), m.group(2), m.group(3)
        labels = {k: _unescape_label(v)
                  for k, v in _LABEL_RE.findall(label_str or "")}
        try:
            val = _parse_value(value)
        except ValueError:
            continue
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in hist_names:
                base = name[:-len(suffix)]
                break
        entry = _metric(base)
        entry["samples"].append({"name": name, "labels": labels,
                                 "value": val})

    # regroup histogram samples into per-labelset series
    for name, entry in out.items():
        if entry["type"] != "histogram":
            continue
        series: Dict[Tuple[Tuple[str, str], ...], Dict[str, Any]] = {}
        for s in entry["samples"]:
            labels = dict(s["labels"])
            le = labels.pop("le", None)
            key = tuple(sorted(labels.items()))
            ser = series.setdefault(key, {"labels": labels, "buckets": [],
                                          "sum": 0.0, "count": 0})
            if s["name"].endswith("_bucket") and le is not None:
                ser["buckets"].append([_parse_value(le), s["value"]])
            elif s["name"].endswith("_sum"):
                ser["sum"] = s["value"]
            elif s["name"].endswith("_count"):
                ser["count"] = int(s["value"])
        for ser in series.values():
            ser["buckets"].sort(key=lambda b: b[0])
        entry["series"] = list(series.values())
    return out


def histogram_quantile(q: float,
                       buckets: Sequence[Sequence[float]]) -> Optional[float]:
    """Prometheus-style quantile from cumulative ``[upper_bound, count]``
    pairs (linear interpolation within the winning bucket; the +Inf
    bucket resolves to the highest finite bound).  None when empty."""
    if not buckets:
        return None
    total = buckets[-1][1]
    if total <= 0:
        return None
    rank = q * total
    prev_bound, prev_cum = 0.0, 0.0
    for bound, cum in buckets:
        if cum >= rank:
            if bound == float("inf"):
                return prev_bound if prev_bound > 0 else None
            if cum == prev_cum:
                return bound
            return prev_bound + (bound - prev_bound) * \
                (rank - prev_cum) / (cum - prev_cum)
        prev_bound, prev_cum = bound, cum
    return prev_bound if prev_bound > 0 else None
