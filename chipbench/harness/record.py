"""What one run writes down while it measures: host spans and samples, on
one clock, and the switch for the profiler's trace.

Spans are kept in memory and read after the window by the metric readers
(``chipbench/metrics``).  While the profiler is on, a span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so that the device trace's
idle gaps can be laid against what the host was doing.
"""

import contextlib
import os
import time
from typing import Any, Dict, List, Optional

now = time.perf_counter


class Record:
    def __init__(self, trace_dir: Optional[str] = None,
                 trace_plan: Optional[Dict[str, float]] = None) -> None:
        self.spans: List[Dict[str, Any]] = []      # name, t0, t1, + fields
        self.samples: Dict[str, List[Any]] = {}
        self.window: Optional[Dict[str, float]] = None   # t0, t1
        #: where the profiler writes, or None in a run without a trace
        self.trace_dir = trace_dir
        plan = trace_plan or {}
        self._trace_start = float(plan.get("start_s", 0.0))
        self._trace_seconds = float(plan.get("seconds", 5.0))
        self._tracing = False
        self.traced: Optional[Dict[str, float]] = None   # t0, t1 (host clock)

    # -- spans and samples ---------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **fields):
        ann = None
        if self._tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        rec = {"name": name, "t0": now(), **fields}
        try:
            yield rec
        finally:
            rec["t1"] = now()
            self.spans.append(rec)
            if ann is not None:
                ann.__exit__(None, None, None)

    def sample(self, name: str, value: Any) -> None:
        self.samples.setdefault(name, []).append(value)

    def spans_named(self, name: str, in_window: bool = True):
        w = self.window
        return [s for s in self.spans if s["name"] == name and (
            not in_window or w is None or w["t0"] <= s["t0"] <= w["t1"])]

    def window_s(self) -> float:
        """The window's length, less what the profiler took to start and to
        stop inside it (seconds in a traced run, nothing otherwise): what a
        rate of the window's work is taken over."""
        w, tr = self.window, self.traced
        lost = 0.0
        if tr is not None:
            for a, b in ((tr["starting"], tr["t0"]),
                         (tr.get("t1", w["t1"]), tr.get("stopped", w["t1"]))):
                lost += max(min(b, w["t1"]) - max(a, w["t0"]), 0.0)
        return w["t1"] - w["t0"] - lost

    def say(self, what: str, **fields) -> None:
        import json

        print("CHIPBENCH " + json.dumps({"what": what, **fields}), flush=True)

    # -- the profiler --------------------------------------------------------
    def trace_tick(self, elapsed_s: float, window_s: float) -> None:
        """Called by a plane between units of work inside the window: turns
        the profiler on at the planned offset (negative: counted back from
        the window's end) and off once the planned length has passed."""
        if self.trace_dir is None:
            return
        start = self._trace_start
        if start < 0:
            start = max(window_s + start, 0.0)
        if not self._tracing and self.traced is None and elapsed_s >= start:
            self.trace_start()
        elif self._tracing and now() - self.traced["t0"] >= self._trace_seconds:
            self.trace_stop()

    def trace_start(self) -> None:
        import jax

        os.makedirs(self.trace_dir, exist_ok=True)
        starting = now()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # a Python tracer slows the host
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        self.traced = {"starting": starting, "t0": now()}

    def trace_stop(self) -> None:
        if not self._tracing:
            return
        import jax

        self.traced["t1"] = now()
        self._tracing = False
        jax.profiler.stop_trace()
        self.traced["stopped"] = now()
