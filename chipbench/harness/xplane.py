"""From the profiler's ``.xplane.pb`` to the numbers the benchmark reports:
device busy time, time per kind of device operation, executions of a named
program, and the device's idle gaps laid against what the host was doing.

``load`` turns the file into plain lists of events (read with nothing but
JAX's ``ProfileData``); everything else is arithmetic on those lists, so the
tests drive it with events written out by hand as well as with a recorded
trace.  Times are nanoseconds on the trace's own clock.
"""

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns
    dur: float            # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    #: device operations, one list per device plane
    ops: Dict[str, List[Event]]
    #: whole-program executions, one list per device plane
    modules: Dict[str, List[Event]]
    #: host events of every thread (TraceAnnotations, PjitFunction(...), ...)
    host: List[Event]


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_device and line.name == "XLA Ops":
                ops.setdefault(plane.name, []).extend(_events(line))
            elif is_device and line.name == "XLA Modules":
                modules.setdefault(plane.name, []).extend(_events(line))
            elif plane.name.startswith("/host:"):
                host.extend(e for e in _events(line) if e.dur > 0)
    return Trace(ops, modules, host)


def _events(line) -> Iterable[Event]:
    for ev in line.events:
        yield Event(ev.name, float(ev.start_ns), float(ev.duration_ns))


# ---------------------------------------------------------------------------
# arithmetic on event lists
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(events: Sequence[Event]) -> float:
    """Time in which at least one operation ran: nested and overlapping
    events count once."""
    return sum(e - s for s, e in union((ev.start, ev.end) for ev in events))


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less that of the events nested directly inside
    it (a loop's event spans its body's operations), in ``events``' order."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].dur))
    own = [ev.dur for ev in events]
    stack: List[int] = []
    for i in order:
        ev = events[i]
        while stack and events[stack[-1]].end <= ev.start:
            stack.pop()
        if stack and ev.end <= events[stack[-1]].end:
            own[stack[-1]] -= ev.dur
        stack.append(i)
    return [max(v, 0.0) for v in own]


_NUMBER = re.compile(r"[.\-_]?\d+$")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def kind_of(ev: Event) -> str:
    """What an operation is, without the number XLA gives each instance.  A
    TPU trace names an operation by its whole HLO text, ``%fusion.12 =
    f32[4,1024,5120]{...} fusion(...)``: the kind is then the instruction's
    name without its number and the first shape it yields, which is what the
    36 layers' copies of one product have in common; a Pallas kernel
    (``tpu_custom_call``) is marked as one."""
    name, _, text = ev.name.lstrip("%").partition(" = ")
    base = _NUMBER.sub("", name)
    shape = _SHAPE.search(text)
    if shape:
        base = f"{base} {shape.group(0)}"
    if "tpu_custom_call" in text:
        base += " tpu_custom_call"
    return base


def time_by_kind(events: Sequence[Event]) -> Dict[str, float]:
    """Self time summed over operations of one kind, in ns."""
    out: Dict[str, float] = {}
    for ev, own in zip(events, self_times(events)):
        k = kind_of(ev)
        out[k] = out.get(k, 0.0) + own
    return out


def matching(events: Sequence[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev.name)]


def gaps(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """The stretches between the first and the last operation in which none
    ran."""
    busy = union((ev.start, ev.end) for ev in events)
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


#: a gap shorter than this is the device's own pause between two operations
#: of one program, not something the host did
SHORT_GAP_NS = 5_000.0
SHORT = "(gaps under 5 us)"
UNCOVERED = "(no host event)"


def idle_by_host_activity(device_events: Sequence[Event],
                          host_events: Sequence[Event],
                          prefer: str = "chipbench.") -> Dict[str, float]:
    """Idle time (ns) by what the host was doing: each gap goes to the
    shortest host event that covers its middle; an event of the benchmark's
    own (``prefer``) wins only when no other covers it, since the program's
    events are the finer ones."""
    out: Dict[str, float] = {}
    spans = sorted(host_events, key=lambda e: e.start)
    active: List[Event] = []
    nxt = 0
    for s, e in gaps(device_events):
        if e - s < SHORT_GAP_NS:
            out[SHORT] = out.get(SHORT, 0.0) + (e - s)
            continue
        mid = 0.5 * (s + e)
        while nxt < len(spans) and spans[nxt].start <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [h for h in active if h.end >= mid]
        fine = [h for h in active if not h.name.startswith(prefer)]
        pick = min(fine or active, key=lambda h: h.dur, default=None)
        name = pick.name if pick is not None else UNCOVERED
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries as ``[name, seconds]`` (from ns)."""
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in rows]


# ---------------------------------------------------------------------------
# the summary a traced run reports
# ---------------------------------------------------------------------------

def summarize(trace: Trace) -> Dict:
    """Busy seconds averaged over the device planes, and the breakdown."""
    if not trace.ops:
        return {"busy_s": 0.0, "device_ops": [], "idle_gaps": []}
    busy = [busy_ns(evs) for evs in trace.ops.values()]
    first = next(iter(trace.ops.values()))
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "device_ops": top(time_by_kind(first)),
        "idle_gaps": top(idle_by_host_activity(first, trace.host)),
    }


# ---------------------------------------------------------------------------
# helpers the metric readers share
# ---------------------------------------------------------------------------

def first_device(trace: Trace) -> List[Event]:
    return next(iter(trace.ops.values()), [])


def first_device_modules(trace: Trace) -> List[Event]:
    return next(iter(trace.modules.values()), [])


def host_spans(trace: Trace, name: str) -> List[Event]:
    """The benchmark's own annotations of one name that the trace holds
    whole."""
    return [h for h in trace.host if h.name == name]


def within(events: Sequence[Event], span: Event) -> List[Event]:
    return [ev for ev in events if ev.start >= span.start
            and ev.end <= span.end]


def busy_within_ns(events: Sequence[Event], span: Event) -> float:
    return sum(min(e, span.end) - max(s, span.start)
               for s, e in union((ev.start, ev.end) for ev in events)
               if e > span.start and s < span.end)
