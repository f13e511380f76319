"""Device time by the program's own scopes, from nothing but the trace.

The program names its device work (``fedml_tpu.core.mlops.tracing.scope``:
``jax.named_scope("fedml." + name)``), and the name reaches the ``op_name`` of
every instruction traced under it.  A profiler trace carries each program that
ran as its optimized HLO: the plane ``/host:metadata`` holds one event
metadata a program, named as the program's executions on the ``XLA Modules``
line are (``jit_sft_epoch(<id>)``), with a stat ``Hlo Proto``.  ``programs``
reads those (a walk of the protobuf wire format; nothing but the file is
needed, and nothing the program kept alive), ``time_by_scope`` joins them to
the ``XLA Ops`` events by instruction name.

An instruction's scope is the innermost ``fedml.*`` component of its
``op_name``; its direction comes from the path JAX writes around it:
``rematted_computation`` is **remat**, else ``transpose(`` is **bwd**, else
**fwd**.  A fusion's time goes to the scope of its root (then its own
``op_name``, then the scope most of what it holds has); what it *holds* is
counted beside it.  An instruction of the compiler's own (a copy, a slice, a
bitcast fusion it put in: no ``op_name`` at all) belongs to what it moves: it
takes the scope of the instruction that made its first operand, through other
such instructions; what is left without one (a slice of a weight, which no
instruction of the program made) stays ``(no op_name)``.  Times are
nanoseconds, as in ``xplane``.

The trap: JAX's persistent compilation cache ignores metadata in its key, so
an executable compiled before the scopes existed is fetched as it was, and its
HLO names none.  ``time_by_scope`` then says so in one line and returns None;
clear the cache (or give ``JAX_COMPILATION_CACHE_DIR`` a fresh directory).
"""

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

from . import xplane

#: an instruction the program wrote (it has an ``op_name``) under no scope
UNSCOPED = "(unscoped)"
#: an instruction of the compiler's own (a copy, a slice, a bitcast fusion it
#: put in): no ``op_name`` at all
NO_NAME = "(no op_name)"
#: an event whose instruction the program's HLO does not hold
NOT_IN_MAP = "(not in the map)"
#: the three places time is lost to the scopes
LOST = (UNSCOPED, NO_NAME, NOT_IN_MAP)
#: opcodes that compute nothing: left out of what a fusion holds
TRIVIAL = frozenset(("parameter", "constant", "bitcast", "broadcast",
                     "convert", "get-tuple-element", "tuple"))

_SCOPE = re.compile(r"(?:^|[/(])(fedml\.[\w.]+)")

#: (scope, direction)
Key = Tuple[str, str]


def scope_of(op_name: str) -> Key:
    """The innermost ``fedml.*`` component of an ``op_name`` (`UNSCOPED`
    without one, `NO_NAME` for no ``op_name``), and the direction its path
    says."""
    found = _SCOPE.findall(op_name)
    direction = ("remat" if "rematted_computation" in op_name else
                 "bwd" if "transpose(" in op_name else "fwd")
    return (found[-1] if found else UNSCOPED if op_name else NO_NAME,
            direction)


@dataclasses.dataclass
class Info:
    """One instruction of an optimized program."""

    opcode: str
    op_name: str
    #: where its time goes: ``scope_of`` its own ``op_name``; a fusion's,
    #: of its root's
    scope: str
    direction: str
    #: for an instruction that calls computations (a fusion, a ``while``, a
    #: ``call``): the instructions of those, counted by scope and direction
    #: without the `TRIVIAL` ones
    holds: Dict[Key, int] = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as these messages need it
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    n = shift = 0
    while True:
        c = buf[i]
        i += 1
        n |= (c & 0x7F) << shift
        if c < 0x80:
            return n, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of each field of one message: an int
    for a varint, a view of the bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _ints(wire: int, value) -> List[int]:
    """A ``repeated int64`` field's values: packed, or one."""
    if wire == 0:
        return [value]
    out, i = [], 0
    while i < len(value):
        n, i = _varint(value, i)
        out.append(n)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def hlo_protos(xplane_path: str) -> Dict[str, bytes]:
    """``{program name: HloProto bytes}`` of a trace's ``/host:metadata``
    plane (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5; XEventMetadata.name = 2, .stats = 5;
    XStat.metadata_id = 1, .bytes_value = 6)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, bytes] = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and _text(v) == "/host:metadata"
                   for f, _, v in parts):
            continue
        stat_id = None
        for f, _, entry in parts:               # map<int64, XStatMetadata>
            if f == 5:
                meta = {k: v for k, _, v in _fields(entry)}.get(2, b"")
                named = {k: v for k, _, v in _fields(meta)}
                if _text(named.get(2, b"")) == "Hlo Proto":
                    stat_id = named.get(1)
        for f, _, entry in parts:               # map<int64, XEventMetadata>
            if f != 4:
                continue
            meta = {k: v for k, _, v in _fields(entry)}.get(2, b"")
            name, proto = "", None
            for k, _, v in _fields(meta):
                if k == 2:
                    name = _text(v)
                elif k == 5:
                    stat = {a: b for a, _, b in _fields(v)}
                    if stat.get(1) == stat_id and 6 in stat:
                        proto = bytes(stat[6])
            if name and proto is not None:
                out[name] = proto
    return out


#: opcodes whose called computations are the work they stand for (a
#: ``reduce``'s or a ``sort``'s are a few scalar instructions of no scope)
CALLERS = frozenset(("fusion", "while", "call", "conditional"))
#: the name `instructions` files the whole program under: an `Info` whose
#: ``holds`` counts every instruction reached from the entry computation
ENTRY = "(entry)"


def instructions(hlo_module: bytes) -> Dict[str, Info]:
    """``{instruction name: Info}`` over every computation of one program,
    and the program whole under `ENTRY` (HloModuleProto.computations = 3,
    .entry_computation_id = 6; HloComputationProto.instructions = 2, .id = 5,
    .root_id = 6; HloInstructionProto.name = 1, .opcode = 2, .metadata = 7,
    .id = 35, .operand_ids = 36, .called_computation_ids = 38;
    OpMetadata.op_name = 2)."""
    out: Dict[str, Info] = {}
    called: Dict[str, List[int]] = {}
    #: instruction id -> name, over the module; name -> its first operand's id
    by_id: Dict[int, str] = {}
    first_operand: Dict[str, int] = {}
    #: computation id -> (its instructions' names, its root's name)
    computations: Dict[int, Tuple[List[str], Optional[str]]] = {}
    entry_id = None
    for f, _, comp in _fields(memoryview(hlo_module)):
        if f == 6:
            entry_id = comp
        if f != 3:
            continue
        comp_id = root_id = None
        names: List[str] = []
        for cf, _, cv in _fields(comp):
            if cf == 5:
                comp_id = cv
            elif cf == 6:
                root_id = cv
            elif cf == 2:
                name = opcode = op_name = ""
                inst_id, calls = None, []
                for k, wire, v in _fields(cv):
                    if k == 1:
                        name = _text(v)
                    elif k == 2:
                        opcode = _text(v)
                    elif k == 7:
                        op_name = next((_text(b) for a, _, b in _fields(v)
                                        if a == 2), "")
                    elif k == 35:
                        inst_id = v
                    elif k == 36:
                        first_operand.setdefault(name, *_ints(wire, v)[:1])
                    elif k == 38:
                        calls += _ints(wire, v)
                out[name] = Info(opcode, op_name, *scope_of(op_name))
                names.append(name)
                by_id[inst_id] = name
                if calls and opcode in CALLERS:
                    called[name] = calls
        computations[comp_id] = (names, by_id.get(root_id))

    def held(comp_ids: Iterable[int], seen: set) -> collections.Counter:
        count: collections.Counter = collections.Counter()
        for cid in comp_ids:
            if cid in seen or cid not in computations:
                continue
            seen.add(cid)
            for name in computations[cid][0]:
                if name in called:          # what it calls stands for it
                    count += held(called[name], seen)
                elif out[name].opcode not in TRIVIAL:
                    count[scope_of(out[name].op_name)] += 1
        return count

    for name, calls in called.items():
        info = out[name]
        info.holds = dict(held(calls, set()))
        if info.opcode != "fusion":
            continue
        root = computations.get(calls[0], ([], None))[1]
        for op_name in (out[root].op_name if root else "", info.op_name):
            if scope_of(op_name)[0] not in LOST:
                info.scope, info.direction = scope_of(op_name)
                break
        else:
            scoped = {k: n for k, n in info.holds.items() if k[0] not in LOST}
            if scoped:
                info.scope, info.direction = max(scoped, key=scoped.get)
    # an instruction of the compiler's own (a copy, a slice it put in) belongs
    # to what it moves: the scope of what made its operand, through other
    # such instructions and the ones that compute nothing
    for name, info in out.items():
        if info.scope != NO_NAME:
            continue
        at = name
        for _ in range(8):
            at = by_id.get(first_operand.get(at))
            made = out.get(at)
            if made is None:
                break
            if made.scope not in LOST:
                info.scope, info.direction = made.scope, made.direction
            if made.scope != NO_NAME and made.opcode not in TRIVIAL:
                break
    out[ENTRY] = Info(ENTRY, "", NO_NAME, "fwd",
                      dict(held([entry_id], set())))
    return out


def programs(xplane_path: str) -> Dict[str, Dict[str, Info]]:
    """``{program name: {instruction name: Info}}`` of every program whose
    HLO the trace carries (HloProto.hlo_module = 1); the names are those of
    the ``XLA Modules`` events."""
    return {name: instructions(next(
        (bytes(v) for f, _, v in _fields(memoryview(proto)) if f == 1), b""))
            for name, proto in hlo_protos(xplane_path).items()}


# ---------------------------------------------------------------------------
# the join
# ---------------------------------------------------------------------------

def instruction_name(event_name: str) -> str:
    """An ``XLA Ops`` event's instruction: a TPU trace names an event by the
    instruction's whole text, ``%fusion.12 = f32[...] fusion(...)``; a CPU
    trace by the bare name."""
    return event_name.lstrip("%").partition(" = ")[0]


def executions(trace: xplane.Trace, module_pattern: str,
               spans: Optional[Sequence[xplane.Event]] = None
               ) -> List[xplane.Event]:
    """The first device's executions (``XLA Modules``) of the programs whose
    name matches, inside one of the given host ``spans`` where given."""
    rx = re.compile(module_pattern)
    return [m for m in xplane.first_device_modules(trace)
            if rx.search(m.name) and (spans is None or any(
                s.start <= m.start and m.end <= s.end for s in spans))]


def scoped_events(trace: xplane.Trace, progs: Dict[str, Dict[str, Info]],
                  module_pattern: str,
                  spans: Optional[Sequence[xplane.Event]] = None
                  ) -> Optional[List[Tuple[xplane.Event, float,
                                           Optional[Info]]]]:
    """(event, its self time, its instruction or None) for the first
    device's ``XLA Ops`` events inside the executions of the programs whose
    name matches ``module_pattern`` (inside the given whole host ``spans``,
    where given).  None, with one line said, where a matching program's HLO
    names no ``fedml.`` scope at all (an executable from a cache filled
    before the scopes existed), or the trace carries no HLO for it."""
    runs = executions(trace, module_pattern, spans)
    for name in sorted({m.name for m in runs}):
        infos = progs.get(name)
        if not infos or all(i.scope in LOST for i in infos.values()):
            print(f"chipbench scopes: {name} " + (
                "names no fedml. scope: compiled before the scopes existed "
                "and fetched from the compilation cache? clear it"
                if infos else "has no HLO in the trace's /host:metadata"),
                flush=True)
            return None
    ops = sorted(xplane.first_device(trace), key=lambda e: e.start)
    own = xplane.self_times(ops)
    starts = [e.start for e in ops]
    out = []
    for m in runs:
        infos = progs[m.name]
        for i in range(bisect.bisect_left(starts, m.start),
                       bisect.bisect_right(starts, m.end)):
            if ops[i].end <= m.end:
                out.append((ops[i], own[i],
                            infos.get(instruction_name(ops[i].name))))
    return out


def key_of(info: Optional[Info]) -> Key:
    """Where an event's time goes: its instruction's scope and direction, or
    ``(NOT_IN_MAP, "")`` for an event whose instruction the HLO lacks."""
    return (NOT_IN_MAP, "") if info is None else (info.scope, info.direction)


def time_by_scope(trace: xplane.Trace, progs: Dict[str, Dict[str, Info]],
                  module_pattern: str,
                  spans: Optional[Sequence[xplane.Event]] = None
                  ) -> Optional[Dict[Key, float]]:
    """``{(scope, direction): ns of self time}`` over `scoped_events`; an
    instruction without a scope under `UNSCOPED` or `NO_NAME` and its
    direction, an event whose instruction the program's HLO does not hold
    under ``(NOT_IN_MAP, "")``.  None where `scoped_events` is."""
    found = scoped_events(trace, progs, module_pattern, spans)
    if found is None:
        return None
    out: Dict[Key, float] = {}
    for _, own, info in found:
        out[key_of(info)] = out.get(key_of(info), 0.0) + own
    return out


# ---------------------------------------------------------------------------
# what the metric readers share
# ---------------------------------------------------------------------------

EPOCH = r"^jit_sft_epoch\b"


def of_run(run) -> Dict[str, Dict[str, Info]]:
    """`programs` of the run's own trace file, read once a run."""
    if "_scopes_programs" not in run.__dict__:
        path = run.rec.trace_dir and xplane.newest_xplane(run.rec.trace_dir)
        run._scopes_programs = programs(path) if path else {}
    return run._scopes_programs


def epoch_by_scope(run) -> Optional[Tuple[Dict[Key, float], int]]:
    """`time_by_scope` of the epoch program inside the ``train()`` calls the
    trace holds whole, and those calls' optimizer steps; once a run."""
    if "_scopes_epoch" not in run.__dict__:
        run._scopes_epoch = None
        calls = [] if run.trace is None else xplane.host_spans(
            run.trace, "chipbench.train_call")
        # an execution is one call's steps
        steps = len(executions(run.trace, EPOCH, calls) if calls else ()
                    ) * int(run.cell["traffic"].get("steps_per_call", 0))
        by = time_by_scope(run.trace, of_run(run), EPOCH, calls) \
            if steps else None
        if by:
            run._scopes_epoch = (by, steps)
    return run._scopes_epoch


def epoch_ms_per_step(run, scopes: Optional[Sequence[str]] = None,
                      directions: Optional[Sequence[str]] = None
                      ) -> Optional[float]:
    """Device milliseconds an optimizer step of the epoch program spends in
    the given scopes (all, left out) and directions (all, left out)."""
    got = epoch_by_scope(run)
    if got is None:
        return None
    by, steps = got
    return sum(ns for (scope, direction), ns in by.items()
               if (scopes is None or scope in scopes)
               and (directions is None or direction in directions)
               ) / steps / 1e6
