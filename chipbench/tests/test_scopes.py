"""Device time by the program's own scopes (``harness/scopes.py``): the
reader of the trace's own HLO on the committed recording and on a program
compiled here, the join and each new metric's reader on events written out by
hand, and the three epoch programs compiled for a described v5e.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_scopes.py -q -s

Outside tier-1, as ``test_span_metrics.py``; the last test describes a
topology (as ``test_fits_*.py``, whose rehearsals it borrows): run alone."""

import os
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import runner, scopes, xplane
from chipbench.harness.scopes import (LOST, NO_NAME, NOT_IN_MAP, UNSCOPED,
                                      Info)
from chipbench.harness.xplane import Event
from test_fits_v5e import one_chip  # noqa: F401  (the described v5e)

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_trace.xplane.pb")
SFT = ("attn_bwd_ms_per_step", "loss_ms_per_step", "norm_ms_per_step",
       "lora_merge_ms_per_step", "opt_update_ms_per_step",
       "remat_ms_per_step", "moe_layout_ms_per_step", "epoch_unscoped_pct")
SERVE = ("decode_dense_ms_per_token", "decode_sample_ms_per_token",
         "decode_cache_write_ms_per_dispatch")
IDLE = ("idle_empty_pct", "idle_with_work_pct")


def _read(name, run):
    return runner.reader_of(name)(run)


# -- the reader of the trace's own HLO ----------------------------------------

def test_programs_reads_the_recorded_traces_own_hlo():
    progs = scopes.programs(SMALL)
    name, = progs
    assert name.startswith("jit_small_matmul_chain(")
    # the name is the one the program's executions carry
    assert {m.name for m in xplane.first_device_modules(
        xplane.load(SMALL))} == {name}
    info = progs[name]["convolution.4"]
    assert (info.opcode, info.op_name) == (
        "convolution", "jit(small_matmul_chain)/dot_general")
    fused = progs[name]["convolution_tanh_fusion.3"]
    assert fused.opcode == "fusion" and fused.holds == {(UNSCOPED, "fwd"): 2}
    assert progs[name]["copy-done"].scope == NO_NAME
    # every operation the device ran is an instruction of that HLO
    ops = xplane.first_device(xplane.load(SMALL))
    assert ops and all(scopes.instruction_name(ev.name) in progs[name]
                       for ev in ops)
    # recorded before the scopes existed: said in a line, and nothing read
    assert scopes.time_by_scope(xplane.load(SMALL), progs, "small") is None


def _compiled_module():
    from fedml_tpu.core.mlops import tracing

    def fn(x):
        with tracing.scope("test.inner"):
            y = x * 2.0 + 1.0
        with tracing.scope("test.root"):
            return jnp.tanh(y)

    compiled = jax.jit(jax.grad(lambda x: jnp.sum(fn(x)))).lower(
        jnp.ones((64, 64))).compile()
    return compiled.runtime_executable().hlo_modules()[
        0].as_serialized_hlo_module_proto()


def test_a_fusions_time_goes_to_its_root_and_it_holds_the_rest():
    infos = scopes.instructions(_compiled_module())
    fusions = [i for i in infos.values() if i.opcode == "fusion"]
    assert fusions
    held = infos[scopes.ENTRY].holds
    assert {s for s, _ in held} >= {"fedml.test.root"}
    for info in fusions:
        # the root decides, whatever else XLA fused in
        assert info.scope.startswith("fedml.test.") and sum(
            info.holds.values()) >= 1


# a module written out by hand, in the wire format `scopes` reads

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _inst(inst_id, name, opcode, op_name="", operands=(), calls=()):
    return _field(2, _field(1, name) + _field(2, opcode) + (
        _field(7, _field(2, op_name)) if op_name else b"") + _field(
            35, inst_id) + b"".join(
                _field(number, b"".join(map(_varint, ids)))
                for number, ids in ((36, operands), (38, calls)) if ids))


def _comp(comp_id, root_id, *insts):
    return _field(3, b"".join(insts) + _field(5, comp_id)
                  + _field(6, root_id))


def _by_hand():
    body = "jit(f)/while/body/"
    return scopes.instructions(
        # a fused sum of squares whose root is a product's: the product's time
        _comp(1, 12,
              _inst(10, "p.1", "parameter"),
              _inst(11, "square.1", "multiply", body + "fedml.norm/square",
                    [10]),
              _inst(12, "dot.1", "convolution", body
                    + "transpose(jvp(fedml.mlp))/dot_general", [11]))
        # two outputs under a tuple of no name: the fusion's own op_name
        + _comp(2, 22,
                _inst(20, "exp.1", "exponential", body + "fedml.loss/exp"),
                _inst(21, "max.1", "reduce", body + "fedml.loss/reduce_max",
                      calls=[5]),
                _inst(22, "tuple.1", "tuple", operands=[20, 21]))
        # a bitcast fusion of the compiler's own
        + _comp(3, 30, _inst(30, "bitcast.1", "bitcast"))
        + _comp(5, 50, _inst(50, "maximum.1", "maximum", "reduce_max"))
        # the loop's body
        + _comp(4, 45,
                _inst(40, "arg.1", "parameter"),
                _inst(41, "gte.1", "get-tuple-element", body[:-1], [40]),
                _inst(42, "reduce_fusion.1", "fusion", body
                      + "fedml.norm/reduce_sum", [41], [1]),
                _inst(43, "fusion.2", "fusion", body + "jvp(fedml.head)/dot",
                      [42], [2]),
                _inst(44, "fusion.3", "fusion", operands=[43], calls=[3]),
                # the compiler's copies: of a scoped value, and of the carry
                _inst(46, "copy-start.1", "copy-start", operands=[44]),
                _inst(47, "copy-done.1", "copy-done", operands=[46]),
                _inst(48, "copy-done.2", "copy-done", operands=[41]),
                _inst(45, "tuple.2", "tuple", operands=[47, 48]))
        + _comp(6, 61,
                _inst(60, "p.2", "parameter"),
                _inst(61, "while.1", "while", "jit(f)/while", [60], [4]))
        + _field(6, 6))


def test_instructions_of_a_module_written_out_by_hand():
    infos = _by_hand()
    at = {n: (i.scope, i.direction) for n, i in infos.items()}
    assert at["reduce_fusion.1"] == ("fedml.mlp", "bwd")      # its root's
    assert infos["reduce_fusion.1"].holds == {
        ("fedml.norm", "fwd"): 1, ("fedml.mlp", "bwd"): 1}
    assert at["fusion.2"] == ("fedml.head", "fwd")            # its own
    # a reduce's region is not what it holds: the reduce is
    assert infos["fusion.2"].holds == {("fedml.loss", "fwd"): 2}
    # the compiler's own take the scope of what they move ...
    assert at["fusion.3"] == at["copy-start.1"] == at["copy-done.1"] == (
        "fedml.head", "fwd")
    # ... and keep none where no instruction of the program made it
    assert at["copy-done.2"] == (NO_NAME, "fwd")
    assert at["while.1"] == (UNSCOPED, "fwd")
    assert infos["while.1"].holds == infos[scopes.ENTRY].holds == {
        ("fedml.norm", "fwd"): 1, ("fedml.mlp", "bwd"): 1,
        ("fedml.loss", "fwd"): 2, (NO_NAME, "fwd"): 3}


def test_the_wire_walk_agrees_with_the_protobuf_classes():
    hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    raw = _compiled_module()
    module = hlo_pb2.HloModuleProto.FromString(raw)
    want = {i.name: (i.opcode, i.metadata.op_name)
            for c in module.computations for i in c.instructions}
    got = {n: (i.opcode, i.op_name)
           for n, i in scopes.instructions(raw).items() if n != scopes.ENTRY}
    assert got == want and len(want) > 5


# -- the join, on events written out by hand -----------------------------------

EPOCH = "jit_sft_epoch(7)"
NORM_BWD = ("jit(sft_epoch)/while/body/closed_call/transpose(jvp())/"
            "checkpoint/fedml.norm/reduce_sum")


def _epoch_program():
    def at(path, opcode="fusion", **holds):
        return Info(opcode, path, *scopes.scope_of(path), holds)

    body = "jit(sft_epoch)/while/body/closed_call/"
    return {EPOCH: {
        "while.1": at("jit(sft_epoch)/while", "while"),
        # a reduction of the norms' backward into which XLA fused the
        # neighbouring products: its time is the root's
        "multiply_reduce_fusion.2": Info(
            "fusion", NORM_BWD, "fedml.norm", "bwd",
            {("fedml.norm", "bwd"): 7, ("fedml.attn.out", "bwd"): 3}),
        "fusion.3": at(body + "transpose(jvp())/fedml.attn/fedml.attn_bwd/"
                       "while/body/dot_general"),
        "fusion.4": at(body + "jvp(fedml.loss)/reduce_max"),
        "fusion.5": at(body + "transpose(jvp())/checkpoint/"
                       "rematted_computation/fedml.head/dot_general"),
        "fusion.6": at(body + "fedml.opt/mul"),
        "fusion.7": at(body + "transpose(jvp(fedml.lora))/dot_general"),
        "fusion.8": at(body + "jvp()/fedml.experts.layout/fedml.experts.plan"
                       "/sort"),
        "copy.9": at(body + "dynamic_slice", "copy"),
    }}


def _op(name, start, dur, text="f32[4096]{0} fusion(...)"):
    return Event(f"%{name} = {text}", start, dur)


def _epoch_run(**kw):
    """Two whole calls of 2 steps and one the trace cuts; in a call the
    loop's event spans its body's."""
    ops, modules, host = [], [], []
    for c, t0 in enumerate((0, 10_000, 20_000)):
        host.append(Event("chipbench.train_call", t0, 9_000))
        modules.append(Event(EPOCH, t0 + 1_000, 7_000))
        ops.append(_op("while.1", t0 + 1_000, 7_000, "(s32[]) while(...)"))
        at = t0 + 1_000
        for name, dur in (
                ("multiply_reduce_fusion.2", 1_000), ("fusion.3", 2_000),
                ("fusion.4", 400), ("fusion.5", 600), ("fusion.6", 200),
                ("fusion.7", 300), ("fusion.8", 500), ("copy.9", 100),
                ("fusion.77", 900)):             # not in the program's HLO
            ops.append(_op(name, at, dur))
            at += dur
    host[-1] = Event("chipbench.train_call", 20_000, 5_000)    # cut
    kw.setdefault("_scopes_programs", _epoch_program())
    return runner.Run(
        trace=xplane.Trace({"d": ops}, {"d": modules}, host),
        cell={"traffic": {"steps_per_call": 2}},
        rec=types.SimpleNamespace(trace_dir=None, traced=None), **kw)


def test_time_by_scope_on_a_loop_a_fusion_and_a_stranger():
    run = _epoch_run()
    calls = xplane.host_spans(run.trace, "chipbench.train_call")[:2]
    by = scopes.time_by_scope(run.trace, run._scopes_programs,
                              r"^jit_sft_epoch\b", calls)
    assert by == {
        (UNSCOPED, "fwd"): 2 * (1_000 + 100),    # the loop's own, the copy
        ("fedml.norm", "bwd"): 2_000, ("fedml.attn_bwd", "bwd"): 4_000,
        ("fedml.loss", "fwd"): 800, ("fedml.head", "remat"): 1_200,
        ("fedml.opt", "fwd"): 400, ("fedml.lora", "bwd"): 600,
        ("fedml.experts.plan", "fwd"): 1_000, (NOT_IN_MAP, ""): 1_800}
    # the loop's body is not counted twice: all of it is the programs' time
    assert sum(by.values()) == 2 * 7_000
    # without spans, every execution the trace holds
    assert sum(scopes.time_by_scope(run.trace, run._scopes_programs,
                                    "sft_epoch").values()) == 3 * 7_000


def test_the_training_readers():
    run = _epoch_run()
    per = 4 * 1e6                             # 2 whole calls of 2 steps, ms
    want = {"attn_bwd_ms_per_step": 4_000, "loss_ms_per_step": 2_000,
            "norm_ms_per_step": 2_000, "lora_merge_ms_per_step": 600,
            "opt_update_ms_per_step": 400, "remat_ms_per_step": 1_200,
            "moe_layout_ms_per_step": 1_000}
    for name, ns in want.items():
        assert _read(name, run) == pytest.approx(ns / per), name
    assert _read("epoch_unscoped_pct", run) == pytest.approx(
        100 * (2_200 + 1_800) / 14_000)


@pytest.mark.parametrize("name", SFT + SERVE + IDLE)
def test_nothing_to_read_reads_nothing(name, capsys):
    rec = types.SimpleNamespace(trace_dir=None, traced=None)
    assert _read(name, runner.Run(trace=None, rec=rec, cell={"traffic": {}},
                                  config={})) is None
    if name in IDLE:
        return
    # the parent's program: the same events, an HLO that names no scope
    made = _epoch_run if name in SFT else _serve_run
    with_scopes = made()._scopes_programs
    bare = {prog: {n: Info(i.opcode, "jit(some)/while/body/mul", UNSCOPED,
                           "fwd") for n, i in infos.items()}
            for prog, infos in with_scopes.items()}
    assert _read(name, made(_scopes_programs=bare)) is None
    assert "names no fedml. scope" in capsys.readouterr().out
    # a trace that carries no HLO at all
    assert _read(name, made(_scopes_programs={})) is None
    assert "has no HLO" in capsys.readouterr().out


# -- the serving readers -------------------------------------------------------

def _serve_run(_scopes_programs=None):
    ops, modules = [], []
    body = "jit(decode_multi_k8)/while/body/closed_call/"
    full = {
        "fusion.1": Info("fusion", body + "fedml.attn.qkv/dot_general",
                         "fedml.attn.qkv", "fwd"),
        "fusion.2": Info("fusion", body + "fedml.head/dot_general",
                         "fedml.head", "fwd"),
        "fusion.3": Info("fusion", body + "fedml.sample/argmax",
                         "fedml.sample", "fwd"),
        "fusion.4": Info("fusion", body + "fedml.attn/fedml.cache_write/"
                         "dynamic_update_slice", "fedml.cache_write", "fwd"),
        "custom-call.5": Info("custom-call", "jit(decode_multi_k8)/"
                              "fedml.cache_write/kv_store_positions",
                              "fedml.cache_write", "fwd"),
        "custom-call.6": Info("custom-call", body + "fedml.attn/"
                              "decode_attention", "fedml.attn", "fwd")}
    for n, (name, k, t0) in enumerate((
            ("jit_decode_multi_k8(1)", 8, 0), ("jit_decode_multi_k2(2)", 2,
                                               50_000),
            ("jit_decode_multi_k8(1)", 8, 100_000))):
        modules.append(Event(name, t0, 40_000))
        for j in range(k):
            t = t0 + 4_000 * j
            ops += [_op("fusion.1", t, 1_000),
                    _op("fusion.2", t + 1_000, 500, "f32[4,211]{1,0} f(...)"),
                    _op("fusion.3", t + 1_500, 300),
                    _op("fusion.4", t + 1_800, 100),
                    _op("custom-call.6", t + 1_900, 700)]
        ops.append(_op("custom-call.5", t0 + 4_000 * k, 2_000))
    if _scopes_programs is None:
        _scopes_programs = {"jit_decode_multi_k8(1)": full,
                            "jit_decode_multi_k2(2)": full}
    return runner.Run(
        trace=xplane.Trace({"d": ops}, {"d": modules}, []),
        cell={"traffic": {"max_batch": 4}}, config={"vocab_size": 211},
        rec=types.SimpleNamespace(trace_dir=None, traced=None),
        _scopes_programs=_scopes_programs)


def test_the_decode_readers_take_the_full_dispatch():
    run = _serve_run()
    assert _read("decode_device_ms", run) == pytest.approx(0.04)
    assert _read("decode_dense_ms_per_token", run) == pytest.approx(1.5e-3)
    assert _read("decode_sample_ms_per_token", run) == pytest.approx(0.3e-3)
    assert _read("decode_cache_write_ms_per_dispatch", run) == pytest.approx(
        (8 * 100 + 2_000) / 1e6)


def test_the_idle_time_splits_by_the_wait_for_a_request():
    ops = [Event("%a = f32[] f()", 0, 1_000), Event("%b = f32[] f()", 1_002, 998),
           Event("%c = f32[] f()", 12_000, 1_000),
           Event("%d = f32[] f()", 20_000, 1_000)]
    host = [Event("fedml.serve.empty", 3_000, 8_000),     # inside a gap
            Event("fedml.serve.admit", 11_000, 1_000),
            Event("fedml.serve.fetch", 13_000, 7_000)]
    run = runner.Run(
        trace=xplane.Trace({"d": ops}, {"d": []}, host),
        rec=types.SimpleNamespace(trace_dir=None,
                                  traced={"t0": 1.0, "t1": 1.0 + 25e-6}))
    # 25 us traced, 3.998 busy; the gap of 10 us lies 8 under the empty wait
    assert _read("idle_empty_pct", run) == pytest.approx(100 * 8 / 25)
    assert _read("idle_with_work_pct", run) == pytest.approx(
        100 * (25 - 3.998 - 8) / 25)
    run.trace.host[:] = host[1:]
    assert _read("idle_empty_pct", run) is None


def test_every_new_metric_is_declared_with_its_cells():
    bench = runner.load_json(os.path.join(runner.ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in bench["per_layer"]}
    sft = {w["name"] for w in bench["workloads"]
           if w["name"].startswith("sft.")}
    for name in SFT + SERVE + IDLE:
        m = declared[name]
        assert m["better"] == "lower" and "bound" not in m
        assert set(m["workloads"]) <= (sft if name in SFT
                                       else {"serve.chat_steady"})
    assert set(declared["moe_layout_ms_per_step"]["workloads"]) == {
        "sft.smallthinker_lora_16k", "sft.gigachat_lora_8k"}


# -- the epoch programs as the chip's compiler leaves them ---------------------

def _epoch_programs(one_chip, monkeypatch):
    """The three cells' epoch programs compiled for the described v5e, by
    the rehearsals of ``test_fits_*.py`` (which hand back the sizes: here
    the compiled program itself)."""
    import importlib

    v5e, small, giga = (importlib.import_module("test_fits_" + n)
                        for n in ("v5e", "smallthinker", "gigachat"))
    for mod in (v5e, small, giga):
        monkeypatch.setattr(mod, "_bytes", lambda c: {"compiled": c})
    cells = {}
    for cell_name in ("sft.lora_1k", "sft.smallthinker_lora_16k",
                      "sft.gigachat_lora_8k"):
        cell = v5e._load("workloads", cell_name + ".json")
        cfg, t = v5e._load("configs", cell["config"] + ".json"), cell["traffic"]
        if cell["plane"] == "sft":
            got = v5e.sft_epoch_bytes(one_chip, cfg, t, monkeypatch)
        elif cell["plane"] == "sft_routed":
            got = small.epoch_bytes(one_chip, cfg, t, t["batch_size"],
                                    monkeypatch)
        else:
            got = giga.epoch_bytes(one_chip, cfg, t, t["seq_len"],
                                   monkeypatch)
        cells[cell_name] = got["compiled"]
    return cells


def _kernel_without_locations(body: bytes) -> str:
    """A Mosaic kernel's serialized body as MLIR text without locations
    (it carries the source lines of the kernel and of its callers)."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jaxlib.mlir import ir
    from jaxlib.mlir.passmanager import PassManager

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    with ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(body)
        PassManager.parse("builtin.module(mosaic-serde{serialize=false})"
                          ).run(module.operation)
        return module.operation.get_asm(enable_debug_info=False)


def without_debug_info(text: str) -> str:
    """An optimized program's text less what a moved line or a named scope
    changes and the compiler never reads: each instruction's
    ``metadata={...}``, the module's tables of files, functions, lines and
    stack frames, the locations inside each Mosaic kernel's body (the body
    is replaced by the SHA-256 of its text without them), and the names of
    instructions and computations (replaced by their order of appearance:
    what is left is every opcode, shape, layout, operand, configuration and
    the schedule, line for line)."""
    import base64
    import hashlib
    import re

    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n.*?\n\n", "", text)
    seen = {}

    def body(m):
        if m.group(1) not in seen:
            seen[m.group(1)] = hashlib.sha256(_kernel_without_locations(
                base64.b64decode(m.group(1))).encode()).hexdigest()
        return f'"body":"kernel without locations {seen[m.group(1)]}"'

    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"', body, text)
    # an instruction's name is made from its op_name's tail and numbered as
    # calls are inlined: a name is replaced by where it first appears
    names = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(
        m.group(0), f"%{len(names)}"), text)


def test_the_epoch_programs_name_their_work(one_chip, monkeypatch):  # noqa: F811
    """Of the instructions the program wrote (those with an ``op_name``:
    the compiler's own copies, slices and bitcasts carry none) and that
    compute something, at least 95% carry a ``fedml.`` scope, in each cell's
    epoch program as the chip's compiler leaves it.  With ``-s`` prints each
    program's shares and the SHA-256 of its text `without_debug_info`: equal
    to the parent tree's (CHANGES.md), since a scope is metadata."""
    import hashlib

    for cell, compiled in _epoch_programs(one_chip, monkeypatch).items():
        module = compiled.runtime_executable().hlo_modules()[0]
        infos = scopes.instructions(module.as_serialized_hlo_module_proto())
        held = infos[scopes.ENTRY].holds
        named = sum(n for (s, _), n in held.items() if s not in LOST)
        bare = sum(n for (s, _), n in held.items() if s == NO_NAME)
        written = sum(held.values()) - bare
        text = without_debug_info(compiled.as_text())
        print(f"{cell}: {named} of {written} instructions with an op_name "
              f"scoped ({100 * named / written:.2f}%), {bare} of the "
              f"compiler's own without one; sha256 without debug "
              f"information {hashlib.sha256(text.encode()).hexdigest()}")
        assert named >= 0.95 * written, (cell, sorted(
            held.items(), key=lambda kv: -kv[1])[:12])
