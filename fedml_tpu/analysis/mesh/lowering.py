"""SPMD lowering + partitioned-HLO views for one (entrypoint, variant).

The single-device perf tier mostly *lowers*; this tier must **compile**:
the collectives XLA's SPMD partitioner inserts exist only in the
optimized HLO (``jit(...).trace(...).lower().compile().as_text()``), not
in the sharding-annotated StableHLO.  ``MeshLoweredEntrypoint`` builds
the variant's named mesh over the forced-CPU device grid, attaches the
declared in-shardings to the abstract args, compiles, and parses the
partitioned module into the facts the SHARD rules read:

* every collective instruction — op, payload bytes (shared conventions
  with ``utils/hlo_costs.py``), expanded replica groups (explicit and
  iota ``[G,S]<=[N]`` forms, including the transposed variant), the
  computation it lives in, and whether that computation is reachable
  from a ``while`` body (the round loop);
* which ENTRY collectives are rooted at a ``parameter`` or feed ROOT
  through pass-through ops only (boundary resharding, SHARD002);
* the lower-time dropped-donation warnings under the mesh lowering
  (SHARD006's authoritative signal).

jax is imported lazily — the module parses text with stdlib ``re`` and
numpy only, so the rule catalog stays importable without a backend.
"""

from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from ...utils.hlo_costs import (
    BUDGET_OPS,
    _COLLECTIVE_OPS,
    _shape_bytes,
    collective_totals,
)
from ..perf.registry import EntrypointSpec
from .variants import INHERIT, MeshVariant

#: ops a value passes through unchanged for boundary attribution —
#: a collective reachable from a parameter (or reaching ROOT) through
#: ONLY these is a boundary reshard, not a mid-program exchange
_PASS_THROUGH = {
    "copy", "bitcast", "reshape", "transpose", "convert", "tuple",
    "get-tuple-element", "optimization-barrier",
}

_HEADER_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\{$")
_INSTR_RE = re.compile(r"^(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_CALL_RE = re.compile(r"(?<![\w.%\-])([a-z][a-z0-9\-]*)\(")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_CALLED_COMP_RE = re.compile(
    r"(?:body|condition|to_apply|calls)=\{?%([\w.\-]+)")
_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{(\{[\d,]+\}(?:,\{[\d,]+\})*)\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_PARAM_NUM_RE = re.compile(r"parameter\((\d+)\)")


@dataclasses.dataclass
class HloInstr:
    name: str
    op: str
    result_type: str             # text before the op call
    operands: List[str]          # %names referenced in the operand list
    attrs: str                   # text after the operand list
    is_root: bool
    computation: str
    line: str


@dataclasses.dataclass
class CollectiveInstr:
    """One collective in the partitioned module, fully attributed."""

    op: str                      # base op ("all-reduce", …)
    nbytes: int                  # result payload (async -start halved)
    groups: List[List[int]]      # expanded replica groups (device ids)
    computation: str
    in_loop: bool                # computation reachable from a while body
    name: str                    # HLO instruction name
    #: ENTRY-only boundary attribution (False elsewhere)
    roots_param: bool = False
    param_indices: Tuple[int, ...] = ()
    feeds_root: bool = False

    @property
    def group_size(self) -> int:
        return max((len(g) for g in self.groups), default=0)

    def hosts_spanned(self, devices_per_host: int) -> int:
        dph = max(int(devices_per_host), 1)
        return max((len({d // dph for d in g}) for g in self.groups),
                   default=1)


def expand_replica_groups(line: str) -> List[List[int]]:
    """Expand a ``replica_groups=`` attribute into device-id lists.

    Handles the explicit ``{{0,1},{2,3}}`` form and the iota
    ``[G,S]<=[N0,N1,...]`` form with optional ``T(perm)`` — semantics of
    ``HloReplicaGroupList``: iota over prod(N) reshaped to the ``<=``
    dims, transposed by perm, reshaped to [G,S]; row i is group i."""
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        return [[int(d) for d in grp.strip("{}").split(",") if d]
                for grp in m.group(1).split("},{")]
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        import numpy as np

        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        arr = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            arr = np.transpose(arr, perm)
        return [list(map(int, row)) for row in arr.reshape(g, s)]
    return []


class HloModule:
    """Minimal text parse of one HLO module: computations → instructions,
    the ENTRY name, and while-body reachability."""

    def __init__(self, hlo_text: str) -> None:
        self.text = hlo_text
        self.computations: Dict[str, Dict[str, HloInstr]] = {}
        self.entry: str = ""
        cur: Optional[str] = None
        for raw in hlo_text.splitlines():
            s = raw.strip()
            h = _HEADER_RE.match(s)
            if h and " -> " in s:
                cur = h.group(2)
                self.computations[cur] = {}
                if h.group(1):
                    self.entry = cur
                continue
            if s == "}":
                cur = None
                continue
            if cur is None:
                continue
            im = _INSTR_RE.match(s)
            if not im:
                continue
            rest = im.group(3)
            oc = _OP_CALL_RE.search(rest)
            if not oc:
                continue
            op = oc.group(1)
            # operand list = balanced parens from the op call
            depth, i = 0, oc.end() - 1
            end = len(rest)
            for i in range(oc.end() - 1, len(rest)):
                if rest[i] == "(":
                    depth += 1
                elif rest[i] == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            operand_text = rest[oc.end():end]
            instr = HloInstr(
                name=im.group(2), op=op,
                result_type=rest[:oc.start()].strip(),
                operands=_OPERAND_NAME_RE.findall(operand_text),
                attrs=rest[end + 1:], is_root=bool(im.group(1)),
                computation=cur, line=s)
            self.computations[cur][instr.name] = instr

    def loop_computations(self) -> Set[str]:
        """Computation names reachable from any ``while`` body/condition
        (transitively through body/condition/to_apply/calls edges)."""
        seeds: Set[str] = set()
        for comp in self.computations.values():
            for instr in comp.values():
                if instr.op == "while":
                    seeds.update(_CALLED_COMP_RE.findall(instr.line))
        reach, frontier = set(), list(seeds)
        while frontier:
            name = frontier.pop()
            if name in reach or name not in self.computations:
                continue
            reach.add(name)
            for instr in self.computations[name].values():
                frontier.extend(_CALLED_COMP_RE.findall(instr.line))
        return reach

    def collectives(self) -> List[CollectiveInstr]:
        loops = self.loop_computations()
        out: List[CollectiveInstr] = []
        for cname, comp in self.computations.items():
            for instr in comp.values():
                base = instr.op
                if base.endswith("-done"):
                    continue        # -start carries the payload
                is_start = base.endswith("-start")
                if is_start:
                    base = base[:-len("-start")]
                if base not in _COLLECTIVE_OPS:
                    continue
                nbytes = _shape_bytes(instr.result_type)
                if is_start:
                    # async result tuple aliases the operands — halve,
                    # matching utils/hlo_costs.parse_collectives
                    nbytes //= 2
                out.append(CollectiveInstr(
                    op=base, nbytes=nbytes,
                    groups=expand_replica_groups(instr.line),
                    computation=cname,
                    in_loop=cname in loops, name=instr.name))
        self._attribute_boundaries(out)
        return out

    def _attribute_boundaries(self, colls: List[CollectiveInstr]) -> None:
        """ENTRY-only: mark collectives rooted at parameters / feeding
        ROOT through pass-through ops (boundary resharding, SHARD002)."""
        entry = self.computations.get(self.entry)
        if not entry:
            return
        by_name = {c.name: c for c in colls if c.computation == self.entry}

        def _walk_back(start: HloInstr) -> Tuple[bool, Tuple[int, ...]]:
            seen, stack, params = set(), list(start.operands), []
            while stack:
                n = stack.pop()
                if n in seen:
                    continue
                seen.add(n)
                instr = entry.get(n)
                if instr is None:
                    continue
                if instr.op == "parameter":
                    m = _PARAM_NUM_RE.search(instr.line)
                    params.append(int(m.group(1)) if m else -1)
                elif instr.op in _PASS_THROUGH:
                    stack.extend(instr.operands)
            return bool(params), tuple(sorted(params))

        for c in by_name.values():
            instr = entry.get(c.name)
            if instr is not None:
                c.roots_param, c.param_indices = _walk_back(instr)
        # ROOT side: BFS back from ROOT through pass-through ops; any
        # collective reached produces the final value layout directly
        root = next((i for i in entry.values() if i.is_root), None)
        if root is None:
            return
        seen: Set[str] = set()
        stack = [root.name]
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            instr = entry.get(n)
            if instr is None:
                continue
            if n in by_name:
                by_name[n].feeds_root = True
                continue
            if instr.op in _PASS_THROUGH or instr is root:
                stack.extend(instr.operands)


# ---------------------------------------------------------------------------
# spec resolution + lowering
# ---------------------------------------------------------------------------
_DONATION_WARNING = "donated buffers were not usable"


def _resolve_arg_shardings(mesh, arg, entry):
    """One ``in_specs`` entry → a sharding pytree matching ``arg``'s
    leaves (see ``variants`` module doc for the entry forms)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if callable(entry):
        return entry(mesh, arg)
    if isinstance(entry, str):
        from ...parallel.sharding import make_param_shardings

        return make_param_shardings(arg, mesh, entry)
    sharding = (NamedSharding(mesh, P()) if entry is None
                else NamedSharding(mesh, P(*entry)))
    return jax.tree_util.tree_map(lambda _: sharding, arg)


def _resolve_out_shardings(mesh, out_specs):
    from jax.sharding import NamedSharding, PartitionSpec as P

    if callable(out_specs):
        return out_specs(mesh)
    if out_specs is None:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(*out_specs))


@dataclasses.dataclass
class MeshArgLeaf:
    argnum: int
    path: str
    shape: Tuple[int, ...]
    dtype: str
    nbytes: int
    sharding: Any                # resolved NamedSharding
    donated: bool


class MeshLoweredEntrypoint:
    """Compile one (spec, variant) pair SPMD-partitioned and expose the
    partitioned-HLO views the SHARD rules read."""

    def __init__(self, spec: EntrypointSpec, variant: MeshVariant,
                 root, cache=None) -> None:
        import jax
        import numpy as np

        self.spec = spec
        self.variant = variant
        self.root = root
        devices = jax.devices()
        if len(devices) < variant.n_devices:
            raise RuntimeError(
                f"mesh variant {variant.name!r} needs "
                f"{variant.n_devices} devices, have {len(devices)} — "
                f"set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{variant.n_devices} before jax initializes")
        if variant.fn_factory is not None:
            fn, args = variant.fn_factory()
            if not (isinstance(args, tuple)):
                args = (args,)
        elif cache is not None:
            fn, args = cache.build(spec)
        else:
            fn, args = spec.build()
        from jax.sharding import Mesh

        sizes = [int(s) for s in variant.mesh_axes.values()]
        self.mesh = Mesh(
            np.asarray(devices[:variant.n_devices]).reshape(sizes),
            tuple(variant.mesh_axes))
        in_specs = variant.in_specs or (None,) * len(args)
        if len(in_specs) != len(args):
            raise ValueError(
                f"variant {variant.name!r}: {len(in_specs)} in_specs "
                f"entries for {len(args)} args")
        donate = (spec.donate_argnums
                  if variant.donate_argnums == INHERIT
                  else variant.donate_argnums)
        self.donate_argnums = tuple(donate or ())
        self.arg_leaves: List[MeshArgLeaf] = []
        shard_args = []
        for argnum, (arg, entry) in enumerate(zip(args, in_specs)):
            sh_tree = _resolve_arg_shardings(self.mesh, arg, entry)
            shard_args.append(jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=sh), arg, sh_tree))
            flat, _ = jax.tree_util.tree_flatten_with_path(arg)
            sh_flat = jax.tree_util.tree_leaves(sh_tree)
            for (kp, leaf), sh in zip(flat, sh_flat):
                path = "/".join(_key_str(k) for k in kp)
                self.arg_leaves.append(MeshArgLeaf(
                    argnum=argnum, path=path,
                    shape=tuple(leaf.shape), dtype=str(leaf.dtype),
                    nbytes=int(np.prod(leaf.shape, dtype=np.int64))
                    * np.dtype(leaf.dtype).itemsize,
                    sharding=sh,
                    donated=argnum in self.donate_argnums))
        self.out_shardings = _resolve_out_shardings(
            self.mesh, variant.out_specs)
        base = fn
        if hasattr(fn, "trace") and getattr(fn, "__wrapped__", None):
            # re-jit the underlying callable: the OUTER jit owns
            # donation/out_shardings under SPMD lowering (a nested jit's
            # donation is ignored once inlined)
            base = fn.__wrapped__
        jitted = jax.jit(base, out_shardings=self.out_shardings,
                         donate_argnums=self.donate_argnums)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            with self.mesh:
                lowered = jitted.trace(*shard_args).lower()
                compiled = lowered.compile()
        self.lower_warnings = [str(w.message) for w in caught]
        self.hlo_text = compiled.as_text()
        self.module = HloModule(self.hlo_text)
        self._collectives: Optional[List[CollectiveInstr]] = None

    def collectives(self) -> List[CollectiveInstr]:
        if self._collectives is None:
            self._collectives = self.module.collectives()
        return self._collectives

    def collective_stats(self) -> Dict[str, Any]:
        """Budgeted-op totals over the partitioned module — the number
        SHARD004 ratchets and ``fedml perf programs`` surfaces."""
        return collective_totals(self.hlo_text, BUDGET_OPS)

    def dropped_donations(self) -> List[str]:
        """Per-device aval reprs (``float32[8,64]``) from the lower-time
        dropped-donation warning (empty → every donation aliased).  The
        pattern takes the bare form jax 0.9 prints and the
        ``ShapedArray(...)`` wrapping of earlier releases alike."""
        out: List[str] = []
        for msg in self.lower_warnings:
            if _DONATION_WARNING in msg:
                out.extend(re.findall(r"[a-z]+[0-9]*\[[0-9,\s]*\]", msg))
        return out


def _key_str(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)
