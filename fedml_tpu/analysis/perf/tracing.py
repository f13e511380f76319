"""IR capture for one registered entrypoint: jaxpr, lowered module text,
donation table, optional compile stats — everything the PERF rules read.

jax is imported lazily (this module must be importable in environments
that only run the AST tiers).  All tracing happens abstractly via
``jax.stages``: ``fn.trace(*ShapeDtypeStructs)`` → jaxpr;
``.lower()`` → StableHLO text whose ``main`` argument attributes mark
GRANTED donations (``tf.aliasing_output``), while the captured lower-time
warning "Some donated buffers were not usable: ShapedArray(...)" is the
authoritative DROPPED set (it fires exactly on mismatches, never for
eliminated unused args — see ``dropped_donations``);
``.compile()`` (lazy, only when a rule asks) → ``memory_analysis()`` /
``cost_analysis()``.
"""

from __future__ import annotations

import dataclasses
import re
import warnings
from typing import Any, Dict, Iterator, List, Optional, Tuple

from .registry import EntrypointSpec

#: StableHLO main-signature argument attribute marking a GRANTED donation
_ALIAS_ATTR = "tf.aliasing_output"
#: a donated arg the lowering could NOT pair with an output of its own
#: shape/dtype but handed to XLA as a reusable buffer (same element
#: count); whether XLA used it shows only in the compiled module
_DONOR_ATTR = "jax.buffer_donor"


@dataclasses.dataclass
class ArgLeaf:
    """One flattened input leaf of the traced program."""

    index: int                   # position in the flattened arg list
    argnum: int                  # which top-level argument it came from
    path: str                    # pytree key path, e.g. "params/conv1/kernel"
    shape: Tuple[int, ...]
    dtype: str
    donated: bool = False        # the jit declared it donated
    aliased: bool = False        # the lowered module actually aliases it
    #: False when the lowering eliminated the arg as unused — a donated
    #: eliminated arg is freed, not leaked, so it is NOT a finding
    present: bool = True

    @property
    def nbytes(self) -> int:
        return aval_nbytes(self.dtype, self.shape)


@dataclasses.dataclass
class EqnSite:
    """A jaxpr equation + where it lives (for rules to filter/report)."""

    primitive: str
    params: Dict[str, Any]
    invars: List[Tuple[str, Tuple[int, ...]]]    # (dtype, shape) per invar
    outvars: List[Tuple[str, Tuple[int, ...]]]
    file: str                    # repo-relative posix path ("" if unknown)
    line: int
    in_scan: bool                # inside a scan/while body (the hot loop)
    depth: int


class TracedEntrypoint:
    """Trace + lower one EntrypointSpec and expose its IR views."""

    def __init__(self, spec: EntrypointSpec, root,
                 prebuilt=None) -> None:
        import jax

        self.spec = spec
        self.root = root
        # ``prebuilt`` is an (fn, args) pair from EntrypointBuildCache —
        # one run_lint mixing the perf and mesh tiers builds each
        # factory once and hands the result to both
        fn, args = prebuilt if prebuilt is not None else spec.build()
        if not hasattr(fn, "trace"):
            fn = jax.jit(fn)
        self._fn = fn
        self._args = args
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            traced = fn.trace(*args)
            self._lowered = traced.lower()
        self.jaxpr = traced.jaxpr
        self.lowered_text = self._lowered.as_text()
        #: lower-time warnings, notably the dropped-donation one
        self.warnings = [str(w.message) for w in caught]
        self._compiled = None
        self._sites: Optional[List[EqnSite]] = None
        self._arg_leaves: Optional[List[ArgLeaf]] = None

    # -- compile-backed views (lazy: compiling is the expensive part) -------
    def compiled(self):
        if self._compiled is None:
            self._compiled = self._lowered.compile()
        return self._compiled

    def memory_analysis(self):
        try:
            return self.compiled().memory_analysis()
        except Exception:       # backends without the stats stay graceful
            return None

    def cost_analysis(self):
        try:
            ca = self.compiled().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            return ca
        except Exception:
            return None

    # -- donation table ------------------------------------------------------
    def arg_leaves(self) -> List[ArgLeaf]:
        """Flattened input leaves annotated with declared-donated (from the
        registry spec) and actually-aliased (from the lowered module)."""
        if self._arg_leaves is not None:
            return self._arg_leaves
        import jax

        donated = set(self.spec.donate_argnums or ())
        leaves: List[ArgLeaf] = []
        idx = 0
        for argnum, arg in enumerate(self._args):
            flat = jax.tree_util.tree_flatten_with_path(arg)[0]
            for keypath, leaf in flat:
                path = "/".join(_key_str(k) for k in keypath)
                leaves.append(ArgLeaf(
                    index=idx, argnum=argnum, path=path,
                    shape=tuple(getattr(leaf, "shape", ())),
                    dtype=str(getattr(leaf, "dtype", "?")),
                    donated=argnum in donated))
                idx += 1
        self._align_with_module(leaves)
        self._arg_leaves = leaves
        return leaves

    def _align_with_module(self, leaves: List[ArgLeaf]) -> None:
        """Mark each leaf aliased/present by aligning the ``main``
        signature's args against the flattened spec leaves.

        The lowering ELIMINATES unused args (keep_unused=False default),
        so HLO positions are a subsequence of the flat leaf order; a
        greedy in-order match by tensor type recovers the mapping.  NB
        the mapping is AMBIGUOUS when an eliminated leaf shares a tensor
        type with a later kept one — rules needing certainty must use
        ``alias_attr_count``/``hlo_arg_type_counts`` (exact, parse-only)
        or the lower-time warning set instead of these per-leaf flags."""
        li = 0
        for type_str, aliased, _ in self._hlo_args():
            while li < len(leaves) and \
                    _mlir_type(leaves[li].dtype, leaves[li].shape) \
                    != type_str:
                leaves[li].present = False      # eliminated as unused
                li += 1
            if li >= len(leaves):
                break
            leaves[li].aliased = aliased
            li += 1
        for leaf in leaves[li:]:
            leaf.present = False

    def _hlo_args(self) -> List[Tuple[str, bool, bool]]:
        """(tensor type, has tf.aliasing_output, has jax.buffer_donor)
        per ``main`` arg, in order — parsed once from the lowered module
        text."""
        if getattr(self, "_hlo_args_cache", None) is None:
            m = re.search(r"func\.func (?:public )?@main\((.*?)\)\s*->",
                          self.lowered_text, re.S)
            self._hlo_args_cache = [] if not m else [
                (am.group(1), _ALIAS_ATTR in (am.group(2) or ""),
                 _DONOR_ATTR in (am.group(2) or ""))
                for am in re.finditer(
                    r"%arg\d+:\s*tensor<([^>]*)>\s*(\{[^}]*\})?",
                    m.group(1))]
        return self._hlo_args_cache

    def alias_attr_count(self) -> int:
        """How many ``main`` args the lowered module actually aliases —
        exact (no leaf alignment involved)."""
        return sum(1 for _, aliased, _ in self._hlo_args() if aliased)

    def hlo_arg_type_counts(self) -> Dict[str, int]:
        """Tensor-type multiset of the kept ``main`` args; comparing it
        against the spec leaves' type multiset tells whether any leaf of
        a given type was eliminated (count mismatch = ambiguity)."""
        counts: Dict[str, int] = {}
        for type_str, _, _ in self._hlo_args():
            counts[type_str] = counts.get(type_str, 0) + 1
        return counts

    def dropped_donations(self) -> List[Tuple[str, Tuple[int, ...]]]:
        """(dtype, shape) of every donated buffer that ends up aliasing
        nothing.  Two exact sources, neither of which aligns HLO args
        against flat leaves (ambiguous when tensor types repeat):

        * jax's lower-time warning ("Some donated buffers were not
          usable: float32[128,128]") — no output has even the element
          count.  An unused donated arg is eliminated and freed WITHOUT
          a warning;
        * a ``jax.buffer_donor`` arg that the COMPILED module still
          lists under ``buffer_donor={...}``: the lowering left the
          pairing to XLA (same element count, other dtype/shape) and
          XLA found no output to reuse it for."""
        out: List[Tuple[str, Tuple[int, ...]]] = []
        for w in self.warnings:
            if "donated buffers were not usable" not in w.lower():
                continue
            for m in re.finditer(r"\b([a-z]+[0-9]*)\[([0-9,\s]*)\]", w):
                out.append((m.group(1), _dims(m.group(2).split(","))))
        args = self._hlo_args()
        if any(donor for _, _, donor in args):
            header = self.compiled().as_text().split("\n", 1)[0]
            m = re.search(r"buffer_donor=\{([^}]*(?:\{\}[^}]*)*)\}", header)
            for pm in re.finditer(r"\((\d+),", m.group(1) if m else ""):
                *dims, el = args[int(pm.group(1))][0].split("x")
                out.append((_NP_DTYPES.get(el, el), _dims(dims)))
        return out

    # -- jaxpr walk ----------------------------------------------------------
    def eqn_sites(self) -> List[EqnSite]:
        if self._sites is None:
            self._sites = list(self._walk(self.jaxpr.jaxpr, False, 0))
        return self._sites

    def _walk(self, jaxpr, in_scan: bool, depth: int) -> Iterator[EqnSite]:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            file, line = self._source_of(eqn)
            yield EqnSite(
                primitive=prim,
                params=dict(eqn.params),
                invars=[(str(v.aval.dtype), tuple(v.aval.shape))
                        for v in eqn.invars if hasattr(v, "aval")],
                outvars=[(str(v.aval.dtype), tuple(v.aval.shape))
                         for v in eqn.outvars if hasattr(v, "aval")],
                file=file, line=line, in_scan=in_scan, depth=depth)
            sub_scan = in_scan or prim in ("scan", "while")
            for v in eqn.params.values():
                for item in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(item, "jaxpr", None)
                    if inner is not None:
                        yield from self._walk(inner, sub_scan, depth + 1)

    def _source_of(self, eqn) -> Tuple[str, int]:
        """Innermost user frame of an eqn, repo-relative ("" when the frame
        falls outside the lint root, e.g. site-packages flax)."""
        try:
            from jax._src import source_info_util

            frame = source_info_util.user_frame(eqn.source_info.traceback)
            if frame is None:
                return "", 0
            fname = frame.file_name
            line = int(frame.start_line)
        except Exception:
            return "", 0
        try:
            from pathlib import Path

            rel = Path(fname).resolve().relative_to(
                Path(self.root).resolve())
            return rel.as_posix(), line
        except Exception:
            return "", 0

    def source_line(self, file: str, line: int) -> str:
        """The raw source text at file:line (for explicitness checks)."""
        try:
            from pathlib import Path

            lines = (Path(self.root) / file).read_text(
                encoding="utf-8").splitlines()
            return lines[line - 1] if 0 < line <= len(lines) else ""
        except Exception:
            return ""


#: numpy dtype name → MLIR element type (tensor<...> rendering)
_MLIR_DTYPES = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int8": "i8", "int16": "i16", "int32": "i32",
    "int64": "i64", "uint8": "ui8", "uint16": "ui16", "uint32": "ui32",
    "uint64": "ui64", "bool": "i1", "complex64": "complex<f32>",
}


_NP_DTYPES = {v: k for k, v in _MLIR_DTYPES.items()}


def _dims(parts) -> Tuple[int, ...]:
    return tuple(int(d) for d in parts if d.strip())


def _mlir_type(dtype: str, shape: Tuple[int, ...]) -> str:
    el = _MLIR_DTYPES.get(dtype, dtype)
    return "x".join([str(int(s)) for s in shape] + [el])


#: public alias — rules compare leaf avals against hlo_arg_type_counts()
aval_mlir_type = _mlir_type


def _key_str(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k).strip("[].'\"")


def nelems(shape: Tuple[int, ...]) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def aval_nbytes(dtype: str, shape: Tuple[int, ...]) -> int:
    """Bytes of one (dtype, shape) aval — shared by ArgLeaf and the
    donation rule so the unknown-dtype fallback lives in one place."""
    import numpy as np

    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 4
    return nelems(shape) * itemsize
