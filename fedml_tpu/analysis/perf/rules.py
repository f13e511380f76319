"""PERF rule family — IR-level performance lints over registered jit
entrypoints (docs/STATIC_ANALYSIS.md "Perf tier" has the catalog).

Each rule reads a ``TracedEntrypoint`` (jaxpr + lowered StableHLO text +
lazy compile stats) and yields findings whose messages are LINE-FREE and
shape-keyed, so the shared fingerprint/baseline machinery stays stable
under unrelated source churn.  jax is never imported at module scope.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Tuple

from ..findings import SEV_ERROR, SEV_WARNING, Finding
from .tracing import TracedEntrypoint, aval_nbytes, nelems

#: PERF002/PERF004 ignore tensors smaller than this (elementwise noise)
DEFAULT_MIN_ELEMS = 4096
#: PERF001 ignores donated leaves smaller than this (bytes)
DEFAULT_MIN_DONATED_BYTES = 1024
#: PERF001's missing-donation clause needs this much matchable in→out
#: traffic before it speaks up (tiny programs gain nothing from donation)
DEFAULT_MIN_MATCH_BYTES = 64 * 1024
#: f32 accumulation sanctioned by design — the aggregation kernels widen
#: deliberately (a bf16 sum over many clients loses low-order bits)
SANCTIONED_WIDEN_PATHS = (
    "fedml_tpu/ml/aggregator/agg_operator.py",
    "fedml_tpu/ml/aggregator/robust.py",
)
#: source-text markers that make a transpose EXPLICIT (autodiff inserts
#: transposes too, attributed to the forward op's line — those lines
#: won't contain any of these tokens, so they are filtered out).  The
#: ``.T`` attribute is matched case-sensitively on a word boundary.
_TRANSPOSE_TOKENS = ("transpose", "swapaxes", "moveaxis", "einsum",
                     "rearrange", "permute")
_TRANSPOSE_ATTR_RE = None  # compiled lazily (avoids re at import in hot path)


def _is_explicit_transpose(text: str) -> bool:
    global _TRANSPOSE_ATTR_RE
    low = text.lower()
    if any(tok in low for tok in _TRANSPOSE_TOKENS):
        return True
    if _TRANSPOSE_ATTR_RE is None:
        import re

        _TRANSPOSE_ATTR_RE = re.compile(r"\.T\b")
    return bool(_TRANSPOSE_ATTR_RE.search(text))

_PERF_REGISTRY: List[type] = []


class PerfRule:
    """Base: one rule instance sees every traced entrypoint once."""

    id: str = ""
    severity: str = SEV_WARNING
    title: str = ""

    def check_entrypoint(self, traced: TracedEntrypoint
                         ) -> Iterable[Finding]:
        return ()


def register_perf(cls):
    _PERF_REGISTRY.append(cls)
    return cls


def make_perf_rules() -> List[PerfRule]:
    return [cls() for cls in _PERF_REGISTRY]


def perf_rule_ids() -> List[str]:
    return [cls.id for cls in _PERF_REGISTRY]


def _entry_site(traced: TracedEntrypoint) -> Tuple[str, int]:
    """(path, line) findings anchor to when they concern the whole
    entrypoint rather than one source equation — the registration site,
    so a ``# fedml: noqa[...]`` next to ``register_jit_entrypoint`` works."""
    return traced.spec.path or "fedml_tpu/analysis/perf/entrypoints.py", \
        int(traced.spec.meta.get("src_line", 1) or 1)


def _fmt_shape(dtype: str, shape: Tuple[int, ...]) -> str:
    return f"{dtype}[{','.join(str(s) for s in shape)}]"


@register_perf
class DonationAuditRule(PerfRule):
    """PERF001 — donated args the lowered program does not actually alias
    (dtype/layout mismatch silently drops donation → both buffers live at
    peak), and large in→out pytrees updated in place with no donation
    declared at all."""

    id = "PERF001"
    severity = SEV_WARNING
    title = "buffer-donation audit on jit entrypoints"

    def check_entrypoint(self, traced):
        spec = traced.spec
        path, line = _entry_site(traced)
        min_bytes = int(spec.meta.get(
            "donation_min_bytes", DEFAULT_MIN_DONATED_BYTES))
        leaves = traced.arg_leaves()
        if spec.donate_argnums:
            # the lower-time warning is the authoritative dropped set (it
            # fires exactly on mismatches, never on eliminated unused
            # args); leaf paths are attached as ATTRIBUTION, matched by
            # aval among the declared-donated leaves
            dropped = traced.dropped_donations()
            for dtype, shape in dropped:
                if aval_nbytes(dtype, shape) < min_bytes:
                    continue
                candidates = [leaf.path or f"arg{leaf.argnum}"
                              for leaf in leaves
                              if leaf.donated and leaf.dtype == dtype
                              and leaf.shape == shape]
                where = (" (candidate leaves: "
                         + ", ".join(sorted(set(candidates))[:4]) + ")"
                         if candidates else "")
                yield Finding(
                    self.id, self.severity, path, line, 0,
                    f"entrypoint '{spec.name}': a donated "
                    f"{_fmt_shape(dtype, shape)} buffer is not aliased "
                    f"by the lowered program — the donation is silently "
                    f"dropped and both buffers stay live at peak (fix "
                    f"the dtype/shape mismatch between the donated "
                    f"input and its output){where}")
            # vacuous-audit guard: the registration DECLARES donation but
            # the traced program aliases NOTHING and no mismatch warning
            # fired — the jit itself almost certainly lost its
            # donate_argnums (a declared+usable donation leaves
            # tf.aliasing_output; a declared+unusable one warns; an
            # unused one is eliminated silently).  Deliberately built on
            # EXACT module facts, not the per-leaf alignment: an
            # eliminated donated arg sharing a tensor type with a kept
            # one makes the alignment ambiguous, so the guard only fires
            # when every donated leaf's type multiset survives intact
            # (nothing of those types was eliminated).
            donated_leaves = [leaf for leaf in leaves if leaf.donated]
            if not dropped and donated_leaves \
                    and traced.alias_attr_count() == 0:
                from .tracing import aval_mlir_type

                hlo_counts = traced.hlo_arg_type_counts()
                leaf_counts: Dict[str, int] = {}
                for leaf in leaves:
                    t = aval_mlir_type(leaf.dtype, leaf.shape)
                    leaf_counts[t] = leaf_counts.get(t, 0) + 1
                donated_types = {aval_mlir_type(leaf.dtype, leaf.shape)
                                 for leaf in donated_leaves}
                unambiguous = all(
                    hlo_counts.get(t, 0) == leaf_counts.get(t, 0)
                    for t in donated_types)
                total = sum(leaf.nbytes for leaf in donated_leaves)
                if unambiguous and total >= min_bytes:
                    yield Finding(
                        self.id, self.severity, path, line, 0,
                        f"entrypoint '{spec.name}': registration "
                        f"declares donate_argnums="
                        f"{tuple(spec.donate_argnums)} but the traced "
                        f"program aliases NONE of the {total} donated "
                        f"input bytes and no mismatch warning fired — "
                        f"the jit call itself likely lost its "
                        f"donate_argnums (re-donate at the jax.jit, or "
                        f"fix the registration)")
            return
        if spec.donate_argnums == ():      # explicit, documented opt-out
            return
        # no donation declared: pair outputs with same-(shape,dtype) input
        # leaves; enough matchable bytes → the jit should donate
        out_shapes = self._output_avals(traced)
        matchable = 0
        budget: Dict[Tuple[str, Tuple[int, ...]], int] = {}
        for dtype, shape in out_shapes:
            budget[(dtype, shape)] = budget.get((dtype, shape), 0) + 1
        for leaf in leaves:
            if not leaf.present:
                continue
            key = (leaf.dtype, leaf.shape)
            if budget.get(key, 0) > 0:
                budget[key] -= 1
                matchable += leaf.nbytes
        min_match = int(spec.meta.get(
            "donation_min_match_bytes", DEFAULT_MIN_MATCH_BYTES))
        if matchable >= min_match:
            yield Finding(
                self.id, self.severity, path, line, 0,
                f"entrypoint '{spec.name}': {matchable} bytes of inputs "
                f"have shape/dtype-identical outputs but the jit declares "
                f"no donate_argnums — an in-place update pytree that "
                f"could alias is copied instead (donate it, or register "
                f"with donate_argnums=() to record that inputs are "
                f"reused after the call)")

    @staticmethod
    def _output_avals(traced) -> List[Tuple[str, Tuple[int, ...]]]:
        return [(str(v.aval.dtype), tuple(v.aval.shape))
                for v in traced.jaxpr.jaxpr.outvars if hasattr(v, "aval")]


@register_perf
class DtypeWideningRule(PerfRule):
    """PERF002 — bf16/f16 tensors upcast to f32 inside the traced program
    (convert_element_type), outside the sanctioned f32 accumulation in the
    aggregation kernels and outside the entrypoint's ``widen_allow``
    paths.  Each distinct source site reports once per entrypoint."""

    id = "PERF002"
    severity = SEV_WARNING
    title = "silent low-precision→f32 widening in hot bodies"

    def check_entrypoint(self, traced):
        spec = traced.spec
        min_elems = int(spec.meta.get("widen_min_elems",
                                      DEFAULT_MIN_ELEMS))
        allow = tuple(SANCTIONED_WIDEN_PATHS) + tuple(
            spec.meta.get("widen_allow", ()))
        seen = set()
        for site in traced.eqn_sites():
            if site.primitive != "convert_element_type" or not site.invars:
                continue
            in_dtype, in_shape = site.invars[0]
            out_dtype = site.outvars[0][0] if site.outvars else ""
            if in_dtype not in ("bfloat16", "float16") \
                    or out_dtype != "float32":
                continue
            if nelems(in_shape) < min_elems:
                continue
            # frames outside the repo (flax norm internals etc.) implement
            # their own mixed-precision policy — not ours to lint
            if not site.file:
                continue
            if any(site.file.startswith(p) for p in allow):
                continue
            key = (site.file, site.line)
            if key in seen:
                continue
            seen.add(key)
            yield Finding(
                self.id, self.severity, site.file, site.line, 0,
                f"entrypoint '{spec.name}': "
                f"{_fmt_shape(in_dtype, in_shape)} widens to float32 in "
                f"the traced hot path ({nelems(in_shape)} elems — doubles "
                f"the bandwidth of every downstream op); keep the chain "
                f"in {in_dtype} or add the site to the entrypoint's "
                f"widen_allow with a justification")


@register_perf
class PaddingWasteRule(PerfRule):
    """PERF003 — static audit of a size-bucketing policy: per-bucket
    padded-vs-real-executed ratio from the dataset histogram the
    entrypoint registers (``meta["bucket_stats"]`` dict or
    ``meta["bucket_stats_fn"]`` callable).  Flags buckets whose padded
    compute exceeds the expected real samples by more than
    ``padding_bucket_threshold`` (default 25%) and the whole round when
    the total exceeds ``padding_round_threshold`` (default 20%)."""

    id = "PERF003"
    severity = SEV_WARNING
    title = "padded-vs-real waste in the size-bucket policy"

    def check_entrypoint(self, traced):
        spec = traced.spec
        stats = spec.meta.get("bucket_stats")
        fn = spec.meta.get("bucket_stats_fn")
        if stats is None and callable(fn):
            stats = fn()
        if not stats:
            return
        path, line = _entry_site(traced)
        thr_b = float(spec.meta.get("padding_bucket_threshold", 0.25))
        thr_r = float(spec.meta.get("padding_round_threshold", 0.20))
        tot_padded = tot_real = 0.0
        for i, b in enumerate(stats.get("buckets", ())):
            padded = float(b["padded"])
            real = max(float(b["real"]), 1e-9)
            tot_padded += padded
            tot_real += real
            if padded / real - 1.0 > thr_b and padded >= 64:
                yield Finding(
                    self.id, self.severity, path, line, 0,
                    f"entrypoint '{spec.name}': bucket {i} pads "
                    f"{int(padded)} sample slots for {real:.0f} expected "
                    f"real samples ({padded / real - 1.0:+.0%} waste) — "
                    f"cap the bucket's batch capacity nearer its size "
                    f"distribution (rotating window for over-cap clients)")
        if tot_real > 0 and tot_padded / tot_real - 1.0 > thr_r:
            yield Finding(
                self.id, self.severity, path, line, 0,
                f"entrypoint '{spec.name}': round-level padding waste "
                f"{tot_padded / tot_real - 1.0:+.0%} "
                f"({int(tot_padded)} padded vs {tot_real:.0f} real "
                f"samples per round) exceeds {thr_r:.0%} — tighten the "
                f"bucketing policy")


@register_perf
class ScanLayoutRule(PerfRule):
    """PERF004 — explicit layout-changing transposes/copies inside
    scan/while bodies (the ROADMAP-named rule).  Autodiff also inserts
    transposes, attributed to the forward op's source line; a site only
    fires when its source text actually spells a transpose-like call, so
    backward-pass artifacts are filtered out."""

    id = "PERF004"
    severity = SEV_WARNING
    title = "layout-changing transpose/copy inside a scan body"

    def check_entrypoint(self, traced):
        spec = traced.spec
        min_elems = int(spec.meta.get("layout_min_elems",
                                      DEFAULT_MIN_ELEMS))
        allow = tuple(spec.meta.get("layout_allow", ()))
        seen = set()
        for site in traced.eqn_sites():
            if site.primitive not in ("transpose", "copy"):
                continue
            if not site.in_scan or not site.invars:
                continue
            in_dtype, in_shape = site.invars[0]
            if nelems(in_shape) < min_elems:
                continue
            if not site.file or any(site.file.startswith(p)
                                    for p in allow):
                continue
            if not _is_explicit_transpose(
                    traced.source_line(site.file, site.line)):
                continue
            key = (site.file, site.line)
            if key in seen:
                continue
            seen.add(key)
            yield Finding(
                self.id, self.severity, site.file, site.line, 0,
                f"entrypoint '{spec.name}': explicit "
                f"{site.primitive} of {_fmt_shape(in_dtype, in_shape)} "
                f"inside a scan body — a layout-changing copy every "
                f"iteration; hoist it out of the loop or restructure the "
                f"layout so the loop body reads it contiguously")


@register_perf
class HostCallbackRule(PerfRule):
    """PERF005 — host callbacks / forced syncs reachable from a jitted
    entrypoint (escalates the JAX003 AST heuristic to an IR fact: the
    callback primitive is IN the traced program, so every execution round
    trips to the host)."""

    id = "PERF005"
    severity = SEV_ERROR
    title = "host callback reachable from a jit entrypoint"

    # jax 0.9 lowers jax.debug.print to its own `debug_print` primitive
    # (it was a `debug_callback` before)
    _PRIMS = ("debug_callback", "debug_print", "pure_callback",
              "io_callback", "host_callback", "outside_call", "infeed",
              "outfeed")

    def check_entrypoint(self, traced):
        spec = traced.spec
        seen = set()
        for site in traced.eqn_sites():
            if not any(site.primitive.startswith(p) for p in self._PRIMS):
                continue
            file = site.file or _entry_site(traced)[0]
            line = site.line or _entry_site(traced)[1]
            key = (file, line, site.primitive)
            if key in seen:
                continue
            seen.add(key)
            where = "a scan body" if site.in_scan else "the traced program"
            yield Finding(
                self.id, self.severity, file, line, 0,
                f"entrypoint '{spec.name}': {site.primitive} reachable "
                f"from {where} — every execution synchronizes with the "
                f"host; move the I/O outside the jit or behind a "
                f"device-buffered metrics path")
