"""FedMLAggOperator — server-side aggregation arithmetic.

Capability parity: reference `ml/aggregator/agg_operator.py:10-234` — weighted
averaging for FedAvg/FedProx/FedAvg_seq/FedOpt/FedDyn, SCAFFOLD
(weights + control variates), Mime (weights + grads), per-engine variants.

TPU-first redesign: ONE engine. Params are pytrees; aggregation is
``jax.tree_util`` math, never per-key Python loops over OrderedDicts. Three
entry points:

* ``agg(args, [(n_k, pytree), ...])`` — host-driven planes (SP, cross-silo).
* ``agg_stacked(stacked_pytree, weights)`` — vectorized Parrot path: client
  axis is a leading array dimension; one fused weighted reduction that XLA
  maps onto the VPU/MXU.
* ``agg_psum(update, weight, axis_name)`` — mesh path: weighted mean via
  ``lax.psum`` over the ``clients`` mesh axis (ICI collective), for use inside
  ``shard_map``.

Deliberate semantic matches with the reference (documented per SURVEY §7):
SCAFFOLD control variates average uniformly over ``client_num_in_total``
(`agg_operator.py:100-118`), not by sample count.
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...constants import (
    FED_OPT_MIME,
    FED_OPT_SCAFFOLD,
)
from ...ops import epilogue as _epilogue


def weighted_average(grad_list: Sequence[Tuple[float, Any]]) -> Any:
    """Sample-count weighted average of pytrees (reference :33-62)."""
    total = float(sum(n for n, _ in grad_list))
    if total <= 0:
        total = float(len(grad_list))
        grad_list = [(1.0, g) for _, g in grad_list]
    ws = [n / total for n, _ in grad_list]
    trees = [g for _, g in grad_list]
    return jax.tree_util.tree_map(
        lambda *leaves: sum(w * leaf for w, leaf in zip(ws, leaves)), *trees
    )


def uniform_average(trees: Sequence[Any], denom: float = None) -> Any:
    denom = float(denom if denom is not None else len(trees))
    return jax.tree_util.tree_map(
        lambda *leaves: sum(leaves) / denom, *trees
    )


def agg_stacked(stacked: Any, weights: jnp.ndarray, **epilogue_kw) -> Any:
    """Weighted average over a leading client axis.

    ``stacked``: pytree whose leaves have shape [n_clients, ...];
    ``weights``: [n_clients] nonnegative (need not be normalized — masked-out
    clients carry weight 0, which implements *selective* aggregation without
    dynamic shapes).

    Accumulation runs in float32 regardless of the leaf dtype (a bf16 sum
    over many clients loses low-order bits), and the reduced leaf is cast
    BACK to its input dtype — a bf16 model tree comes back bf16, not
    silently widened to f32.  Non-float leaves keep the f32 result (a
    "weighted average" of integers is fractional by construction).

    Routed through the fused round-epilogue kernel family
    (``ops/epilogue.py``): on TPU each leaf is one pallas HBM pass; off
    TPU the jnp fallback is this contract's original math, bit for bit.
    ``epilogue_kw`` passes through to ``weighted_reduce``.
    """
    return _epilogue.weighted_reduce(stacked, weights, **epilogue_kw)


def mix_global(global_tree: Any, agg_tree: Any, server_lr: Any) -> Any:
    """Server-rate mixing ``global ← global + server_lr · (agg − global)``
    in the global leaf's dtype (``server_lr`` = 1.0 replaces outright, the
    sync-equivalent).  Non-float leaves take the aggregate as-is — a
    fractional mix of step counters is meaningless.  Jittable (traced by
    the ``async/aggregate_buffer`` registry entry) and host-callable (the
    buffered-async server mixes with it after the robust funnel)."""

    def _mix(g, a):
        ga, aa = jnp.asarray(g), jnp.asarray(a)
        if not jnp.issubdtype(ga.dtype, jnp.floating):
            return aa
        # mix in f32, come back in the global's dtype: an f32 server_lr
        # would otherwise PROMOTE a bf16 mix to f32 — silently widening
        # the global and (under jit) dropping the donated-global alias
        gf = ga.astype(jnp.float32)
        mixed = gf + jnp.asarray(server_lr, jnp.float32) * (
            aa.astype(jnp.float32) - gf)
        return mixed.astype(ga.dtype)

    return jax.tree_util.tree_map(_mix, global_tree, agg_tree)


def fold_buffer(global_tree: Any, stacked: Any, weights: jnp.ndarray,
                server_lr: Any = 1.0) -> Any:
    """Buffered-async fold core (PR-6 ``aggregate_buffer``), jittable:
    staleness-decayed ``weights`` ([n_buffer], computed host-side by
    ``staleness_fn`` × sample counts) weight one fused reduction over the
    stacked update buffer, and the result mixes into the global at
    ``server_lr``.  The device-side hot path of the async server — the
    ``async/aggregate_buffer`` registry entry traces exactly this.

    Reduce + mix run as ONE fused-epilogue pass per leaf (on TPU, one
    pallas program; the jnp fallback composes ``mix_global`` over
    ``agg_stacked`` exactly, so off-TPU folds are unchanged)."""
    return _epilogue.fused_epilogue(global_tree, stacked, weights,
                                    server_lr)[0]


def _stackable_payload(grad_list: Sequence[Tuple[float, Any]]) -> bool:
    """True when every client payload is the same pytree of numeric
    arrays with matching shapes/dtypes — the precondition for routing
    the host-driven funnel through the stacked fused reduction.  FHE
    ciphertexts, ragged trees and scalar payloads fall back to
    ``weighted_average``."""
    try:
        trees = [g for _, g in grad_list]
        defs = [jax.tree_util.tree_structure(t) for t in trees]
        if any(d != defs[0] for d in defs[1:]):
            return False
        rows = [jax.tree_util.tree_leaves(t) for t in trees]
        first = rows[0]
        if not first:
            return False
        for leaves in rows:
            for a, b in zip(first, leaves):
                if not (hasattr(b, "shape") and hasattr(b, "dtype")):
                    return False
                if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                    return False
                if not (jnp.issubdtype(b.dtype, jnp.floating)
                        or jnp.issubdtype(b.dtype, jnp.integer)):
                    return False
        return True
    except Exception:
        return False


def agg_psum(update: Any, weight: jnp.ndarray, axis_name: str) -> Any:
    """Weighted mean across a mesh axis — the NCCL-allreduce equivalent
    (reference `simulation/nccl/.../LocalAggregator.py:69-80`) as an XLA
    collective riding ICI."""
    total = jax.lax.psum(weight, axis_name)
    return jax.tree_util.tree_map(
        lambda x: jax.lax.psum(x * weight, axis_name) / jnp.maximum(total, 1e-12),
        update,
    )


class FedMLAggOperator:
    """Dispatch on ``args.federated_optimizer`` (reference :10-30), with a
    byzantine-robust override: ``args.robust_agg`` replaces the weighted
    average with a stacked robust operator (trimmed mean / median / Krum /
    geometric median / norm clipping — `ml/aggregator/robust.py`) on every
    plane that funnels through here (SP, cross-silo server)."""

    @staticmethod
    def _reduce(args: Any, grad_list: List[Tuple[float, Any]],
                center: Any = None) -> Any:
        """One weighted reduction — robust when ``args.robust_agg`` asks
        for it, the plain sample-weighted average otherwise."""
        from .robust import parse_robust_agg, robust_agg_stacked, stack_grad_list

        spec = parse_robust_agg(getattr(args, "robust_agg", None))
        if spec is None or not grad_list:
            if (grad_list
                    and bool(getattr(args, "fused_epilogue", True))
                    and _stackable_payload(grad_list)):
                # fused funnel: stack once, reduce every leaf in a single
                # f32-accumulating epilogue pass (the agg_stacked
                # contract; on TPU a pallas kernel).  Zero-total rounds
                # keep weighted_average's uniform-fallback semantics.
                stacked = stack_grad_list([g for _, g in grad_list])
                total = float(sum(n for n, _ in grad_list))
                weights = (jnp.ones((len(grad_list),), jnp.float32)
                           if total <= 0 else
                           jnp.asarray([float(n) for n, _ in grad_list],
                                       jnp.float32))
                return agg_stacked(stacked, weights)
            return weighted_average(grad_list)
        # a single-result round still goes through the operator: every op
        # degenerates to that client EXCEPT norm_clip, which must keep
        # clipping exactly when a lone upload has maximal influence
        stacked = stack_grad_list([g for _, g in grad_list])
        weights = jnp.asarray([float(n) for n, _ in grad_list], jnp.float32)
        return robust_agg_stacked(spec, stacked, weights, center=center)

    @staticmethod
    def agg(args: Any, raw_grad_list: List[Tuple[float, Any]],
            center: Any = None) -> Any:
        """``center`` is the current global model when the caller has one
        (ServerAggregator passes it) — the clipping center for
        ``robust_agg=norm_clip:C``; ignored by every other path."""
        opt = getattr(args, "federated_optimizer", "FedAvg")
        # pair-payload paths apply only when callers actually ship
        # (params, extra) tuples (reference passes state+variate pairs)
        is_pair = raw_grad_list and isinstance(raw_grad_list[0][1], tuple)
        if not is_pair and opt in (FED_OPT_SCAFFOLD, FED_OPT_MIME):
            return FedMLAggOperator._reduce(args, raw_grad_list, center)
        if opt == FED_OPT_SCAFFOLD:
            # items are (n_k, (params, c_delta)); weights by n_k, c uniform
            # over client_num_in_total (reference :100-118).  The robust
            # operator applies to the PARAMS component only: control
            # variates average uniformly by contract, and a byzantine
            # variate's reach is bounded by 1/client_num_in_total.
            n_total = float(getattr(args, "client_num_in_total", len(raw_grad_list)))
            params_avg = FedMLAggOperator._reduce(
                args, [(n, pair[0]) for n, pair in raw_grad_list], center)
            c_avg = uniform_average(
                [pair[1] for _, pair in raw_grad_list], denom=n_total)
            return params_avg, c_avg
        if opt == FED_OPT_MIME:
            # items are (n_k, (params, grads)): both sample-weighted
            # (:120-134) — and both robustly reduced under robust_agg (a
            # poisoned full-grad corrupts the server momentum just as
            # surely as poisoned params corrupt the model)
            params_avg = FedMLAggOperator._reduce(
                args, [(n, pair[0]) for n, pair in raw_grad_list], center)
            grads_avg = FedMLAggOperator._reduce(
                args, [(n, pair[1]) for n, pair in raw_grad_list])
            return params_avg, grads_avg
        return FedMLAggOperator._reduce(args, raw_grad_list, center)
