"""Parrot-TPU — vectorized federated simulation.

Capability parity: reference `simulation/mpi/` + `simulation/nccl/` (SURVEY
§2.4) — scaling simulated clients over hardware.  The reference does it with
MPI worker processes and NCCL reduce; this build does it the TPU way
(SURVEY §7 step 4):

* The WHOLE ROUND is one jit-compiled function: gather the sampled clients'
  padded batches from the device-resident dataset (XLA gather, no host
  transfer), ``vmap`` the local-update engine over the client axis, and
  aggregate with a fused weighted reduction (`agg_stacked`).
* Per-client algorithm state (SCAFFOLD control variates, FedDyn lambdas) is a
  stacked leading-axis pytree, gathered/scattered by client id inside the
  same jit.
* ``use_mesh=True`` shards the client axis over the `clients` mesh axis with
  ``with_sharding_constraint``; XLA lowers the aggregation sum to psum-style
  collectives over ICI — the NCCL-allreduce equivalent
  (`simulation/nccl/.../LocalAggregator.py:69-80`) with zero manual
  communication code.

Host work per round: sampling client ids (numpy, reference-parity seeding)
and logging.  Everything else stays in HBM.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...constants import (
    AXIS_CLIENTS,
    FED_OPT_FEDDYN,
    FED_OPT_FEDNOVA,
    FED_OPT_FEDOPT,
    FED_OPT_MIME,
    FED_OPT_SCAFFOLD,
)
from ...core import mlops
from ...core.mlops import flight_recorder, ledger
from ...core.mlops.lock_profiler import named_lock
from ...ml.aggregator.agg_operator import agg_stacked
from ...ml.aggregator.robust import parse_robust_agg, robust_agg_stacked
from ...ops import epilogue as _epilogue
from ...ml.engine.local_update import build_eval_step, build_local_update, make_batches
from ...ml.engine.mesh import MeshManager, build_hybrid_mesh, build_mesh
from ...ml.engine.optimizers import build_server_optimizer
from jax.sharding import NamedSharding, PartitionSpec as P


def bucket_plan(sizes: np.ndarray, k: int, bs: int, n_buckets: int,
                cap_ratio: float = 0.0) -> List[Dict[str, Any]]:
    """Pure size-bucket policy — shared by ``ParrotAPI._build_buckets``,
    bench.py's per-bucket waste report and the PERF003 padding-waste lint.

    Clients sort by size into ``B`` equal-count strata (B snapped to a
    divisor of ``k`` so quotas stay equal — every client's inclusion
    probability is exactly k/N).  Each stratum's batch capacity is

    * ``cap_ratio == 0``: ``nb = ceil(max_size_in_stratum / bs)`` — every
      sampled client runs its full local epoch (reference semantics), at
      the cost of padding every stratum to its LARGEST member.
    * ``cap_ratio > 0``:  ``nb = ceil(cap_ratio·mean_size / bs)`` (capped
      at the full capacity) — clients above the cap run a per-round
      ROTATING window of ``nb·bs`` of their samples instead of a full
      epoch, so padded compute tracks the stratum's mean, not its max.
      Coverage is preserved across rounds (the window start is uniform
      per round) and aggregation weights still use full sample counts.

    Returns one dict per stratum: ``members`` (client ids, size-sorted),
    ``q`` (clients sampled per round), ``nb`` (compute batch capacity),
    ``nb_full`` (capacity covering the largest member — the index-matrix
    width rotation addresses into), ``padded`` (q·nb·bs slots per round)
    and ``real`` (q·E[min(size, nb·bs)] expected real samples per round).
    """
    sizes = np.asarray(sizes)
    n_total = int(sizes.shape[0])
    divisors = [d for d in range(1, int(k) + 1)
                if int(k) % d == 0 and d <= n_total]
    b_eff = min(divisors, key=lambda d: (abs(d - int(n_buckets)), -d))
    order = np.argsort(sizes, kind="stable")
    groups = [g for g in np.array_split(order, b_eff) if len(g)]
    q = int(k) // len(groups)
    plan = []
    for g in groups:
        gsz = sizes[g]
        nb_full = max(1, -(-int(gsz.max()) // int(bs)))
        nb = nb_full
        if cap_ratio and cap_ratio > 0:
            cap = max(1, int(round(float(cap_ratio) * float(gsz.mean()))))
            nb = min(nb_full, max(1, -(-cap // int(bs))))
        quota = int(min(q, len(g)))
        capn = nb * int(bs)
        plan.append({
            "members": g.astype(np.int64),
            "q": quota,
            "nb": nb,
            "nb_full": nb_full,
            "padded": quota * capn,
            "real": float(quota * np.minimum(gsz, capn).mean()),
        })
    return plan


# ---------------------------------------------------------------------------
# Shared round-engine pieces.  ParrotAPI (device-resident dataset) and the
# hyper-scale streaming path (simulation/parrot/hyperscale.py — host-assembled
# cohorts, population too large for HBM) run the SAME per-cohort arithmetic:
# vmapped local updates over a stacked client axis, per-algorithm server-state
# handling, fused weighted aggregation.  These module-level functions are that
# shared contract; the two APIs differ only in how the batch grids reach the
# device.
# ---------------------------------------------------------------------------

def per_client_algo_state(algo: str, server_state: Dict[str, Any],
                          client_ids) -> Dict[str, Any]:
    """Per-cohort gather of the per-client algorithm state (SCAFFOLD
    variates, FedDyn lambdas) from the stacked ``[N, ...]`` server tables.
    Runs inside the round jit — when the tables are laid out sharded along
    the client axis, XLA lowers this to the cross-device cohort gather."""
    if algo == FED_OPT_SCAFFOLD:
        return {
            "c_global": server_state["c_global"],
            "c_local": jax.tree_util.tree_map(
                lambda t: t[client_ids], server_state["c_locals"]),
        }
    if algo == FED_OPT_FEDDYN:
        return {"feddyn_lambda": jax.tree_util.tree_map(
            lambda t: t[client_ids], server_state["lambdas"])}
    if algo == FED_OPT_MIME:
        return {"server_momentum": server_state["momentum"]}
    return {}


def algo_in_axes(algo: str):
    """vmap in_axes for the algo_state argument of ``local_update``."""
    return {
        FED_OPT_SCAFFOLD: {"c_global": None, "c_local": 0},
        FED_OPT_FEDDYN: {"feddyn_lambda": 0},
        FED_OPT_MIME: {"server_momentum": None},
    }.get(algo)


def grid_sharding(mesh, k_b: int, bs: int) -> Optional[NamedSharding]:
    """How a ``[K, nb, bs, ...]`` batch grid shards over the mesh.

    Prefer the client axis (pure client parallelism, aggregation lowers
    to one all-reduce over the mesh).  When a cohort/bucket quota K is
    smaller than the mesh, shard the INTRA-BATCH axis instead: each
    client's SGD step becomes data-parallel over devices and XLA inserts
    the gradient all-reduce.  Falls back to replicated (None) when
    neither axis divides the mesh.  Balanced layouts first (exact
    divisibility on either axis), then UNEVEN sharding (GSPMD pads the
    ragged shard) — never silently replicate while an axis is at least
    mesh-sized."""
    if mesh is None:
        return None
    names = tuple(mesh.axis_names)
    msize = int(np.prod([mesh.shape[n] for n in names]))
    if msize <= 1:
        return None
    if k_b % msize == 0:
        return NamedSharding(mesh, P(names))
    if bs % msize == 0:
        return NamedSharding(mesh, P(None, None, names))
    if k_b >= msize:
        return NamedSharding(mesh, P(names))
    if bs >= msize:
        return NamedSharding(mesh, P(None, None, names))
    logging.warning(
        "parrot mesh: clients-per-step %d and batch_size %d are both "
        "smaller than the %d-device mesh — running replicated", k_b,
        bs, msize)
    return None


def stacked_client_sharding(mesh) -> Optional[NamedSharding]:
    """Leading-axis sharding for ``[N, ...]`` per-client state tables:
    the client axis spreads over EVERY mesh axis so state capacity scales
    with chips instead of replicating N copies of the table."""
    if mesh is None:
        return None
    names = tuple(mesh.axis_names)
    if int(np.prod([mesh.shape[n] for n in names])) <= 1:
        return None
    return NamedSharding(mesh, P(names))


def build_aggregate(args: Any, algo: str, n_total: int,
                    server_tx: Any = None, mesh: Any = None):
    """Shared post-vmap logic: weighted aggregation + per-algorithm
    server-state update, operating on stacked per-client outputs (the
    uniform round, the bucketed round and the hyper-scale streaming round
    all feed the same contract).

    ``robust_agg`` swaps the fused weighted mean for a stacked robust
    operator (`ml/aggregator/robust.py`) INSIDE the same jit — the
    per-client outputs already carry the leading client axis the robust
    kernels consume, so byzantine-robust rounds cost one fused
    sort/distance reduction, not a host round-trip.

    ``mesh`` is the mesh the surrounding jit is partitioned over, if any.
    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so over more than one device the epilogue keeps to its
    jnp form, which XLA lowers to the all-reduce over the client axis;
    on one device the kernel choice stays the epilogue's own."""
    robust_spec = parse_robust_agg(getattr(args, "robust_agg", None))
    prefer_pallas = (False if mesh is not None and mesh.devices.size > 1
                     else None)
    # FedOpt's server step fuses into the epilogue kernel when the
    # optimizer maps onto a fused channel (sgd/momentum/adam): the params
    # subtree runs reduce → pseudo-grad → optimizer → cast in ONE pass
    # per leaf instead of reduce + optax update + apply.  Robust rounds
    # keep the optax path (the sort/distance center can't fuse).
    fused_opt = (_epilogue.spec_from_args(args)
                 if algo == FED_OPT_FEDOPT and robust_spec is None
                 else None)

    def aggregate(global_vars, server_state, client_ids, new_vars,
                  algo_out, metrics, weights):
        agg_vars = (robust_agg_stacked(robust_spec, new_vars, weights,
                                       center=global_vars)
                    if robust_spec is not None
                    else agg_stacked(new_vars, weights,
                                     prefer_pallas=prefer_pallas))
        new_state = dict(server_state)

        if algo == FED_OPT_FEDOPT and fused_opt is not None:
            # the plain params reduce above is dead code under the fused
            # channel (XLA DCEs it): the epilogue re-reads the stacked
            # params and emits the post-optimizer global directly
            params, opt_state = _epilogue.fused_epilogue(
                global_vars["params"], new_vars["params"], weights,
                1.0, fused_opt, server_state["opt_state"],
                prefer_pallas=prefer_pallas)
            agg_vars = dict(agg_vars, params=params)
            new_state["opt_state"] = opt_state
        elif algo == FED_OPT_FEDOPT:
            pseudo = jax.tree_util.tree_map(
                lambda g, a: g - a, global_vars["params"],
                agg_vars["params"])
            updates, opt_state = server_tx.update(
                pseudo, server_state["opt_state"], global_vars["params"])
            params = optax.apply_updates(global_vars["params"], updates)
            agg_vars = dict(agg_vars, params=params)
            new_state["opt_state"] = opt_state
        elif algo == FED_OPT_SCAFFOLD:
            new_state["c_locals"] = jax.tree_util.tree_map(
                lambda all_c, new_c: all_c.at[client_ids].set(new_c),
                server_state["c_locals"], algo_out["c_local"])
            delta = jax.tree_util.tree_map(
                lambda d: jnp.sum(d, axis=0) / float(n_total),
                algo_out["c_delta"])
            new_state["c_global"] = jax.tree_util.tree_map(
                lambda c, d: c + d, server_state["c_global"], delta)
        elif algo == FED_OPT_FEDDYN:
            alpha = float(getattr(args, "feddyn_alpha", 0.01) or 0.01)
            new_state["lambdas"] = jax.tree_util.tree_map(
                lambda all_l, new_l: all_l.at[client_ids].set(new_l),
                server_state["lambdas"], algo_out["feddyn_lambda"])
            m_frac = client_ids.shape[0] / float(n_total)
            new_state["h"] = jax.tree_util.tree_map(
                lambda h, avg, g: h - alpha * m_frac * (avg - g),
                server_state["h"], agg_vars["params"],
                global_vars["params"])
            agg_vars = dict(agg_vars, params=jax.tree_util.tree_map(
                lambda p, h: p - h / alpha, agg_vars["params"],
                new_state["h"]))
        elif algo == FED_OPT_FEDNOVA:
            w = weights / jnp.maximum(jnp.sum(weights), 1e-12)
            tau_eff = jnp.sum(w * algo_out["tau"])
            lr = float(getattr(args, "learning_rate", 0.03))
            d_avg = jax.tree_util.tree_map(
                lambda d: jnp.tensordot(w, d, axes=1), algo_out["nova_d"])
            agg_vars = dict(agg_vars, params=jax.tree_util.tree_map(
                lambda p, d: p - tau_eff * lr * d,
                global_vars["params"], d_avg))
        elif algo == FED_OPT_MIME:
            beta = float(getattr(args, "server_momentum", 0.9) or 0.9)
            # robust reduce the full grads too: poisoned grads corrupt
            # the server momentum just as poisoned params corrupt w
            g = (robust_agg_stacked(robust_spec,
                                    algo_out["full_grad"], weights)
                 if robust_spec is not None
                 else agg_stacked(algo_out["full_grad"], weights,
                                  prefer_pallas=prefer_pallas))
            new_state["momentum"] = jax.tree_util.tree_map(
                lambda m, gg: beta * m + (1.0 - beta) * gg,
                server_state["momentum"], g)

        round_metrics = {
            "train_loss": jnp.sum(metrics["train_loss"] * weights)
            / jnp.maximum(jnp.sum(weights), 1e-12),
            "train_acc": jnp.sum(metrics["train_acc"] * weights)
            / jnp.maximum(jnp.sum(weights), 1e-12),
            "samples": jnp.sum(weights),
        }
        return agg_vars, new_state, round_metrics

    return aggregate


def _zeros_like(t):
    return jax.tree_util.tree_map(jnp.zeros_like, t)


def _stack_zeros_like(t, n: int):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((n,) + x.shape, x.dtype), t)


class ParrotAPI:
    def __init__(self, args: Any, device: Any, dataset: Tuple, bundle: Any,
                 use_mesh: bool = False) -> None:
        self.args = args
        self.bundle = bundle
        self.algo = str(getattr(args, "federated_optimizer", "FedAvg"))
        self.use_mesh = use_mesh
        (self.train_num, self.test_num, self.train_global, self.test_global,
         self.local_num_dict, self.train_data_local_dict,
         self.test_data_local_dict, self.class_num) = dataset

        self.n_total = int(args.client_num_in_total)
        self.k = int(args.client_num_per_round)
        bs = int(getattr(args, "batch_size", 32))
        self.bs = bs
        max_n = max(self.local_num_dict.values())
        self.nb = max(1, -(-int(max_n) // bs))
        #: hetero size-bucketing (reference `core/schedule` capability on the
        #: vmapped hot path): >1 splits clients into size strata so per-round
        #: compute tracks the size DISTRIBUTION, not the max client
        self.n_buckets = max(1, int(getattr(args, "hetero_buckets", 1) or 1))

        # ---- device-resident dataset + per-client index matrix ------------
        x_all, y_all = self.train_global
        # data_dtype: bfloat16 halves the resident footprint AND the gather
        # bandwidth for image data (models cast to their compute dtype
        # anyway); default keeps the bundle's input dtype
        store_dtype = bundle.input_dtype
        if str(getattr(args, "data_dtype", "") or "") == "bfloat16" \
                and bundle.input_dtype == jnp.float32:
            store_dtype = jnp.bfloat16
        self.x_all = jnp.asarray(np.asarray(x_all), store_dtype)
        self.y_all = jnp.asarray(np.asarray(y_all))
        cap = self.nb * bs
        idx_mat = np.full((self.n_total, cap), -1, np.int32)
        # map each client's global sample indices into its padded slots
        self._client_rows = {}
        for cid in range(self.n_total):
            xi, yi = self.train_data_local_dict[cid]
            n_i = min(len(yi), cap)
            rows = self._find_rows(cid, n_i)
            idx_mat[cid, :n_i] = rows
        self.idx_mat = jnp.asarray(idx_mat)
        self.n_samples = jnp.asarray(
            [float(self.local_num_dict[c]) for c in range(self.n_total)],
            jnp.float32)

        # ---- model / engine ------------------------------------------------
        rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0) or 0))
        self.global_vars = bundle.init_variables(rng, batch_size=min(bs, 8))
        self.local_update = build_local_update(bundle, args)
        self.eval_step = jax.jit(build_eval_step(bundle))

        # ---- server state --------------------------------------------------
        self.server_state: Dict[str, Any] = {}
        if self.algo == FED_OPT_FEDOPT:
            # mirror build_aggregate's channel choice: fused epilogue
            # state ({m, v, t} f32 trees) when the server optimizer maps,
            # optax state otherwise (yogi/adagrad/robust rounds)
            fused_opt = (_epilogue.spec_from_args(args)
                         if parse_robust_agg(
                             getattr(args, "robust_agg", None)) is None
                         else None)
            if fused_opt is not None:
                self.server_state["opt_state"] = _epilogue.init_opt_state(
                    self.global_vars["params"], fused_opt)
            else:
                self.server_tx = build_server_optimizer(args)
                self.server_state["opt_state"] = self.server_tx.init(
                    self.global_vars["params"])
        if self.algo == FED_OPT_SCAFFOLD:
            self.server_state["c_global"] = _zeros_like(
                self.global_vars["params"])
            self.server_state["c_locals"] = _stack_zeros_like(
                self.global_vars["params"], self.n_total)
        if self.algo == FED_OPT_FEDDYN:
            self.server_state["h"] = _zeros_like(self.global_vars["params"])
            self.server_state["lambdas"] = _stack_zeros_like(
                self.global_vars["params"], self.n_total)
        if self.algo == FED_OPT_MIME:
            self.server_state["momentum"] = _zeros_like(
                self.global_vars["params"])

        # ---- mesh ----------------------------------------------------------
        self.mesh = None
        if use_mesh:
            dcn = dict(getattr(args, "dcn_mesh_shape", None) or {})
            dcn_prod = int(np.prod(list(dcn.values()))) if dcn else 1
            shape = getattr(args, "mesh_shape", None) or {
                AXIS_CLIENTS: max(
                    min(len(jax.devices()) // dcn_prod, self.k), 1)}
            self.mesh = (build_hybrid_mesh(shape, dcn) if dcn
                         else build_mesh(shape))

        self._build_buckets()
        # the dataset/index arrays ride as EXPLICIT jit arguments — if the
        # round step closed over them they would be lowered as embedded HLO
        # constants (hundreds of MB at 50k-sample scale), which bloats the
        # program beyond what remote-compile services accept
        self.device_data = {"x": self.x_all, "y": self.y_all,
                            "idx": self.idx_mat, "w": self.n_samples}
        if self.buckets is not None:
            self.device_data["bidx"] = [b["idx"] for b in self.buckets]
            self.device_data["bgids"] = [b["gids"] for b in self.buckets]
            if any(b["nb"] < b["nb_full"] for b in self.buckets):
                # capped buckets rotate per-round sample windows, which
                # needs each member's true size inside the jit
                self.device_data["bsizes"] = [b["sizes"]
                                              for b in self.buckets]
        self._place_on_mesh()
        self.round_step = jax.jit(self._build_round_step(),
                                  donate_argnums=(1, 2))
        if self.n_buckets > 1:
            self.bucketed_round_step = jax.jit(
                self._build_bucketed_round_step(), donate_argnums=(1, 2))
        self.multi_round_step = None  # built lazily for the scan fast path
        #: True when the fused executable was deserialized from the AOT
        #: cache instead of compiled — the committed cross-process proof
        #: (tests/test_aot_cache.py) and bench.py's warm/cold marker
        self.aot_cache_hit = False
        self._fused_is_plain_jit = False
        #: XLA cost/memory analysis of the fused program, captured by the
        #: flight recorder at AOT time (None until built, or when the
        #: backend reports nothing) — bench.py's measured-MFU source
        self.program_costs: Optional[Dict[str, Any]] = None
        self.metrics_history: List[Dict[str, Any]] = []
        #: warm pool (compile-ahead): {tag: {hit, seconds}} per executable
        #: precompiled/cache-loaded in the background; empty until started
        self._compile_ahead_thread: Optional[threading.Thread] = None
        #: guards compile_ahead_report and the start-once check-then-act:
        #: the warm-pool worker fills the report while the main thread
        #: reads it (and two concurrent starters must not spawn two pools)
        self._ca_lock = named_lock("ParrotAPI._ca_lock")
        self.compile_ahead_report: Dict[str, Any] = {}
        #: resize warm pool: {mesh axis size: compiled step} precompiled
        #: for the ±1-step slot ladder (half/double of the current gang)
        #: so an announced re-mesh installs a ready executable instead of
        #: paying a fresh compile inside the downtime window
        self._resize_warm: Dict[int, Any] = {}
        self._resize_warm_thread: Optional[threading.Thread] = None
        #: last resize announce this process acked — a fast next boundary
        #: must not re-latch the same request before the scheduler
        #: collects the ack and clears the file
        self._resize_acked: Optional[Dict[str, Any]] = None
        if self.compile_ahead_enabled():
            self.start_compile_ahead()
        if flight_recorder.enabled():
            # the uploads above are async; force + time them so the h2d
            # bucket carries the real dataset-transfer cost, and count
            # the resident bytes at the boundary
            with flight_recorder.phase("h2d", program="parrot/device_data"):
                jax.block_until_ready(self.device_data)
            flight_recorder.note_transfer(
                "h2d", flight_recorder.tree_nbytes(self.device_data))

    def _place_on_mesh(self) -> None:
        """Commit the round programs' operands to the mesh, replicated.
        Left where ``jnp.asarray`` put them they all sit on the first
        device: every dispatch would then re-broadcast the dataset, and
        an AOT compile that pins the operands' shardings is refused for
        mixing one device with the mesh."""
        if self.mesh is None:
            return
        self.device_data, self.global_vars, self.server_state = \
            jax.device_put(
                (self.device_data, self.global_vars, self.server_state),
                NamedSharding(self.mesh, P()))
        self.x_all, self.y_all = self.device_data["x"], self.device_data["y"]
        self.idx_mat = self.device_data["idx"]
        self.n_samples = self.device_data["w"]

    def _build_buckets(self) -> None:
        """Split clients into size strata (equal client counts, stratum
        count snapped to a divisor of k) with per-stratum batch capacity
        nb_b = ceil(max_size_in_stratum / bs).  Per round each stratum
        contributes exactly k/B clients (proportionate stratified sampling
        — every client's inclusion probability is exactly k/N), so the
        padded compute is Σ_b (k/B)·nb_b·bs ≈ k·mean_size instead of
        k·max_size.

        This is the reference heterogeneity-aware scheduler capability
        (`core/schedule/seq_train_scheduler.py`, SURVEY §2.4 fedavg_seq)
        re-expressed for the vmapped hot path: strata ARE the schedule,
        chosen once from the static partition."""
        #: 0 = off (full local epochs, pad to the stratum max); >0 caps
        #: each stratum's batch capacity at cap·mean_size with per-round
        #: rotating sample windows for over-cap clients (PERF003's fix:
        #: padded compute tracks the size DISTRIBUTION's mean, not max)
        self.bucket_cap = float(
            getattr(self.args, "hetero_bucket_cap", 0.0) or 0.0)
        if self.n_buckets <= 1:
            self.buckets = None
            return
        # snap the stratum count to a DIVISOR of k (closest to the request,
        # larger on ties): equal-count strata with equal integer quotas
        # q = k/B make every client's inclusion probability exactly
        # q/(N/B) = k/N — fixed unequal quotas would permanently
        # over-sample one size class.  Residual bias only when B ∤ N
        # (array_split sizes differ by 1 → |Δp| ≤ k/(N·(N/B−1))).
        sizes = np.asarray([self.local_num_dict[c]
                            for c in range(self.n_total)])
        plan = bucket_plan(sizes, self.k, self.bs, self.n_buckets,
                           self.bucket_cap)
        if len(plan) <= 1:
            self.buckets = None
            self.n_buckets = 1
            return
        self.n_buckets = len(plan)
        idx_mat = np.asarray(self.idx_mat)
        self.buckets = []
        for b in plan:
            g = b["members"]
            # the index matrix keeps FULL capacity (largest member) so a
            # capped bucket's rotating window can address every sample;
            # the compute capacity nb may be smaller
            self.buckets.append({
                "gids": jnp.asarray(g.astype(np.int32)),
                "idx": jnp.asarray(idx_mat[g, :b["nb_full"] * self.bs]),
                "sizes": jnp.asarray(sizes[g].astype(np.int32)),
                "nb": b["nb"],
                "nb_full": b["nb_full"],
                "k": b["q"],
                "padded": b["padded"],
                "real": b["real"],
            })

    def bucket_waste_stats(self) -> Optional[Dict[str, Any]]:
        """Per-bucket padded-vs-real accounting for the bench JSON and the
        PERF003 padding-waste lint (None on the uniform path)."""
        if self.buckets is None:
            return None
        return {
            "bs": self.bs,
            "cap_ratio": self.bucket_cap,
            "buckets": [{"q": b["k"], "nb": b["nb"],
                         "nb_full": b["nb_full"], "padded": b["padded"],
                         "real": round(float(b["real"]), 1)}
                        for b in self.buckets],
            "padded_samples_per_round": int(
                sum(b["padded"] for b in self.buckets)),
            "expected_real_per_round": round(
                float(sum(b["real"] for b in self.buckets)), 1),
        }

    def _find_rows(self, cid: int, n_i: int) -> np.ndarray:
        """Global row indices of client cid's samples (the partition index
        map stashed by data_loader.load; recomputed identically if absent)."""
        rows_map = getattr(self.args, "client_row_map", None)
        if rows_map is None:
            from ...data.partition import partition
            y = np.asarray(self.train_global[1])
            labels = y if y.ndim == 1 else y[:, 0]
            m = partition(labels, self.n_total,
                          str(getattr(self.args, "partition_method", "hetero")),
                          float(getattr(self.args, "partition_alpha", 0.5) or 0.5),
                          int(getattr(self.args, "random_seed", 0) or 0))
            rows_map = {c: np.asarray(m[c], np.int64) for c in m}
            setattr(self.args, "client_row_map", rows_map)
        return rows_map[cid][:n_i]

    def _gather_batches(self, data, client_ids, idx_mat, nb_b):
        """Device-resident gather: padded per-client slots → [K, nb_b, bs]
        batch grids with validity masks (shared by the uniform and
        bucketed round steps).  ``data`` carries the traced dataset arrays
        (explicit jit args, never closure constants)."""
        idx = idx_mat[client_ids]                           # [K, cap]
        return self._grid_from_idx(data, idx, nb_b)

    def _gather_batches_windowed(self, data, client_rows, idx_mat, sizes,
                                 nb_b, key):
        """Rotating-window gather for capped buckets: a client larger than
        the bucket's compute capacity contributes a per-round circular
        window of ``nb_b·bs`` of its samples (uniform random start)
        instead of a full epoch — padded compute tracks the stratum mean
        while every sample is still visited across rounds.  Shapes stay
        static: the window is a mod-n_i position gather."""
        capn = nb_b * self.bs
        rows = idx_mat[client_rows]                        # [K, full_cap]
        n_i = jnp.maximum(sizes[client_rows], 1)[:, None]  # [K, 1]
        j = jnp.arange(capn, dtype=jnp.int32)[None, :]
        start = jax.random.randint(
            key, (rows.shape[0], 1), 0, jnp.int32(1 << 30),
            dtype=jnp.int32) % n_i
        # over-cap clients read a circular window; everyone else reads
        # their padded slots verbatim (idx -1 padding masks the tail)
        pos = jnp.where(n_i > capn, (start + j) % n_i, j)
        idx = jnp.take_along_axis(rows, pos, axis=1)       # [K, capn]
        return self._grid_from_idx(data, idx, nb_b)

    def _grid_from_idx(self, data, idx, nb_b):
        bs = self.bs
        safe = jnp.maximum(idx, 0)
        x = data["x"][safe]                                 # [K, cap, ...]
        y = data["y"][safe]
        mask = (idx >= 0).astype(jnp.float32)
        return {"x": x.reshape((x.shape[0], nb_b, bs) + x.shape[2:]),
                "y": y.reshape((y.shape[0], nb_b, bs) + y.shape[2:]),
                "mask": mask.reshape((mask.shape[0], nb_b, bs))}

    # ------------------------------------------------------------------
    def _grid_sharding(self, k_b: int, mesh: Any = None
                       ) -> Optional[NamedSharding]:
        return grid_sharding(mesh if mesh is not None else self.mesh,
                             k_b, self.bs)

    def _build_round_step(self, mesh: Any = None):
        # the client axis shards over EVERY mesh axis (clients is parrot's
        # only parallel dimension, so a DCN axis extends it across slices
        # rather than replicating the round); a quota smaller than the
        # mesh shards the intra-batch axis instead (see _grid_sharding).
        # ``mesh`` overrides self.mesh so the resize warm pool can build
        # steps for candidate slot counts without touching the live mesh
        clients_sharding = self._grid_sharding(self.k, mesh=mesh)

        per_client_algo_state = self._per_client_algo_state
        in_axes_algo = self._in_axes_algo()
        aggregate = self._build_aggregate(mesh)

        def round_step(data, global_vars, server_state, client_ids, rng):
            batches = self._gather_batches(data, client_ids, data["idx"],
                                           self.nb)
            if clients_sharding is not None:
                batches = jax.lax.with_sharding_constraint(
                    batches, clients_sharding)
            rngs = jax.random.split(rng, client_ids.shape[0])
            algo_state = per_client_algo_state(server_state, client_ids)
            new_vars, algo_out, metrics = jax.vmap(
                self.local_update,
                in_axes=(None, 0, 0, in_axes_algo))(
                    global_vars, batches, rngs, algo_state or None)
            weights = data["w"][client_ids]
            return aggregate(global_vars, server_state, client_ids,
                             new_vars, algo_out, metrics, weights)

        return round_step

    def _per_client_algo_state(self, server_state, client_ids):
        return per_client_algo_state(self.algo, server_state, client_ids)

    def _in_axes_algo(self):
        return algo_in_axes(self.algo)

    def _build_aggregate(self, mesh: Any = None):
        return build_aggregate(self.args, self.algo, self.n_total,
                               server_tx=getattr(self, "server_tx", None),
                               mesh=mesh if mesh is not None else self.mesh)

    def _build_bucketed_round_step(self, mesh: Any = None):
        """One round over size strata: each bucket vmaps its own quota of
        clients at its own batch capacity (one compile total — the python
        loop over buckets unrolls into one jit graph), then all buckets'
        stacked outputs concatenate into the shared aggregation.  Client
        sampling is proportionate-stratified ON DEVICE (inclusion
        probability k/N per client; deviation from the reference's host
        `np.random.seed(round)` draws is documented in run_rounds_fused)."""
        per_client_algo_state = self._per_client_algo_state
        in_axes_algo = self._in_axes_algo()
        aggregate = self._build_aggregate(mesh)
        buckets = self.buckets
        # per-bucket sharding chosen from the bucket's own quota (mesh
        # path: the round-2 bucketed step never sharded — VERDICT weak #1)
        bucket_shardings = [self._grid_sharding(b["k"], mesh=mesh)
                            for b in buckets]

        # capped buckets draw a third key for the rotating window; the
        # uncapped layout keeps the historical 2-key stream so existing
        # configs trace (and AOT-cache) identically
        any_capped = any(b["nb"] < b["nb_full"] for b in buckets)
        keys_per_bucket = 3 if any_capped else 2

        def round_step(data, global_vars, server_state, rng):
            outs = []
            keys = jax.random.split(rng, keys_per_bucket * len(buckets))
            for i, b in enumerate(buckets):
                rows = jax.random.permutation(
                    keys[keys_per_bucket * i], b["gids"].shape[0])[:b["k"]]
                gids = data["bgids"][i][rows]
                if b["nb"] < b["nb_full"]:
                    batches = self._gather_batches_windowed(
                        data, rows, data["bidx"][i], data["bsizes"][i],
                        b["nb"], keys[keys_per_bucket * i + 2])
                else:
                    batches = self._gather_batches(data, rows,
                                                   data["bidx"][i], b["nb"])
                if bucket_shardings[i] is not None:
                    batches = jax.lax.with_sharding_constraint(
                        batches, bucket_shardings[i])
                rngs = jax.random.split(keys[keys_per_bucket * i + 1],
                                        b["k"])
                algo_state = per_client_algo_state(server_state, gids)
                new_vars, algo_out, metrics = jax.vmap(
                    self.local_update,
                    in_axes=(None, 0, 0, in_axes_algo))(
                        global_vars, batches, rngs, algo_state or None)
                outs.append((new_vars, algo_out, metrics,
                             data["w"][gids], gids))

            def cat(trees):
                return jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *trees)

            new_vars = cat([o[0] for o in outs])
            algo_out = cat([o[1] for o in outs])
            metrics = cat([o[2] for o in outs])
            weights = jnp.concatenate([o[3] for o in outs])
            client_ids = jnp.concatenate([o[4] for o in outs])
            return aggregate(global_vars, server_state, client_ids,
                             new_vars, algo_out, metrics, weights)

        return round_step

    # ------------------------------------------------------------------
    def _build_multi_round_step(self):
        """Scan-rounds fast path: up to FUSED_CHUNK_ROUNDS rounds inside
        ONE jit dispatch.

        Amortizes per-call dispatch/transfer overhead (dominant when client
        models are small or the device is remote).  Client sampling moves
        on-device (`jax.random.permutation`), which deliberately diverges
        from the reference's host `np.random.seed(round)` stream — same
        distribution, different draws; the default per-round path keeps
        reference parity.

        The scan length is ALWAYS the full chunk; a traced ``n_active``
        scalar masks the tail via per-round `lax.cond` (idle rounds pass
        the carry through at ~zero cost).  One compiled program therefore
        serves EVERY round count — which is what makes the AOT export
        cache (`_ensure_multi_round_step`) a single artifact instead of
        one per remainder shape."""
        k = self.k
        n_total = self.n_total
        chunk = self.FUSED_CHUNK_ROUNDS
        #: stable metrics contract of `_build_aggregate`
        idle_rm = {"train_loss": jnp.zeros((), jnp.float32),
                   "train_acc": jnp.zeros((), jnp.float32),
                   "samples": jnp.zeros((), jnp.float32)}
        if self.n_buckets > 1:
            bucketed = self._build_bucketed_round_step()

            def make_body(data, n_active):
                def body(carry, r):
                    gv, st, rng = carry
                    rng, k2 = jax.random.split(rng)
                    gv, st, rm = jax.lax.cond(
                        r < n_active,
                        lambda op: bucketed(data, op[0], op[1], k2),
                        lambda op: (op[0], op[1], dict(idle_rm)),
                        (gv, st))
                    return (gv, st, rng), rm
                return body
        else:
            round_step = self._build_round_step()

            def make_body(data, n_active):
                def body(carry, r):
                    gv, st, rng = carry
                    rng, k1, k2 = jax.random.split(rng, 3)

                    def run(op):
                        ids = jax.random.permutation(k1, n_total)[:k]
                        return round_step(data, op[0], op[1], ids, k2)

                    gv, st, rm = jax.lax.cond(
                        r < n_active, run,
                        lambda op: (op[0], op[1], dict(idle_rm)), (gv, st))
                    return (gv, st, rng), rm
                return body

        def multi(data, global_vars, server_state, rng, n_active):
            (gv, st, _), rms = jax.lax.scan(
                make_body(data, n_active),
                (global_vars, server_state, rng), jnp.arange(chunk))
            return gv, st, rms

        return jax.jit(multi, donate_argnums=(1, 2))

    # ------------------------------------------------------------------
    def _aot_cache_path(self, tag: str = "mrs") -> Optional[str]:
        """Disk path for a serialized parrot executable, or None when
        AOT caching is off.  ``tag`` names the program — ``mrs`` (fused
        multi-round scan), ``rs`` (uniform round step), ``brs`` (bucketed
        round step; one program embedding every bucket signature from
        ``bucket_plan()``) — and the key digests everything the traced
        program depends on: config knobs, data/model shapes, bucket
        layout, device topology, jax version, AND the source files that
        build the trace — so a stale artifact can never be replayed."""
        if not bool(getattr(self.args, "parrot_aot_cache", True)):
            return None
        import hashlib
        import os

        # FEDML_TPU_AOT_CACHE_DIR is the pod scheduler's compile-sharing
        # contract: every job dispatched on the pod points here, so one
        # tenant's parrot compile is a digest-keyed cache hit for the
        # next job with the same executable shape.  Explicit config wins.
        base = (getattr(self.args, "aot_cache_dir", None)
                or os.environ.get("FEDML_TPU_AOT_CACHE_DIR")
                or jax.config.jax_compilation_cache_dir)
        if not base:
            return None

        h = hashlib.sha256()
        h.update(jax.__version__.encode())
        devs = jax.devices()
        h.update(f"{devs[0].platform}:{devs[0].device_kind}:"
                 f"{len(devs)}".encode())
        if self.mesh is not None:
            h.update(repr(tuple(zip(self.mesh.axis_names,
                                    self.mesh.devices.shape))).encode())
        cfg = [str(getattr(self.args, f, None)) for f in (
            "model", "dataset", "federated_optimizer", "client_optimizer",
            "learning_rate", "momentum", "weight_decay", "epochs",
            "batch_size", "client_num_in_total", "client_num_per_round",
            "compute_dtype", "data_dtype", "hetero_buckets", "conv_impl",
            "server_lr", "server_momentum", "feddyn_alpha", "fedprox_mu",
            "random_seed", "robust_agg", "hetero_bucket_cap",
            "fused_epilogue", "server_optimizer")]
        h.update("|".join(cfg).encode())
        h.update(repr((self.x_all.shape, str(self.x_all.dtype),
                       self.y_all.shape, self.nb, self.bs,
                       self.FUSED_CHUNK_ROUNDS)).encode())
        if self.buckets is not None:
            h.update(repr([(b["k"], b["nb"], b["nb_full"])
                           for b in self.buckets]).encode())
        pkg = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        for rel in ("simulation/parrot/parrot_api.py",
                    "ml/engine/local_update.py",
                    "ml/engine/model_bundle.py",
                    "ml/engine/optimizers.py",
                    "ml/aggregator/agg_operator.py",
                    "ml/aggregator/robust.py",
                    "ops/epilogue.py",
                    "ops/pallas_ops.py"):
            try:
                with open(os.path.join(pkg, rel), "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(rel.encode())
        try:
            for mod in sorted(os.listdir(os.path.join(pkg, "models"))):
                if mod.endswith(".py"):
                    with open(os.path.join(pkg, "models", mod), "rb") as f:
                        h.update(f.read())
            # the artifact is a pickle, so the cache dir must be a private
            # trust domain: create 0o700, refuse dirs owned by another
            # uid, and strip group/other permissions from pre-existing
            # dirs (makedirs only applies the mode on creation) — an
            # attacker able to write here gets code execution in the
            # training process
            os.makedirs(base, mode=0o700, exist_ok=True)
            if hasattr(os, "getuid"):
                st = os.stat(base)
                if st.st_uid != os.getuid():
                    logging.warning(
                        "parrot: AOT cache dir %s owned by uid %d (not "
                        "ours); caching off", base, st.st_uid)
                    return None
                if st.st_mode & 0o077:
                    os.chmod(base, 0o700)
                    if os.stat(base).st_mode & 0o022:
                        logging.warning(
                            "parrot: AOT cache dir %s stays group/world "
                            "writable; caching off", base)
                        return None
        except OSError as e:  # unwritable cache dir degrades, never aborts
            logging.warning("parrot: AOT cache dir unusable (%s); caching "
                            "off", e)
            return None
        return os.path.join(base,
                            f"parrot_{tag}_{h.hexdigest()[:24]}.jaxexp")

    def _ensure_multi_round_step(self) -> None:
        """Build (or load) the fused program, attributing the wall time
        to the flight recorder's ``compile`` bucket and capturing the
        program's XLA cost/memory analysis (``self.program_costs``) for
        measured MFU."""
        if self.multi_round_step is not None:
            return
        t = self._compile_ahead_thread
        if t is not None and t.is_alive():
            # warm pool is already building it — join instead of racing
            t.join()
        if self.multi_round_step is not None:
            return
        with flight_recorder.phase("compile",
                                   program="parrot/fused_round_scan"):
            self._build_or_load_multi_round_step()
        if self.program_costs is None:
            # works for a freshly-compiled AND a cache-loaded executable
            self.program_costs = flight_recorder.note_program(
                "parrot/fused_round_scan", self.multi_round_step,
                chunk_rounds=self.FUSED_CHUNK_ROUNDS)

    def _build_or_load_multi_round_step(self) -> None:
        """With a cache dir
        configured, the COMPILED EXECUTABLE round-trips through
        `jax.experimental.serialize_executable`: a warm process skips the
        retrace, the lowering AND the XLA compile entirely (warm start not
        re-measured on a local chip).  `jax.export` was tried first and
        REJECTED: its deserialized StableHLO recompiles into a program
        that executes the chunk 2.4x slower than the jit path (44.8 s vs
        18.9 s measured on the north star — BENCH_NOTES round 4); the
        serialized executable is bit-identical to what jit ran.

        The artifact is a pickle (executable bytes + arg trees) keyed by
        `_aot_cache_path`'s config+code digest, loaded only from the
        local cache dir this process also writes — same trust domain as
        jax's own persistent compilation cache."""
        if self.multi_round_step is not None:
            return

        fn = self._build_multi_round_step()
        path = self._aot_cache_path()
        loaded = self._load_executable(path)
        if loaded is not None:
            self.multi_round_step = loaded
            self.aot_cache_hit = True
            logging.info("parrot: fused executable loaded from "
                         "AOT cache %s", path)
            return
        # compile EAGERLY even without a cache dir: readiness then always
        # includes the compile, so callers timing "program ready" vs
        # "first chunk" (bench.py) measure the same thing on every path
        # a compiler refusal raises: a plain-jit stand-in would only meet
        # the same refusal later, somewhere harder to read
        spec = self._aot_arg_spec(
            (self.device_data, self.global_vars,
             self.server_state, jax.random.PRNGKey(0),
             jnp.zeros((), jnp.int32)))
        compiled = fn.trace(*spec).lower().compile()
        self.multi_round_step = compiled
        self._save_executable(path, compiled)

    @staticmethod
    def _aot_arg_spec(args_tree):
        """ShapeDtypeStructs for ``trace()`` that carry the committed
        arrays' shardings — specs from shape/dtype alone can compile a
        program that reshards (or fails) at call time on a multi-chip
        mesh."""

        def _spec(a):
            # an uncommitted array (a fresh rng key, a scalar) goes where
            # the program wants it; pinning it to its current device
            # would clash with the mesh
            sh = a.sharding if getattr(a, "committed", False) else None
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

        return jax.tree_util.tree_map(_spec, args_tree)

    def _load_executable(self, path: Optional[str]):
        """Deserialize a cached executable, or None (missing/stale/
        corrupt/foreign-owned — load failures degrade to a recompile,
        never abort)."""
        import os
        import pickle

        if not path or not os.path.exists(path):
            return None
        try:
            from jax.experimental import serialize_executable

            with open(path, "rb") as f:
                # fstat the OPEN fd (not the path) so a symlink swap
                # between check and read can't redirect the unpickle
                if hasattr(os, "getuid"):
                    import stat as _stat

                    st = os.fstat(f.fileno())
                    if (st.st_uid != os.getuid()
                            or not _stat.S_ISREG(st.st_mode)):
                        raise PermissionError(
                            f"{path} not a regular file owned by us; "
                            "refusing to unpickle")
                blob = pickle.load(f)
            # load onto the devices the program was compiled for — the
            # default is every local device, and a host with more chips
            # than the program spans then rejects the args at bind time
            devs = (list(self.mesh.devices.flat) if self.mesh is not None
                    else list(self.x_all.sharding.device_set))
            return serialize_executable.deserialize_and_load(
                *blob, execution_devices=devs)
        except Exception as e:  # stale/corrupt → rebuild
            logging.warning("parrot: AOT cache load failed (%s); "
                            "recompiling", e)
            return None

    def _save_executable(self, path: Optional[str], compiled) -> None:
        """Serialize ``compiled`` to the shared cache (atomic replace);
        persistence failures must not discard the live executable."""
        import os
        import pickle

        if not path:
            return
        try:
            from jax.experimental import serialize_executable

            blob = serialize_executable.serialize(compiled)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                pickle.dump(blob, f)
            os.replace(tmp, path)
            logging.info("parrot: executable cached to %s", path)
        except Exception as e:
            logging.warning("parrot: AOT cache write failed (%s); "
                            "executable kept in-memory only", e)

    # ---- per-bucket AOT compile-ahead (warm pool) ---------------------

    def compile_ahead_enabled(self) -> bool:
        import os

        return bool(getattr(self.args, "parrot_compile_ahead", False)
                    or os.environ.get("FEDML_TPU_COMPILE_AHEAD"))

    def start_compile_ahead(self, wait: bool = False) -> Dict[str, Any]:
        """Background warm pool: precompile (or cache-load) every round
        executable this config can dispatch — the per-round step (``rs``,
        or ``brs``: ONE program embedding every bucket signature from
        ``bucket_plan()``) and the fused multi-round scan (``mrs``) —
        keyed by the ``_aot_cache_path`` digests and shared through
        ``FEDML_TPU_AOT_CACHE_DIR``.  Round 1 then stops paying compile
        in the flight log: the wall time lands in the standalone
        ``compile_ahead`` phase (concurrent with host setup) instead of
        the first round's ``compile`` bucket, and a second process with
        the same digest loads the serialized executables outright.

        Returns ``compile_ahead_report`` — ``{tag: {hit, seconds}}``,
        fully populated once the worker finishes (``wait=True`` blocks)."""
        with self._ca_lock:
            # start-once under the lock: two concurrent starters (e.g. an
            # eager __init__ and an explicit warm-up call) must not spawn
            # two pools compiling the same executables
            t = self._compile_ahead_thread
            if t is None:
                t = threading.Thread(target=self._compile_ahead_worker,
                                     name="parrot-compile-ahead",
                                     daemon=True)
                self._compile_ahead_thread = t
                t.start()
        if wait:
            t.join()
        with self._ca_lock:
            # snapshot: the worker may still be appending to the live dict
            return dict(self.compile_ahead_report)

    def _note_compile_ahead(self, tag: str, entry: Any) -> None:
        with self._ca_lock:
            self.compile_ahead_report[tag] = entry

    def join_compile_ahead(self, timeout: Optional[float] = None) -> None:
        """Wait out the warm pool (no-op when never started).  Called on
        every train() exit path so the compile thread cannot outlive the
        run — a daemon thread killed at interpreter exit can die mid
        AOT-cache write and leave a torn cache entry for the next
        process to load."""
        t = self._compile_ahead_thread
        if t is None or not t.is_alive():
            return
        t.join(timeout=timeout)
        if t.is_alive():
            logging.warning(
                "parrot: compile-ahead worker still running after %ss — "
                "continuing without it", timeout)

    def _compile_ahead_worker(self) -> None:
        try:
            tag = "brs" if self.n_buckets > 1 else "rs"
            self._note_compile_ahead(tag, self._warm_step(tag))
            t0 = time.perf_counter()
            with flight_recorder.phase("compile_ahead",
                                       program="parrot/fused_round_scan"):
                self._build_or_load_multi_round_step()
            self._note_compile_ahead(
                "mrs", {"hit": bool(self.aot_cache_hit),
                        "seconds": round(time.perf_counter() - t0, 3)})
            if self.program_costs is None and not self._fused_is_plain_jit:
                self.program_costs = flight_recorder.note_program(
                    "parrot/fused_round_scan", self.multi_round_step,
                    chunk_rounds=self.FUSED_CHUNK_ROUNDS)
        except Exception as e:  # warm pool must never take the run down
            self._note_compile_ahead("error", str(e))
            logging.warning("parrot: compile-ahead worker failed (%s)", e)

    def _warm_step(self, tag: str) -> Dict[str, Any]:
        """Precompile (or cache-load) one per-round step executable and
        install it in place of the plain jit, wrapped with a bind-failure
        fallback."""
        t0 = time.perf_counter()
        if tag == "brs":
            jit_fn = self.bucketed_round_step
            spec = self._aot_arg_spec(
                (self.device_data, self.global_vars, self.server_state,
                 jax.random.PRNGKey(0)))
        else:
            jit_fn = self.round_step
            spec = self._aot_arg_spec(
                (self.device_data, self.global_vars, self.server_state,
                 jnp.zeros((self.k,), jnp.int32), jax.random.PRNGKey(0)))
        path = self._aot_cache_path(tag)
        compiled = self._load_executable(path)
        hit = compiled is not None
        if compiled is None:
            with flight_recorder.phase(
                    "compile_ahead", program=f"parrot/round_step_{tag}"):
                compiled = jit_fn.trace(*spec).lower().compile()
            self._save_executable(path, compiled)
        wrapped = self._wrap_step_with_fallback(compiled, jit_fn, tag)
        if tag == "brs":
            self.bucketed_round_step = wrapped
        else:
            self.round_step = wrapped
        return {"hit": hit, "seconds": round(time.perf_counter() - t0, 3)}

    def _wrap_step_with_fallback(self, compiled, jit_fn, tag: str):
        """An AOT executable can reject its args at bind time (layout/
        sharding drift vs what jit would infer); bind failures leave the
        donated buffers intact, so fall back to the plain jit once.  An
        execution failure has already consumed the donation — detect
        (deleted leaves) and re-raise."""
        state = {"fn": compiled, "fell_back": False}

        def call(*call_args):
            if state["fell_back"]:
                return jit_fn(*call_args)
            try:
                return state["fn"](*call_args)
            except Exception as e:
                for tree in call_args:
                    for leaf in jax.tree_util.tree_leaves(tree):
                        if (hasattr(leaf, "is_deleted")
                                and leaf.is_deleted()):
                            raise
                logging.warning(
                    "parrot: warm %s executable rejected its args (%s); "
                    "falling back to plain jit", tag, e)
                state["fell_back"] = True
                return jit_fn(*call_args)

        return call

    # ---- elastic resize (pod scheduler contract) ----------------------

    def _resize_file(self) -> Optional[str]:
        return (os.environ.get("FEDML_TPU_RESIZE_FILE")
                or getattr(self.args, "resize_file", None))

    def _mesh_axis_for(self, n_slots: int) -> int:
        """Clients-axis size for a gang of ``n_slots`` devices.  Unlike
        __init__'s default-shape heuristic this does NOT clamp to the
        client quota — an explicit mesh wider than ``k`` is legal (the
        intra-batch axis shards instead), and clamping would turn a
        grow-back to 8 slots into a silent 4-wide mesh."""
        return max(min(int(n_slots), len(jax.devices())), 1)

    def _step_arg_spec(self, tag: str):
        """Shape/dtype-only specs (NO shardings, unlike `_aot_arg_spec`):
        a resize candidate compiles against a mesh the live arrays aren't
        on yet, and a pinned committed sharding would be rejected as an
        incompatible device set.  The uncommitted-arg layout the compiler
        picks here is exactly what the post-remesh call binds with."""
        if tag == "brs":
            tree = (self.device_data, self.global_vars, self.server_state,
                    jax.random.PRNGKey(0))
        else:
            tree = (self.device_data, self.global_vars, self.server_state,
                    jnp.zeros((self.k,), jnp.int32), jax.random.PRNGKey(0))
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def prewarm_resize(self, around: int) -> None:
        """Warm the resize ladder: precompile the per-round step for the
        ±1-step slot counts (half and double of ``around``) in the
        background, so the executable an announced re-mesh will need is
        already sitting in ``_resize_warm`` when the round boundary
        latches it.  Arg shapes don't change with the gang size — only
        the shardings do — so one spec serves every candidate."""
        if not self.use_mesh or self.mesh is None:
            return
        if dict(getattr(self.args, "dcn_mesh_shape", None) or {}):
            return  # hybrid meshes don't resize (see remesh)
        cands = sorted({self._mesh_axis_for(max(int(around) // 2, 1)),
                        self._mesh_axis_for(int(around) * 2)}
                       - {self._mesh_axis_for(int(around))})
        if not cands:
            return
        with self._ca_lock:
            t = self._resize_warm_thread
            if t is not None and t.is_alive():
                return
            t = threading.Thread(target=self._prewarm_resize_worker,
                                 args=(cands,), daemon=True,
                                 name="parrot-resize-warm")
            self._resize_warm_thread = t
            t.start()

    def _compile_resize_candidate(self, axis: int, tag: str) -> None:
        mesh = build_mesh({AXIS_CLIENTS: axis})
        fn = (self._build_bucketed_round_step(mesh=mesh)
              if tag == "brs" else self._build_round_step(mesh=mesh))
        t0 = time.perf_counter()
        with flight_recorder.phase(
                "compile_ahead",
                program=f"parrot/round_step_{tag}_slots{axis}"):
            compiled = (jax.jit(fn, donate_argnums=(1, 2))
                        .trace(*self._step_arg_spec(tag))
                        .lower().compile())
        with self._ca_lock:
            self._resize_warm[axis] = compiled
        self._note_compile_ahead(
            f"{tag}_slots{axis}",
            {"hit": False,
             "seconds": round(time.perf_counter() - t0, 3)})

    def _prewarm_resize_worker(self, axis_sizes: List[int]) -> None:
        tag = "brs" if self.n_buckets > 1 else "rs"
        for axis in axis_sizes:
            with self._ca_lock:
                if axis in self._resize_warm:
                    continue
            try:
                self._compile_resize_candidate(axis, tag)
            except Exception as e:  # noqa: BLE001 — warm pool must never
                # take the run down; a cold resize just compiles inline
                logging.warning(
                    "parrot: resize prewarm for %d slots failed (%s)",
                    axis, e)

    def remesh(self, n_slots: int) -> None:
        """Rebuild the device mesh at ``n_slots`` and re-install the
        round executables — the in-place half of the elastic resize
        contract (docs/SCHEDULER.md "Elastic resize").  State crosses
        through host memory (device_get → device_put), so the restored
        values are bitwise-identical and only the sharding changes.
        Raises on any failure; the caller degrades to the preempt
        ladder."""
        if not self.use_mesh or self.mesh is None:
            return  # mesh-free layout: a gang resize changes nothing
        if dict(getattr(self.args, "dcn_mesh_shape", None) or {}):
            raise RuntimeError(
                "elastic resize over a hybrid (DCN) mesh is not "
                "supported — fall back to preempt/resume")
        axis = self._mesh_axis_for(n_slots)
        gv = jax.device_get(self.global_vars)
        ss = jax.device_get(self.server_state)
        self.mesh = build_mesh({AXIS_CLIENTS: axis})
        self.global_vars = jax.device_put(gv)
        self.server_state = jax.device_put(ss)
        self._place_on_mesh()
        with self._ca_lock:
            warm = self._resize_warm.get(axis)
        tag = "brs" if self.n_buckets > 1 else "rs"
        self.round_step = jax.jit(self._build_round_step(),
                                  donate_argnums=(1, 2))
        if self.n_buckets > 1:
            jit_fn = jax.jit(self._build_bucketed_round_step(),
                             donate_argnums=(1, 2))
            self.bucketed_round_step = (
                self._wrap_step_with_fallback(warm, jit_fn, tag)
                if warm is not None else jit_fn)
        elif warm is not None:
            self.round_step = self._wrap_step_with_fallback(
                warm, self.round_step, tag)
        # the fused scan re-lowers lazily at the new layout; its AOT
        # digest keys on the mesh, so the old artifact stays valid for
        # the old size
        self.multi_round_step = None
        self._fused_is_plain_jit = False

    def _maybe_resize(self, ckpt: Any, round_idx: int) -> None:
        """Round-boundary resize latch (the parrot twin of the cross-silo
        server's `_resize_requested`/`_perform_resize`): checkpoint
        first, re-mesh in place, ack — a failed re-mesh acks ``failed``
        (the scheduler walks the resize → preempt → kill ladder) and
        training continues at the old gang until the drain arrives."""
        path = self._resize_file()
        if not path:
            return
        from ...scheduler.pod.runners import ack_resize, read_resize

        req = read_resize(path)
        if req is None or req == self._resize_acked:
            return
        target = int(req["slots"])
        prev = (int(self.mesh.devices.size)
                if self.mesh is not None else None)
        t0 = time.perf_counter()
        try:
            if ckpt is not None:
                # boundary checkpoint BEFORE touching the mesh: whatever
                # happens next, this round is never lost (force=True —
                # the periodic save may already hold this round)
                ckpt.save(round_idx, {
                    "round_idx": round_idx,
                    "global_vars": self.global_vars,
                    "server_state": self.server_state,
                }, force=True)
            self.remesh(target)
            downtime = round(time.perf_counter() - t0, 6)
            self._resize_acked = req
            ack_resize(path, "ok", target, downtime_s=downtime,
                       round=int(round_idx))
            ledger.event("parrot", "resize", round_idx=int(round_idx),
                         outcome="ok", downtime_s=downtime,
                         **{"from": prev, "to": target})
            logging.info(
                "parrot: re-meshed %s -> %d slots in place at round "
                "boundary %d (%.3fs pause)", prev, target, round_idx,
                downtime)
            self.prewarm_resize(target)  # warm the new ladder neighbours
        except Exception:  # noqa: BLE001 — a failed re-mesh must degrade
            # to the preempt ladder, never take the run down mid-round
            logging.exception(
                "parrot: in-place resize to %d slots failed — acking "
                "failed (scheduler falls back to preempt)", target)
            self._resize_acked = req
            try:
                ack_resize(path, "failed", target, round=int(round_idx))
            except OSError:
                pass
            ledger.event("parrot", "resize", round_idx=int(round_idx),
                         outcome="failed", downtime_s=None,
                         **{"from": prev, "to": target})

    #: rounds per fused call — the scan ALWAYS runs this many iterations
    #: and a traced ``n_active`` masks the tail, so exactly ONE compiled
    #: program (and one AOT-cache artifact) serves every total round
    #: count, remainders included.  The value amortises per-dispatch
    #: cost over the chunk; not re-measured on a local chip.
    FUSED_CHUNK_ROUNDS = 64

    def run_rounds_fused(self, n_rounds: int, rng: Optional[jax.Array] = None):
        """Public fast path: run n_rounds fused in fixed-size scan chunks;
        returns stacked per-round metrics (concatenated across chunks)."""
        self._ensure_multi_round_step()
        if rng is None:
            rng = jax.random.PRNGKey(
                int(getattr(self.args, "random_seed", 0) or 0) + 23)
        chunk = self.FUSED_CHUNK_ROUNDS
        out = []
        remaining = int(n_rounds)
        if remaining <= 0:
            # valid no-op: empty stacked metrics, WITHOUT invoking the
            # jitted step (it donates global_vars/server_state — running it
            # just to learn the metrics shape would delete the live state)
            return {"train_loss": np.zeros((0,), np.float32),
                    "train_acc": np.zeros((0,), np.float32),
                    "samples": np.zeros((0,), np.float32)}
        while remaining > 0:
            step = min(chunk, remaining)
            rng, sub = jax.random.split(rng)
            # the scan always runs the full chunk; n_active masks the tail
            # (idle rounds pass the carry through), so one compiled
            # program serves every round count
            with flight_recorder.record_round(
                    "parrot_fused", rounds=step,
                    program="parrot/fused_round_scan") as fr:
                with fr.phase("device_compute"):
                    try:
                        self.global_vars, self.server_state, rms = \
                            self.multi_round_step(
                                self.device_data, self.global_vars,
                                self.server_state, sub,
                                jnp.asarray(step, jnp.int32))
                    except Exception as e:
                        # an executable DESERIALIZED from the AOT cache can
                        # reject its args at bind time (input layout/sharding
                        # mismatch vs what this process would compile);
                        # bind-time failures leave the donated buffers
                        # intact, so drop the stale artifact and fall back to
                        # the plain jit fn once.  A freshly compiled program
                        # that rejects its own args is a bug and raises, as
                        # does an EXECUTION-time failure, which has already
                        # consumed the donated state (deleted leaves).
                        if not self.aot_cache_hit:
                            raise

                        def _live(tree):
                            return all(
                                not (hasattr(leaf, "is_deleted")
                                     and leaf.is_deleted())
                                for leaf in jax.tree_util.tree_leaves(tree))

                        if not (_live(self.global_vars)
                                and _live(self.server_state)):
                            raise
                        logging.warning(
                            "parrot: cached fused step rejected its "
                            "args (%s); falling back to plain jit", e)
                        # drop the artifact so later processes
                        # recompile+rewrite instead of paying
                        # load→bind-fail→retrace forever
                        import os

                        stale = self._aot_cache_path()
                        if stale:
                            try:
                                os.remove(stale)
                            except OSError:
                                pass
                        self.multi_round_step = self._build_multi_round_step()
                        self._fused_is_plain_jit = True
                        self.aot_cache_hit = False
                        self.global_vars, self.server_state, rms = \
                            self.multi_round_step(
                                self.device_data, self.global_vars,
                                self.server_state, sub,
                                jnp.asarray(step, jnp.int32))
                    if flight_recorder.enabled():
                        # device-completion sync point: without it the
                        # phase measures dispatch, not execution
                        rms = jax.block_until_ready(rms)
                flops = (self.program_costs or {}).get("flops")
                dev_s = fr.phase_seconds("device_compute")
                if flops and dev_s > 0:
                    # idle masked tail rounds are ~free — charge only the
                    # active fraction of the chunk's analytic FLOPs
                    mfu = flight_recorder.measured_mfu(
                        "parrot/fused_round_scan",
                        flops * (step / chunk), dev_s)
                    if mfu is not None:
                        fr.note(mfu=mfu)
            if step < chunk:
                rms = jax.tree_util.tree_map(lambda a: a[:step], rms)
            out.append(rms)
            remaining -= step
        if len(out) == 1:
            return out[0]
        # host-side concat: per-round metrics are tiny, and a device-side
        # jnp.concatenate would pay a fresh XLA compile per chunk count
        return jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *out)

    def _client_sampling(self, round_idx: int) -> np.ndarray:
        if self.n_total == self.k:
            return np.arange(self.k, dtype=np.int32)
        np.random.seed(round_idx)  # reference parity (fedavg_api.py:127-136)
        return np.random.choice(self.n_total, self.k,
                                replace=False).astype(np.int32)

    def train(self) -> Dict[str, Any]:
        try:
            if getattr(self.args, "fused_rounds", False):
                return self._train_fused()
            return self._train_rounds()
        finally:
            self.join_compile_ahead(timeout=60.0)

    def _train_rounds(self) -> Dict[str, Any]:
        comm_rounds = int(self.args.comm_round)
        rng = jax.random.PRNGKey(
            int(getattr(self.args, "random_seed", 0) or 0) + 17)
        test_batches = self._make_test_batches()
        final_metrics: Dict[str, Any] = {}

        # round-level checkpoint/resume (new capability vs reference)
        ckpt = None
        start_round = 0
        ckpt_dir = getattr(self.args, "checkpoint_dir", None)
        ckpt_freq = int(getattr(self.args, "checkpoint_frequency", 10) or 10)
        if ckpt_dir:
            from ...utils.checkpoint import RoundCheckpointer

            ckpt = RoundCheckpointer(str(ckpt_dir))
            state = ckpt.restore()
            if state is not None:
                start_round = int(np.asarray(state["round_idx"])) + 1
                self.global_vars = state["global_vars"]
                if state.get("server_state"):
                    self.server_state = state["server_state"]
                logging.info("resumed from round %d", start_round - 1)

        if self._resize_file() and self.compile_ahead_enabled() \
                and self.mesh is not None:
            # elastic job under the pod scheduler: warm the ±1-step slot
            # ladder now so an announced re-mesh finds its executable hot
            self.prewarm_resize(int(self.mesh.devices.size))
        for round_idx in range(start_round, comm_rounds):
            # the mesh context re-enters per round (not once around the
            # loop) because a round-boundary resize swaps self.mesh
            ctx = (self.mesh if self.mesh is not None
                   else contextlib.nullcontext())
            with ctx:
                t0 = time.time()
                rng, sub = jax.random.split(rng)
                with flight_recorder.record_round(
                        "parrot_round", rounds=1,
                        program="parrot/round_step") as fr:
                    if self.n_buckets > 1:
                        # stratified on-device sampling (documented
                        # deviation from the reference's host
                        # np.random.seed(round) draws)
                        with fr.phase("device_compute"):
                            (self.global_vars, self.server_state,
                             rm) = self.bucketed_round_step(
                                self.device_data, self.global_vars,
                                self.server_state, sub)
                            if flight_recorder.enabled():
                                rm = jax.block_until_ready(rm)
                    else:
                        # host-side sampling stays outside the device
                        # phase — it lands in the host_gap residual
                        client_ids = jnp.asarray(
                            self._client_sampling(round_idx))
                        with fr.phase("device_compute"):
                            (self.global_vars, self.server_state,
                             rm) = self.round_step(
                                self.device_data, self.global_vars,
                                self.server_state, client_ids, sub)
                            if flight_recorder.enabled():
                                rm = jax.block_until_ready(rm)
                freq = int(getattr(self.args, "frequency_of_the_test", 5)
                           or 5)
                if round_idx % freq == 0 or round_idx == comm_rounds - 1:
                    out = self.eval_step(self.global_vars, test_batches)
                    n = max(float(out["n"]), 1.0)
                    final_metrics = self._record_metrics({
                        "test_loss": float(out["loss_sum"]) / n,
                        "test_acc": float(out["correct"]) / n,
                        "train_loss": float(rm["train_loss"]),
                        "round": round_idx,
                        "round_time": time.time() - t0,
                    }, f"parrot round {round_idx}")
                if ckpt is not None and (round_idx % ckpt_freq == 0
                                         or round_idx == comm_rounds - 1):
                    ckpt.save(round_idx, {
                        "round_idx": round_idx,
                        "global_vars": self.global_vars,
                        "server_state": self.server_state,
                    })
            # round boundary, outside the (old) mesh context: latch any
            # announced resize — checkpoint, re-mesh in place, ack
            self._maybe_resize(ckpt, round_idx)
        return final_metrics


    def _make_test_batches(self):
        x_te, y_te = self.test_global
        nb_te = max(1, -(-len(y_te) // self.bs))
        return make_batches(x_te, y_te, self.bs, nb_te,
                            self.bundle.input_dtype)

    def _record_metrics(self, metrics: Dict[str, Any], tag: str
                        ) -> Dict[str, Any]:
        self.metrics_history.append(metrics)
        mlops.log(metrics)
        logging.info("%s: %s", tag, metrics)
        return metrics

    def _train_fused(self) -> Dict[str, Any]:
        """``fused_rounds: true`` — run the scan-over-rounds fast path
        between eval points (~7x dispatch amortization through a remote
        accelerator).  Client sampling moves on-device (same distribution,
        different draws than the host path — documented deviation).
        Checkpoints (when ``checkpoint_dir`` is set) land at eval
        boundaries."""
        comm_rounds = int(self.args.comm_round)
        freq = int(getattr(self.args, "frequency_of_the_test", 5) or 5)
        test_batches = self._make_test_batches()
        rng = jax.random.PRNGKey(
            int(getattr(self.args, "random_seed", 0) or 0) + 23)
        final_metrics: Dict[str, Any] = {}
        done = 0

        ckpt = None
        ckpt_dir = getattr(self.args, "checkpoint_dir", None)
        if ckpt_dir:
            from ...utils.checkpoint import RoundCheckpointer

            ckpt = RoundCheckpointer(str(ckpt_dir))
            state = ckpt.restore()
            if state is not None:
                done = int(np.asarray(state["round_idx"])) + 1
                self.global_vars = state["global_vars"]
                if state.get("server_state"):
                    self.server_state = state["server_state"]
                logging.info("fused: resumed from round %d", done - 1)

        ctx = (self.mesh if self.mesh is not None
               else contextlib.nullcontext())
        with ctx:
            while done < comm_rounds:
                t0 = time.time()
                step = min(freq, comm_rounds - done)
                rng, sub = jax.random.split(rng)  # fresh stream per chunk
                rms = self.run_rounds_fused(step, rng=sub)
                done += step
                out = self.eval_step(self.global_vars, test_batches)
                n = max(float(out["n"]), 1.0)
                train_loss = np.asarray(rms["train_loss"])
                final_metrics = self._record_metrics({
                    "test_loss": float(out["loss_sum"]) / n,
                    "test_acc": float(out["correct"]) / n,
                    "train_loss": float(train_loss[-1]),
                    "round": done - 1,
                    "round_time": (time.time() - t0) / step,
                }, f"parrot fused rounds {done - step}-{done - 1}")
                if ckpt is not None:
                    ckpt.save(done - 1, {
                        "round_idx": done - 1,
                        "global_vars": self.global_vars,
                        "server_state": self.server_state,
                    })
        return final_metrics

