"""Hyper-scale Parrot: streamed cohorts over virtual client populations.

``ParrotAPI`` keeps the whole dataset and a ``[N, cap]`` per-client
index matrix device-resident — the right call at 10²–10³ clients, a
dead end at 10⁵–10⁶ (the index matrix alone is gigabytes and every
client's padded slots live in HBM forever).  This module is the scale
path from ROADMAP item 1:

- **Streaming cohort pipeline** — each round's cohort grid is assembled
  on host from a :class:`~fedml_tpu.data.population.ClientPopulation`
  (lazy per-client row streams, nothing O(N·cap) materialized) and
  staged host→device with async ``jax.device_put``.  With
  ``stream_prefetch >= 2`` the staging is **double-buffered**: round
  ``r`` computes while round ``r+1``'s grid assembles and uploads, so
  the flight recorder's ``h2d`` phase collapses to the residual
  synchronization wait.  ``stream_prefetch <= 1`` is the sequential
  baseline (stage-then-compute) the overlap claim is measured against.
- **Client axis sharded across the mesh** — cohort grids carry the
  `grid_sharding` layout (client axis over every mesh axis, intra-batch
  fallback for small quotas), so a 4096-client cohort spreads over all
  chips/hosts and aggregation lowers to one all-reduce.
- **Hierarchical cohort sampling** — stratified size buckets (the
  shared `bucket_plan`) sampled per round by a counter-based RNG keyed
  on ``(run_id, seed, round)``: deterministic under crash-resume and
  never materializes per-client index matrices for the population.
  Optional availability traces (diurnal duty cycles) filter candidates
  before the draw.
- **Sharded per-client algorithm state** — SCAFFOLD variates / FedDyn
  lambdas live device-resident as ``[N_pad, ...]`` tables laid out
  along the client axis (`stacked_client_sharding`) and are
  gathered/scattered per cohort inside the round jit instead of held
  replicated per device.

Headline metric: **clients-simulated/sec** (`stream_stats()`), with the
h2d/compute overlap fraction read from the same flight-recorder phases
`fedml perf report` prints.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...constants import AXIS_CLIENTS, FED_OPT_FEDDYN, FED_OPT_FEDOPT, \
    FED_OPT_MIME, FED_OPT_SCAFFOLD
from ...core import mlops
from ...core.mlops import flight_recorder, ledger
from ...data.population import ClientPopulation, load_population, \
    philox_generator
from ...ml.engine.local_update import build_eval_step, build_local_update, \
    make_batches
from ...ml.engine.mesh import build_hybrid_mesh, build_mesh
from ...ml.aggregator.robust import parse_robust_agg
from ...ml.engine.optimizers import build_server_optimizer
from ...ops import epilogue as _epilogue
from .parrot_api import _stack_zeros_like, _zeros_like, algo_in_axes, \
    bucket_plan, build_aggregate, grid_sharding, per_client_algo_state, \
    stacked_client_sharding

__all__ = [
    "HierarchicalCohortSampler",
    "StreamingParrotAPI",
    "make_availability",
]


def make_availability(spec: Optional[str], n_clients: int, seed: int = 0
                      ) -> Optional[Callable[[int, np.ndarray], np.ndarray]]:
    """Availability trace factory.

    ``None``/``"always"`` → no trace.  ``"diurnal:<duty>:<period>"`` →
    each client gets a deterministic phase offset in [0, 1) and is
    available at round ``r`` iff ``(r/period + phase) % 1 < duty`` — a
    rotating duty cycle approximating device-charging/idle windows
    (Parrot §3.2's trace-driven availability, reproduced synthetically
    so runs need no external trace files)."""
    if not spec or spec == "always":
        return None
    parts = str(spec).split(":")
    if parts[0] != "diurnal":
        raise ValueError(f"unknown availability trace {spec!r} "
                         "(supported: 'always', 'diurnal:<duty>:<period>')")
    duty = float(parts[1]) if len(parts) > 1 else 0.5
    period = float(parts[2]) if len(parts) > 2 else 24.0
    # one O(N) float32 vector — the only per-client state the trace keeps
    phases = philox_generator("avail_phase", seed, n_clients).random(
        n_clients, dtype=np.float32)

    def available(round_idx: int, ids: np.ndarray) -> np.ndarray:
        return ((round_idx / period + phases[ids]) % 1.0) < duty

    return available


class HierarchicalCohortSampler:
    """Stratified cohort sampling for populations of 10⁵–10⁶ clients.

    Strata come from the shared `bucket_plan` (equal-count size buckets
    with quotas summing to ``k``); each round draws every stratum's
    quota independently with a Philox generator keyed on
    ``(run_id, seed, round)``.  Determinism is per-round and positional
    — a crashed run that resumes at round ``r`` re-solicits the exact
    cohort round ``r`` would have had, with no sequential RNG state to
    replay.  The only O(N) state is the stratum membership arrays (a
    permutation of ``arange(N)``); no ``[N, cap]`` index matrices, no
    per-client objects."""

    def __init__(self, sizes: np.ndarray, k: int, bs: int,
                 n_buckets: int = 1, cap_ratio: float = 0.0,
                 run_id: str = "", seed: int = 0,
                 availability: Optional[Callable] = None) -> None:
        sizes = np.asarray(sizes)
        self.k = int(k)
        self.run_id = str(run_id)
        self.seed = int(seed)
        self.availability = availability
        plan = bucket_plan(sizes, k, bs, max(1, int(n_buckets)),
                           float(cap_ratio))
        self.strata = [{
            "members": np.asarray(b["members"], np.int64),
            "q": int(b["q"]),
            "nb": int(b["nb"]),
            "nb_full": int(b["nb_full"]),
        } for b in plan]

    def cohort(self, round_idx: int) -> List[Dict[str, np.ndarray]]:
        """Per-stratum ``{"ids", "starts"}`` draws for one round.

        ``starts`` seeds the rotating sample window of over-capacity
        clients (host-side analogue of `_gather_batches_windowed`'s
        on-device draw) — carried with the cohort so a resumed run
        reads the identical windows."""
        g = philox_generator("cohort", self.run_id, self.seed, round_idx)
        out = []
        for s in self.strata:
            members, q = s["members"], s["q"]
            pool = members
            if self.availability is not None:
                avail = members[self.availability(round_idx, members)]
                if len(avail) >= q:
                    pool = avail
                elif len(avail) > 0:
                    logging.warning(
                        "hyperscale sampler: stratum has %d available < "
                        "quota %d at round %d — over-soliciting the "
                        "available set", len(avail), q, round_idx)
                    pool = avail
            if len(pool) >= q:
                ids = pool[g.choice(len(pool), size=q, replace=False)]
            else:  # degenerate trace: fill the quota with replacement
                ids = pool[g.integers(0, len(pool), size=q)]
            starts = g.integers(0, 1 << 30, size=q, dtype=np.int64)
            out.append({"ids": np.asarray(ids, np.int64), "starts": starts,
                        "nb": s["nb"], "nb_full": s["nb_full"]})
        return out


class _Staged:
    """One round's cohort, in flight to the device."""

    __slots__ = ("grids", "weights", "ids", "cohort_ids", "nbytes",
                 "assemble_s")

    def __init__(self, grids, weights, ids, cohort_ids, nbytes, assemble_s):
        self.grids = grids          # tuple of {"x","y","mask"} device trees
        self.weights = weights      # tuple of [q_b] device arrays
        self.ids = ids              # tuple of [q_b] int32 device arrays
        self.cohort_ids = cohort_ids  # host np.ndarray (for logging/tests)
        self.nbytes = nbytes
        self.assemble_s = assemble_s


class StreamingParrotAPI:
    """Parrot rounds over a virtual population with streamed cohorts.

    Shares the round arithmetic with `ParrotAPI` (same `local_update`,
    `build_aggregate`, `per_client_algo_state`) — the difference is the
    data plane: cohort grids are host-assembled per round and streamed
    in, instead of gathered from a device-resident ``[N, cap]`` matrix.
    With ``cohort_sampling="reference"`` and one stratum the trajectory
    matches `ParrotAPI.train()` (same sampling draws, same rng stream,
    same vmap/aggregate graph) — the parity tests pin this.
    """

    def __init__(self, args: Any, device: Any, dataset: Optional[Tuple],
                 bundle: Any, population: Optional[ClientPopulation] = None,
                 use_mesh: bool = False) -> None:
        self.args = args
        self.bundle = bundle
        self.algo = str(getattr(args, "federated_optimizer", "FedAvg"))
        self.pop = population if population is not None \
            else load_population(args, dataset)
        self.n_total = self.pop.n_clients
        self.k = int(args.client_num_per_round)
        self.bs = int(getattr(args, "batch_size", 32))
        self.n_buckets = max(1, int(getattr(args, "hetero_buckets", 1) or 1))
        self.bucket_cap = float(
            getattr(args, "hetero_bucket_cap", 0.0) or 0.0)
        self.prefetch = int(getattr(args, "stream_prefetch", 2) or 2)
        seed = int(getattr(args, "random_seed", 0) or 0)

        # ---- host-resident base arrays (the ONLY copy of the data) ----
        store_dtype = bundle.input_dtype
        if str(getattr(args, "data_dtype", "") or "") == "bfloat16" \
                and bundle.input_dtype == jnp.float32:
            store_dtype = jnp.bfloat16
        self.x_base = np.asarray(self.pop.x, dtype=store_dtype)
        self.y_base = np.asarray(self.pop.y)

        # ---- mesh -----------------------------------------------------
        self.mesh = None
        if use_mesh:
            dcn = dict(getattr(args, "dcn_mesh_shape", None) or {})
            dcn_prod = int(np.prod(list(dcn.values()))) if dcn else 1
            shape = getattr(args, "mesh_shape", None) or {
                AXIS_CLIENTS: max(
                    min(len(jax.devices()) // dcn_prod, self.k), 1)}
            self.mesh = (build_hybrid_mesh(shape, dcn) if dcn
                         else build_mesh(shape))
        msize = 1 if self.mesh is None else int(
            np.prod([self.mesh.shape[n] for n in self.mesh.axis_names]))
        #: per-client state tables pad N to a multiple of the mesh so the
        #: client-axis layout is balanced (GSPMD would otherwise give one
        #: device the ragged shard)
        self.n_pad = -(-self.n_total // msize) * msize

        # ---- sampler --------------------------------------------------
        self.sampling = str(getattr(args, "cohort_sampling", "") or
                            ("reference" if self.n_buckets <= 1
                             else "hierarchical"))
        avail = make_availability(
            getattr(args, "availability_trace", None), self.n_total, seed)
        if self.sampling == "reference" and avail is not None:
            raise ValueError("availability traces need "
                             "cohort_sampling='hierarchical'")
        self.sampler = HierarchicalCohortSampler(
            self.pop.sizes, self.k, self.bs,
            n_buckets=self.n_buckets, cap_ratio=self.bucket_cap,
            run_id=str(getattr(args, "run_id", "") or ""), seed=seed,
            availability=avail)
        if self.sampling == "reference":
            # parity with ParrotAPI: ONE stratum at the global max
            # capacity, cohorts drawn with the reference host RNG
            nb = max(1, -(-int(self.pop.sizes.max()) // self.bs))
            self.sampler.strata = [{
                "members": np.arange(self.n_total, dtype=np.int64),
                "q": self.k, "nb": nb, "nb_full": nb}]

        # ---- model / engine (identical to ParrotAPI) ------------------
        rng = jax.random.PRNGKey(seed)
        self.global_vars = bundle.init_variables(
            rng, batch_size=min(self.bs, 8))
        self.local_update = build_local_update(bundle, args)
        self.eval_step = jax.jit(build_eval_step(bundle))

        # ---- server state: per-client tables sharded on the client axis
        self.server_state: Dict[str, Any] = {}
        state_shard = stacked_client_sharding(self.mesh)
        if self.algo == FED_OPT_FEDOPT:
            # same channel choice as build_aggregate: fused-epilogue
            # optimizer state when the server optimizer maps onto the
            # kernel family, optax state otherwise
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
            self.server_state["c_locals"] = self._stacked_table(
                self.global_vars["params"], state_shard)
        if self.algo == FED_OPT_FEDDYN:
            self.server_state["h"] = _zeros_like(self.global_vars["params"])
            self.server_state["lambdas"] = self._stacked_table(
                self.global_vars["params"], state_shard)
        if self.algo == FED_OPT_MIME:
            self.server_state["momentum"] = _zeros_like(
                self.global_vars["params"])

        self._shardings = [grid_sharding(self.mesh, s["q"], self.bs)
                           for s in self.sampler.strata]
        self.round_step_fn = self._build_round_step()
        self.round_step = jax.jit(self.round_step_fn,
                                  donate_argnums=(3, 4))
        self.metrics_history: List[Dict[str, Any]] = []
        self._reset_stats()

    # ------------------------------------------------------------------
    def _stacked_table(self, template, sharding):
        table = _stack_zeros_like(template, self.n_pad)
        return jax.device_put(table, sharding) if sharding is not None \
            else table

    def _reset_stats(self) -> None:
        self._h2d_s = 0.0
        self._compute_s = 0.0
        self._assemble_s = 0.0
        self._bytes_h2d = 0
        self._clients_done = 0
        self._wall_s = 0.0

    # ------------------------------------------------------------------
    def _cohort(self, round_idx: int) -> List[Dict[str, np.ndarray]]:
        if self.sampling == "reference":
            s = self.sampler.strata[0]
            if self.n_total == self.k:
                ids = np.arange(self.k, dtype=np.int64)
            else:
                np.random.seed(round_idx)  # ParrotAPI._client_sampling
                ids = np.random.choice(self.n_total, self.k,
                                       replace=False).astype(np.int64)
            return [{"ids": ids,
                     "starts": np.zeros(self.k, np.int64),
                     "nb": s["nb"], "nb_full": s["nb_full"]}]
        return self.sampler.cohort(round_idx)

    def _assemble(self, sl: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Host gather: one stratum's cohort → a padded [q, nb, bs, ...]
        batch grid.  Over-capacity clients contribute the rotating
        circular window seeded by the sampler; everyone else their
        padded slots (-1 masks the tail) — mirrors the device gather of
        `_gather_batches(_windowed)` exactly, one cohort at a time."""
        ids, starts = sl["ids"], sl["starts"]
        nb = int(sl["nb"])
        capn = nb * self.bs
        q = len(ids)
        idx = np.full((q, capn), -1, np.int64)
        for j, cid in enumerate(ids):
            rows = self.pop.rows(int(cid))
            n_i = len(rows)
            if n_i > capn:
                pos = (int(starts[j]) % n_i + np.arange(capn)) % n_i
                idx[j] = rows[pos]
            else:
                idx[j, :n_i] = rows[:n_i]
        safe = np.maximum(idx, 0).reshape(-1)
        x = self.x_base[safe].reshape(
            (q, nb, self.bs) + self.x_base.shape[1:])
        y = self.y_base[safe].reshape(
            (q, nb, self.bs) + self.y_base.shape[1:])
        mask = (idx >= 0).astype(np.float32).reshape(q, nb, self.bs)
        return {"x": x, "y": y, "mask": mask}

    def _stage(self, round_idx: int) -> _Staged:
        """Assemble round ``round_idx``'s cohort and start its upload.

        ``jax.device_put`` is async — the copy proceeds while the caller
        keeps dispatching; the consumer pays only the residual wait in
        its ``h2d`` phase.  Under double-buffering this is called right
        after round ``r``'s compute is dispatched, so assembly and
        upload hide behind device work."""
        t0 = time.perf_counter()
        cohort = self._cohort(round_idx)
        grids, weights, ids_dev, nbytes = [], [], [], 0
        for i, sl in enumerate(cohort):
            grid = self._assemble(sl)
            sh = self._shardings[i]
            dev = (jax.device_put(grid, sh) if sh is not None
                   else jax.device_put(grid))
            grids.append(dev)
            w = self.pop.sizes[sl["ids"]].astype(np.float32)
            weights.append(jax.device_put(w))
            ids_dev.append(jax.device_put(sl["ids"].astype(np.int32)))
            nbytes += sum(int(a.nbytes) for a in grid.values()) + w.nbytes
        if flight_recorder.enabled():
            flight_recorder.note_transfer("h2d", nbytes)
        self._bytes_h2d += nbytes
        cohort_ids = np.concatenate([sl["ids"] for sl in cohort])
        ledger.event("hyperscale", "stage", round_idx=int(round_idx),
                     clients=int(cohort_ids.size), nbytes=int(nbytes))
        return _Staged(tuple(grids), tuple(weights), tuple(ids_dev),
                       cohort_ids, nbytes, time.perf_counter() - t0)

    # ------------------------------------------------------------------
    def _build_round_step(self):
        """The streamed round jit: per-stratum vmapped local updates over
        grids that arrive as EXPLICIT traced arguments (already sharded
        by `_stage`), concatenated into the shared aggregation.  Same
        contract as `ParrotAPI._build_bucketed_round_step`, minus the
        on-device sampling/gather — sampling moved to the host sampler
        and the gather to `_assemble`."""
        in_axes = algo_in_axes(self.algo)
        aggregate = build_aggregate(self.args, self.algo, self.n_total,
                                    server_tx=getattr(self, "server_tx",
                                                      None),
                                    mesh=self.mesh)
        algo = self.algo
        local_update = self.local_update
        n_strata = len(self.sampler.strata)
        shardings = self._shardings

        def round_step(grids, weights, client_ids, global_vars,
                       server_state, rng):
            outs = []
            # single stratum consumes rng exactly like ParrotAPI's
            # uniform round (split to K client keys) — bit parity
            keys = ([rng] if n_strata == 1
                    else list(jax.random.split(rng, n_strata)))
            for i in range(n_strata):
                grid = grids[i]
                if shardings[i] is not None:
                    grid = jax.lax.with_sharding_constraint(
                        grid, shardings[i])
                ids = client_ids[i]
                rngs = jax.random.split(keys[i], ids.shape[0])
                algo_state = per_client_algo_state(algo, server_state, ids)
                new_vars, algo_out, metrics = jax.vmap(
                    local_update, in_axes=(None, 0, 0, in_axes))(
                        global_vars, grid, rngs, algo_state or None)
                outs.append((new_vars, algo_out, metrics, weights[i], ids))

            def cat(trees):
                return jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *trees)

            new_vars = cat([o[0] for o in outs])
            algo_out = cat([o[1] for o in outs])
            metrics = cat([o[2] for o in outs])
            all_w = jnp.concatenate([o[3] for o in outs])
            all_ids = jnp.concatenate([o[4] for o in outs])
            return aggregate(global_vars, server_state, all_ids,
                             new_vars, algo_out, metrics, all_w)

        return round_step

    # ------------------------------------------------------------------
    def train(self) -> Dict[str, Any]:
        comm_rounds = int(self.args.comm_round)
        seed = int(getattr(self.args, "random_seed", 0) or 0)
        rng = jax.random.PRNGKey(seed + 17)  # ParrotAPI.train's stream
        test_batches = self._make_test_batches()
        final_metrics: Dict[str, Any] = {}
        streaming = self.prefetch >= 2
        self._reset_stats()

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
                # replay the rng stream to the resume point so the
                # cohort AND client-key draws match the unbroken run
                for _ in range(start_round):
                    rng, _ = jax.random.split(rng)
                logging.info("hyperscale: resumed from round %d",
                             start_round - 1)

        t_wall = time.perf_counter()
        ctx = (self.mesh if self.mesh is not None
               else contextlib.nullcontext())
        staged: Optional[_Staged] = None
        with ctx:
            if streaming:
                staged = self._stage(start_round)
                self._assemble_s += staged.assemble_s
            for round_idx in range(start_round, comm_rounds):
                t0 = time.time()
                rng, sub = jax.random.split(rng)
                with flight_recorder.record_round(
                        "hyperscale_round", rounds=1,
                        program="parrot/streaming_round_step") as fr:
                    if streaming:
                        th = time.perf_counter()
                        with fr.phase("h2d"):
                            # residual wait only: the upload started
                            # last round, behind the device compute
                            jax.block_until_ready(staged.grids)
                        self._h2d_s += time.perf_counter() - th
                        (self.global_vars, self.server_state,
                         rm) = self.round_step(
                            staged.grids, staged.weights, staged.ids,
                            self.global_vars, self.server_state, sub)
                        # round r+1 assembles + uploads WHILE the device
                        # runs round r — the double buffer
                        nxt = None
                        if round_idx + 1 < comm_rounds:
                            nxt = self._stage(round_idx + 1)
                            self._assemble_s += nxt.assemble_s
                        tc = time.perf_counter()
                        with fr.phase("device_compute"):
                            rm = jax.block_until_ready(rm)
                        self._compute_s += time.perf_counter() - tc
                        staged = nxt
                    else:
                        th = time.perf_counter()
                        with fr.phase("h2d"):
                            cur = self._stage(round_idx)
                            self._assemble_s += cur.assemble_s
                            jax.block_until_ready(cur.grids)
                        self._h2d_s += time.perf_counter() - th
                        tc = time.perf_counter()
                        with fr.phase("device_compute"):
                            (self.global_vars, self.server_state,
                             rm) = self.round_step(
                                cur.grids, cur.weights, cur.ids,
                                self.global_vars, self.server_state, sub)
                            rm = jax.block_until_ready(rm)
                        self._compute_s += time.perf_counter() - tc
                self._clients_done += self.k
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
                    }, f"hyperscale round {round_idx}")
                if ckpt is not None and (round_idx % ckpt_freq == 0
                                         or round_idx == comm_rounds - 1):
                    ckpt.save(round_idx, {
                        "round_idx": round_idx,
                        "global_vars": self.global_vars,
                        "server_state": self.server_state,
                    })
        self._wall_s = time.perf_counter() - t_wall
        return final_metrics

    # ------------------------------------------------------------------
    def stream_stats(self) -> Dict[str, Any]:
        """The headline: clients-simulated/sec, plus the h2d/compute
        decomposition the overlap claim is made from.  ``h2d_share`` is
        the fraction of wall time spent BLOCKED on staging — under
        double-buffering it collapses toward 0 because the upload hides
        behind the previous round's compute; ``overlap_frac`` is the
        share of staging work hidden that way."""
        wall = max(self._wall_s, 1e-9)
        stage_total = self._assemble_s
        hidden = max(0.0, stage_total - self._h2d_s)
        return {
            "n_clients": self.n_total,
            "clients_simulated": self._clients_done,
            "clients_per_sec": round(self._clients_done / wall, 2),
            "wall_s": round(wall, 4),
            "h2d_blocked_s": round(self._h2d_s, 4),
            "h2d_share": round(self._h2d_s / wall, 4),
            "compute_s": round(self._compute_s, 4),
            "compute_share": round(self._compute_s / wall, 4),
            "stage_work_s": round(stage_total, 4),
            "overlap_frac": round(hidden / max(stage_total, 1e-9), 4),
            "h2d_bytes": int(self._bytes_h2d),
            "prefetch": self.prefetch,
            "sampling": self.sampling,
            "strata": len(self.sampler.strata),
        }

    def _make_test_batches(self):
        x_te, y_te = self.pop.test
        nb_te = max(1, -(-len(y_te) // self.bs))
        return make_batches(x_te, y_te, self.bs, nb_te,
                            self.bundle.input_dtype)

    def _record_metrics(self, metrics: Dict[str, Any], tag: str
                        ) -> Dict[str, Any]:
        self.metrics_history.append(metrics)
        mlops.log(metrics)
        logging.info("%s: %s", tag, metrics)
        return metrics
