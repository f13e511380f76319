"""The bench accuracy guard must be real evidence: on the HARD synthetic
image data (class mixing + jitter + label noise, the north-star bench
construction) a healthy run clears its target while a deliberately
sabotaged aggregator does not (VERDICT r3 item 4)."""

import numpy as np
import pytest

import fedml_tpu
from fedml_tpu.runner import FedMLRunner


def _run(monkeypatch=None, sabotage=False):
    if sabotage:
        import fedml_tpu.simulation.parrot.parrot_api as pa

        orig = pa.agg_stacked

        def broken(new_vars, weights, **kw):
            # sabotage: the aggregate comes out 20x too small (the
            # "aggregation output numerically wrong" failure class — e.g.
            # a mis-scaled weight normalization); learning stalls and the
            # run must miss the guard threshold
            out = orig(new_vars, weights, **kw)
            import jax

            return jax.tree_util.tree_map(
                lambda a: a * 0.05, out)

        monkeypatch.setattr(pa, "agg_stacked", broken)
    args = fedml_tpu.init(fedml_tpu.Config(
        dataset="mnist", model="lr", backend="parrot",
        partition_method="hetero", partition_alpha=0.5,
        synthetic_hard=True,
        client_num_in_total=12, client_num_per_round=6, comm_round=60,
        epochs=1, batch_size=16, learning_rate=0.1, data_scale=0.2,
        frequency_of_the_test=100, enable_tracking=False,
        compute_dtype="float32", hetero_buckets=1))
    device = fedml_tpu.device.get_device(args)
    dataset = fedml_tpu.data.load(args)
    bundle = fedml_tpu.model.create(args, dataset[-1])
    api = FedMLRunner(args, device, dataset, bundle).runner
    api.run_rounds_fused(60)
    tb = api._make_test_batches()
    out = api.eval_step(api.global_vars, tb)
    return float(out["correct"]) / max(float(out["n"]), 1.0)


@pytest.mark.slow
def test_guard_discriminates_broken_aggregation(monkeypatch):
    healthy = _run()
    broken = _run(monkeypatch, sabotage=True)
    # measured (CPU, deterministic, hard_v2 data): healthy 0.295 vs
    # sabotaged 0.13 — a guard threshold between them fails the sabotage
    assert healthy > 0.22, healthy
    assert broken < healthy - 0.10, (healthy, broken)
