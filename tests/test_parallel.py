"""Parallelism layer on the virtual 8-device CPU mesh: ring attention parity,
TP/FSDP sharding rules, pipeline schedule, MoE routing."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.ml.engine.mesh import build_mesh


def test_ring_attention_matches_full_attention():
    from fedml_tpu.parallel.ring_attention import (
        make_ring_attention_fn,
        reference_attention,
    )

    mesh = build_mesh({"seq": 4})
    rng = np.random.RandomState(0)
    b, h, t, d = 2, 2, 32, 8
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    for causal in (True, False):
        ring = make_ring_attention_fn(mesh, causal=causal)
        with mesh:
            out = jax.jit(ring)(q, k, v)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)


def test_tp_and_fsdp_sharding_rules():
    from fedml_tpu.parallel.sharding import make_param_shardings

    mesh = build_mesh({"data": 2, "model": 4})
    params = {
        "attn": {"query": {"kernel": jnp.zeros((128, 128))},
                 "out": {"kernel": jnp.zeros((128, 128))}},
        "mlp": {"Dense_0": {"kernel": jnp.zeros((128, 512))},
                "Dense_1": {"kernel": jnp.zeros((512, 128))}},
        "norm": {"scale": jnp.zeros((128,))},
    }
    sh = make_param_shardings(params, mesh, "tp_fsdp")
    assert sh["attn"]["query"]["kernel"].spec == P(None, "model")
    assert sh["attn"]["out"]["kernel"].spec == P("model", None)
    assert sh["mlp"]["Dense_0"]["kernel"].spec == P(None, "model")
    assert sh["mlp"]["Dense_1"]["kernel"].spec == P("model", None)
    # small norm param stays replicated
    assert sh["norm"]["scale"].spec == P()
    # fsdp-only: large kernels shard over data on an even axis
    sh2 = make_param_shardings(params, mesh, "fsdp")
    assert sh2["mlp"]["Dense_0"]["kernel"].spec in (P("data", None),
                                                    P(None, "data"))


def test_sharded_train_step_runs_dp_and_fsdp():
    import fedml_tpu
    from fedml_tpu.parallel.sharding import (
        batch_sharding,
        build_sharded_train_step,
    )

    args = fedml_tpu.Config(model="cnn", dataset="mnist", batch_size=16,
                            compute_dtype="float32", learning_rate=0.05)
    bundle = fedml_tpu.model.create(args, 10)
    mesh = build_mesh({"data": 8})
    variables = bundle.init_variables(jax.random.PRNGKey(0))
    for strategy in ("dp", "fsdp"):
        train_step, init_shardings, tx = build_sharded_train_step(
            bundle, args, mesh, strategy)
        shardings = init_shardings(variables)
        v = jax.device_put(variables, shardings)
        opt_state = tx.init(v["params"])
        batch = {
            "x": jax.device_put(
                jnp.zeros((16, 28, 28, 1)), batch_sharding(mesh)),
            "y": jax.device_put(jnp.zeros((16,), jnp.int32),
                                batch_sharding(mesh)),
            "mask": None,
        }
        step = jax.jit(train_step)
        with mesh:
            v2, opt_state, metrics = step(v, opt_state, batch,
                                          jax.random.PRNGKey(1))
        assert np.isfinite(float(metrics["loss"]))


def test_pipeline_matches_sequential():
    from fedml_tpu.parallel.pipeline import make_pipeline_fn, stack_stage_params

    mesh = build_mesh({"pipe": 4})
    rng = np.random.RandomState(0)
    d = 16

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    stages = [{"w": jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32),
               "b": jnp.asarray(rng.randn(d) * 0.1, jnp.float32)}
              for _ in range(4)]
    stacked = stack_stage_params(stages)
    x = jnp.asarray(rng.randn(8, 4, d), jnp.float32)  # [M=8 microbatches, mb=4]

    pipe = make_pipeline_fn(stage_fn, mesh, n_microbatches=8)
    with mesh:
        out = jax.jit(pipe)(stacked, x)

    expect = x
    for s in stages:
        expect = jnp.tanh(expect @ s["w"] + s["b"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_switch_moe_forward_and_balance():
    from fedml_tpu.parallel.expert_parallel import SwitchMoE

    moe = SwitchMoE(n_experts=4, d_ff=32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 8), jnp.float32)
    variables = moe.init(jax.random.PRNGKey(0), x)
    out, state = moe.apply(variables, x, mutable=["intermediates"])
    assert out.shape == x.shape
    aux = state["intermediates"]["moe_aux_loss"][0]
    assert np.isfinite(float(aux)) and float(aux) > 0.5  # ~1 when balanced


def test_ulysses_attention_matches_full_attention():
    from fedml_tpu.parallel.ring_attention import reference_attention
    from fedml_tpu.parallel.ulysses import make_ulysses_attention_fn

    mesh = build_mesh({"seq": 4})
    rng = np.random.RandomState(1)
    b, h, t, d = 2, 8, 32, 8  # heads (8) divisible by axis size (4)
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    for causal in (True, False):
        uly = make_ulysses_attention_fn(mesh, causal=causal)
        with mesh:
            out = jax.jit(uly)(q, k, v)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)


def test_hybrid_mesh_single_slice_fallback():
    """On one slice (CPU test devices) the DCN axes collapse to size 1 and
    the same sharding program runs; collectives still compile over both
    axis names."""
    from fedml_tpu.ml.engine.mesh import build_hybrid_mesh

    import pytest as _pytest

    with _pytest.raises(ValueError, match="BOTH"):
        build_hybrid_mesh({"data": 2}, {"data": 4})
    mesh = build_hybrid_mesh({"model": 4}, {"data": 2})
    assert mesh.axis_names == ("model", "data")
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "model": 4, "data": 2}

    # psum over BOTH axes (the dp-over-dcn + tp-over-ici layout)
    @partial(jax.shard_map, mesh=mesh, in_specs=P("data", "model"),
             out_specs=P(None, None), check_vma=False)
    def total(x):
        return jax.lax.psum(jax.lax.psum(x, "model"), "data")

    x = jnp.arange(8.0).reshape(2, 4)
    out = jax.jit(total)(x)
    np.testing.assert_allclose(np.asarray(out)[0, 0], x.sum())


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_gradients_match_reference(causal):
    """Sequence-parallel training path: grads through the custom second-ring
    backward equal grads of plain full attention."""
    from fedml_tpu.parallel.ring_attention import (
        make_ring_attention_fn,
        reference_attention,
    )

    mesh = build_mesh({"seq": 4})
    rng = np.random.RandomState(1)
    b, h, t, d = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    w = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)  # fixed cotangent

    ring = make_ring_attention_fn(mesh, causal=causal)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) * w)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) * w)

    with mesh:
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("t,blocks", [(24, 8), (300, None)],
                         ids=["t24-blocks8", "t300-derived"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gradients_match_reference(causal, t, blocks):
    """The flash custom VJP (the kernels `flash_fwd` and `flash_bwd`)
    through the interpreter on CPU, with explicit 8 x 8 blocks and with
    blocks derived from 300 positions (padded to 384: 128 x 128, forward and
    backward).  The kernels round their operands to bfloat16, so: (a) closely
    against a jnp backward that rounds the same operands, fed the residuals
    of a reference that rounds as the forward does, (b) against autodiff of
    the float32 formulation within the bfloat16 tolerance."""
    from conftest import rounded_flash_backward, rounded_flash_reference
    from fedml_tpu.ops.pallas_attention import flash_attention
    from fedml_tpu.parallel.ring_attention import reference_attention

    rng = np.random.RandomState(2)
    b, h, d = 1, 2, 8
    q = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)
    w = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=blocks,
                              block_k=blocks, interpret=True)
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) * w)

    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)

    block = blocks or 128
    pad = [(0, 0), (0, 0), (0, -t % block), (0, 0)]
    qp, kp, vp, wp = (jnp.pad(a, pad) for a in (q, k, v, w))
    o, l, m = rounded_flash_reference(qp, kp, vp, causal, block, t_valid=t)
    g_tight = rounded_flash_backward(qp, kp, vp, o, l, m, wp, causal,
                                     t_valid=t)
    # at heads of 8 the sums that feed a rounding to bfloat16 come out the
    # same on both sides; at larger heads tests/test_window_attention.py
    # compares on operands of a few bits
    for a, b_ in zip(g_fl, g_tight):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_[:, :, :t]),
                                   atol=5e-5, rtol=5e-5)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_seq_parallel_lm_train_step_matches_full(strategy):
    """End-to-end sequence-parallel LM training: one jitted step over a
    seq=4 mesh (tokens sharded [B, T/4]) produces the same loss and updated
    params as the unsharded model, and training reduces the loss."""
    from fedml_tpu.models.functional_lm import init_lm_params
    from fedml_tpu.parallel.seq_parallel import build_seq_parallel_train_step

    mesh = build_mesh({"seq": 4})
    vocab, heads, t = 37, 4, 32
    params = init_lm_params(jax.random.PRNGKey(0), vocab, dim=32, layers=2,
                            heads=heads, max_len=t)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, vocab, size=(4, t)), jnp.int32)

    step_sp, tok_shard = build_seq_parallel_train_step(
        mesh, heads, strategy=strategy)
    step_full, _ = build_seq_parallel_train_step(mesh, heads,
                                                 strategy="full")
    with mesh:
        p_sp, loss_sp = step_sp(params, jax.device_put(tokens, tok_shard))
        p_full, loss_full = step_full(params, tokens)
        np.testing.assert_allclose(float(loss_sp), float(loss_full),
                                   rtol=1e-4)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4),
            p_sp, p_full)
        # a few more steps: the sharded path actually trains
        p, losses = p_sp, [float(loss_sp)]
        toks = jax.device_put(tokens, tok_shard)
        for _ in range(5):
            p, l = step_sp(p, toks)
            losses.append(float(l))
        assert losses[-1] < losses[0]


def test_seq_parallel_remat_matches_no_remat():
    """jax.checkpoint over blocks changes memory, not math."""
    from fedml_tpu.models.functional_lm import init_lm_params
    from fedml_tpu.parallel.seq_parallel import build_seq_parallel_train_step

    mesh = build_mesh({"seq": 4})
    params = init_lm_params(jax.random.PRNGKey(0), 31, dim=32, layers=2,
                            heads=4, max_len=16)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 31, (2, 16)))
    outs = []
    for remat in (False, True):
        step, shard = build_seq_parallel_train_step(mesh, 4, strategy="ring",
                                                    remat=remat)
        with mesh:
            p, loss = step(params, jax.device_put(tokens, shard))
        outs.append((p, float(loss)))
    assert outs[0][1] == pytest.approx(outs[1][1], rel=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                atol=1e-5, rtol=1e-5),
        outs[0][0], outs[1][0])


def test_models_import_nothing_from_parallel():
    """The model definitions sit under the strategies that shard them:
    `fedml_tpu.parallel` imports `fedml_tpu.models`, never the other way
    round, at the top of a module or inside a function."""
    import ast
    import pathlib

    import fedml_tpu

    root = pathlib.Path(fedml_tpu.__file__).parent
    found = []
    for path in sorted((root / "models").rglob("*.py")):
        package = ("fedml_tpu",) + path.relative_to(root).parts[:-1]
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                base = ".".join(package[:len(package) - node.level + 1]
                                if node.level else ())
                module = ".".join(x for x in (base, node.module) if x)
                names = [module] + [f"{module}.{a.name}" for a in node.names]
            else:
                continue
            found += [f"{path.name}: {n}" for n in names
                      if (n + ".").startswith("fedml_tpu.parallel.")]
    assert not found, found
