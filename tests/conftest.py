"""Test config: run everything on a virtual 8-device CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (SURVEY §4 implication)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()

import pytest  # noqa: E402

#: the fast CI tier (`pytest -m smoke`, CI target ~3-4 min): one
#: representative file per major subsystem; everything in these files is
#: smoke unless explicitly marked slow.  Measured 3:09-3:37 on this box
#: (141 tests; varies with background load).
_SMOKE_FILES = {
    "test_algorithms.py", "test_sp_simulation.py", "test_parrot.py",
    "test_transports.py", "test_security.py", "test_mpc.py",
    "test_fhe.py", "test_aux_subsystems.py", "test_multiprocess.py",
    "test_lint.py", "test_lint_wholeprogram.py", "test_lint_perf.py",
    "test_lint_mesh.py",
    # test_reliability.py runs in its own dedicated smoke.yml step (like
    # test_observability.py) — listing it here would run the chaos soak
    # twice per CI job; test_aggregation.py likewise runs in the
    # byzantine-soak step (its slow-marked soaks only run there),
    # test_async_agg.py in the async-soak step (wan-lossy straggler
    # soak), and test_fed_llm.py in the fed-llm step (e2e federations +
    # the federated bench guard)
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.fspath.basename in _SMOKE_FILES
                and "slow" not in item.keywords):
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(autouse=True)
def _reset_singletons():
    """Each test gets fresh process-wide singletons."""
    yield
    from fedml_tpu.core.alg_frame.context import Context
    from fedml_tpu.core.dp.fedml_differential_privacy import (
        FedMLDifferentialPrivacy,
    )
    from fedml_tpu.core.security.fedml_attacker import FedMLAttacker
    from fedml_tpu.core.security.fedml_defender import FedMLDefender
    from fedml_tpu.ml.engine.mesh import MeshManager

    Context.reset()
    MeshManager.reset()
    FedMLAttacker._instance = None
    FedMLDefender._instance = None
    FedMLDifferentialPrivacy._instance = None


def make_args(**kw):
    from fedml_tpu.arguments import Config

    base = dict(
        dataset="synthetic",
        model="lr",
        client_num_in_total=4,
        client_num_per_round=4,
        comm_round=3,
        epochs=1,
        batch_size=16,
        learning_rate=0.1,
        frequency_of_the_test=1,
        data_scale=0.1,
        enable_tracking=False,
        compute_dtype="float32",
    )
    base.update(kw)
    return Config(**base)


@pytest.fixture
def args_factory():
    return make_args


def rounded_flash_reference(q, k, v, causal, block_k, t_valid=None):
    """The flash recurrence in plain jnp with the kernel's rounding, for the
    tight comparisons: q x scale, k, v and p reach the two products as
    bfloat16, everything else is float32, and the keys are taken `block_k`
    at a pass (p is rounded against the running max, so the pass length is
    part of the arithmetic).  Whole [B, H, T, D] arrays, no tiling of the
    queries, no skipped pass.  Returns (o, l, m)."""
    import jax.numpy as jnp

    t, tk, d = q.shape[2], k.shape[2], q.shape[3]
    t_valid = tk if t_valid is None else t_valid
    bf, f32 = jnp.bfloat16, jnp.float32
    qs = (q.astype(f32) * (1.0 / float(d) ** 0.5)).astype(bf)
    o = jnp.zeros(q.shape, f32)
    l = jnp.zeros(q.shape[:3] + (1,), f32)
    m = jnp.full(q.shape[:3] + (1,), -1e30, f32)
    q_pos = jnp.arange(t)[:, None]
    for start in range(0, tk, block_k):
        rows = slice(start, start + block_k)
        k_pos = jnp.arange(start, start + block_k)[None, :]
        mask = k_pos < t_valid
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, k[:, :, rows].astype(bf),
                       preferred_element_type=f32)
        s = jnp.where(mask, s, -1e30)
        new_m = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - new_m), 0.0)
        alpha = jnp.exp(m - new_m)
        l = l * alpha + p.sum(-1, keepdims=True)
        o = o * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(bf), v[:, :, rows].astype(bf),
            preferred_element_type=f32)
        m = new_m
    return (o / jnp.maximum(l, 1e-12)).astype(q.dtype), l[..., 0], m[..., 0]


def rounded_flash_backward(q, k, v, o, l, m, do, causal, t_valid=None,
                           window=None):
    """The attention backward in plain jnp with the rounding of the kernel
    `flash_bwd`, for the tight comparisons: p = exp(s - (m + log l)) from
    the forward's residuals, delta = sum(do * o), ds = p (dp - delta); q x
    scale, k, v, do, p and ds reach the five products as bfloat16,
    everything else is float32.  Whole [B, H, T, D] arrays, no tiling, no
    skipped tile; K and V of fewer heads are spread over their groups and
    their gradients summed.  Returns float32 (dq, dk, dv)."""
    import jax.numpy as jnp

    b, h, t, d = q.shape
    hk, tk = k.shape[1], k.shape[2]
    t_valid = tk if t_valid is None else t_valid
    bf, f32 = jnp.bfloat16, jnp.float32
    scale = 1.0 / float(d) ** 0.5
    qs = (q.astype(f32) * scale).astype(bf)
    kr, vr = (jnp.repeat(z, h // hk, axis=1).astype(bf) for z in (k, v))
    gap = jnp.arange(t)[:, None] - jnp.arange(tk)[None, :]
    mask = jnp.broadcast_to(jnp.arange(tk)[None, :] < t_valid, (t, tk))
    if causal:
        mask = mask & (gap >= 0)
    if window is not None:
        mask = mask & (gap < window)

    def mm(spec, x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=f32)

    lse = m + jnp.log(jnp.maximum(l, 1e-12))
    p = jnp.where(mask, jnp.exp(mm("bhqd,bhkd->bhqk", qs, kr)
                                - lse[..., None]), 0.0)
    delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)
    ds = (p * (mm("bhqd,bhkd->bhqk", do.astype(bf), vr)
               - delta[..., None])).astype(bf)
    dq = mm("bhqk,bhkd->bhqd", ds, kr) * scale
    dk = mm("bhqk,bhqd->bhkd", ds, qs)
    dv = mm("bhqk,bhqd->bhkd", p.astype(bf), do.astype(bf))
    dk, dv = (z.reshape(b, hk, h // hk, tk, d).sum(2) for z in (dk, dv))
    return dq, dk, dv


def few_bits(seed, shape, step=0.25):
    """Unit-normal draws rounded to multiples of ``step`` within +-2, as
    float32: the products of two such numbers summed in float32 are exact
    in any order, so two computations that round such a sum to bfloat16
    round it the same way."""
    import jax.numpy as jnp
    import numpy as np

    draw = np.random.RandomState(seed).randn(*shape)
    return jnp.asarray(np.clip(np.round(draw / step) * step, -2, 2),
                       jnp.float32)
