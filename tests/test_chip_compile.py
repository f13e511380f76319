"""The main path's Pallas kernels compile for the real chip, at real widths,
and `decode_multi` around its kernels keeps the cache in place and scores no
position that is not live.

No chip is attached here: the TPU compiler that is installed compiles for a
v5e that is described, not present (on-chip-measurement guide, section 2,
third rehearsal).  That catches what interpret mode cannot — a slice not
aligned to the tiling, too much VMEM, a kernel that cannot be lowered —
before any chip time is spent.  Nothing runs, so nothing here says anything
about results or speed.

This is the only file that describes a topology.  The description happens
inside a fixture, never at import: only one process may load the TPU
library, and every xdist worker imports every test file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from fedml_tpu.ops import epilogue, pallas_ops, wire_compression
from fedml_tpu.ops.pallas_attention import flash_attention

#: ResNet-56 (CIFAR) — flat parameter count and the distinct leaf shapes of
#: its variables (conv kernels, norm scales/biases/stats, the dense head)
RESNET56_FLAT = 860_026
RESNET56_LEAVES = ((3, 3, 3, 16), (3, 3, 16, 16), (3, 3, 16, 32),
                   (1, 1, 16, 32), (3, 3, 32, 32), (3, 3, 32, 64), (1, 1, 32, 64),
                   (3, 3, 64, 64), (16,), (32,), (64,), (64, 10), (10,))
N_CLIENTS = 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, one_chip, *specs):
    """Compile ``fn`` for the described chip from (shape, dtype) specs and
    return the program text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


def _kernels(text):
    """{kernel name: its instruction} of a program's Mosaic kernels."""
    return {m.group(1): m.group(0) for m in re.finditer(
        r"%([\w.-]+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*", text)}


def _assert_attention_kernels(text, grad, gone):
    """The program's kernels are `flash_fwd` and, under `grad`, `flash_bwd`
    and no other; the backward's carries the scope `fedml.attn_bwd`, which
    `attn_bwd_ms_per_step` reads, and no array of the shape ``gone`` (a
    score tile of the jnp backward this kernel replaced) is left."""
    kernels = _kernels(text)
    assert set(kernels) == ({"flash_fwd", "flash_bwd"} if grad
                            else {"flash_fwd"}), set(kernels)
    if grad:
        assert "fedml.attn_bwd" in kernels["flash_bwd"]
        assert not re.search(gone, text), re.search(gone, text).group(0)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("qkv", [
    ((4, 12, 1024, 64), jnp.bfloat16),    # GPT-2 small, the SFT smoke
    ((4, 20, 1024, 64), jnp.float32),     # GPT-2 large, the cell `sft.lora_1k`
], ids=["small-bf16", "large-f32"])
def test_flash_attention_compiles_for_v5e(one_chip, qkv, grad):
    """The training attention at the smoke's and at the cell's shape.  The
    program holds `flash_fwd` and, differentiated, `flash_bwd`, and no other
    Mosaic kernel: the benchmark's `flash_fwd_ms_per_step` sums every
    `tpu_custom_call` of the epoch program, so a third kernel must not slip
    in unnoticed.  Nothing of the jnp backward's [B, H, T, 128] scores is
    left."""
    attn = functools.partial(flash_attention, causal=True, interpret=False)
    fn = attn
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attn(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
    text = _compile_text(fn, one_chip, qkv, qkv, qkv)
    _assert_attention_kernels(
        text, grad, r"f32\[%d,%d,1024,128\]" % qkv[0][:2])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("opt", ["none", "momentum", "adam"])
def test_fused_epilogue_compiles_for_v5e(one_chip, opt, dtype):
    """The round epilogue over ResNet-56's leaf shapes, 10 clients, every
    fused optimizer channel, plus one integer leaf (a step counter)."""
    spec = epilogue.EpilogueSpec(opt=opt, lr=1e-3)
    names = [f"p{i}" for i in range(len(RESNET56_LEAVES))]

    def tree(lead, dt, int_leaf=True):
        t = {n: jax.ShapeDtypeStruct(lead + s, dt, sharding=one_chip)
             for n, s in zip(names, RESNET56_LEAVES)}
        if int_leaf:
            t["count"] = jax.ShapeDtypeStruct(lead + (1,), jnp.int32,
                                              sharding=one_chip)
        return t

    global_tree = tree((), dtype)
    stacked = tree((N_CLIENTS,), dtype)
    weights = jax.ShapeDtypeStruct((N_CLIENTS,), jnp.float32,
                                   sharding=one_chip)
    # optimizer moments are f32 trees shaped like the global (the integer
    # leaf included: `init_opt_state` zeros every leaf)
    opt_state = jax.eval_shape(
        lambda g: epilogue.init_opt_state(g, spec), global_tree)
    if opt_state is not None:
        opt_state = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), opt_state)

    def fn(g, x, w, st):
        return epilogue.fused_epilogue(g, x, w, 1.0, spec, st,
                                       interpret=False, prefer_pallas=True)

    text = jax.jit(fn).lower(global_tree, stacked, weights,
                             opt_state).compile().as_text()
    _assert_kernel(text)


def test_weighted_average_flat_compiles_for_v5e(one_chip):
    fn = functools.partial(pallas_ops.weighted_average_flat,
                           interpret=False)
    _assert_kernel(_compile_text(
        fn, one_chip, ((N_CLIENTS, RESNET56_FLAT), jnp.float32),
        ((N_CLIENTS,), jnp.float32)))


def test_int8_quantize_compiles_for_v5e(one_chip, monkeypatch):
    """Off the TPU ``interpret=False`` alone takes the jnp path, so the
    test answers the backend probe itself — no option of the program."""
    monkeypatch.setattr(wire_compression, "_on_tpu", lambda: True)
    _assert_kernel(_compile_text(
        wire_compression.quantize_int8_blocked, one_chip,
        ((RESNET56_FLAT,), jnp.float32)))


def test_int8_dequantize_compiles_for_v5e(one_chip, monkeypatch):
    monkeypatch.setattr(wire_compression, "_on_tpu", lambda: True)
    rows = -(-RESNET56_FLAT // wire_compression.BLOCK)
    fn = functools.partial(wire_compression.dequantize_int8_blocked,
                           d=RESNET56_FLAT)
    _assert_kernel(_compile_text(
        fn, one_chip, ((RESNET56_FLAT,), jnp.int8),
        ((rows,), jnp.float32)))


#: the serving cell's widths: GPT-2 large, 32 slots, 1024 positions
SLOTS, HEADS, DIM, POSITIONS, VOCAB = 32, 20, 1280, 1024, 50257


@pytest.fixture(scope="module")
def decode_multi_compiled(one_chip):
    """`decode_multi` at the serving cell's widths, one layer, compiled for
    the described chip with its kernels steered to their TPU branch: one
    compile a dispatch length, shared by the tests that read it."""
    from fedml_tpu.ops import pallas_decode_attention, pallas_kv_store
    from fedml_tpu.models.functional_lm import init_lm_params
    from fedml_tpu.serving import kv_cache_lm

    spec = functools.partial(jax.tree_util.tree_map, lambda a: (
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)))
    params = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16),
        init_lm_params(jax.random.PRNGKey(0), VOCAB, dim=DIM, layers=1,
                       heads=HEADS, max_len=POSITIONS)))
    cache = jax.eval_shape(functools.partial(
        kv_cache_lm.init_cache, batch=SLOTS, max_len=POSITIONS, heads=HEADS),
        params)
    vec = lambda dt, *s: jax.ShapeDtypeStruct((SLOTS, *s), dt,
                                              sharding=one_chip)

    @functools.lru_cache(maxsize=None)
    def compiled(k):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(pallas_kv_store, "_on_tpu", lambda: True)
            patch.setattr(pallas_decode_attention, "_on_tpu", lambda: True)
            return kv_cache_lm.decode_multi.lower(
                spec(params), spec(cache), vec(jnp.int32, k), vec(jnp.int32),
                vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
                vec(jnp.float32),
                spec(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
                heads=HEADS, k=k).compile()

    return compiled


@pytest.mark.parametrize("k", [2, 8])
def test_decode_multi_stores_in_place_for_v5e(decode_multi_compiled, k):
    """The k new positions go into the donated cache in place.  The output
    aliases the cache and no temporary comes near a K/V array's size (84 MB;
    with the select write-back this program held 271 MB of them a layer), so
    an edit that brings a whole-cache temporary back fails here and not in a
    cell."""
    compiled = decode_multi_compiled(k)
    _assert_kernel(compiled.as_text())
    array_bytes = SLOTS * POSITIONS * DIM * 2
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == 2 * array_bytes, "cache not updated in place"
    assert m.temp_size_in_bytes < array_bytes // 4, m.temp_size_in_bytes


@pytest.mark.parametrize("k", [2, 8])
def test_decode_multi_scores_no_dead_position_for_v5e(decode_multi_compiled,
                                                      k):
    """The attention over the cache is the kernel `decode_attention`, which
    visits live blocks only: no value of the program has a score for every
    position of every row (``f32[32,20,1024]``, what the two contractions
    over the whole cache made until PR 28, 6 GB read a token step)."""
    text = decode_multi_compiled(k).as_text()
    assert "decode_attention" in text and "kv_store_positions" in text
    assert f"f32[{SLOTS},{HEADS},{POSITIONS}]" not in text


@pytest.mark.parametrize("shape,query", [
    ((32, 20, 64, 1024), jnp.float32),    # the serving cell
    ((32, 20, 64, 1024), jnp.bfloat16),   # its first layer's query
    ((32, 20, 64, 1000), jnp.float32),    # `lm_max_len` 1000: a ragged block
    ((8, 12, 64, 300), jnp.float32),      # GPT-2 small, a short ragged cache
    ((4, 12, 64, 40), jnp.float32),       # shorter than one lane tile
], ids=["large-1024", "large-1024-bf16-query", "large-1000", "small-300",
        "small-40"])
def test_decode_attention_compiles_for_v5e(one_chip, monkeypatch, shape,
                                           query):
    """`decode_attention` at the cell's size and at lengths that are not
    whole blocks: blocks of whole lane tiles whose last is ragged, the
    grid's length counted on the device."""
    from fedml_tpu.ops import pallas_decode_attention

    monkeypatch.setattr(pallas_decode_attention, "_on_tpu", lambda: True)
    array = (shape, jnp.bfloat16)
    text = _compile_text(
        lambda q, k, v, n: pallas_decode_attention.decode_attention(
            q, k, v, n, 0.125),
        one_chip, (shape[:3], query), array, array, (shape[:1], jnp.int32))
    _assert_kernel(text)


@pytest.mark.parametrize("shape,k", [
    ((32, 20, 64, 1000), 8),      # GPT-2 large, `lm_max_len` 1000
    ((8, 12, 64, 2000), 8),       # GPT-2 small, a longer cache
    ((32, 20, 64, 1000), 130),    # a window over three blocks, the last ragged
    ((4, 12, 64, 40), 2),         # shorter than one lane tile
], ids=["large-1000", "small-2000", "large-1000-k130", "small-40"])
def test_kv_store_compiles_at_ragged_lengths_for_v5e(one_chip, monkeypatch,
                                                     shape, k):
    """`store_positions` serves any cache length (`model_hub` hands the
    user's `lm_max_len` through): 128-position blocks whose last is ragged,
    never a whole row, which at these sizes does not fit the chip's VMEM."""
    from fedml_tpu.ops import pallas_kv_store

    monkeypatch.setattr(pallas_kv_store, "_on_tpu", lambda: True)
    array = (shape, jnp.bfloat16)
    chunk = (shape[:3] + (k,), jnp.bfloat16)
    text = _compile_text(
        lambda a, b, c, d, p: pallas_kv_store.store_positions(
            [a, b], [c, d], p),
        one_chip, array, array, chunk, chunk, ((shape[0],), jnp.int32))
    _assert_kernel(text)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_grouped_window_attention_compiles_for_v5e(one_chip, window, grad):
    """The attention of the cell `sft.smallthinker_lora_16k`: 7 query heads
    over 1 key/value head of 128 at 16,384 positions, fully causal and under
    a 4096 window.  K and V of that length stay in VMEM as one block (32 MiB
    double-buffered, over the compiler's default limit, which the call
    raises); the backward kernel holds dK and dV of that length beside them
    (64 MiB together) and no [.., 1024, 1024] score tile is left."""
    attn = functools.partial(flash_attention, causal=True, interpret=False,
                             window=window)
    fn = attn
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attn(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
    q, kv = ((1, 7, 16384, 128), jnp.float32), ((1, 1, 16384, 128),
                                                jnp.float32)
    text = _compile_text(fn, one_chip, q, kv, kv)
    _assert_attention_kernels(text, grad, r"\[[\d,]*1024,1024\]")


@pytest.mark.parametrize("transposed", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("k,n", [(2560, 1536), (768, 2560)],
                         ids=["gate_up", "down"])
def test_expert_products_compile_for_v5e(one_chip, k, n, transposed):
    """`moe_experts` at the same cell's size: the worst case's rows (every
    pick of 16,384 tokens on the 16 held experts, in tiles of 256) against
    float32 [16, K, N] matrices, one whole matrix a grid step in VMEM with
    its bfloat16 copy."""
    from fedml_tpu.ops import routed_experts as rex

    experts = rex.Experts(total=64, held=16, first_held=0, top_k=6)
    plan = jax.eval_shape(lambda p: rex.plan_rows(p, experts),
                          jax.ShapeDtypeStruct((16384, 6), jnp.int32))
    rows = plan.real.shape[0]
    assert rows == (16384 * 6 + 16 * 256) + 256

    def fn(x, w, tile_expert, live_tiles):
        return rex._experts_call(x, w, tile_expert, live_tiles,
                                 transposed=transposed, interpret=False)

    text = _compile_text(
        fn, one_chip, ((rows, n if transposed else k), jnp.bfloat16),
        ((16, k, n), jnp.float32), (plan.tile_expert.shape, jnp.int32),
        ((1,), jnp.int32))
    assert ("moe_experts_t" if transposed else "moe_experts") in text
    _assert_kernel(text)


def test_resnet56_constants_match_the_model():
    """The shapes above are ResNet-56's, not a guess: every leaf shape of
    the model is in the list and the flat size is the model's."""
    import fedml_tpu

    args = fedml_tpu.Config(model="resnet56", dataset="cifar10",
                            compute_dtype="bfloat16")
    bundle = fedml_tpu.model.create(args, 10)
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda k: bundle.init_variables(k, batch_size=8),
        jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape)) for x in leaves) == RESNET56_FLAT
    assert {x.shape for x in leaves} == set(RESNET56_LEAVES)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_latent_attention_compiles_for_v5e(one_chip, grad):
    """The attention of the cell `sft.gigachat_lora_8k`: 64 heads of 192
    (one and a half lane tiles) at 4,096 positions, q, k and v alike,
    forward and backward kernel; no [.., 1024, 1024] score tile is left."""
    attn = functools.partial(flash_attention, causal=True, interpret=False)
    fn = attn
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attn(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
    qkv = ((1, 64, 4096, 192), jnp.float32)
    text = _compile_text(fn, one_chip, qkv, qkv, qkv)
    _assert_attention_kernels(text, grad, r"\[[\d,]*1024,1024\]")


@pytest.mark.parametrize("transposed", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("k,n,blocks", [(7168, 4096, 4), (2048, 7168, 2)],
                         ids=["gate_up", "down"])
def test_expert_products_in_column_blocks_compile_for_v5e(one_chip, k, n,
                                                          blocks, transposed):
    """`moe_experts` at the same cell's size: the worst case's rows (every
    pick of 4,096 tokens on the 8 held experts) against bfloat16 [8, K, N]
    matrices of 59 and 29 MB, which no grid step holds whole: multiplied as
    they are, in blocks of their columns."""
    from fedml_tpu.ops import routed_experts as rex

    experts = rex.Experts(total=256, held=8, first_held=0, top_k=8)
    plan = jax.eval_shape(lambda p: rex.plan_rows(p, experts),
                          jax.ShapeDtypeStruct((4096, 8), jnp.int32))
    rows = plan.real.shape[0]
    w = (8, n, k) if transposed else (8, k, n)
    assert rex._column_blocks(n, k * 2) == blocks

    def fn(x, w, tile_expert, live_tiles):
        return rex._experts_call(x, w, tile_expert, live_tiles,
                                 transposed=transposed, interpret=False)

    text = _compile_text(
        fn, one_chip, ((rows, k), jnp.bfloat16), (w, jnp.bfloat16),
        (plan.tile_expert.shape, jnp.int32), ((1,), jnp.int32))
    assert ("moe_experts_t" if transposed else "moe_experts") in text
    _assert_kernel(text)


@pytest.mark.parametrize("weighted", [True, False], ids=["fwd", "bwd"])
@pytest.mark.parametrize("n,d,experts,block", [
    (16384, 2560, dict(total=64, held=16, first_held=0, top_k=6), 1024),
    (4096, 7168, dict(total=256, held=8, first_held=0, top_k=8), 512),
    (1500, 2560, dict(total=64, held=16, first_held=0, top_k=6), 1024),
], ids=["smallthinker", "gigachat", "a part block"])
def test_the_combine_compiles_for_v5e(one_chip, monkeypatch, n, d, experts,
                                      block, weighted):
    """`moe_sum_picks` at the two routed cells' sizes, with the forward's
    weights and without, as the backward calls it, and over tokens that
    are no whole number of blocks: the plan, the list of chunks
    and the kernel, whose token block follows from the rows' width.  It
    copies slices of a 1-D array from HBM to SMEM, which the interpreter
    takes at any size and Mosaic only at whole tiles of 1,024."""
    from fedml_tpu.ops import routed_experts as rex

    monkeypatch.setattr(rex, "_on_tpu", lambda: True)
    experts = rex.Experts(**experts)
    assert rex._sum_block(n, d) == block
    plan = jax.eval_shape(lambda p: rex.plan_rows(p, experts),
                          jax.ShapeDtypeStruct((n, experts.top_k), jnp.int32))

    def fn(rows, weights, *plan):       # the plan's sorts are not the point
        return rex._sum_picks(rows, rex.Plan(*plan),
                              weights if weighted else None)

    text = _compile_text(
        fn, one_chip, ((plan.real.shape[0], d), jnp.float32),
        ((n, experts.top_k), jnp.float32),
        *((leaf.shape, leaf.dtype) for leaf in plan))
    assert "moe_sum_picks" in text
    _assert_kernel(text)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_gated_attention_shape_compiles_for_v5e(one_chip, grad):
    """The softmax layers of the cell `sft.qwen3next_lora_16k`: 16 query
    heads over 2 key/value heads of 256 (two lane tiles) at 16,384 positions,
    K and V of that length resident; forward and backward kernel, and no
    [.., 1024, 1024] score tile is left."""
    attn = functools.partial(flash_attention, causal=True, interpret=False)
    fn = attn
    if grad:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attn(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
    q, kv = ((1, 16, 16384, 256), jnp.float32), ((1, 2, 16384, 256),
                                                 jnp.float32)
    text = _compile_text(fn, one_chip, q, kv, kv)
    _assert_attention_kernels(text, grad, r"\[[\d,]*1024,1024\]")


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_delta_rule_compiles_for_v5e(one_chip, grad):
    """The scan of the cell `sft.qwen3next_lora_16k`: 16 key heads and 32
    value heads of 128 at 16,384 positions.  The program holds `gdn_fwd`
    and, differentiated, `gdn_bwd` under the scope `fedml.gdn.scan_bwd`,
    which the benchmark's readers match, and no other Mosaic kernel; the
    forward called for a gradient keeps a state a chunk, rounded to
    bfloat16, and nothing of [T, T] is made."""
    from fedml_tpu.ops.delta_rule import gated_delta_rule

    rule = functools.partial(gated_delta_rule, interpret=False)
    fn = rule
    if grad:
        def fn(*operands):
            return jax.grad(lambda *a: rule(*a).sum(),
                            argnums=(0, 1, 2, 3, 4))(*operands)
    qk, v = ((1, 16384, 16, 128), jnp.float32), ((1, 16384, 32, 128),
                                                 jnp.float32)
    gate = ((1, 16384, 32), jnp.float32)
    text = _compile_text(fn, one_chip, qk, qk, v, gate, gate)
    kernels = _kernels(text)
    assert set(kernels) == ({"gdn_fwd", "gdn_bwd"} if grad else {"gdn_fwd"})
    assert "fedml.gdn.scan" in kernels["gdn_fwd"]
    if grad:
        assert "fedml.gdn.scan_bwd" in kernels["gdn_bwd"]
        assert "bf16[32,256,128,128]" in kernels["gdn_fwd"]
    assert not re.search(r"\[[\d,]*16384,16384\]", text)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_delta_mixer_compiles_for_v5e(one_chip, grad):
    """The mixer of the same cell between its projection `[1, 16384, 12288]`
    and its way out: the operands' and the gated norm's kernels beside the
    scan's, each under the scope the benchmark's readers sum (none under a
    name that begins with `gdn_fwd` or `gdn_bwd`), the convolution's weights
    as stored (bfloat16).  Differentiated to the projection, the decay and
    the writing strength (LoRA's case: the convolution and the norm's scale
    frozen), the projection's gradient is one array that two kernels write,
    the second in place, and nothing else passes over an array of 8,192 or
    12,288 columns."""
    from fedml_tpu.ops.delta_rule import gated_delta_mixer

    mixer = functools.partial(gated_delta_mixer, heads=(16, 32), eps=1e-6,
                              interpret=False)
    fn = mixer
    if grad:
        def fn(*operands):
            return jax.grad(lambda *a: jnp.square(mixer(*a)).sum(),
                            argnums=(0, 1, 2))(*operands)
    gate = ((1, 16384, 32), jnp.float32)
    text = _compile_text(fn, one_chip, ((1, 16384, 12288), jnp.float32), gate,
                         gate, ((8192, 4), jnp.bfloat16),
                         ((128,), jnp.float32))
    kernels = _kernels(text)
    scopes = {"gdn_operands_fwd": "fedml.gdn.conv", "gdn_fwd": "fedml.gdn.scan",
              "gdn_gate_fwd": "fedml.gdn.out"}
    if grad:
        scopes.update(gdn_gate_bwd="fedml.gdn.out",
                      gdn_bwd="fedml.gdn.scan_bwd",
                      gdn_operands_bwd="fedml.gdn.conv")
    assert set(kernels) == set(scopes)
    for name, scope in scopes.items():
        assert scope in kernels[name], name
    if grad:
        assert "output_to_operand_aliasing" in kernels["gdn_operands_bwd"]
    # what XLA is left with is the [16384, 32] arrays' work
    wide = re.findall(r"\n\s*(?:ROOT )?%\S+ = \w+\[1,16384,(?:8192|12288)\]"
                      r"\S* (?!custom-call|parameter|get-tuple-element)\w",
                      text)
    assert not wide, wide
