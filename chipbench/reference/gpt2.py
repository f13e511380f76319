"""Plain GPT-2 (Radford et al. 2019): weights from a seed, forward, next-token
loss, LoRA and AdamW, in straightforward ``jax.numpy``.

This is the yardstick the cells of the GPT-2 configurations are held to.  It
imports nothing of the program and takes nothing the program has made: the
benchmark makes the weights here, from the seed, and hands the same values to
the program and to this reference.

Architecture as published: learned positions, pre-LayerNorm blocks (eps 1e-5),
a bias on every projection, ``gelu_new`` (the tanh approximation), attention
scaled by 1/sqrt(head size), the output head tied to the token embedding.  The
only departure from the Hugging Face layout is that ``c_attn`` is kept as three
matrices ``wq``/``wk``/``wv`` (the same numbers, unfused), which is the layout
of the program's weight importer.

Every matrix product goes through ``_mm``, which fixes the precision of the
whole computation:

* ``"float32"``  float32 operands, ``precision="highest"``: the reference;
* ``"bfloat16"`` operands, products and activations in bfloat16: the control
  for a configuration that states float32;
* ``"fp8"``      the operands of each product, forward and backward, rounded
  to per-tensor scaled float8_e4m3fn, activations bfloat16: the control for
  one that states bfloat16, or float32 with bfloat16 products.
"""

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: the six per-block matrices LoRA adapts (the program's default targets)
LORA_TARGETS = ("wq", "wk", "wv", "wo", "w1", "w2")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)),
                              seed >> 32)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("vocab", "dim", "layers",
                                             "max_len", "init_range",
                                             "dtype"))
def _init(key, vocab, dim, layers, max_len, init_range, dtype):
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    s = init_range
    s_res = init_range / math.sqrt(2 * layers)     # GPT-2's residual scaling
    stacked = {
        "wq": normal((layers, dim, dim), s),
        "wk": normal((layers, dim, dim), s),
        "wv": normal((layers, dim, dim), s),
        "wo": normal((layers, dim, dim), s_res),
        "w1": normal((layers, dim, 4 * dim), s),
        "w2": normal((layers, 4 * dim, dim), s_res),
        # a published GPT-2 starts its biases at zero and trains them away
        # from it; drawn here, so that a path that drops one shows
        "bq": normal((layers, dim), s), "bk": normal((layers, dim), s),
        "bv": normal((layers, dim), s), "bo": normal((layers, dim), s),
        "b1": normal((layers, 4 * dim), s), "b2": normal((layers, dim), s),
        "ln1s": 1.0 + normal((layers, dim), s), "ln1b": normal((layers, dim), s),
        "ln2s": 1.0 + normal((layers, dim), s), "ln2b": normal((layers, dim), s),
    }
    blocks = []
    for i in range(layers):
        b = {k: v[i] for k, v in stacked.items() if not k.startswith("ln")}
        b["ln1"] = {"scale": stacked["ln1s"][i], "bias": stacked["ln1b"][i]}
        b["ln2"] = {"scale": stacked["ln2s"][i], "bias": stacked["ln2b"][i]}
        blocks.append(b)
    return {
        "embed": normal((vocab, dim), s),
        "pos": normal((max_len, dim), s / 2),
        "blocks": blocks,
        "ln_f": {"scale": 1.0 + normal((dim,), s), "bias": normal((dim,), s)},
    }


def init_params(cfg: dict, seed: int, dtype=jnp.float32):
    """The model's weights, made on the device in one jitted call.  The draw
    is float32 and is then cast, so a bfloat16 model is the rounding of the
    float32 one of the same seed."""
    return _init(seed_key(seed), int(cfg["vocab_size"]), int(cfg["n_embd"]),
                 int(cfg["n_layer"]), int(cfg["n_positions"]),
                 float(cfg["initializer_range"]), jnp.dtype(dtype))


def init_lora(cfg: dict, seed: int, rank: int):
    """LoRA factors as published (Hu et al. 2021): A normal, B zero, so the
    adapted model starts as the base model.  ``{(layer, name): {"a", "b"}}``."""
    d = int(cfg["n_embd"])
    shapes = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
              "w1": (d, 4 * d), "w2": (4 * d, d)}
    key = jax.random.fold_in(seed_key(seed), 0x10a)

    @jax.jit
    def make(key):
        out = {}
        for i in range(int(cfg["n_layer"])):
            for j, name in enumerate(LORA_TARGETS):
                d_in, d_out = shapes[name]
                k = jax.random.fold_in(key, i * len(LORA_TARGETS) + j)
                out[(i, name)] = {
                    "a": jax.random.normal(k, (d_in, rank), jnp.float32) * 0.01,
                    "b": jnp.zeros((rank, d_out), jnp.float32)}
        return out

    return make(key)


def stack_blocks(params):
    """The same weights with the blocks' arrays stacked along a leading layer
    axis, which is how the reference walks them (one traced block, scanned)."""
    blocks = params["blocks"]
    if isinstance(blocks, dict):
        return params
    return dict(params, blocks=jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *blocks))


def stack_lora(lora, layers: int):
    """``{(layer, name): {"a", "b"}}`` -> ``{name: {"a": [L, ...], "b"}}``."""
    return {name: {ab: jnp.stack([lora[(i, name)][ab] for i in range(layers)])
                   for ab in ("a", "b")} for name in LORA_TARGETS}


def unstack_lora(stacked):
    layers = next(iter(stacked.values()))["a"].shape[0]
    return {(i, name): {ab: f[ab][i] for ab in ("a", "b")}
            for name, f in stacked.items() for i in range(layers)}


def merge_lora(blk, factors, alpha: float):
    """``W + (alpha / rank) * A @ B`` on every adapted matrix of one block."""
    blk = dict(blk)
    for name, f in factors.items():
        scale = alpha / f["a"].shape[-1]
        delta = jnp.matmul(f["a"], f["b"], precision=HIGHEST)
        blk[name] = blk[name] + (scale * delta).astype(blk[name].dtype)
    return blk


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _round_fp8(x):
    """Per-tensor scaled float8_e4m3fn (largest magnitude -> 448), the usual
    way a model is run in fp8; the value comes back in ``x``'s own type."""
    f = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f)), 1e-30) / 448.0
    return ((f / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale).astype(x.dtype)


def _fp8_operand(x):
    """``x`` rounded to fp8 where it enters a product.  The gradient passes
    the rounding unchanged (it is a step function; its own derivative would
    be zero), as in every fp8 training recipe."""
    return x + jax.lax.stop_gradient(_round_fp8(x) - x)


@jax.custom_vjp
def _fp8_cotangent(y):
    """The identity, whose cotangent is rounded to fp8: the backward pass's
    two products then take fp8 operands too."""
    return y


_fp8_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_round_fp8(g),))


def _mm(x, w, mode: str, spec: str = None):
    if mode == "float32":
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
        if spec:
            return jnp.einsum(spec, x, w, precision=HIGHEST)
        return jnp.matmul(x, w, precision=HIGHEST)
    x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    if mode == "fp8":
        x, w = _fp8_operand(x), _fp8_operand(w)
    y = jnp.einsum(spec, x, w) if spec else jnp.matmul(x, w)
    return _fp8_cotangent(y) if mode == "fp8" else y


def _layer_norm(x, g, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g["scale"] + g["bias"]


def _block(h, blk, n_head: int, eps: float, mode: str):
    t, d = h.shape
    dh = d // n_head
    y = _layer_norm(h, blk["ln1"], eps)
    q = (_mm(y, blk["wq"], mode) + blk["bq"]).reshape(t, n_head, dh)
    k = (_mm(y, blk["wk"], mode) + blk["bk"]).reshape(t, n_head, dh)
    v = (_mm(y, blk["wv"], mode) + blk["bv"]).reshape(t, n_head, dh)
    s = _mm(q, k, mode, "qhd,khd->hqk") / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s.astype(jnp.float32), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(h.dtype)
    o = _mm(p, v, mode, "hqk,khd->qhd").reshape(t, d)
    h = h + _mm(o, blk["wo"], mode) + blk["bo"]
    y = _layer_norm(h, blk["ln2"], eps)
    z = jax.nn.gelu(_mm(y, blk["w1"], mode) + blk["b1"], approximate=True)
    return h + _mm(z, blk["w2"], mode) + blk["b2"]


def logits_one(params, tokens, n_head: int, eps: float = 1e-5,
               mode: str = "float32", remat: bool = False, lora=None,
               alpha: float = 0.0):
    """``[T]`` tokens of one sequence -> ``[T, vocab]`` float32 logits.  One
    row at a time, so that the reference fits beside nothing else.  ``lora``:
    stacked factors (``stack_lora``) merged into each block as it is used."""
    act = jnp.float32 if mode == "float32" else jnp.bfloat16
    params = stack_blocks(params)
    t = tokens.shape[0]
    cast = functools.partial(jax.tree_util.tree_map, lambda a: a.astype(act))
    h = (params["embed"][tokens] + params["pos"][:t]).astype(act)

    def block(h, layer):
        blk, factors = layer
        if factors is not None:
            blk = merge_lora(blk, factors, alpha)
        return _block(h, cast(blk), n_head, eps, mode), None

    if remat:
        block = jax.checkpoint(block)
    h, _ = jax.lax.scan(block, h, (params["blocks"], lora))
    h = _layer_norm(h, cast(params["ln_f"]), eps)
    return _mm(h, params["embed"], mode, "td,vd->tv").astype(jnp.float32)


def next_token_loss(params, x, y, n_head: int, eps: float = 1e-5,
                    mode: str = "float32", lora=None, alpha: float = 0.0):
    """Mean cross-entropy of one row: ``x`` [T] in, ``y`` [T] the targets."""
    logits = logits_one(params, x, n_head, eps, mode, True, lora, alpha)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# LoRA fine-tuning: AdamW over the factors, gradient clipped by global norm
# ---------------------------------------------------------------------------

def tree_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


@functools.partial(jax.jit, static_argnames=("n_head", "alpha", "eps", "mode"))
def lora_row_grad(lora, params, x, y, n_head, alpha, eps=1e-5,
                  mode="float32"):
    """Loss and LoRA gradient of one row (stacked weights and factors)."""
    def f(lora):
        return next_token_loss(params, x, y, n_head, eps, mode, lora, alpha)
    return jax.value_and_grad(f)(lora)


@functools.partial(jax.jit, static_argnames=("lr", "clip", "b1", "b2",
                                             "eps", "weight_decay"),
                   donate_argnums=(0, 2, 3))
def adamw_step(lora, grads, mu, nu, count, lr, clip, b1=0.9, b2=0.999,
               eps=1e-8, weight_decay=1e-4):
    """One step of clip-by-global-norm then AdamW (decoupled decay), the
    published update; ``count`` is the number of steps taken before it."""
    gnorm = tree_norm(grads)
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-30))
    g = jax.tree_util.tree_map(lambda a: a * scale, grads)
    mu = jax.tree_util.tree_map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
    nu = jax.tree_util.tree_map(lambda n, a: b2 * n + (1 - b2) * a * a, nu, g)
    c = count + 1
    mhat = 1.0 / (1 - b1 ** c)
    nhat = 1.0 / (1 - b2 ** c)
    lora = jax.tree_util.tree_map(
        lambda p, m, n: p - lr * (m * mhat / (jnp.sqrt(n * nhat) + eps)
                                  + weight_decay * p), lora, mu, nu)
    return lora, mu, nu


def finetune(params, lora, batches_x, batches_y, n_head: int, alpha: float,
             lr: float, clip: float, eps: float = 1e-5,
             mode: str = "float32", steps_with_data: int = None):
    """Follow ``len(batches_x)`` optimizer steps, each over a ``[B, T]`` batch
    taken row by row.  From step ``steps_with_data`` on the batches count as
    masked out: loss and gradient are zero there and only the optimizer's
    state moves the factors.  Returns the loss of every step, the factors
    after the last, and AdamW's two moments after the last (the running means
    of the gradients as the optimizer got them, and of their squares), each
    as ``{(layer, name): ...}``."""
    layers = len(params["blocks"])
    params = jax.jit(stack_blocks)(params)
    lora = stack_lora(lora, layers)
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    mu, nu = zeros(lora), zeros(lora)
    losses = []
    for step, (bx, by) in enumerate(zip(batches_x, batches_y)):
        if steps_with_data is not None and step >= steps_with_data:
            losses.append(0.0)
            lora, mu, nu = adamw_step(lora, zeros(lora), mu, nu, step, lr,
                                      clip)
            continue
        total, grads = 0.0, None
        for x, y in zip(bx, by):
            loss, g = lora_row_grad(lora, params, jnp.asarray(x),
                                    jnp.asarray(y), n_head, alpha, eps, mode)
            total = total + loss
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        n = len(bx)
        grads = jax.tree_util.tree_map(lambda a: a / n, grads)
        losses.append(total / n)
        lora, mu, nu = adamw_step(lora, grads, mu, nu, step, lr, clip)
    return ([float(v) for v in losses], unstack_lora(lora), unstack_lora(mu),
            unstack_lora(nu))
