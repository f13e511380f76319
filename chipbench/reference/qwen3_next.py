"""Plain Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type:
qwen3_next``): weights from a seed, forward, next-token loss, LoRA and AdamW,
in straightforward ``jax.numpy``, for the share of the model that one chip of
a 4-chip expert group holds.

The yardstick of the ``qwen3_next_*`` configurations.  It imports nothing of
the program and takes nothing the program has made; the precision switch of
its products (``_mm``), the optimizer step and the seed's key are
``reference/gpt2.py``'s, softmax attention, the SwiGLU, the merge of LoRA's
factors and the loss in row blocks ``reference/gigachat3.py``'s.

The stream ``h`` is ``[T, hidden_size]`` float32.  ``N(x; g) = x /
sqrt(mean(x^2) + rms_norm_eps) * (1 + g)``: the scale is centred on zero
(``Qwen3NextRMSNorm``).  A layer: ``a = h + Mixer(N(h; g1))``, ``h' = a +
MoE(N(a; g2))``; layer i (from 0) is a softmax layer where ``(i + 1) %
full_attention_interval == 0``, else a delta-rule layer; logits ``N(h_L; g_f)
W_head``, untied.

* *Softmax layer* (``y`` the normed input): ``[q_j ; z_j] = y Wq`` for head j
  (a head's ``head_dim`` query numbers beside as many gate numbers), ``k = y
  Wk``, ``v = y Wv``; ``q_j <- rot(N(q_j; g_q))``, ``k_m <- rot(N(k_m; g_k))``,
  ``rot`` turning the first ``head_dim * partial_rotary_factor`` numbers of a
  head (paired by halves within them, base ``rope_theta``); causal softmax of
  ``q k^T / sqrt(head_dim)`` over the key head ``j // (heads / kv heads)``;
  out ``concat_j(o_j * sigmoid(z_j)) Wo``.
* *Delta-rule layer* (``Qwen3NextGatedDeltaNet``): ``[q ; k ; v ; z] = y
  W_qkvz``, ``[b ; a] = y W_ba``; ``[q ; k ; v] <- silu(conv(.))``, causal and
  depthwise, ``linear_conv_kernel_dim`` taps, no bias, zeros before the row's
  start; ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``;
  ``q <- q / (|q| sqrt(Dk))``, ``k <- k / |k|`` by head (epsilon 1e-6 under
  the root); for each value head (reading key head ``j // (value heads / key
  heads)``) a state ``S`` [Dk, Dv], zero at the row's start, **position by
  position**: ``S~ = exp(g_t) S``; ``u = beta_t (v_t - S~^T k_t)``; ``S = S~ +
  k_t u^T``; ``o_t = S^T q_t``; out ``concat_j(o_j / sqrt(mean(o_j^2) + eps)
  * g_o * silu(z_j)) W_out`` (the gated norm's scale is plain ``g_o``).
* *Experts*: ``p = softmax(y W_r)`` over all ``published.num_experts``; the
  ``num_experts_per_tok`` largest, weights ``p_e`` over the sum of the picked;
  ``sum_e w_e SwiGLU_e(y) + sigmoid(y w_sg) SwiGLU_shared(y)``.

Departures from the published description, each because one chip holds a share
and not the model, or because the config does not say (``assumed`` in the
configuration's file):

* the counts of layers, experts and vocabulary rows are the *held* ones.  Of
  the experts, ``experts_first_held .. + held`` are computed and a pick that
  landed on another adds nothing (the router still scores all of them);
* the frozen matrices are *stored* in bfloat16 and taken up to float32 where
  they are used; norms' scales, ``A_log``, ``dt_bias``, the router and the
  shared expert's gate are float32; the router's product and scores are
  float32 at ``highest`` in every ``mode``, as the program's are;
* ``W_qkvz``, ``W_ba`` and ``Wq`` keep each part's columns together (the
  source's checkpoints group them by key head: a permutation of columns); a
  head's rotary numbers are paired by halves; gate and up side by side;
* no multi-token-prediction module (the config has no key for one);
* the recurrence's state is float32 in every ``mode``; it is walked in blocks
  of 64 positions that the backward makes again, a quarter of a layer's
  heads at a time; attention and the loss are
  computed in row blocks: the same sums in another order.  The picks that
  landed are sorted by expert into tiles of 256 rows, a tile through its
  expert's SwiGLU (an expert over every token and a mask would be 128 times
  the work).
"""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.gigachat3 import (_attention, _loss_sum, _merged,
                                           _swiglu)
from chipbench.reference.gpt2 import HIGHEST, _mm, adamw_step, seed_key

__all__ = ["seed_key", "init_params", "init_lora", "finetune", "row_grad",
           "picks_one", "forward_one", "sizes", "route", "delta_rule",
           "lora_targets"]

#: positions of a block of the recurrence (each made again in the backward)
SCAN_ROWS = 64
#: rows of a tile of the experts' layout, and the groups its tiles are
#: taken in
TILE = 256
GROUPS = 8
#: groups a delta-rule layer's heads are taken in
HEAD_GROUPS = 4


def sizes(cfg: dict) -> dict:
    """The static sizes of the share, from the configuration's own keys."""
    return dict(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        layers=int(cfg["num_hidden_layers"]),
        interval=int(cfg["full_attention_interval"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        rotary=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        key_heads=int(cfg["linear_num_key_heads"]),
        value_heads=int(cfg["linear_num_value_heads"]),
        key_dim=int(cfg["linear_key_head_dim"]),
        value_dim=int(cfg["linear_value_head_dim"]),
        conv=int(cfg["linear_conv_kernel_dim"]),
        ffn=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["shared_expert_intermediate_size"]),
        experts=int(cfg["published"]["num_experts"]),
        held=int(cfg["num_experts"]),
        first_held=int(cfg["experts_first_held"]),
        top_k=int(cfg["num_experts_per_tok"]))


def _static(cfg: dict):
    return tuple(sorted(sizes(cfg).items()))


def is_softmax(z: dict, i: int) -> bool:
    return (i + 1) % z["interval"] == 0


def lora_targets(z: dict, i: int):
    """The matrices of layer ``i`` LoRA adapts: what the program's default
    targets reach in this layout."""
    return ("wq", "wk", "wv", "wo") if is_softmax(z, i) else ("w_qkvz", "wo")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _block_shapes(z: dict, softmax: bool) -> dict:
    """name -> (shape, kind): "w" a frozen matrix, "res" one that writes to
    the residual stream, "centred" a norm scale stored about zero, "scale"
    one stored about one, "router", "gate", "conv", "rate", "step"."""
    d, f = z["dim"], z["ffn"]
    if softmax:
        h, kv, dh = z["heads"], z["kv_heads"], z["head_dim"]
        mixer = {"wq": ((d, 2 * h * dh), "w"), "wk": ((d, kv * dh), "w"),
                 "wv": ((d, kv * dh), "w"), "q_norm": ((dh,), "centred"),
                 "k_norm": ((dh,), "centred"), "wo": ((h * dh, d), "res")}
    else:
        nq = z["key_heads"] * z["key_dim"]
        nv = z["value_heads"] * z["value_dim"]
        mixer = {"w_qkvz": ((d, 2 * nq + 2 * nv), "w"),
                 "w_ba": ((d, 2 * z["value_heads"]), "w"),
                 "conv": ((2 * nq + nv, z["conv"]), "conv"),
                 "a_log": ((z["value_heads"],), "rate"),
                 "dt_bias": ((z["value_heads"],), "step"),
                 "gdn_norm": ((z["value_dim"],), "scale"),
                 "wo": ((nv, d), "res")}
    return {"ln1": ((d,), "centred"), **mixer, "ln2": ((d,), "centred"),
            "router": ((d, z["experts"]), "router"),
            "w_gate_up": ((z["held"], d, 2 * f), "w"),
            "w_down": ((z["held"], f, d), "res"),
            "shared_gate_up": ((d, 2 * z["shared"]), "w"),
            "shared_down": ((z["shared"], d), "res"),
            "shared_gate": ((d,), "gate")}


@functools.partial(jax.jit, static_argnames=("z", "init_range", "stored"))
def _init(key, z, init_range, stored):
    z = dict(z)
    s_res = init_range / math.sqrt(2 * z["layers"])     # residual projections
    count = iter(range(10 ** 6))

    def draw(shape, kind):
        k = jax.random.fold_in(key, next(count))
        if kind == "rate":          # A_log: the log of a rate in (0, 16)
            return jnp.log(jnp.maximum(
                jax.random.uniform(k, shape, jnp.float32) * 16.0, 1e-4))
        if kind == "step":          # dt_bias: softplus^-1 of a step
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        n = jax.random.normal(k, shape, jnp.float32)
        if kind in ("centred", "scale"):
            # a published model's norm scales are trained away from their
            # centre; drawn here, so that a path that drops one shows
            return {"scale": n * init_range + (kind == "scale")}
        if kind in ("router", "gate"):
            return n * init_range
        if kind == "conv":
            return (n / math.sqrt(shape[1])).astype(stored)
        return (n * (s_res if kind == "res" else init_range)).astype(stored)

    d = z["dim"]
    return {"embed": draw((z["vocab"], d), "w"),
            "blocks": [{name: draw(*spec) for name, spec in _block_shapes(
                z, is_softmax(z, i)).items()} for i in range(z["layers"])],
            "ln_f": draw((d,), "centred"),
            "w_out": draw((d, z["vocab"]), "w")}


def init_params(cfg: dict, seed: int, stored=None):
    """The share's weights in the program's layout, made on the device in one
    jitted call: the matrices in the type the configuration states
    (``weights_stored``; ``stored`` overrides it), norms' scales, ``a_log``,
    ``dt_bias``, the routers and the shared experts' gates in float32; all
    drawn, so that a dropped term shows."""
    return _init(seed_key(seed), _static(cfg),
                 float(cfg["initializer_range"]),
                 jnp.dtype(stored or cfg["weights_stored"]))


def init_lora(cfg: dict, seed: int, rank: int):
    """LoRA factors as published (Hu et al. 2021): A normal, B zero.
    ``{(layer, name): {"a", "b"}}`` over `lora_targets` of every layer."""
    z = sizes(cfg)
    key = jax.random.fold_in(seed_key(seed), 0x10a)

    @jax.jit
    def make(key):
        out = {}
        for i in range(z["layers"]):
            shapes = _block_shapes(z, is_softmax(z, i))
            for j, name in enumerate(lora_targets(z, i)):
                d_in, d_out = shapes[name][0]
                k = jax.random.fold_in(key, i * 4 + j)
                out[(i, name)] = {
                    "a": jax.random.normal(k, (d_in, rank), jnp.float32) * 0.01,
                    "b": jnp.zeros((rank, d_out), jnp.float32)}
        return out

    return make(key)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _norm(x, g, eps):
    """``Qwen3NextRMSNorm``: the scale is ``1 + g``."""
    f = x.astype(jnp.float32)
    return (f / jnp.sqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
            * (1.0 + g["scale"])).astype(x.dtype)


def _rotate_first(x, n: int, theta: float):
    """Rotary positions on the first ``n`` numbers of every head of [T, H,
    Dh] (position = row), paired by halves within them; the rest pass."""
    t, half = x.shape[0], n // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:n].astype(jnp.float32)
    return jnp.concatenate([(a * cos - b * sin).astype(x.dtype),
                            (b * cos + a * sin).astype(x.dtype), x[..., n:]],
                           axis=-1)


def _softmax_mixer(y, blk, z: dict, mode: str):
    t, h, kv, dh = y.shape[0], z["heads"], z["kv_heads"], z["head_dim"]
    qz = _mm(y, blk["wq"], mode).reshape(t, h, 2 * dh)
    q, gate = qz[..., :dh], qz[..., dh:]
    k = _mm(y, blk["wk"], mode).reshape(t, kv, dh)
    v = _mm(y, blk["wv"], mode).reshape(t, kv, dh)
    q = _rotate_first(_norm(q, blk["q_norm"], z["eps"]), z["rotary"],
                      z["theta"])
    k = _rotate_first(_norm(k, blk["k_norm"], z["eps"]), z["rotary"],
                      z["theta"])
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    o = _attention(q, k, v, dh ** -0.5, mode).reshape(t, h, dh)
    return _mm((o * jax.nn.sigmoid(gate)).reshape(t, h * dh), blk["wo"], mode)


def _conv(x, w):
    """``c_t = sum_i w[:, i] x_{t - (taps - 1) + i}`` over [T, C], zeros
    before the row's start."""
    taps, t = w.shape[1], x.shape[0]
    padded = jnp.pad(x.astype(jnp.float32), ((taps - 1, 0), (0, 0)))
    out = jnp.zeros((t, x.shape[1]), jnp.float32)
    for i in range(taps):
        out = out + padded[i:i + t] * w[:, i].astype(jnp.float32)[None, :]
    return out.astype(x.dtype)


def delta_rule(q, k, v, g, beta, mode: str = "float32"):
    """The gated delta rule position by position: ``q``, ``k`` [T, H, Dk],
    ``v`` [T, H, Dv], ``g`` and ``beta`` [T, H] float32 -> [T, H, Dv].  The
    state [H, Dk, Dv] is float32; its three products go through ``_mm``."""
    t = q.shape[0]
    rows = min(SCAN_ROWS, t)
    if t % rows:
        raise ValueError(f"{t} positions are not whole blocks of {rows}")

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = state * jnp.exp(g_t)[:, None, None]
        read = _mm(k_t, state, mode, "hk,hkv->hv").astype(jnp.float32)
        u = b_t[:, None] * (v_t.astype(jnp.float32) - read)
        state = state + _mm(k_t, u, mode, "hk,hv->hkv").astype(jnp.float32)
        return state, _mm(q_t, state, mode, "hk,hkv->hv").astype(v.dtype)

    @jax.checkpoint
    def some(state, block):
        return jax.lax.scan(step, state, block)

    blocks = tuple(a.reshape(t // rows, rows, *a.shape[1:])
                   for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(some, jnp.zeros(
        (q.shape[1], q.shape[2], v.shape[2]), jnp.float32), blocks)
    return o.reshape(t, *o.shape[2:])


def _unit(x):
    f = x.astype(jnp.float32)
    return (f / jnp.sqrt(jnp.sum(jnp.square(f), -1, keepdims=True) + 1e-6)
            ).astype(x.dtype)


def _delta_heads(y, w, scale, hk: int, hv: int, z: dict, mode: str):
    """Some of a delta-rule layer's heads (``hk`` key heads, the ``hv`` value
    heads that read them) from the normed input ``y`` [T, D] to what they add
    to the stream: ``w`` holds their columns of ``W_qkvz`` (``q``, ``k``,
    ``v``, ``z``) and ``W_ba`` (``b``, ``a``), their channels of the
    convolution, their ``a_log`` and ``dt_bias``, and their rows of
    ``W_out``; ``scale`` is the gated norm's."""
    t, dk, dv = y.shape[0], z["key_dim"], z["value_dim"]
    q, k, v = (jax.nn.silu(_conv(_mm(y, w[n], mode), w["conv_" + n]))
               for n in "qkv")
    beta = jax.nn.sigmoid(_mm(y, w["b"], mode).astype(jnp.float32))
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(
        _mm(y, w["a"], mode).astype(jnp.float32) + w["dt_bias"])
    q = _unit(q.reshape(t, hk, dk)) * (dk ** -0.5)
    k = _unit(k.reshape(t, hk, dk))
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    f = delta_rule(q, k, v.reshape(t, hv, dv), g, beta, mode).astype(
        jnp.float32)
    f = f / jnp.sqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + z["eps"])
    gated = (f * scale["scale"]).astype(y.dtype) * jax.nn.silu(
        _mm(y, w["z"], mode).reshape(t, hv, dv))
    return _mm(gated.reshape(t, hv * dv), w["wo"], mode)


def _delta_mixer(y, blk, z: dict, mode: str):
    """A delta-rule layer's mixer: its heads in `HEAD_GROUPS` groups, each
    made again in the backward (the heads do not meet before ``W_out``)."""
    hk, hv = z["key_heads"], z["value_heads"]
    nq, nv = hk * z["key_dim"], hv * z["value_dim"]
    groups = math.gcd(HEAD_GROUPS, hk)

    def part(w, start, width, g, axis=-1):
        n = width // groups
        return jax.lax.slice_in_dim(w, start + g * n, start + (g + 1) * n,
                                    axis=axis)

    out = 0.0
    for g in range(groups):
        w = {"a_log": part(blk["a_log"], 0, hv, g),
             "dt_bias": part(blk["dt_bias"], 0, hv, g),
             "b": part(blk["w_ba"], 0, hv, g),
             "a": part(blk["w_ba"], hv, hv, g),
             "wo": part(blk["wo"], 0, nv, g, axis=0)}
        for n, start, width in (("q", 0, nq), ("k", nq, nq),
                                ("v", 2 * nq, nv), ("z", 2 * nq + nv, nv)):
            w[n] = part(blk["w_qkvz"], start, width, g)
            if n != "z":
                w["conv_" + n] = part(blk["conv"], start, width, g, axis=0)
        out = out + jax.checkpoint(functools.partial(
            _delta_heads, hk=hk // groups, hv=hv // groups, z=z, mode=mode))(
                y, w, blk["gdn_norm"])
    return out


def route(x, w_router, z: dict):
    """``x`` [T, D] -> (picks [T, top_k], weights [T, top_k]): the softmax
    over all experts, its ``top_k`` largest (ties to the lower number),
    renormalised over the picked.  Float32 at ``highest``."""
    p = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=HIGHEST), axis=-1)
    top, picks = jax.lax.top_k(p, z["top_k"])
    return picks, top / jnp.sum(top, -1, keepdims=True)


def _experts(x, blk, z: dict, mode: str):
    """The held experts' share of the routed term.  The picks that landed on
    a held expert are sorted by expert, each expert's rows begin at a
    multiple of `TILE` (room for the worst case, every pick of every token
    on a held expert), a tile of rows crosses its expert's SwiGLU, and every
    row is added back to its token by its pick's weight; the tiles are taken
    in `GROUPS` groups, each made again in the backward.  Also the picks."""
    t, k, held = x.shape[0], z["top_k"], z["held"]
    picks, weights = route(x, blk["router"], z)
    rows = min(TILE, t)
    local = picks.reshape(-1) - z["first_held"]
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)           # sorted place -> flat pick
    expert = key[order]
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0)
    room = -(-counts // rows) * rows                # an expert's whole tiles
    begins = jnp.cumsum(room) - room
    firsts = jnp.cumsum(counts) - counts            # its first sorted place
    e = jnp.minimum(expert, held - 1)
    tiles = -(-(t * min(k, held) + held * (rows - 1)) // rows)
    groups = min(GROUPS, tiles)
    tiles = -(-tiles // groups) * groups
    # a pick that did not land gets a row past the layout: dropped below
    row = jnp.where(expert < held,
                    begins[e] + jnp.arange(t * k) - firsts[e], tiles * rows)
    # the flat pick a row computes; t * k where it computes none
    pick_of_row = jnp.full((tiles * rows,), t * k, jnp.int32).at[row].set(
        order.astype(jnp.int32), mode="drop")
    tile_expert = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(room), jnp.arange(tiles) * rows, side="right"), held - 1)

    def tile(args):
        xs, e = args
        return _swiglu(xs, blk["w_gate_up"][e], blk["w_down"][e], mode)

    @jax.checkpoint
    def group(out, args):
        pick, experts = args
        token = jnp.where(pick < t * k, pick // k, t)
        got = jnp.take(x, token, axis=0, mode="fill", fill_value=0)
        ys = jax.lax.map(tile, (got.reshape(-1, rows, x.shape[1]), experts))
        w = jnp.take(weights.reshape(-1), pick, mode="fill", fill_value=0)
        return out.at[token].add(
            ys.reshape(got.shape) * w[:, None].astype(x.dtype),
            mode="drop"), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(x), (
        pick_of_row.reshape(groups, -1), tile_expert.reshape(groups, -1)))
    return out, picks


def _block(h, blk, z: dict, mode: str, softmax: bool):
    """One layer over [T, D]: the stream, and the layer's picks."""
    y = _norm(h, blk["ln1"], z["eps"])
    a = h + (_softmax_mixer if softmax else _delta_mixer)(y, blk, z, mode)
    x = _norm(a, blk["ln2"], z["eps"])
    out, picks = _experts(x, blk, z, mode)
    gate = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), blk["shared_gate"], precision=HIGHEST))
    shared = _swiglu(x, blk["shared_gate_up"], blk["shared_down"], mode)
    return a + out + gate[:, None].astype(x.dtype) * shared, picks


def forward_one(params, x, z: dict, mode: str = "float32",
                remat: bool = False, lora=None, alpha: float = 0.0):
    """``[T]`` tokens of one sequence -> the stream after its final norm [T,
    D] and every layer's picks [L, T, top_k].  ``lora``: factors merged into
    each block as it is used."""
    act = jnp.float32 if mode == "float32" else jnp.bfloat16

    def run(h, blk, factors, softmax):
        blk = _merged(blk, factors, alpha)
        # norms, the decay's two vectors, the router and the shared expert's
        # gate stay float32 whatever the mode
        # (and the experts' stacks as stored: a tile's product takes its own)
        blk = {k: v if isinstance(v, dict) or v.ndim != 2 or k == "router"
               else v.astype(act) for k, v in blk.items()}
        return _block(h, blk, z, mode, softmax)

    if remat:
        run = jax.checkpoint(run, static_argnums=(3,))
    h = params["embed"][x].astype(act)
    picks = []
    for i, blk in enumerate(params["blocks"]):
        factors = None if lora is None else {
            name: lora[(i, name)] for name in lora_targets(z, i)}
        h, p = run(h, blk, factors, is_softmax(z, i))
        picks.append(p)
    return _norm(h, params["ln_f"], z["eps"]), jnp.stack(picks)


def loss_sum(params, x, y, mask, z: dict, mode: str = "float32", lora=None,
             alpha: float = 0.0):
    """The masked sum of one row's next-token cross-entropy."""
    h, _ = forward_one(params, x, z, mode, True, lora, alpha)
    return _loss_sum(h, params["w_out"], y, mask, mode)


# ---------------------------------------------------------------------------
# LoRA fine-tuning: AdamW over the factors, gradient clipped by global norm
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("z", "alpha", "mode", "n"))
def _row_grad(lora, params, x, y, mask, z, alpha, mode, n):
    z = dict(z)
    return jax.value_and_grad(lambda lora: loss_sum(
        params, x, y, mask, z, mode, lora, alpha) / n)(lora)


def row_grad(lora, params, x, y, mask, cfg: dict, alpha: float,
             mode: str = "float32", n: float = None):
    """One row's part of the batch's loss (its masked sum over ``n``, the
    batch's positions; left out, this row's own) and its LoRA gradient."""
    return _row_grad(lora, params, x, y, mask, _static(cfg), alpha, mode,
                     float(n or len(x)))


@functools.partial(jax.jit, static_argnames=("z", "mode"))
def _picks_one(params, x, z, mode):
    return forward_one(params, x, dict(z), mode)[1]


def picks_one(params, x, cfg: dict, mode: str = "float32"):
    """``[L, T, top_k]``: the experts each token of one row picks in each
    layer, at the base weights."""
    return _picks_one(params, x, _static(cfg), mode)


def finetune(params, lora, batches_x, batches_y, cfg: dict, alpha: float,
             lr: float, clip: float, mode: str = "float32",
             steps_with_data: int = None):
    """Follow ``len(batches_x)`` optimizer steps, each over a ``[B, T]`` batch
    taken row by row (the loss is the mean over the batch's positions, as
    the program's).  From step ``steps_with_data`` on the batches count as
    masked out: loss and gradient are zero there and only the optimizer's
    state moves the factors.  Returns the loss of every step, the factors
    after the last and AdamW's two moments after the last, each as
    ``{(layer, name): ...}``."""
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
            loss, g = row_grad(
                lora, params, jnp.asarray(x), jnp.asarray(y),
                jnp.ones(len(x), jnp.float32), cfg, alpha, mode, bx.size)
            total = total + loss
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        losses.append(float(total))
        lora, mu, nu = adamw_step(lora, grads, mu, nu, step, lr, clip)
    return losses, lora, mu, nu
