"""Plain LFM2-MoE (LiquidAI/LFM2-24B-A2B, ``model_type: lfm2_moe``): weights
from a seed and the forward pass in straightforward ``jax.numpy``, for the
layers that one pipeline stage holds.

The yardstick of the ``lfm2_*`` configurations.  It imports nothing of the
program and takes nothing the program has made: the benchmark makes the
weights here, from the seed, and hands the same values to the program and to
this reference.  The precision switch of its products (``_mm``) and the
seed's key are ``reference/gpt2.py``'s.  No kernel, no cache, no batching:
one sequence, every position in one pass.

The stream ``h`` is ``[T, hidden_size]``.  ``N(x; g) = x / sqrt(mean(x^2) +
norm_eps) * g``.  A layer: ``r = h + Mixer(N(h; g1))``, ``h' = r + FFN(N(r;
g2))``; after the last ``N(h; g_f)``, and the logits on the embedding's
transpose (tied).  Layer i of the published model is a convolution or an
attention layer as ``layer_types[i]`` says, and its FFN is dense where ``i <
num_dense_layers``, else routed; the layers held are ``held_layers``.

* *Short convolution*: ``[b ; c ; x] = y W_in`` (``W_in`` [D, 3 D]); ``u = b
  * x``; ``z_t = sum_j w[:, j] u_{t - (L - 1) + j}``, depthwise and causal,
  ``L = conv_L_cache`` taps, zeros left of position 0, written as ``L``
  shifted products; out ``(c * z) W_out``.  No bias, no activation.
* *Attention*: q ``num_attention_heads`` heads, k and v
  ``num_key_value_heads``, of ``hidden_size / num_attention_heads``; q and k
  RMS-normed by head (scales of a head's size), then rotated whole, base
  ``rope_theta``, a head's halves the pairs' two members; causal softmax at
  ``1 / sqrt(head size)``, query head j reading key head ``j // (heads / kv
  heads)``; out ``W_o``.
* *Dense FFN*: ``(silu(y G) * (y U)) D`` at ``intermediate_size``.
* *Routed FFN*: ``s = sigmoid(y W_r)``, float32 at ``highest`` in every
  ``mode``, as the program's is; the ``num_experts_per_tok`` largest of ``s +
  bias`` (ties to the lower number); weights ``s[picked] / (sum + 1e-20)``
  (``norm_topk_prob``), times ``routed_scaling_factor``; the weighted sum of
  the picked experts' SwiGLUs at ``moe_intermediate_size``.  No shared
  expert.  The picks are sorted by expert into tiles of rows, a tile through
  its expert's SwiGLU (an expert over every token and a mask would be 16
  times the work); the matrices are taken up to the computation's type a
  tile at a time.

Departures from the published description (``assumed`` in the
configuration's file): gate and up side by side in one matrix, an expert's
too (the same numbers); ``W_in``'s columns as ``b``, ``c``, ``x`` in thirds;
every matrix *stored* in the type the caller names and taken up where it is
used, norms' scales, the router and its bias float32 whatever that is.
"""

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.gpt2 import HIGHEST, _mm, seed_key

__all__ = ["seed_key", "init_params", "logits_one", "picks_one", "sizes"]

#: most rows of a tile of the experts' layout
TILE = 256
#: positions whose logits are made at a time
HEAD_ROWS = 1024
#: queries whose scores are alive at a time
ATTN_ROWS = 512


def sizes(cfg: dict) -> tuple:
    """The static sizes of the stage, from the configuration's own keys, as
    sorted items (a ``jit``'s static argument; ``dict(...)`` reads them)."""
    held = tuple(int(i) for i in cfg["held_layers"])
    heads = int(cfg["num_attention_heads"])
    return tuple(sorted(dict(
        vocab=int(cfg["vocab_size"]), dim=int(cfg["hidden_size"]),
        kinds=tuple(cfg["layer_types"][i] for i in held),
        dense=tuple(i < int(cfg["num_dense_layers"]) for i in held),
        heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["hidden_size"]) // heads,
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        eps=float(cfg["norm_eps"]), taps=int(cfg["conv_L_cache"]),
        dense_ffn=int(cfg["intermediate_size"]),
        ffn=int(cfg["moe_intermediate_size"]),
        experts=int(cfg["num_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        scale=float(cfg["routed_scaling_factor"])).items()))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("z", "kind", "dense", "s",
                                             "s_res", "dtype"))
def _init_block(key, z, kind, dense, s, s_res, dtype):
    z = dict(z)
    d, dh = z["dim"], z["head_dim"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dt=dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dt)

    def scale(n):   # drawn about 1, so that a dropped scale shows
        return {"scale": 1.0 + normal((n,), s, jnp.float32)}

    blk = {"ln1": scale(d), "ln2": scale(d)}
    if kind == "conv":
        blk.update(w_in=normal((d, 3 * d), s),
                   conv=normal((d, z["taps"]), 1 / math.sqrt(z["taps"])),
                   wo=normal((d, d), s_res))
    else:
        blk.update(wq=normal((d, z["heads"] * dh), s),
                   wk=normal((d, z["kv_heads"] * dh), s),
                   wv=normal((d, z["kv_heads"] * dh), s),
                   wo=normal((z["heads"] * dh, d), s_res),
                   q_norm=scale(dh), k_norm=scale(dh))
    if dense:
        blk.update(w_gate_up=normal((d, 2 * z["dense_ffn"]), s),
                   w_down=normal((z["dense_ffn"], d), s_res))
    else:
        e, f = z["experts"], z["ffn"]
        blk.update(router=normal((d, e), s, jnp.float32),
                   # a trained bias is a few hundredths of a score: drawn,
                   # so that a choice made without it shows
                   router_bias=normal((e,), 0.05, jnp.float32),
                   w_gate_up=normal((e, d, 2 * f), s),
                   w_down=normal((e, f, d), s_res))
    return blk


@functools.partial(jax.jit, static_argnames=("vocab", "dim", "s", "dtype"))
def _init_ends(key, vocab, dim, s, dtype):
    k1, k2 = jax.random.split(key)
    return {"embed": (jax.random.normal(k1, (vocab, dim), jnp.float32)
                      * s).astype(dtype),
            "ln_f": {"scale": 1.0 + jax.random.normal(k2, (dim,)) * s}}


def init_params(cfg: dict, seed: int, dtype=jnp.float32):
    """The stage's weights in the program's layout, made on the device a
    block a call (a layer's 64 experts are 2.4 GB while they are float32).
    The draw is float32 and is then cast, so a bfloat16 model is the
    rounding of the float32 one of the same seed."""
    z = dict(sizes(cfg))
    s = float(cfg["initializer_range"])
    s_res = s / math.sqrt(2 * len(z["kinds"]))
    key, dt = seed_key(seed), jnp.dtype(dtype)
    blocks = [_init_block(jax.random.fold_in(key, i), sizes(cfg), kind, dense,
                          s, s_res, dt)
              for i, (kind, dense) in enumerate(zip(z["kinds"], z["dense"]))]
    return dict(_init_ends(jax.random.fold_in(key, 0xe0d), z["vocab"],
                           z["dim"], s, dt), blocks=blocks)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _norm(x, g, eps):
    f = x.astype(jnp.float32)
    return (f / jnp.sqrt(jnp.mean(jnp.square(f), -1, keepdims=True) + eps)
            * g["scale"]).astype(x.dtype)


def _rotate(x, theta: float, pos):
    """Rotary positions on [T, H, Dh], row t at position ``pos[t]``, a head's
    first half the pairs' first members."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, scale: float, mode: str):
    """Causal attention of [T, H, Dh] over [T, H, Dh], `ATTN_ROWS` queries at
    a time."""
    t, h, _ = q.shape
    rows = min(ATTN_ROWS, t)
    pad = -t % rows
    k_pos = jnp.arange(t)[None, :]

    def some(args):
        q_i, i = args
        s = _mm(q_i, k, mode, "qhd,khd->hqk").astype(jnp.float32) * scale
        seen = i * rows + jnp.arange(rows)[:, None] >= k_pos
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm(p.astype(q.dtype), v, mode, "hqk,khd->qhd")

    blocks = (t + pad) // rows
    o = jax.lax.map(some, (jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, rows, h, -1), jnp.arange(blocks)))
    return o.reshape(t + pad, -1)[:t]


def _attention_mixer(y, blk, z: dict, mode: str, pos):
    t, h, kv, dh = y.shape[0], z["heads"], z["kv_heads"], z["head_dim"]
    q = _mm(y, blk["wq"], mode).reshape(t, h, dh)
    k = _mm(y, blk["wk"], mode).reshape(t, kv, dh)
    v = _mm(y, blk["wv"], mode).reshape(t, kv, dh)
    q = _rotate(_norm(q, blk["q_norm"], z["eps"]), z["theta"], pos)
    k = _rotate(_norm(k, blk["k_norm"], z["eps"]), z["theta"], pos)
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    return _mm(_attention(q, k, v, dh ** -0.5, mode), blk["wo"], mode)


def _conv_mixer(y, blk, z: dict, mode: str, taps: bool = True):
    t = y.shape[0]
    b, c, x = jnp.split(_mm(y, blk["w_in"], mode), 3, axis=-1)
    u = b * x
    n = z["taps"]
    padded = jnp.pad(u, ((n - 1, 0), (0, 0)))
    conv = sum(padded[j:j + t] * blk["conv"][:, j].astype(u.dtype)[None, :]
               for j in range(n)) if taps else u
    return _mm(c * conv, blk["wo"], mode)


def _swiglu(x, w_gate_up, w_down, mode: str):
    gate_up = _mm(x, w_gate_up, mode)
    f = w_down.shape[0]
    return _mm(jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:], w_down, mode)


def route(x, w_router, bias, z: dict):
    """``x`` [T, D] -> (picks [T, top_k], weights [T, top_k]).  Float32 at
    ``highest``."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=HIGHEST))
    _, picks = jax.lax.top_k(scores + bias, z["top_k"])
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    return picks, picked / (jnp.sum(picked, -1, keepdims=True)
                            + 1e-20) * z["scale"]


def _experts(x, blk, z: dict, mode: str):
    """The routed term of [T, D] rows, and the picks.  The picks are sorted
    by expert, each expert's rows begin at a multiple of the tile (room for
    the worst case), a tile of rows crosses its expert's SwiGLU, and every
    row is added back to its token by its pick's weight."""
    t, k, held = x.shape[0], z["top_k"], z["experts"]
    picks, weights = route(x, blk["router"], blk["router_bias"], z)
    share = max(2 * t * k // held, 1)
    rows = min(max(1 << (share - 1).bit_length(), 8), TILE)
    key = picks.reshape(-1)
    order = jnp.argsort(key, stable=True)           # sorted place -> flat pick
    expert = key[order]
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0)
    room = -(-counts // rows) * rows                # an expert's whole tiles
    begins = jnp.cumsum(room) - room
    firsts = jnp.cumsum(counts) - counts            # its first sorted place
    tiles = -(-(t * k + held * (rows - 1)) // rows)
    row = begins[expert] + jnp.arange(t * k) - firsts[expert]
    # the flat pick a row computes; t * k where it computes none
    pick_of_row = jnp.full((tiles * rows,), t * k, jnp.int32).at[row].set(
        order.astype(jnp.int32))
    tile_expert = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(room), jnp.arange(tiles) * rows, side="right"), held - 1)
    token = jnp.where(pick_of_row < t * k, pick_of_row // k, t)
    got = jnp.take(x, token, axis=0, mode="fill", fill_value=0)

    def tile(args):
        xs, e = args
        return _swiglu(xs, blk["w_gate_up"][e].astype(x.dtype),
                       blk["w_down"][e].astype(x.dtype), mode)

    ys = jax.lax.map(tile, (got.reshape(tiles, rows, -1), tile_expert))
    w = jnp.take(weights.reshape(-1), pick_of_row, mode="fill", fill_value=0)
    out = jnp.zeros_like(x).at[token].add(
        ys.reshape(got.shape) * w[:, None].astype(x.dtype), mode="drop")
    return out, picks


def _block(h, blk, z: dict, mode: str, kind: str, dense: bool, pos,
           taps: bool = True):
    """One layer over [T, D]: the stream, and the layer's picks (None for a
    dense one)."""
    y = _norm(h, blk["ln1"], z["eps"])
    r = h + (_conv_mixer(y, blk, z, mode, taps) if kind == "conv"
             else _attention_mixer(y, blk, z, mode, pos))
    y = _norm(r, blk["ln2"], z["eps"])
    if dense:
        return r + _swiglu(y, blk["w_gate_up"], blk["w_down"], mode), None
    out, picks = _experts(y, blk, z, mode)
    return r + out, picks


def _forward(params, tokens, z: dict, mode: str, pos=None, taps: bool = True):
    act = jnp.float32 if mode == "float32" else jnp.bfloat16
    pos = jnp.arange(tokens.shape[0]) if pos is None else pos
    h = params["embed"][tokens].astype(act)
    picks = []
    for blk, kind, dense in zip(params["blocks"], z["kinds"], z["dense"]):
        # norms, the router and its bias stay float32 whatever the mode, and
        # the experts' stacks as stored: a tile's product takes its own up
        blk = {k: v if isinstance(v, dict) or v.ndim != 2 or k == "router"
               else v.astype(act) for k, v in blk.items()}
        h, p = _block(h, blk, z, mode, kind, dense, pos, taps)
        if p is not None:
            picks.append(p)
    return _norm(h, params["ln_f"], z["eps"]), jnp.stack(picks)


def logits_one(params, tokens, z: tuple, mode: str = "float32", pos=None,
               taps: bool = True):
    """``[T]`` tokens of one sequence -> ``[T, vocab]`` float32 logits, the
    head `HEAD_ROWS` positions at a time.  ``z``: `sizes` of the
    configuration.  ``pos``: each row's position, where it is not its index
    (a control: the rotation at the wrong position); ``taps`` false: the
    convolution left out (a control)."""
    h, _ = _forward(params, tokens, dict(z), mode, pos, taps)
    t = h.shape[0]
    rows = min(HEAD_ROWS, t)
    pad = -t % rows
    embed = params["embed"].astype(h.dtype)
    out = jax.lax.map(
        lambda hb: _mm(hb, embed, mode, "td,vd->tv").astype(jnp.float32),
        jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows, h.shape[1]))
    return out.reshape(t + pad, -1)[:t]


def picks_one(params, tokens, z: tuple, mode: str = "float32"):
    """``[T]`` tokens -> every routed layer's picks ``[L, T, top_k]``."""
    return _forward(params, tokens, dict(z), mode)[1]
