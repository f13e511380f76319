"""Operations and bytes the routed family's training step *requires*, from
the configuration's sizes and from the picks the program counted: for the
whole step's utilization figure and for the two kernels' rooflines.
Recomputation, padding to tiles and anything an implementation adds on top
do not count in the step's figure; a kernel's roofline counts what each of
its executions was asked to do, a rematerialised one too.

Under LoRA a frozen matrix needs its forward product and the activations'
gradient, 4 operations a parameter and token; the factors' own products are
``2 r (d_in + d_out)`` forward and twice that backward.  Attention: a query
multiplies only the keys it may see (all before it in a full layer, the last
``window`` in a window layer), scores and values ``2 * 2 * Dh`` operations a
pair and query head, the backward twice the forward.  Experts: only the
picks that landed on a held expert, as the program counted them.
"""


def visible_pairs(t: int, window=None) -> float:
    """(query, key) pairs of one head over ``t`` positions."""
    if window is None or window >= t:
        return t * (t + 1) / 2.0
    return window * (window + 1) / 2.0 + (t - window) * float(window)


def layer_windows(cfg: dict):
    n = int(cfg["num_hidden_layers"])
    return [int(cfg["sliding_window_size"]) if on else None
            for on in cfg["sliding_window_layout"][:n]]


def flops_per_token(cfg: dict, seq_len: int, lora_rank: int,
                    landed_per_token_layer: float) -> dict:
    """Required operations a trained token, by part."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    f, n = cfg["moe_ffn_hidden_size"], int(cfg["num_hidden_layers"])
    routed = cfg["published"]["moe_num_primary_experts"]
    pairs = sum(visible_pairs(seq_len, w) for w in layer_windows(cfg))
    factors = n * lora_rank * (2 * (d + q) + 2 * (d + kv))
    return {
        "projections": 4.0 * n * (2 * d * q + 2 * d * kv),
        "router": 4.0 * n * d * routed,
        "experts": 4.0 * n * landed_per_token_layer * 3 * d * f,
        "attention": 3 * 2 * 2.0 * dh * cfg["num_attention_heads"]
        * pairs / seq_len,
        "head": 4.0 * d * cfg["vocab_size"],
        "factors": 3 * 2.0 * factors,
    }


def attention_least_seconds(cfg: dict, batch: int, seq_len: int,
                            itemsize: int, peaks: dict) -> dict:
    """The least time the chip could take for one forward execution of the
    attention kernel in every layer (full and window layers together): the
    two products over the visible pairs; q and the output of every query
    head, k and v of every key/value head, at the operands' own itemsize,
    and the two float32 residuals a query."""
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    total, bound = 0.0, {}
    for window in layer_windows(cfg):
        ops = 2 * 2.0 * batch * h * dh * visible_pairs(seq_len, window)
        moved = batch * seq_len * (2.0 * (h + hk) * dh * itemsize + 2 * h * 4)
        by_ops = ops / peaks["bf16_flops_per_s"]
        by_bytes = moved / peaks["hbm_bytes_per_s"]
        total += max(by_ops, by_bytes)
        bound["window" if window else "full"] = (
            "compute" if by_ops >= by_bytes else "memory")
    return {"seconds": total, "bound": bound}


def experts_least_seconds(cfg: dict, rows: float, peaks: dict) -> dict:
    """The least time for the six expert products of one layer in one step
    (gate-up and down forward, each again when the block is rematerialised,
    and the two transposed products of the backward) over ``rows`` picks
    that landed: rows in bfloat16, float32 out, each held expert's float32
    matrix read once a product."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    held = cfg["moe_num_primary_experts"]
    total = 0.0
    for k, n, times in ((d, 2 * f, 2), (f, d, 2), (d, f, 1), (2 * f, d, 1)):
        by_ops = 2.0 * rows * k * n / peaks["bf16_flops_per_s"]
        by_bytes = (rows * (2.0 * k + 4.0 * n) + 4.0 * held * k * n) \
            / peaks["hbm_bytes_per_s"]
        total += times * max(by_ops, by_bytes)
    return {"seconds": total, "products": 6}
