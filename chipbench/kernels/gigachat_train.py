"""Operations and bytes the latent-attention family's training step
*requires*, from the configuration's sizes and from the picks the program
counted: for the whole step's utilization figure and for the two kernels'
rooflines.  Recomputation, padding to tiles and anything an implementation
adds on top do not count in the step's figure; a kernel's roofline counts
what each of its executions was asked to do, a rematerialised one too.

Under LoRA a frozen matrix needs its forward product and the activations'
gradient, 4 operations a parameter and token; the factors' own products are
``2 r (d_in + d_out)`` forward and twice that backward.  Attention: a query
multiplies the keys before it, scores ``2 * (nope + rope)`` and values ``2 *
v`` operations a pair and head, the backward twice the forward.  Experts: only
the picks that landed on a held expert, as the program counted them; the
shared expert every token.  The second head's block counts as a routed layer,
with its joining matrix and one more pass of the output head.
"""


def blocks(cfg: dict) -> dict:
    """How many blocks of each kind a step crosses: ``dense``, ``routed``
    (the second head's block among them), and ``heads`` of the vocabulary."""
    mtp = int(cfg["num_nextn_predict_layers"])
    dense = int(cfg["first_k_dense_replace"])
    return {"dense": dense, "mtp": mtp, "heads": 1 + mtp,
            "routed": int(cfg["num_hidden_layers"]) - dense + mtp}


def attention_matrices(cfg: dict):
    """(d_in, d_out) of the five matrices of one block's attention."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return [(d, cfg["q_lora_rank"]), (cfg["q_lora_rank"], h * qk),
            (d, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]),
            (cfg["kv_lora_rank"],
             h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
            (h * cfg["v_head_dim"], d)]


def visible_pairs(t: int) -> float:
    """(query, key) pairs of one head over ``t`` causal positions."""
    return t * (t + 1) / 2.0


def flops_per_token(cfg: dict, seq_len: int, lora_rank: int,
                    landed_per_token_layer: float) -> dict:
    """Required operations a trained token, by part."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = blocks(cfg)
    every = n["dense"] + n["routed"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mats = attention_matrices(cfg)
    return {
        "projections": 4.0 * every * sum(a * b for a, b in mats),
        "attention": 3 * 2.0 * (qk + cfg["v_head_dim"])
        * cfg["num_attention_heads"] * every * visible_pairs(seq_len)
        / seq_len,
        "dense_mlp": 4.0 * n["dense"] * 3 * d * cfg["intermediate_size"],
        "shared_expert": 4.0 * n["routed"] * cfg["n_shared_experts"] * 3 * d * f,
        "experts": 4.0 * n["routed"] * landed_per_token_layer * 3 * d * f,
        "router": 4.0 * n["routed"] * d * cfg["published"]["n_routed_experts"],
        "eh_proj": 4.0 * n["mtp"] * 2 * d * d,
        "head": 4.0 * n["heads"] * d * cfg["vocab_size"],
        "factors": 3 * 2.0 * every * lora_rank * sum(a + b for a, b in mats),
    }


def attention_least_seconds(cfg: dict, batch: int, seq_len: int,
                            itemsize: int, peaks: dict) -> dict:
    """The least time the chip could take for one forward execution of the
    attention kernel in every block: the two products over the visible
    pairs at heads of ``nope + rope`` and ``v``; q, k, v and the output of
    every head at the operands' own itemsize (the one rotary key is laid
    under every head before the call), and the two float32 residuals a
    query."""
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    v = cfg["v_head_dim"]
    n = blocks(cfg)
    ops = 2.0 * batch * h * (qk + v) * visible_pairs(seq_len)
    moved = batch * seq_len * h * (2.0 * (qk + v) * itemsize + 2 * 4)
    by_ops = ops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return {"seconds": (n["dense"] + n["routed"]) * max(by_ops, by_bytes),
            "blocks": n["dense"] + n["routed"],
            "bound": "compute" if by_ops >= by_bytes else "memory"}


def experts_least_seconds(cfg: dict, rows: float, itemsize: int,
                          peaks: dict) -> dict:
    """The least time for the six expert products of one routed block in one
    step (gate-up and down forward, each again when the block is
    rematerialised, and the two transposed products of the backward) over
    ``rows`` picks that landed: rows in bfloat16, float32 out, the matrix of
    each held expert that has a row (all of them, from ``rows`` >= held on)
    read once a product at the stored ``itemsize``."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    live = min(cfg["n_routed_experts"], rows)
    total, bound = 0.0, []
    for k, n, times in ((d, 2 * f, 2), (f, d, 2), (d, f, 1), (2 * f, d, 1)):
        by_ops = 2.0 * rows * k * n / peaks["bf16_flops_per_s"]
        by_bytes = (rows * (2.0 * k + 4.0 * n) + itemsize * live * k * n) \
            / peaks["hbm_bytes_per_s"]
        total += times * max(by_ops, by_bytes)
        bound.append("compute" if by_ops >= by_bytes else "memory")
    return {"seconds": total, "products": 6, "bound": bound}
