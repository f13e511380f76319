"""Operations and bytes the training step of the family with delta-rule
layers *requires*, from the configuration's sizes and from the picks the
program counted: for the whole step's utilization figure and for the kernels'
rooflines.  Recomputation, padding to tiles and anything an implementation
adds on top do not count in the step's figure; a kernel's roofline counts
what each of its executions was asked to do, a rematerialised one too.

Under LoRA a frozen matrix needs its forward product and the activations'
gradient, 4 operations a parameter and token; the factors' own products are
``2 r (d_in + d_out)`` forward and twice that backward.  Softmax attention: a
query multiplies the keys before it, ``2 * 2 * head_dim`` operations a pair and
head, the backward twice the forward.  The scan: the chunked form of the gated
delta rule **at chunks of 64 positions, whatever chunk a kernel takes**, so
that a later kernel is read against the same work (`scan_macs_per_token`); its
backward twice its forward.  Experts: only the picks that landed on a held
expert, as the program counted them; the shared expert every token.
"""

#: the chunk the scan's required operations are counted at
SCAN_CHUNK = 64


def layers(cfg: dict) -> dict:
    """How many layers of each kind a step crosses."""
    every = int(cfg["num_hidden_layers"])
    softmax = every // int(cfg["full_attention_interval"])
    return {"every": every, "softmax": softmax, "delta": every - softmax}


def softmax_matrices(cfg: dict):
    """(d_in, d_out) of a softmax layer's adapted matrices: ``wq`` (q beside
    its gate), ``wk``, ``wv``, ``wo``."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return [(d, 2 * h * dh), (d, kv * dh), (d, kv * dh), (h * dh, d)]


def delta_sizes(cfg: dict):
    """(q or k columns, v columns) of a delta-rule layer."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def delta_matrices(cfg: dict):
    """(d_in, d_out) of a delta-rule layer's adapted matrices: ``w_qkvz``
    and ``wo``."""
    nq, nv = delta_sizes(cfg)
    return [(cfg["hidden_size"], 2 * nq + 2 * nv), (nv, cfg["hidden_size"])]


def visible_pairs(t: int) -> float:
    """(query, key) pairs of one head over ``t`` causal positions."""
    return t * (t + 1) / 2.0


def scan_macs_per_token(cfg: dict) -> float:
    """Multiply-adds a position and value head of the chunked forward at
    `SCAN_CHUNK`: ``K K^T`` and ``Q K^T`` (2 C Dk), the solve by forward
    substitution (C^2 / 2), ``X R`` and ``P V'`` (2 C Dv), and the three
    products with the state (3 Dk Dv)."""
    c, dk, dv = (SCAN_CHUNK, cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    return 2.0 * c * dk + c * c / 2.0 + 2.0 * c * dv + 3.0 * dk * dv


def flops_per_token(cfg: dict, seq_len: int, lora_rank: int,
                    landed_per_token_layer: float) -> dict:
    """Required operations a trained token, by part."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n = layers(cfg)
    nq, nv = delta_sizes(cfg)
    soft, delta = softmax_matrices(cfg), delta_matrices(cfg)
    return {
        "softmax_projections": 4.0 * n["softmax"] * sum(
            a * b for a, b in soft),
        "delta_projections": 4.0 * n["delta"] * (sum(
            a * b for a, b in delta) + d * 2 * cfg["linear_num_value_heads"]),
        "conv": 4.0 * n["delta"] * cfg["linear_conv_kernel_dim"] * (
            2 * nq + nv),
        "attention": 3 * 2.0 * 2 * cfg["head_dim"]
        * cfg["num_attention_heads"] * n["softmax"] * visible_pairs(seq_len)
        / seq_len,
        "scan": 3 * 2.0 * n["delta"] * cfg["linear_num_value_heads"]
        * scan_macs_per_token(cfg),
        "shared_expert": 4.0 * n["every"] * (
            3 * d * cfg["shared_expert_intermediate_size"] + d),
        "experts": 4.0 * n["every"] * landed_per_token_layer * 3 * d * f,
        "router": 4.0 * n["every"] * d * cfg["published"]["num_experts"],
        "head": 4.0 * d * cfg["vocab_size"],
        "factors": 3 * 2.0 * lora_rank * (
            n["softmax"] * sum(a + b for a, b in soft)
            + n["delta"] * sum(a + b for a, b in delta)),
    }


def _least(ops: float, moved: float, peaks: dict) -> dict:
    by_ops = ops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "bound": "compute" if by_ops >= by_bytes else "memory"}


def scan_least_seconds(cfg: dict, batch: int, seq_len: int, backward: bool,
                       peaks: dict) -> dict:
    """The least time for one execution of the scan's kernel over one
    layer.  Forward: `scan_macs_per_token`; float32 q and k of every key
    head, v, ``g`` and ``beta`` of every value head read and o written,
    once.  Backward: twice the operations; those, ``do``, and the five
    gradients (dq and dk a key head's)."""
    nq, nv = delta_sizes(cfg)
    hv = cfg["linear_num_value_heads"]
    ops = 2.0 * batch * seq_len * hv * scan_macs_per_token(cfg)
    rows = 2 * nq + nv + 2 * hv
    moved = 4.0 * batch * seq_len * (rows + nv)
    if backward:
        ops, moved = 2 * ops, 4.0 * batch * seq_len * 2 * (rows + nv)
    return _least(ops, moved, peaks)


def attention_least_seconds(cfg: dict, batch: int, seq_len: int,
                            itemsize: int, peaks: dict) -> dict:
    """The least time for one forward execution of the attention kernel in
    one softmax layer: the two products over the visible pairs at heads of
    ``head_dim``; q and the output of every query head, k and v of every
    key/value head at the operands' own itemsize, and the two float32
    residuals a query."""
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ops = 2.0 * batch * h * 2 * dh * visible_pairs(seq_len)
    moved = batch * seq_len * (2.0 * (h + kv) * dh * itemsize + 2 * 4 * h)
    return _least(ops, moved, peaks)


def experts_least_seconds(cfg: dict, rows: float, itemsize: int,
                          peaks: dict) -> dict:
    """The least time for the six expert products of one layer in one step
    (gate-up and down forward, each again when the block is rematerialised,
    and the two transposed products of the backward) over ``rows`` picks
    that landed: rows in bfloat16, float32 out, the matrix of each held
    expert that has a row (all of them, from ``rows`` >= held on) read once
    a product at the stored ``itemsize``."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    live = min(cfg["num_experts"], rows)
    total, bound = 0.0, []
    for k, n, times in ((d, 2 * f, 2), (f, d, 2), (d, f, 1), (2 * f, d, 1)):
        one = _least(2.0 * rows * k * n,
                     rows * (2.0 * k + 4.0 * n) + itemsize * live * k * n,
                     peaks)
        total += times * one["seconds"]
        bound.append(one["bound"])
    return {"seconds": total, "products": 6, "bound": bound}
