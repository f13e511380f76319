"""Bytes and operations a token step of the LFM2 stage cannot avoid, from the
configuration's sizes and from what the program counted: for the dispatch's
share of the memory roofline and for the expert kernels' own.

A token step of ``decode_multi`` reads every matrix outside the experts once
(the mixers, the dense layer's SwiGLU, the routers, the tied head; of the
embedding's rows it gathers a batch's few, which are not counted again), of
each routed layer the gate-up and down matrices of the experts a live row
picked (``fedml_moe_experts_touched_total``), and the blocks of the key/value
cache that hold a live position (``fedml_llm_cache_blocks_live_total``, 128
positions of one row, over the attention layers' key/value heads).  The
short convolutions' states are two columns a row: not counted.
"""

#: positions of one row a counted cache block holds (`llm_engine.CACHE_BLOCK`)
CACHE_BLOCK = 128


def kinds(cfg: dict):
    """(is a short convolution, is dense) of each held layer."""
    held = [int(i) for i in cfg["held_layers"]]
    return [(cfg["layer_types"][i] == "conv",
             i < int(cfg["num_dense_layers"])) for i in held]


def routed_layers(cfg: dict) -> int:
    return sum(not dense for _, dense in kinds(cfg))


def expert_parameters(cfg: dict) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def parameters_outside_experts(cfg: dict) -> int:
    """Every parameter a token step reads whatever its rows picked."""
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    kv_cols = int(cfg["num_key_value_heads"]) * (d // heads)
    total = int(cfg["vocab_size"]) * d + d          # the tied head, its norm
    for conv, dense in kinds(cfg):
        total += 2 * d                              # the block's two norms
        total += (4 * d * d + d * int(cfg["conv_L_cache"]) if conv
                  else 2 * d * d + 2 * d * kv_cols + 2 * (d // heads))
        total += (3 * d * int(cfg["intermediate_size"]) if dense
                  else d * int(cfg["num_experts"]) + int(cfg["num_experts"]))
    return total


def cache_block_bytes(cfg: dict, itemsize: int) -> int:
    """K and V of one block of one row over the attention layers."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    attention = sum(not conv for conv, _ in kinds(cfg))
    return (attention * 2 * int(cfg["num_key_value_heads"]) * (d // heads)
            * CACHE_BLOCK * itemsize)


def token_step_bytes(cfg: dict, touched: float, live_blocks: float,
                     itemsize: int) -> dict:
    """What a token step reads: ``touched`` experts over all routed layers,
    ``live_blocks`` cache blocks over all rows."""
    parts = {"outside_experts": parameters_outside_experts(cfg) * itemsize,
             "experts": touched * expert_parameters(cfg) * itemsize,
             "cache": live_blocks * cache_block_bytes(cfg, itemsize)}
    return dict(parts, total=sum(parts.values()))


def experts_least_seconds(cfg: dict, touched: float, picks: float,
                          itemsize: int, peaks: dict) -> dict:
    """The least time of a token step's expert products over all routed
    layers: the touched experts' matrices fetched once, or the picks' rows
    multiplied (2 operations a parameter and row), whichever is longer."""
    by_bytes = (touched * expert_parameters(cfg) * itemsize
                / peaks["hbm_bytes_per_s"])
    by_ops = (2.0 * picks * expert_parameters(cfg)
              / peaks["bf16_flops_per_s"])
    return {"seconds": max(by_bytes, by_ops),
            "bound": "memory" if by_bytes >= by_ops else "compute"}
