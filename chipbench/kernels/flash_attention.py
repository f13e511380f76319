"""Operations and bytes of one flash-attention forward call, from its shapes.

``[B, H, T, Dh]`` queries, keys and values.  Two matrix products per head
(scores, then values), 2*T*T*Dh operations each; with a causal mask only the
lower triangle is needed, and it is counted once.  Bytes: each of q, k, v read
once and the output written once, plus the two float32 softmax residuals per
query that the training forward keeps for its backward.
"""


def fwd_ops(b: int, h: int, t: int, dh: int, causal: bool = True) -> float:
    full = 2 * 2.0 * b * h * t * t * dh
    return full / 2 if causal else full


def fwd_bytes(b: int, h: int, t: int, dh: int, itemsize: int,
              residuals: bool = True) -> float:
    qkvo = 4.0 * b * h * t * dh * itemsize
    return qkvo + (2.0 * b * h * t * 4 if residuals else 0.0)


def least_seconds(b, h, t, dh, itemsize, peaks, causal=True) -> dict:
    """The least time the chip could take, and which peak sets it."""
    by_ops = fwd_ops(b, h, t, dh, causal) / peaks["bf16_flops_per_s"]
    by_bytes = fwd_bytes(b, h, t, dh, itemsize) / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_ops, by_bytes),
            "bound": "compute" if by_ops >= by_bytes else "memory"}
