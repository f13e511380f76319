"""Operations one trained token *requires* of a GPT-2, for the utilization
figure.  Recomputation, padding and anything an implementation adds on top do
not count.

``N`` is the parameters that sit in matrix products: 12*d*d per block and the
tied output head's vocab*d.  Full fine-tuning needs the forward (2N), the
activations' gradient (2N) and the weights' gradient (2N).  With LoRA the base
weights are frozen and need no gradient; the factors' own products are
2*r*(d_in + d_out) per adapted matrix, forward and twice that backward.
Causal attention: scores and values are 2*T*d per token and layer once the
masked half is left out, and its backward twice its forward.
"""


def matmul_params(cfg: dict) -> float:
    d, n, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return n * 12.0 * d * d + float(v) * d


def flops_per_token(cfg: dict, seq_len: int, lora_rank: int = 0) -> float:
    d, n = cfg["n_embd"], cfg["n_layer"]
    base = matmul_params(cfg)
    attention = 3 * 2.0 * seq_len * d * n
    if not lora_rank:
        return 6 * base + attention
    # wq wk wv wo: d x d; w1: d x 4d; w2: 4d x d
    factors = n * lora_rank * (4 * 2 * d + 2 * 5 * d)
    return 4 * base + attention + 3 * 2.0 * factors
