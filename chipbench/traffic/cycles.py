"""The token stream of a long-sequence training cell: as ``tokens.py``'s
(every row its own cycle, 10% of positions replaced by noise, no two rows
alike), with the cycle's length a parameter of the cell.

A routed model's load follows its tokens: a row that repeats 64 tokens puts
nine tenths of its positions on 64 distinct embeddings, and which experts
those pick is one seed's accident.  A cycle of 1024 gives a row of 16,384
some two thousand distinct tokens, so that the load of the held experts is
the router's and not the draw's."""

import numpy as np


def call_tokens(seed: int, call: int, rows: int, seq_len: int, vocab: int,
                cycle: int) -> np.ndarray:
    """The flat stream of one training call, ``rows * seq_len + 1`` tokens
    (the last is the final row's last target), a pure function of
    ``(seed, call)``; ids below ``vocab``, the slice of the vocabulary that
    is held."""
    rng = np.random.default_rng([int(seed), int(call), 0x70c, int(cycle)])
    cycles = rng.integers(0, vocab, (rows, cycle))
    toks = cycles[:, np.arange(seq_len) % cycle]
    noise = rng.random((rows, seq_len)) < 0.1
    toks[noise] = rng.integers(0, vocab, int(noise.sum()))
    last = rng.integers(0, vocab, 1)
    return np.concatenate([toks.reshape(-1), last]).astype(np.int64)
