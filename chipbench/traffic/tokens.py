"""The token stream a training cell is fed: every row has structure to learn
(its own 64-token cycle, 10% of positions replaced by noise) and no two rows
are alike, so a part of the batch left out moves the loss."""

import numpy as np


def call_tokens(seed: int, call: int, rows: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """The flat stream of one training call, ``rows * seq_len + 1`` tokens
    (the last is the final row's last target), a pure function of
    ``(seed, call)``."""
    rng = np.random.default_rng([int(seed), int(call), 0x70c])
    cycles = rng.integers(0, vocab, (rows, 64))
    toks = cycles[:, np.arange(seq_len) % 64]
    noise = rng.random((rows, seq_len)) < 0.1
    toks[noise] = rng.integers(0, vocab, int(noise.sum()))
    last = rng.integers(0, vocab, 1)
    return np.concatenate([toks.reshape(-1), last]).astype(np.int64)


def as_batches(stream: np.ndarray, steps: int, batch: int, seq_len: int):
    """``[steps, batch, seq_len]`` inputs and next-token targets of a flat
    stream: the packing a trainer is expected to make of it."""
    n = steps * batch * seq_len
    x = stream[:n].reshape(steps, batch, seq_len)
    y = stream[1:n + 1].reshape(steps, batch, seq_len)
    return x, y
