"""Bytes one decoding step of a GPT-2 cannot avoid reading: every weight
once.  The key/value cache of the tokens then alive is needed too, but how
many those are is the engine's state, which the benchmark does not see; it is
left out, so a roofline share built on this understates the step."""


def weight_bytes(cfg: dict, itemsize: int) -> float:
    d, n, v, p = (cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"],
                  cfg["n_positions"])
    return (n * (12.0 * d * d + 13 * d) + float(v) * d + p * d
            + 2 * d) * itemsize
