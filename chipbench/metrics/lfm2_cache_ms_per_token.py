"""Device milliseconds a token step of the full ``decode_multi`` dispatch
spends on what the LFM2 stage's rows keep: the scopes ``fedml.attn`` (the
kernel ``decode_attention`` over the live blocks and the merge with the
chunk), ``fedml.cache_write`` (the chunk's write and ``kv_store_positions``
behind the scan) and ``fedml.state_write`` (the convolutions' carried
inputs)."""

from chipbench.metrics.decode_dense_ms_per_token import decode_ms


def read(run):
    return decode_ms(run, ("fedml.attn", "fedml.cache_write",
                           "fedml.state_write"), per_token=True)
