"""Device milliseconds a full ``decode_multi`` dispatch spends writing what
it made: the chunk buffer's write of every token step and layer and the
store into the cache behind the scan (``kv_store_positions``): the scope
``fedml.cache_write``, over the same executions as
``decode_dense_ms_per_token``, a dispatch and not a token."""

from chipbench.metrics.decode_dense_ms_per_token import decode_ms


def read(run):
    return decode_ms(run, ("fedml.cache_write",), per_token=False)
