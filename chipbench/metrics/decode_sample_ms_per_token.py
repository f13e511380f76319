"""Device milliseconds a token step of the full ``decode_multi`` dispatch
spends in the sampler (`serving/kv_cache_lm._filter_sample` or the exact
form): the scope ``fedml.sample``, over the same executions as
``decode_dense_ms_per_token``."""

from chipbench.metrics.decode_dense_ms_per_token import decode_ms


def read(run):
    return decode_ms(run, ("fedml.sample",), per_token=True)
