"""Device milliseconds a token step of the full ``decode_multi`` dispatch
spends in the short-convolution mixers of the LFM2 stage: the scopes
``fedml.conv.*`` (the projection, the taps over the carried inputs, the way
out)."""

from chipbench.metrics.lfm2_experts_ms_per_token import scopes_ms


def read(run):
    return scopes_ms(run, ("fedml.conv",))
