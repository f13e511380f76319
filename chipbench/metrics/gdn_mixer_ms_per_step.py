"""Device milliseconds an optimizer step spends in the delta-rule mixers, all
directions: the scopes ``fedml.gdn.proj`` (the two projections), ``.conv``
(the causal convolution and its SiLU), ``.gates`` (the decay, the writing
strength, q and k normed), ``.scan`` and ``.scan_bwd`` (the kernels and what
XLA roots beside them) and ``.out`` (the gated norm and the way out), as
``attn_bwd_ms_per_step`` reads its own: what the mixer costs around its
kernels is this less ``gdn_fwd_ms_per_step`` and ``gdn_bwd_ms_per_step``."""

from chipbench.harness import scopes

SCOPES = tuple("fedml.gdn." + part for part in (
    "proj", "conv", "gates", "scan", "scan_bwd", "out"))


def read(run):
    return scopes.epoch_ms_per_step(run, SCOPES)
