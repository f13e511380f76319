"""Device milliseconds an optimizer step spends in the norms (every
LayerNorm / RMSNorm, `functional_lm._norm`), all directions: the scope
``fedml.norm`` of the epoch program, as ``attn_bwd_ms_per_step`` reads its
own.  A fusion counts here when its root is a norm's instruction, whatever
else XLA fused into it: ``chipbench/tools/time_by_scope.py`` says what."""

from chipbench.harness import scopes


def read(run):
    return scopes.epoch_ms_per_step(run, ("fedml.norm",))
