"""Device milliseconds an optimizer step spends making the forward pass
again for the backward (``jax.checkpoint``: a block, a row block of the
loss): every scope of the epoch program, the unscoped too, in the direction
**remat** (``rematted_computation`` in the instruction's ``op_name``), as
``attn_bwd_ms_per_step`` reads its own."""

from chipbench.harness import scopes


def read(run):
    return scopes.epoch_ms_per_step(run, directions=("remat",))
