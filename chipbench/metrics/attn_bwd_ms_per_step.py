"""Device milliseconds an optimizer step spends in the attention backward:
the self time of the epoch program's instructions whose scope is
``fedml.attn_bwd`` (`ops/pallas_attention._flash_core`'s ``bwd`` whole:
`_flash_backward_tiled` or `_flash_backward_blockwise`), inside the
``train()`` calls the trace holds whole, over those calls' steps.  Read from
the trace's own HLO (``chipbench/harness/scopes.py``); nothing on a program
that names no scope."""

from chipbench.harness import scopes


def read(run):
    return scopes.epoch_ms_per_step(run, ("fedml.attn_bwd",))
