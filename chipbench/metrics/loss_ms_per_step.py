"""Device milliseconds an optimizer step spends on the head's product and the
loss over its logits, forward, backward and made again: the scopes
``fedml.head`` + ``fedml.loss`` of the epoch program (`functional_lm.head`,
`loss_in_row_blocks`, `masked_loss` in the trainer's ``loss_fn``), as
``attn_bwd_ms_per_step`` reads its own."""

from chipbench.harness import scopes


def read(run):
    return scopes.epoch_ms_per_step(run, ("fedml.head", "fedml.loss"))
