"""Device milliseconds an optimizer step spends in the optimizer (the clip
and AdamW inside the scan: ``tx.update`` and ``apply_updates`` of
`trainer.sft_epoch`'s ``step``): the scope ``fedml.opt`` of the epoch
program, as ``attn_bwd_ms_per_step`` reads its own."""

from chipbench.harness import scopes


def read(run):
    return scopes.epoch_ms_per_step(run, ("fedml.opt",))
