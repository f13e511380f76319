"""Device milliseconds an optimizer step spends on the LoRA merge ``W + sAB``
(`train/llm/lora.apply_lora`), all directions; its transpose is the factors'
gradient through the full ``dW``: the scope ``fedml.lora`` of the epoch
program, as ``attn_bwd_ms_per_step`` reads its own."""

from chipbench.harness import scopes


def read(run):
    return scopes.epoch_ms_per_step(run, ("fedml.lora",))
