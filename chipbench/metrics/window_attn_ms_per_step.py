"""Device milliseconds the flash-attention forward kernel takes per optimizer
step in the routed family's epoch program, over its full and its window
layers together: the ``flash_fwd`` kernel's events inside the ``train()``
calls the trace holds whole, over those calls' steps."""

from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps

#: the kernel of ``ops/pallas_attention.py`` by its own name: this epoch
#: program holds other Mosaic kernels beside it
KERNEL = r"^%?flash_fwd"


def read(run):
    got = kernel_ns_and_steps(run, KERNEL)
    return None if got is None else got[0] / got[2] / 1e6
