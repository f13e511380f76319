"""Device milliseconds the expert products take per optimizer step in the
latent-attention family's epoch program (bfloat16 matrices of [7168, 4096]
and [2048, 7168] an expert, taken in column blocks): the summed durations of
the ``moe_experts`` / ``moe_experts_t`` kernels' events inside the ``train()``
calls the trace holds whole, over those calls' steps
(``moe_experts_ms_per_step``'s reading, of another cell)."""

from chipbench.metrics.moe_experts_ms_per_step import read  # noqa: F401
