"""Device milliseconds the expert products take per optimizer step in the
family with delta-rule layers (128 held bfloat16 experts of [2048, 1024] and
[512, 2048]): the summed durations of the ``moe_experts`` / ``moe_experts_t``
kernels' events inside the ``train()`` calls the trace holds whole, over those
calls' steps (``moe_experts_ms_per_step``'s reading, of another cell)."""

from chipbench.metrics.moe_experts_ms_per_step import read  # noqa: F401
