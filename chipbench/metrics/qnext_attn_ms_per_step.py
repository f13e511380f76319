"""Device milliseconds the flash-attention forward kernel takes per optimizer
step in the softmax layers of the family with delta-rule layers, at 16 query
heads over 2 key/value heads of 256: the ``flash_fwd`` kernel's events inside
the ``train()`` calls the trace holds whole, over those calls' steps
(``window_attn_ms_per_step``'s reading, of another cell)."""

from chipbench.metrics.window_attn_ms_per_step import KERNEL, read  # noqa: F401
