"""``ttft_p95_ms``'s reading in the LFM2 cell, as a per-layer number: the
cell's prompts of 1,025-2,048 tokens are a ninth of its requests and their
prefill sets them apart from the rest, so the 95th percentile lies among
thirty requests a window and spreads over seeds by more than half the
end-to-end bound (``PERF.md``, section 2)."""

from chipbench.metrics.ttft_p95_ms import read  # noqa: F401
