"""``ttft_p50_ms``'s reading in the LFM2 cell, which reports no
``ttft_p95_ms`` end to end and so is not handed the metrics that move it."""

from chipbench.metrics.ttft_p50_ms import read  # noqa: F401
