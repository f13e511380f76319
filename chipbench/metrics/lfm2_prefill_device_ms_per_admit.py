"""``prefill_device_ms_per_admit``'s reading in the LFM2 cell: what an
admission costs the device before the dispatch behind it can start, which
the rows that are decoding wait for as the new request does."""

from chipbench.metrics.prefill_device_ms_per_admit import read  # noqa: F401
