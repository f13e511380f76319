"""The scan's backward kernel's share of its roofline: the least time for one
execution over one layer (``chipbench/kernels/qwen3next_train.py``: twice the
forward's operations; q, k, v, ``g``, ``beta`` and ``do`` read, five gradients
written, float32) times the kernel's executions, over their device time."""

from chipbench.metrics import gdn_fwd_roofline
from chipbench.metrics.gdn_bwd_ms_per_step import KERNEL


def read(run):
    return gdn_fwd_roofline.read(run, KERNEL, True, "gdn_bwd_roofline")
