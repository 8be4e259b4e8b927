"""TPU kernels (Pallas) for the hot ops XLA doesn't fuse well enough.

Runs in Pallas interpret mode on CPU so the whole stack stays testable on the
virtual device mesh (SURVEY.md §4 strategy).
"""

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.kda import kda_scan

__all__ = ["flash_attention", "kda_scan"]
