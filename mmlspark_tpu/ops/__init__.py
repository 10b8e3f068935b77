"""TPU device kernels (Pallas) and op-level utilities.

The reference's device compute lives in CNTK's C++ kernels behind JNI;
here the hot device ops XLA doesn't already schedule optimally get
hand-written Pallas kernels, with jnp reference implementations for
equivalence tests and non-TPU backends.
"""

from mmlspark_tpu.ops.augment import (
    augment_batch, random_brightness, random_contrast, random_crop,
    random_flip_lr, random_flip_ud,
)
from mmlspark_tpu.ops.group_norm import group_norm, group_norm_reference
from mmlspark_tpu.ops.pallas import (
    attention_block_update, flash_attention, flash_attention_host,
    flash_attention_reference,
)
from mmlspark_tpu.ops.resize import fused_resize_norm, fused_resize_norm_host

__all__ = [
    "attention_block_update", "augment_batch", "flash_attention",
    "flash_attention_host", "flash_attention_reference",
    "fused_resize_norm", "fused_resize_norm_host",
    "group_norm", "group_norm_reference",
    "random_brightness", "random_contrast", "random_crop",
    "random_flip_lr", "random_flip_ud",
]
