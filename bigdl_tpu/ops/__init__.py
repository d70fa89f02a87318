"""TPU kernels (pallas) for hot ops: flash attention for training and
prefill over dense K and V (``flash_attention``), and paged attention for
the serving decode step, one query token a lane over the pages its block
table names, read from the pool where they lie (``paged_attention``)."""

from bigdl_tpu.ops.flash_attention import flash_attention  # noqa: F401
from bigdl_tpu.ops.paged_attention import paged_attention  # noqa: F401
