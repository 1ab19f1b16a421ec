"""TPU-native op kernels (Pallas + XLA) for the hot paths.

Reference: libnd4j's declarable-op library supplies fused kernels (attention
helpers, cuDNN platform helpers) — here the hot ops that XLA does not fuse
optimally get hand-written Pallas kernels (compiled to Mosaic), everything
else rides ``jax.numpy``/``lax`` + XLA fusion (SURVEY.md §2.1 equivalence
plan).
"""

from deeplearning4j_tpu.ops.attention import (  # noqa: F401
    bounded_decode_attention,
    cache_update,
    chunk_decode_attention,
    decode_attention,
    dot_product_attention,
    flash_attention,
    blockwise_attention,
    reference_attention,
)
from deeplearning4j_tpu.ops.ring import (  # noqa: F401
    ring_attention,
    ring_attention_local,
)
