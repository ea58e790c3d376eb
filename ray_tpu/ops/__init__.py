"""TPU-native neural-net ops: the hot kernels of the model layer.

The reference delegates these to torch/CUDA (vLLM, flash-attn); here they are
first-class: pure-JAX reference implementations everywhere, Pallas TPU
kernels on the MXU path, and ring/all-to-all sequence parallelism built on
``shard_map`` + ``ppermute`` (SURVEY.md §5.7 — absent in the reference, a
native requirement for this build).
"""

from ray_tpu.ops.norms import rms_norm, layer_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.ops.attention import (attention, flash_attention,
                                   packed_attention)
from ray_tpu.ops.ring_attention import ring_attention

__all__ = [
    "rms_norm", "layer_norm", "apply_rope", "rope_frequencies",
    "attention", "flash_attention", "packed_attention", "ring_attention",
]
