"""ray_tpu.ops — TPU compute kernels (Pallas) with XLA reference paths.

The reference framework delegates attention/normalization kernels to vLLM /
torch CUDA kernels (e.g. /root/reference/python/ray/llm/_internal/serve/
deployments/llm/vllm/vllm_engine.py:254). Here the hot ops are implemented
TPU-first: Pallas kernels tiled for the MXU/VPU, with pure-XLA reference
implementations that tests compare them with and that run off TPU.

Dispatch convention: every op takes `implementation=` ("pallas" | "xla" |
None). None is a static rule (attention.resolve_attention_impl: the
backend alone; ragged_paged_attention.resolve_ragged_impl: backend and
shape) — a kernel is never tried and swapped for the reference when it
fails.
"""

from .attention import flash_attention, flash_attention_kept, mha_reference  # noqa: F401
from .ragged_paged_attention import (  # noqa: F401
    ragged_paged_attention,
    ragged_reference_attention,
)
from .ring_attention import ring_attention, ring_attention_sharded  # noqa: F401
from .ulysses import ulysses_attention, ulysses_attention_sharded  # noqa: F401
from .layers import (  # noqa: F401
    apply_rope,
    gelu,
    layernorm,
    rmsnorm,
    rope_frequencies,
    swiglu,
)
from .losses import cross_entropy_loss, z_loss  # noqa: F401
