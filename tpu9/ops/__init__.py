"""TPU-native ops: pallas kernels for the hot paths, XLA fallbacks everywhere.

The reference platform runs accelerator math inside user containers (CUDA);
tpu9 ships these ops in the runner image so workloads hit the MXU with
bf16-friendly, statically-shaped kernels.
"""

from .norms import rms_norm
from .rotary import apply_rope, rope_rows
from .attention import flash_attention, xla_attention, decode_attention
from .paged_attention import ragged_decode_attention
from .sampling import sample_logits
from .quant import quantize_decoder, quantize_weight, quantized_matmul

__all__ = ["rms_norm", "apply_rope", "rope_rows", "flash_attention",
           "xla_attention", "decode_attention", "ragged_decode_attention",
           "sample_logits", "quantize_decoder", "quantize_weight",
           "quantized_matmul"]
