"""JAX model zoo for tpu9 runner workloads.

These are the in-container workloads of the baseline configs (BASELINE.md):
text classifier (CPU), Llama 3 (v5e serving), CLIP ViT (fan-out embedding),
Gemma + LoRA (multi-host FSDP fine-tune). All models are functional pytrees —
params flow through ``jax.jit``/``pjit`` with shardings from tpu9.parallel.
"""

from .transformer import DecoderConfig, init_decoder, decoder_forward
from .kvstate import init_kv_cache
from .llama import LLAMA_PRESETS, llama_config
from .gemma import GEMMA_PRESETS, gemma_config
from .clip_vit import ClipVisionConfig, init_clip_vision, clip_vision_forward, CLIP_VIT_L14
from .classifier import TextClassifierConfig, init_classifier, classifier_forward
from . import lora, moe
from .mixtral import MIXTRAL_PRESETS, mixtral_config
from .ouro import OURO_PRESETS, ouro_config

__all__ = [
    "DecoderConfig", "init_decoder", "decoder_forward", "init_kv_cache",
    "LLAMA_PRESETS", "llama_config", "GEMMA_PRESETS", "gemma_config",
    "MIXTRAL_PRESETS", "mixtral_config", "moe",
    "OURO_PRESETS", "ouro_config",
    "ClipVisionConfig", "init_clip_vision", "clip_vision_forward", "CLIP_VIT_L14",
    "TextClassifierConfig", "init_classifier", "classifier_forward", "lora",
]
