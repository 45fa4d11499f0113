"""Looped-decoder configs (the Ouro family): a decoder whose layers run
``loop_steps`` times a token over ONE set of weights.

Architecture constants follow the public Ouro-2.6B ``config.json``
(48 layers x 4 passes, plain multi-head attention, SwiGLU, untied head)
and, for what it has no key for, the ``modeling_ouro.py`` beside it: four
RMSNorms a layer (one before and one after each sub-layer), the final
norm closing every pass, and a one-logit exit gate whose cumulative exit
probability picks the pass the head reads. A token owns ``loop_steps x
n_layers`` planes of keys and values (``DecoderConfig.kv_layers``), so
the KV pool, not the weights, is what fills the chip.
"""

from __future__ import annotations

from .transformer import DecoderConfig


def ouro_config(**kw) -> DecoderConfig:
    base = dict(act="silu", norm_offset=0.0, rope_theta=1e6,
                norm_eps=1e-6, tie_embeddings=False, loop_steps=4,
                sandwich_norm=True, exit_gate=True, exit_threshold=1.0)
    base.update(kw)
    return DecoderConfig(**base)


OURO_PRESETS: dict[str, DecoderConfig] = {
    # test-scale: 2 layers x 2 passes, exercised by unit tests / CPU runs
    "ouro-tiny": ouro_config(vocab_size=512, dim=128, n_layers=2, n_heads=4,
                             n_kv_heads=4, head_dim=32, hidden_dim=256,
                             max_seq_len=512, loop_steps=2),
    # Ouro-2.6B: 2.67B parameters, the compute of a 4x deeper model
    "ouro-2.6b": ouro_config(vocab_size=49152, dim=2048, n_layers=48,
                             n_heads=16, n_kv_heads=16, head_dim=128,
                             hidden_dim=5632, max_seq_len=65536),
}
