"""Ouro looped causal LM (ByteDance Ouro 1.4B / 2.6B, "LoopLM").

One stack of decoder layers run ``total_ut_steps`` times with the same
weights (arXiv:2510.25741 section 3; ``modeling_ouro.py`` beside the
published ``config.json``). The block is the Llama block — RMSNorm, RoPE,
full attention, SwiGLU, no bias — with a SANDWICH of norms: one before
each sub-layer and one on the sub-layer's output before it joins the
residual stream (``input_layernorm_2``, ``post_attention_layernorm_2``).
The final norm runs at the end of EVERY pass and its output feeds the
next pass. Keys and values of pass ``t`` are that pass's own: a decoder
holds ``num_hidden_layers * total_ut_steps`` cache layers over
``num_hidden_layers`` weight layers (``LlamaConfig.num_cache_layers``).

Departure from the published model: the early-exit gate (a ``hidden_size
-> 1`` linear and a sigmoid per pass) is not built. At the published
``early_exit_threshold`` of 1.0 every token runs every pass and the gate
decides nothing.

Built from ``LlamaAttention`` / ``LlamaMLP``; served by ``LlamaDecoder``
(the config selects the loop, the parameters the extra norms) and trained
by ``ShardedTrainer`` (the tape accumulates the T uses of each weight).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.models.llama import (
    LlamaAttention, LlamaConfig, LlamaForCausalLM, LlamaMLP, _constrain,
    _rope_tables,
)

__all__ = ["OuroConfig", "OuroForCausalLM", "OuroModel", "OURO_TINY"]


@dataclass
class OuroConfig(LlamaConfig):
    total_ut_steps: int = 4         # passes over the layer list
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps must be >= 1, got {self.total_ut_steps}")
        if self.early_exit_threshold < 1.0:
            raise ValueError(
                "early exit is not built: every token runs every pass, "
                "which is the published early_exit_threshold of 1.0; got "
                f"{self.early_exit_threshold}")


OURO_TINY = OuroConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=4, max_position_embeddings=128,
                       total_ut_steps=4)


class OuroDecoderLayer(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()

        def norm():
            return nn.RMSNorm(config.hidden_size,
                              epsilon=config.rms_norm_eps)
        self.input_layernorm = norm()
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = norm()
        self.mlp = LlamaMLP(config)
        self.input_layernorm_2 = norm()             # on the attention output
        self.post_attention_layernorm_2 = norm()    # on the MLP output

    def forward(self, hidden, cos, sin, attn_mask=None):
        a = self.self_attn(self.input_layernorm(hidden), cos, sin, attn_mask)
        hidden = hidden + self.input_layernorm_2(a)
        m = self.mlp(self.post_attention_layernorm(hidden))
        hidden = hidden + self.post_attention_layernorm_2(m)
        return _constrain(hidden, ("dp", "sep", None))


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([OuroDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None):
        cfg = self.config
        cos, sin = _rope_tables(input_ids.shape[1], cfg.head_dim,
                                cfg.rope_theta, jnp.dtype(cfg.dtype))
        hidden = _constrain(self.embed_tokens(input_ids),
                            ("dp", "sep", None))
        for _ in range(cfg.total_ut_steps):
            for layer in self.layers:
                hidden = layer(hidden, cos, sin, attn_mask)
            hidden = self.norm(hidden)
        return hidden


class OuroForCausalLM(LlamaForCausalLM):
    """The Llama head, loss and ``generate`` over an ``OuroModel``."""

    model_class = OuroModel

    def flops_per_token(self, seq_len: int) -> float:
        """Train-step FLOPs per token: every layer's weights are used
        ``total_ut_steps`` times, the embedding and the head once."""
        cfg = self.config
        T = cfg.total_ut_steps
        once = sum(p.size for n, p in self.named_parameters()
                   if ".layers." not in n)
        looped = self.num_params() - once
        return (6 * (once + T * looped)
                + 12 * T * cfg.num_hidden_layers * cfg.hidden_size * seq_len)
