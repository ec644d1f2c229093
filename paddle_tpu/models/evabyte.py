"""EvaByte byte-level causal LM (``model_type: evabyte``, 6.5B).

The Llama block — RMSNorm, RoPE, MHA, SwiGLU, no bias — with four
departures (``modeling_evabyte.py`` / ``eva.py`` beside the published
``config.json``):

- attention is EVA, chunked linearized attention (``ops/eva.py``): a query
  sees the exact keys of its own ALIGNED window of ``window_size``
  positions and, for every ``chunk_size`` keys of the windows before it,
  one summary key and value pooled with the per-head learned vectors
  ``adaptive_mu_k`` / ``adaptive_phi`` — all under one softmax;
- RMSNorm multiplies by ``1 + w`` (``norm_add_unit_offset``) and runs in
  the compute dtype (``fp32_ln: false``);
- the residual stream is added in float32 (``fp32_skip_add``), the logits
  are float32 (``fp32_logits``);
- the untied head has ``num_pred_heads * vocab_size`` rows: head ``h``
  predicts byte ``t + 1 + h``. ``forward`` returns all of them; serving
  picks the next byte from head 0 (columns ``[0, vocab_size)``) — the
  multi-byte self-speculative decoding the other heads exist for is a
  decoding scheme, not part of the forward pass, and is not built.

Served by ``LlamaDecoder`` (the config selects the two cache leaves a
layer and their write rules, ``inference/generate.py``); the eager model
here runs XLA's masked form and trains through it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework import random as rnd
from paddle_tpu.framework.dtype import convert_dtype
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models.llama import (
    LlamaAttention, LlamaConfig, LlamaForCausalLM, LlamaMLP, _constrain,
    _rope_tables,
)
from paddle_tpu.nn.layer_base import param_dtype
from paddle_tpu.ops.registry import OpDef, apply_op, op_api

__all__ = ["EvabyteConfig", "EvabyteConfigError", "EvabyteForCausalLM",
           "EvabyteModel", "EVABYTE_TINY"]


class EvabyteConfigError(ValueError):
    """A key of the published config this program does not build."""


@dataclass
class EvabyteConfig(LlamaConfig):
    vocab_size: int = 320
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    attention_class: str = "eva"
    window_size: int = 2048
    chunk_size: int = 16
    num_chunks: Optional[int] = None
    num_pred_heads: int = 8
    rope_scaling: Optional[dict] = None

    # what the program builds one way only: class constants, not options
    eva = True              # a window leaf and a summary leaf a cache layer
    norm_add_unit_offset = True
    fp32_skip_add = True
    has_windows = True      # S > 1 is a prefill from position 0; what
    #                         addresses cache rows by position refuses it

    def __post_init__(self):
        if self.attention_class != "eva":
            raise EvabyteConfigError(
                f"attention_class {self.attention_class!r}: EVA only")
        if self.num_chunks is not None:
            raise EvabyteConfigError(
                "num_chunks (a fixed number of chunks of a growing size) "
                "is not built: chunks are chunk_size keys")
        if self.rope_scaling is not None:
            raise EvabyteConfigError("rope scaling is not built")
        if self.num_key_value_heads != self.num_attention_heads:
            raise EvabyteConfigError(
                "EVA pools keys per query head: num_key_value_heads must "
                "equal num_attention_heads")
        if self.chunk_size < 1 or self.window_size % self.chunk_size:
            raise EvabyteConfigError(
                f"window_size {self.window_size} is not whole chunks of "
                f"chunk_size {self.chunk_size}")

    def cache_len(self, bi: int, max_len: int) -> int:
        """Rows of buffer ``bi`` of the carry: an even one is a layer's
        window leaf (exact positions, reset at a window's end), an odd one
        its summary leaf (one entry a chunk of ``max_len``)."""
        if bi % 2 == 0:
            return min(max_len, self.window_size)
        return -(-max_len // self.chunk_size)


EVABYTE_TINY = EvabyteConfig(
    hidden_size=64, intermediate_size=128, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4,
    max_position_embeddings=128, window_size=8, chunk_size=2,
    num_pred_heads=2)


class _ClippedNormal(nn.initializer.Initializer):
    """N(0, 1) clipped to +-1, times ``scale``: the pooling vectors start
    within a unit score of zero, so a fresh summary is near its chunk's
    mean."""

    def __init__(self, scale: float):
        self.scale = scale

    def __call__(self, shape, dtype="float32"):
        r = jax.random.normal(rnd.split_key(), tuple(shape), jnp.float32)
        return (jnp.clip(r, -1.0, 1.0) * self.scale).astype(
            convert_dtype(dtype))


class EvabyteRMSNorm(nn.Layer):
    """RMSNorm by ``1 + w``, ``w`` born zero."""

    def __init__(self, hidden_size, epsilon):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter(
            (hidden_size,), default_initializer=nn.initializer.Constant(0.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight + 1.0, self.epsilon)


_EVA_OPS: dict = {}


def _eva_op(window: int, chunk: int):
    """The tape op over ``ops/eva.py``: summaries of the complete chunks,
    then the one softmax over window and summaries."""
    key = (window, chunk)
    if key not in _EVA_OPS:
        from paddle_tpu.ops.eva import chunk_summaries, eva_attention

        def impl(q, k, v, mu, phi):
            n = q.shape[1] // chunk * chunk
            ks, vs = chunk_summaries(jnp.swapaxes(k[:, :n], 1, 2),
                                     jnp.swapaxes(v[:, :n], 1, 2), mu, phi,
                                     chunk)
            return eva_attention(q, k, v, ks, vs, window, chunk)
        opdef = OpDef(f"eva_attention<{window},{chunk}>", impl)
        _EVA_OPS[key] = lambda *args: apply_op(opdef, args, {})
    return _EVA_OPS[key]


class EvabyteAttention(LlamaAttention):
    def __init__(self, config: EvabyteConfig):
        super().__init__(config)
        init = _ClippedNormal(config.head_dim ** -0.5)
        shape = [config.num_attention_heads, config.head_dim]
        self.adaptive_mu_k = self.create_parameter(
            shape, default_initializer=init)
        self.adaptive_phi = self.create_parameter(
            shape, default_initializer=init)

    def forward(self, hidden, cos, sin, attn_mask=None):
        if attn_mask is not None:
            raise EvabyteConfigError(
                "EVA's visibility is its own: no attn_mask")
        cfg = self.config
        B, S, _ = hidden.shape
        H, D = cfg.num_attention_heads, cfg.head_dim
        rope = op_api("rope")
        q = rope(self.q_proj(hidden).reshape([B, S, H, D]),
                 Tensor(cos), Tensor(sin))
        k = rope(self.k_proj(hidden).reshape([B, S, H, D]),
                 Tensor(cos), Tensor(sin))
        v = self.v_proj(hidden).reshape([B, S, H, D])
        out = _eva_op(cfg.window_size, cfg.chunk_size)(
            q, k, v, self.adaptive_mu_k, self.adaptive_phi)
        return self.o_proj(out.reshape([B, S, H * D]))


class EvabyteDecoderLayer(nn.Layer):
    def __init__(self, config: EvabyteConfig):
        super().__init__()
        self.config = config
        self.input_layernorm = EvabyteRMSNorm(config.hidden_size,
                                              config.rms_norm_eps)
        self.self_attn = EvabyteAttention(config)
        self.post_attention_layernorm = EvabyteRMSNorm(config.hidden_size,
                                                       config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, hidden, cos, sin, attn_mask=None):
        # the stream is float32 (fp32_skip_add); each sub-layer reads it
        # in the compute dtype
        dt = self.config.dtype
        a = self.self_attn(self.input_layernorm(hidden.astype(dt)), cos,
                           sin, attn_mask)
        hidden = hidden + a.astype("float32")
        m = self.mlp(self.post_attention_layernorm(hidden.astype(dt)))
        hidden = hidden + m.astype("float32")
        return _constrain(hidden, ("dp", "sep", None))


class EvabyteModel(nn.Layer):
    def __init__(self, config: EvabyteConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([EvabyteDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = EvabyteRMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None):
        cfg = self.config
        cos, sin = _rope_tables(input_ids.shape[1], cfg.head_dim,
                                cfg.rope_theta, jnp.dtype(cfg.dtype))
        hidden = _constrain(self.embed_tokens(input_ids),
                            ("dp", "sep", None)).astype("float32")
        for layer in self.layers:
            hidden = layer(hidden, cos, sin, attn_mask)
        return self.norm(hidden.astype(cfg.dtype))


class EvabyteForCausalLM(LlamaForCausalLM):
    """The Llama loss and ``generate`` over an ``EvabyteModel``; the head
    holds ``num_pred_heads`` vocabularies and the logits are float32."""

    model_class = EvabyteModel

    def __init__(self, config: EvabyteConfig):
        with param_dtype(config.dtype):
            super().__init__(config)
            self.lm_head = nn.Linear(
                config.hidden_size,
                config.num_pred_heads * config.vocab_size, bias_attr=False)

    def forward(self, input_ids, attn_mask=None):
        return self.lm_head(self.model(input_ids, attn_mask)).astype(
            "float32")

    def loss(self, input_ids, labels):
        """Next-byte cross-entropy, over head 0."""
        V = self.config.vocab_size
        logits = self(input_ids)[..., :V]
        return F.cross_entropy(logits.reshape([-1, V]), labels.reshape([-1]))
