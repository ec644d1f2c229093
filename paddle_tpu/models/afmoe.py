"""AFMoE causal LM (Arcee Trinity: ``model_type: afmoe``).

The Llama block with five departures (``modeling_afmoe.py`` beside the
published ``config.json``):

- attention: per-head RMSNorm on queries and keys, an output gate
  (``(o * sigmoid(x Wg)) Wo``), and LAYER KINDS — a ``sliding_attention``
  layer rotates its queries and keys (RoPE) and sees the last
  ``sliding_window`` positions, itself included; a ``full_attention`` layer
  rotates nothing (NoPE) and sees all of the past;
- a SANDWICH of norms, Ouro's: one before each sub-layer and one on its
  output before it joins the residual stream (the parameter names are
  ``models/ouro.py``'s, which is what ``LlamaDecoder`` reads);
- the first ``num_dense_layers`` feed-forwards are SwiGLU of
  ``intermediate_size``; the others a ROUTED feed-forward: sigmoid scores
  in float32 over ``num_experts``, the top ``num_experts_per_tok`` of
  score + ``expert_bias`` chosen, their scores normalised over the chosen
  and scaled by ``route_scale``, plus ``num_shared_experts`` shared SwiGLU
  experts every token passes; every expert of width
  ``moe_intermediate_size``;
- the embedding is scaled by ``sqrt(hidden_size)`` (``mup_enabled``);
- ``head_dim`` is the config's own, not ``hidden_size / heads``.

THE SHARE. ``num_experts`` is the router's width, what the model has.
``experts_held`` of them, from ``expert_offset``, are this model's: one
chip's share of an expert-parallel layer. The router scores all, the
weights are normalised over the chosen whether or not they are held, and
only the held experts' part is computed (``ops/moe.py:routed_ffn``);
nothing stands in for the absent chips or their exchange. The default
holds all, which is the whole model.

Parameters are created in ``config.dtype`` (``nn.layer_base.param_dtype``):
a share of the published model does not fit the chip in float32 first. The
routed experts are two stacked leaves a layer, ``(held, H, 2F)`` gate|up
and ``(held, F, H)`` down, in the layout ``LlamaDecoder`` reads, so the
decoder takes them by reference.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models.llama import (
    LlamaAttention, LlamaConfig, LlamaForCausalLM, LlamaMLP, _constrain,
    _rope_tables,
)
from paddle_tpu.nn.layer_base import param_dtype
from paddle_tpu.ops.registry import OpDef, apply_op, op_api

__all__ = ["AfmoeConfig", "AfmoeConfigError", "AfmoeForCausalLM",
           "AfmoeModel", "AFMOE_TINY"]

SLIDING, FULL = "sliding_attention", "full_attention"


class AfmoeConfigError(ValueError):
    """A key of the published config this program does not build."""


@dataclass
class AfmoeConfig(LlamaConfig):
    head_dim: int = 128
    layer_types: Optional[Tuple[str, ...]] = None   # None: 3 sliding, 1 full
    sliding_window: int = 4096
    num_dense_layers: int = 6
    num_experts: int = 256              # the router's width
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    moe_intermediate_size: int = 3072
    route_scale: float = 2.448
    route_norm: bool = True
    score_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    rope_scaling: Optional[dict] = None
    mup_enabled: bool = True
    experts_held: Optional[int] = None  # None: all of num_experts
    expert_offset: int = 0

    def __post_init__(self):
        if self.score_func != "sigmoid":
            raise AfmoeConfigError(
                f"score_func {self.score_func!r}: the router is sigmoid only")
        if self.n_group != 1 or self.topk_group != 1:
            raise AfmoeConfigError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                f"group-limited routing is not built")
        if self.rope_scaling is not None:
            raise AfmoeConfigError("rope scaling is not built")
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if (i + 1) % 4 == 0 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise AfmoeConfigError(
                f"layer_types must name {self.num_hidden_layers} layers as "
                f"{SLIDING!r} or {FULL!r}, got {self.layer_types}")
        if self.experts_held is None:
            self.experts_held = self.num_experts - self.expert_offset
        if not 0 < self.experts_held <= self.num_experts - self.expert_offset \
                or self.expert_offset < 0:
            raise AfmoeConfigError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.experts_held}) are not among the {self.num_experts}")

    @property
    def embedding_scale(self) -> float:
        return math.sqrt(self.hidden_size) if self.mup_enabled else 1.0

    @property
    def routed(self) -> bool:
        return self.num_dense_layers < self.num_hidden_layers

    @property
    def has_windows(self) -> bool:
        return SLIDING in self.layer_types

    def layer_window(self, li: int) -> Optional[int]:
        return self.sliding_window if self.layer_types[li] == SLIDING \
            else None

    def layer_rope(self, li: int) -> bool:
        return self.layer_types[li] == SLIDING


AFMOE_TINY = AfmoeConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=128, rms_norm_eps=1e-5,
    layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
    sliding_window=8, num_dense_layers=1, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=32)


def band_mask(sq: int, window: int):
    """(sq, sq) bool: key j visible to query i iff 0 <= i - j < window."""
    d = jnp.arange(sq)[:, None] - jnp.arange(sq)[None, :]
    return jnp.logical_and(d >= 0, d < window)


class AfmoeAttention(LlamaAttention):
    def __init__(self, config: AfmoeConfig, li: int):
        super().__init__(config)
        self.window = config.layer_window(li)
        self.rotates = config.layer_rope(li)
        self.gate_proj = nn.Linear(
            config.hidden_size, config.num_attention_heads * config.head_dim,
            bias_attr=False)
        self.q_norm = nn.RMSNorm(config.head_dim, epsilon=config.rms_norm_eps)
        self.k_norm = nn.RMSNorm(config.head_dim, epsilon=config.rms_norm_eps)

    def forward(self, hidden, cos, sin, attn_mask=None):
        cfg = self.config
        B, S, _ = hidden.shape
        H, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        q = self.q_norm(self.q_proj(hidden).reshape([B, S, H, D]))
        k = self.k_norm(self.k_proj(hidden).reshape([B, S, KV, D]))
        v = self.v_proj(hidden).reshape([B, S, KV, D])
        if self.rotates:
            rope = op_api("rope")
            q = rope(q, Tensor(cos), Tensor(sin))
            k = rope(k, Tensor(cos), Tensor(sin))
        if H != KV:
            k = paddle.repeat_interleave(k, H // KV, axis=2)
            v = paddle.repeat_interleave(v, H // KV, axis=2)
        if self.window is not None and self.window < S:
            band = Tensor(band_mask(S, self.window))
            attn_mask = band if attn_mask is None \
                else paddle.logical_and(attn_mask, band)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=True,
            training=self.training)
        out = out.reshape([B, S, H * D]) * F.sigmoid(self.gate_proj(hidden))
        return self.o_proj(out)


_ROUTED_OPS: dict = {}


def _routed_ffn_op(top_k, route_norm, route_scale, expert_offset,
                   n_group=1, topk_group=1):
    """The tape op over ``ops/moe.py:routed_ffn`` (its output alone)."""
    key = (top_k, route_norm, route_scale, expert_offset, n_group,
           topk_group)
    if key not in _ROUTED_OPS:
        from paddle_tpu.ops.moe import routed_ffn

        def impl(x, router_w, expert_bias, w_gate_up, w_down):
            return routed_ffn(x, router_w, expert_bias, w_gate_up, w_down,
                              top_k=top_k, route_norm=route_norm,
                              route_scale=route_scale,
                              expert_offset=expert_offset, n_group=n_group,
                              topk_group=topk_group)[0]
        opdef = OpDef(f"afmoe_routed_ffn<{top_k},{expert_offset}>", impl)
        _ROUTED_OPS[key] = lambda *args: apply_op(opdef, args, {})
    return _ROUTED_OPS[key]


class AfmoeMoE(nn.Layer):
    """Router over ``num_experts`` (in ``n_group`` groups of which
    ``topk_group`` are kept: models/axk1.py), the held experts' stacks,
    the shared expert. ``expert_bias`` is a buffer that selects and does not weigh
    (trained by a bias update outside the forward pass; zero here)."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        H, F_ = config.hidden_size, config.moe_intermediate_size
        held = config.experts_held
        self.router = nn.Linear(H, config.num_experts, bias_attr=False)
        lim = math.sqrt(6.0 / (H + F_))
        init = nn.initializer.Uniform(-lim, lim)
        self.experts_gate_up = self.create_parameter(
            [held, H, 2 * F_], default_initializer=init)
        self.experts_down = self.create_parameter(
            [held, F_, H], default_initializer=init)
        self.register_buffer("expert_bias", Tensor(
            jnp.zeros((config.num_experts,), jnp.float32)))
        # the shared experts every token passes, as one SwiGLU
        self.shared_experts = LlamaMLP(
            config, F_ * config.num_shared_experts) \
            if config.num_shared_experts else None

    def forward(self, x):
        cfg = self.config
        B, S, H = x.shape
        routed = _routed_ffn_op(
            cfg.num_experts_per_tok, cfg.route_norm, cfg.route_scale,
            cfg.expert_offset, cfg.n_group, cfg.topk_group)(
            paddle.reshape(x, [B * S, H]), self.router.weight,
            self.expert_bias, self.experts_gate_up, self.experts_down)
        out = paddle.reshape(routed, [B, S, H])
        if self.shared_experts is not None:
            out = out + self.shared_experts(x)
        return out


class AfmoeDecoderLayer(nn.Layer):
    def __init__(self, config: AfmoeConfig, li: int):
        super().__init__()

        def norm():
            return nn.RMSNorm(config.hidden_size,
                              epsilon=config.rms_norm_eps)
        self.input_layernorm = norm()
        self.self_attn = AfmoeAttention(config, li)
        self.post_attention_layernorm = norm()      # before the feed-forward
        self.mlp = LlamaMLP(config) if li < config.num_dense_layers \
            else AfmoeMoE(config)
        self.input_layernorm_2 = norm()             # on the attention output
        self.post_attention_layernorm_2 = norm()    # on the feed-forward's

    def forward(self, hidden, cos, sin, attn_mask=None):
        a = self.self_attn(self.input_layernorm(hidden), cos, sin, attn_mask)
        hidden = hidden + self.input_layernorm_2(a)
        m = self.mlp(self.post_attention_layernorm(hidden))
        hidden = hidden + self.post_attention_layernorm_2(m)
        return _constrain(hidden, ("dp", "sep", None))


class AfmoeModel(nn.Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([AfmoeDecoderLayer(config, li)
                                    for li in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None):
        cfg = self.config
        cos, sin = _rope_tables(input_ids.shape[1], cfg.head_dim,
                                cfg.rope_theta, jnp.dtype(cfg.dtype))
        hidden = self.embed_tokens(input_ids)
        if cfg.embedding_scale != 1.0:
            hidden = hidden * cfg.embedding_scale
        hidden = _constrain(hidden, ("dp", "sep", None))
        for layer in self.layers:
            hidden = layer(hidden, cos, sin, attn_mask)
        return self.norm(hidden)


class AfmoeForCausalLM(LlamaForCausalLM):
    """The Llama head, loss and ``generate`` over an ``AfmoeModel``, its
    parameters born in ``config.dtype``."""

    model_class = AfmoeModel

    def __init__(self, config: AfmoeConfig):
        with param_dtype(config.dtype):
            super().__init__(config)
