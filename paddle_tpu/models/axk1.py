"""A.X-K1 causal LM (SK Telecom, ``model_type: axk1``): DeepSeek-V3's
block — multi-head latent attention (MLA) and sigmoid-routed experts with
a group limit — at its own widths.

The block (``config.json`` keys in brackets), for one token ``x``:

- attention is MLA. The query goes through a low-rank bottleneck:
  ``q = RMSNorm(x W_qa) W_qb`` [``q_lora_rank``], per head a part that is
  not rotated and a rotated one [``qk_nope_head_dim``,
  ``qk_rope_head_dim``]. Keys and values come from ONE compressed latent
  and one rotated key part shared by every head: ``[c_kv ; k_pe] = x
  W_kva``, ``c_kv = RMSNorm(c_kv)`` [``kv_lora_rank``], and per head
  ``[k_nope ; v] = c_kv W_kvb`` [``v_head_dim``]. RoPE is YaRN
  [``rope_scaling``] over interleaved pairs; the softmax scale is
  ``(qk_nope + qk_rope)^-0.5 * mscale^2``;
- the first ``first_k_dense_replace`` feed-forwards are SwiGLU of
  ``intermediate_size``; every later one routes: sigmoid scores in float32
  over ``n_routed_experts``, the ``topk_group`` best of ``n_group`` groups
  kept (a group scores the sum of its two best), the top
  ``num_experts_per_tok`` of the kept experts chosen, their scores
  normalised over the chosen [``norm_topk_prob``] and scaled
  [``routed_scaling_factor``], plus ``n_shared_experts`` shared SwiGLU
  experts every token passes (``models/afmoe.py:AfmoeMoE``, whose router
  the group limit reaches through ``ops/moe.py``).

Served by ``LlamaDecoder``: the config says the attention is latent
(``latent``), and the decoder's cache layer holds the normed latent and
the rotated key part instead of keys and values per head — decode
attends in latent space (the ABSORBED form), a prefill from position 0
expands per-head keys and values and runs flash (the EXPANDED form):
``inference/generate.py:_latent_attention``. The eager model here runs the
expanded form through XLA.

THE SHARE is AFMoE's (``experts_held`` from ``expert_offset``); the
default holds all. Parameters are born in ``config.dtype``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

import paddle_tpu.nn as nn
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models.afmoe import AfmoeMoE
from paddle_tpu.models.llama import (
    LlamaConfig, LlamaForCausalLM, LlamaMLP, _constrain,
)
from paddle_tpu.nn.layer_base import param_dtype
from paddle_tpu.ops.registry import OpDef, apply_op

__all__ = ["AxK1Config", "AxK1ConfigError", "AxK1ForCausalLM", "AxK1Model",
           "AXK1_TINY", "yarn_inv_freq", "yarn_mscale"]


class AxK1ConfigError(ValueError):
    """A key of the published config this program does not build."""


def _yarn():
    return {"type": "yarn", "factor": 32, "original_max_position_embeddings":
            4096, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
            "mscale_all_dim": 1}


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention factor (``yarn_get_mscale``)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict):
    """The ``dim / 2`` rotation frequencies of YaRN: between the
    correction dimensions of ``beta_fast`` and ``beta_slow`` rotations
    over ``original_max_position_embeddings`` a linear ramp blends the
    base frequencies into the base over ``factor``."""
    def corr(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    base = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * base + ramp * base / rs["factor"]


@dataclass
class AxK1Config(LlamaConfig):
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    num_hidden_layers: int = 61
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 192
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "none"
    rope_scaling: Optional[dict] = field(default_factory=_yarn)
    experts_held: Optional[int] = None  # None: all of n_routed_experts
    expert_offset: int = 0

    # what the program builds one way only: class constants, not options
    latent = True
    rope_interleaved = True

    def __post_init__(self):
        if self.scoring_func != "sigmoid":
            raise AxK1ConfigError(
                f"scoring_func {self.scoring_func!r}: the router is "
                f"sigmoid only")
        # "none": no selection-correction bias, which with a zero bias is
        # DeepSeek-V3's noaux_tc selection
        if self.topk_method not in ("none", "noaux_tc"):
            raise AxK1ConfigError(f"topk_method {self.topk_method!r}")
        if self.q_lora_rank is None or self.moe_layer_freq != 1:
            raise AxK1ConfigError(
                "a query without its low-rank bottleneck, or routed layers "
                "that are not every layer after the dense ones, are not "
                "built")
        rs = self.rope_scaling
        if rs is not None and rs.get("type", rs.get("rope_type")) != "yarn":
            raise AxK1ConfigError(f"rope_scaling {rs}: YaRN only")
        if self.n_routed_experts % self.n_group or not \
                0 < self.topk_group <= self.n_group:
            raise AxK1ConfigError(
                f"{self.n_routed_experts} experts in {self.n_group} groups "
                f"with {self.topk_group} kept")
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts - self.expert_offset
        if not 0 < self.experts_held <= \
                self.n_routed_experts - self.expert_offset \
                or self.expert_offset < 0:
            raise AxK1ConfigError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.experts_held}) are not among the "
                f"{self.n_routed_experts}")

    # the names the decoder and AfmoeMoE read
    @property
    def head_dim(self) -> int:
        """A query's and a key's width a head (the rotated part too)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def num_shared_experts(self) -> int:
        return self.n_shared_experts

    @property
    def route_norm(self) -> bool:
        return self.norm_topk_prob

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor

    @property
    def routed(self) -> bool:
        return self.first_k_dense_replace < self.num_hidden_layers

    @property
    def rope_cache_width(self) -> int:
        """Width of the rope key's cache leaf: ``qk_rope_head_dim`` zero-
        padded to a whole lane tile of 128, which a narrower leaf takes in
        HBM's tiles anyway; the token-row write and the decode kernel then
        take it as it is."""
        return -(-self.qk_rope_head_dim // 128) * 128

    @property
    def softmax_scale(self) -> float:
        rs = self.rope_scaling
        s = self.head_dim ** -0.5
        if rs is not None and rs.get("mscale_all_dim"):
            m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
            s *= m * m
        return s

    def rope_tables(self, max_len: int, dtype):
        d = self.qk_rope_head_dim
        rs = self.rope_scaling
        if rs is None:
            inv = 1.0 / self.rope_theta ** (
                jnp.arange(0, d, 2, dtype=jnp.float32) / d)
            ms = 1.0
        else:
            inv = yarn_inv_freq(d, self.rope_theta, rs)
            ms = (yarn_mscale(rs["factor"], rs.get("mscale", 1))
                  / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))
        ang = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), inv)
        return ((jnp.cos(ang) * ms).astype(dtype),
                (jnp.sin(ang) * ms).astype(dtype))


AXK1_TINY = AxK1Config(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    max_position_embeddings=256, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    moe_intermediate_size=16, n_routed_experts=16, num_experts_per_tok=4,
    n_group=4, topk_group=2,
    rope_scaling={"type": "yarn", "factor": 4,
                  "original_max_position_embeddings": 32, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1})


def _mla_expanded(x, wqa, qan, wqb, wkva, kvan, wkvb, wo, cos, sin, *, cfg):
    """The eager MLA block's attention over x (B, S, hidden): queries,
    the latent, per-head keys and values expanded from it, causal
    softmax. Plain jnp, the decoder's expanded form."""
    B, S, _ = x.shape
    H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    R, eps = cfg.kv_lora_rank, cfg.rms_norm_eps

    def rms(t, w):
        var = jnp.mean(jnp.square(t.astype(jnp.float32)), -1, keepdims=True)
        return (t.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
                ).astype(t.dtype) * w

    def rot(t):                          # interleaved pairs, out as halves
        c, s = cos[None, :, None, :], sin[None, :, None, :]
        t1, t2 = t[..., 0::2], t[..., 1::2]
        return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], -1)

    q = (rms(x @ wqa, qan) @ wqb).reshape(B, S, H, dn + dr)
    a = x @ wkva
    ckv, kpe = rms(a[..., :R], kvan), rot(a[..., None, R:])
    kv = (ckv @ wkvb).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(kpe, (B, S, H, dr))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        * cfg.softmax_scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, -1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:])
    return o.reshape(B, S, H * dv) @ wo


class AxK1Attention(nn.Layer):
    def __init__(self, config: AxK1Config):
        super().__init__()
        self.config = config
        c = config
        H = c.num_attention_heads
        self.q_a_proj = nn.Linear(c.hidden_size, c.q_lora_rank,
                                  bias_attr=False)
        self.q_a_layernorm = nn.RMSNorm(c.q_lora_rank, epsilon=c.rms_norm_eps)
        self.q_b_proj = nn.Linear(c.q_lora_rank, H * c.head_dim,
                                  bias_attr=False)
        self.kv_a_proj_with_mqa = nn.Linear(
            c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim,
            bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(c.kv_lora_rank,
                                         epsilon=c.rms_norm_eps)
        self.kv_b_proj = nn.Linear(
            c.kv_lora_rank, H * (c.qk_nope_head_dim + c.v_head_dim),
            bias_attr=False)
        self.o_proj = nn.Linear(H * c.v_head_dim, c.hidden_size,
                                bias_attr=False)
        self._op = OpDef("axk1_mla_attention",
                         lambda *a: _mla_expanded(*a, cfg=config))

    def forward(self, hidden, cos, sin):
        return apply_op(self._op, (
            hidden, self.q_a_proj.weight, self.q_a_layernorm.weight,
            self.q_b_proj.weight, self.kv_a_proj_with_mqa.weight,
            self.kv_a_layernorm.weight, self.kv_b_proj.weight,
            self.o_proj.weight, Tensor(cos), Tensor(sin)), {})


class AxK1DecoderLayer(nn.Layer):
    def __init__(self, config: AxK1Config, li: int):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.self_attn = AxK1Attention(config)
        self.post_attention_layernorm = nn.RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config) if li < config.num_dense_layers \
            else AfmoeMoE(config)

    def forward(self, hidden, cos, sin):
        hidden = hidden + self.self_attn(self.input_layernorm(hidden), cos,
                                         sin)
        hidden = hidden + self.mlp(self.post_attention_layernorm(hidden))
        return _constrain(hidden, ("dp", "sep", None))


class AxK1Model(nn.Layer):
    def __init__(self, config: AxK1Config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([AxK1DecoderLayer(config, li)
                                    for li in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None):
        if attn_mask is not None:
            raise AxK1ConfigError("the eager A.X-K1 model is causal only")
        cfg = self.config
        cos, sin = cfg.rope_tables(input_ids.shape[1], jnp.dtype(cfg.dtype))
        hidden = _constrain(self.embed_tokens(input_ids),
                            ("dp", "sep", None))
        for layer in self.layers:
            hidden = layer(hidden, cos, sin)
        return self.norm(hidden)


class AxK1ForCausalLM(LlamaForCausalLM):
    """The Llama head, loss and ``generate`` over an ``AxK1Model``, its
    parameters born in ``config.dtype``."""

    model_class = AxK1Model

    def __init__(self, config: AxK1Config):
        with param_dtype(config.dtype):
            super().__init__(config)
