"""Llama-family causal LM — the flagship model.

Capability analog of the reference's hybrid-parallel Llama configs
(test/auto_parallel/hybrid_strategy/, PaddleNLP-style modeling): RMSNorm +
RoPE + GQA attention + SwiGLU MLP, with tensor/sequence parallelism
expressed TPU-natively as GSPMD sharding annotations instead of
ColumnParallelLinear/RowParallelLinear comm layers
(fleet/layers/mpu/mp_layers.py:334,:541) — XLA inserts the
allgather/reduce-scatter that Megatron-style code issues by hand.

The module doubles as the benchmark workload (`bench.py`) and the driver
entry (`__graft_entry__.py`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.parallel import (
    ProcessMesh, Replicate, Shard, get_mesh, placements_to_spec,
)

__all__ = ["LlamaConfig", "LlamaForCausalLM", "LlamaModel", "llama_tp_plan",
           "TINY_CONFIG", "LLAMA_7B_CONFIG"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    # passes over the layer list: one here, a field of a looped model's
    # config (models/ouro.py)
    total_ut_steps = 1
    # what the embedding's rows are multiplied by, and whether any layer
    # is windowed: properties of a config that has either (models/afmoe.py)
    embedding_scale = 1.0
    has_windows = False
    routed = False      # whether any feed-forward is routed over experts
    # a routed feed-forward's group limit (ops/moe.py:sigmoid_topk_route):
    # one group, no limit, here and in every config that does not say
    n_group = 1
    topk_group = 1
    # what a cache layer holds: keys and values per KV head here; a config
    # whose attention is multi-head LATENT attention (models/axk1.py) says
    # so — a cache layer is then a compressed latent and one rotated key
    # part shared by all heads — and rotates interleaved pairs
    latent = False
    rope_interleaved = False
    # what a layer's attention is over its cache: a softmax over one buffer
    # of keys (and one of values) by position here; a config whose
    # attention is EVA (models/evabyte.py) says so — a cache layer is then
    # a window leaf and a summary leaf, two buffers — and says beside it
    # that the residual stream is float32 and a norm multiplies by 1 + w
    eva = False
    fp32_skip_add = False
    norm_add_unit_offset = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def cache_leaves(self) -> int:
        """Buffers a cache layer holds in the carry's ``kc`` (and as many
        in ``vc``): one, or EVA's window leaf and summary leaf, the
        layer's buffers one after the other."""
        return 2 if self.eva else 1

    @property
    def num_cache_layers(self) -> int:
        """KV buffers a decoder holds: each pass over the layers keeps
        its own keys and values."""
        return self.num_hidden_layers * self.total_ut_steps

    # what a layer's attention is, by its index: every layer of this family
    # rotates its queries and keys and attends over all of the past; a
    # config with layer kinds (models/afmoe.py) answers per layer
    def layer_window(self, li: int) -> Optional[int]:
        """Positions a query of layer ``li`` sees, itself included, or
        None for all of the past."""
        return None

    def layer_rope(self, li: int) -> bool:
        return True

    @property
    def cache_head_major(self) -> bool:
        """The layout of a cache buffer: head-major ``(B, KV, L, D)``,
        whose blocks of positions are contiguous tiles for the decode
        attention kernel, which reads only a row's live positions — under
        GQA and under MHA alike (``rep = H // KV`` query heads a KV head,
        1 for MHA). Which program reads the cache is the routing's
        (``generate._decode_kernels``, each kernel's ``supported``), not
        the layout's."""
        return True

    def rope_tables(self, max_len: int, dtype):
        """cos / sin ``(max_len, rotated width / 2)`` of positions ``[0,
        max_len)``, which the decoder precomputes once: the width a head
        rotates and the frequencies are the config's."""
        return _rope_tables(max_len, self.head_dim, self.rope_theta, dtype)

    def cache_len(self, ci: int, max_len: int) -> int:
        """Positions cache layer ``ci`` holds in a decoder of ``max_len``:
        a windowed layer keeps a rolling buffer of its window."""
        w = self.layer_window(ci % self.num_hidden_layers)
        return max_len if w is None else min(max_len, w)


TINY_CONFIG = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, max_position_embeddings=128)

LLAMA_7B_CONFIG = LlamaConfig()  # Llama-2-7B dims (chip_smoke.py's width)


def _rope_tables(seq_len: int, head_dim: int, theta: float, dtype, offset=0):
    """cos/sin tables for positions ``offset + [0..seq_len)``; offset may be
    a traced scalar (KV-cache decode)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = offset + jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)            # (S, D/2)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


from paddle_tpu.ops.registry import register_op


@register_op("rope", ref="paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu (capability analog)")
def _rope_op(x, cos, sin):
    """Rotate (B, S, H, D) by position tables (S, D/2). Interleaved halves
    (Llama convention: split at D/2, not even/odd). Left to XLA, which
    fuses it into its neighbours."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _constrain(x: Tensor, spec_entries) -> Tensor:
    """Annotate activation sharding if a mesh is active (GSPMD's
    with_sharding_constraint = the reference's implicit activation
    dist_attr propagation). No-op off-mesh, so the model runs anywhere."""
    mesh = get_mesh()
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    names = set(mesh.dim_names)
    entries = [e if (e in names if isinstance(e, str) else False) else None
               for e in spec_entries]
    if not any(entries):
        return x
    from paddle_tpu.ops.registry import OpDef, apply_op
    ns = NamedSharding(mesh.jax_mesh, P(*entries))
    opdef = OpDef("sharding_constraint",
                  lambda v: jax.lax.with_sharding_constraint(v, ns))
    return apply_op(opdef, (x,), {})


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, kv = config.num_attention_heads, config.num_key_value_heads
        d = config.head_dim
        self.q_proj = nn.Linear(config.hidden_size, h * d, bias_attr=False)
        self.k_proj = nn.Linear(config.hidden_size, kv * d, bias_attr=False)
        self.v_proj = nn.Linear(config.hidden_size, kv * d, bias_attr=False)
        self.o_proj = nn.Linear(h * d, config.hidden_size, bias_attr=False)

    def forward(self, hidden, cos, sin, attn_mask=None):
        cfg = self.config
        B, S, _ = hidden.shape
        q = self.q_proj(hidden).reshape([B, S, cfg.num_attention_heads, cfg.head_dim])
        k = self.k_proj(hidden).reshape([B, S, cfg.num_key_value_heads, cfg.head_dim])
        v = self.v_proj(hidden).reshape([B, S, cfg.num_key_value_heads, cfg.head_dim])
        # heads are the tp-sharded axis ('mp'); batch rides 'dp'
        q = _constrain(q, ("dp", None, "mp", None))
        k = _constrain(k, ("dp", None, "mp", None))
        v = _constrain(v, ("dp", None, "mp", None))
        from paddle_tpu.ops.registry import op_api
        rope = op_api("rope")
        q = rope(q, Tensor(cos), Tensor(sin))
        k = rope(k, Tensor(cos), Tensor(sin))
        rep = cfg.num_attention_heads // cfg.num_key_value_heads
        if rep > 1:
            k = paddle.repeat_interleave(k, rep, axis=2)
            v = paddle.repeat_interleave(v, rep, axis=2)
        mesh = get_mesh()
        from paddle_tpu.flags import flags
        if (attn_mask is None and mesh is not None and flags.use_ring_attention
                and "sep" in mesh.dim_names and mesh.dim_size("sep") > 1
                and S % mesh.dim_size("sep") == 0):
            # context parallelism: blockwise ring attention over the sep axis
            from paddle_tpu.parallel.ring_attention import ring_attention
            out = ring_attention(q, k, v, mesh, axis="sep", causal=True)
        else:
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                 is_causal=True,
                                                 training=self.training)
        out = out.reshape([B, S, cfg.num_attention_heads * cfg.head_dim])
        return self.o_proj(out)


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig, width: Optional[int] = None):
        """SwiGLU of ``width`` (the config's ``intermediate_size``)."""
        super().__init__()
        width = config.intermediate_size if width is None else width
        self.gate_proj = nn.Linear(config.hidden_size, width, bias_attr=False)
        self.up_proj = nn.Linear(config.hidden_size, width, bias_attr=False)
        self.down_proj = nn.Linear(width, config.hidden_size, bias_attr=False)

    def forward(self, x):
        a = _constrain(F.silu(self.gate_proj(x)) * self.up_proj(x),
                       ("dp", None, "mp"))
        return self.down_proj(a)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, hidden, cos, sin, attn_mask=None):
        hidden = hidden + self.self_attn(self.input_layernorm(hidden), cos, sin, attn_mask)
        hidden = hidden + self.mlp(self.post_attention_layernorm(hidden))
        # sequence parallelism: between blocks activations shard S over 'sep'
        return _constrain(hidden, ("dp", "sep", None))


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None):
        cfg = self.config
        S = input_ids.shape[1]
        dt = jnp.dtype(cfg.dtype)
        cos, sin = _rope_tables(S, cfg.head_dim, cfg.rope_theta, dt)
        hidden = self.embed_tokens(input_ids)
        hidden = _constrain(hidden, ("dp", "sep", None))
        for layer in self.layers:
            hidden = layer(hidden, cos, sin, attn_mask)
        return self.norm(hidden)


class LlamaForCausalLM(nn.Layer):
    model_class = LlamaModel     # the trunk under the head (see ouro.py)

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = self.model_class(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias_attr=False)

    def _head_weight(self):
        """The (H, V) lm-head matrix — single source for forward and the
        fused loss (tied: transposed embedding; untied: lm_head weight)."""
        if self.lm_head is None:
            return self.model.embed_tokens.weight.t()
        return self.lm_head.weight

    def forward(self, input_ids, attn_mask=None):
        hidden = self.model(input_ids, attn_mask)
        if self.lm_head is None:
            return paddle.matmul(hidden, self._head_weight())
        return self.lm_head(hidden)

    def loss(self, input_ids, labels):
        from paddle_tpu.flags import flags
        V = self.config.vocab_size
        if flags.use_fused_lm_ce and V >= 4096:
            # chunked-vocab fused head+CE: never materializes the (T, V)
            # logits (the largest activation of the step — shared routing
            # in ops/fused_ce.py; phi cross_entropy_with_softmax analog)
            from paddle_tpu.ops.fused_ce import fused_lm_loss
            return fused_lm_loss(self.model(input_ids),
                                 self._head_weight(), labels)
        logits = self(input_ids)
        return F.cross_entropy(logits.reshape([-1, V]), labels.reshape([-1]))

    def generate(self, input_ids, max_new_tokens: int = 32,
                 max_len: Optional[int] = None,
                 decode_strategy: str = "greedy_search", **kwargs):
        """Decode with the compile-once KV-cache engine (GenerationMixin
        surface; inference/generate.py). The decoder is cached on the
        model, so repeated calls reuse the compiled executables.
        ``draft_model=`` (a smaller LlamaForCausalLM or 'skip:N') plus
        ``num_speculative_tokens=`` run the speculative one-dispatch
        decode; the cache is sized with K slots of slack (speculative
        rounds can overshoot the budget by up to K positions).
        decode_strategy='beam_search' routes to the no-cache beam decoder
        (nn/generation.py — the cached engine is greedy/sampling-only)."""
        import numpy as np
        from paddle_tpu.inference.generate import LlamaDecoder
        if decode_strategy not in ("greedy_search", "sampling",
                                   "beam_search"):
            raise ValueError(f"unknown decode_strategy {decode_strategy!r}")
        need = int(np.asarray(input_ids).shape[1]) + max_new_tokens
        if kwargs.get("draft_model") is not None:
            k = kwargs.get("num_speculative_tokens")
            if k is None:
                from paddle_tpu.flags import flags as _flags
                k = _flags.decode_speculative_tokens
            need += int(k)
        if max_len is not None and max_len < need:
            raise ValueError(f"max_len {max_len} < prompt + new tokens "
                             f"({need})")
        if decode_strategy == "beam_search":
            from paddle_tpu.nn.generation import beam_search
            return beam_search(self, input_ids,
                               max_new_tokens=max_new_tokens, **kwargs)
        if decode_strategy == "sampling":
            kwargs.setdefault("do_sample", True)
        ml = max(64, need) if max_len is None else max_len
        # mesh= routes the decode through the GSPMD tensor-parallel
        # decoder (inference/sharding.py); the mesh topology is part of
        # the decoder cache key — switching meshes rebuilds
        mesh = kwargs.pop("mesh", None)
        mesh_key = None
        if mesh is not None:
            from paddle_tpu.inference.sharding import DecodeSharding
            if not isinstance(mesh, DecodeSharding):
                mesh = DecodeSharding(mesh)
            mesh_key = tuple(sorted(mesh.axes.items()))
        # quant= picks the decode dtype recipe (int8w weight-only /
        # int8wk weights+KV; quantization/kv_cache) — part of the
        # decoder cache key: switching recipes rebuilds
        from paddle_tpu.quantization.kv_cache import resolve_decode_quant
        quant = resolve_decode_quant(kwargs.pop("quant", None))
        # the decoder snapshots weights: rebuild when any param buffer has
        # been swapped since (optimizer step / set_state_dict)
        version = (tuple(id(p._value) for p in self.parameters()),
                   mesh_key, quant)
        dec = self.__dict__.get("_decoder")
        if (dec is None or dec.max_len < need
                or self.__dict__.get("_decoder_version") != version):
            dec = LlamaDecoder(self, max_len=ml, mesh=mesh, quant=quant)
            self.__dict__["_decoder"] = dec
            self.__dict__["_decoder_version"] = version
        return dec.generate(input_ids, max_new_tokens=max_new_tokens,
                            **kwargs)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len: int) -> float:
        """Train-step FLOPs per token: 6N matmul (fwd+bwd) plus the
        attention score/value term 12·L·H·S (PaLM appendix-B accounting)."""
        cfg = self.config
        return (6 * self.num_params()
                + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq_len)


def llama_tp_plan(model: LlamaForCausalLM, mesh: ProcessMesh) -> Dict[str, Sequence]:
    """Megatron-parity tensor-parallel plan as placements per param name.

    Column-parallel (shard output dim=1 of (in,out) weights): q/k/v, gate/up.
    Row-parallel (shard input dim=0): o_proj, down_proj.
    Vocab-parallel embedding: shard vocab dim 0; lm_head shard output.
    Norm weights replicate. Reference layers being replaced:
    fleet/layers/mpu/mp_layers.py:47 (VocabParallelEmbedding), :334
    (ColumnParallelLinear), :541 (RowParallelLinear).
    """
    mp_axis = mesh.dim_names.index("mp") if "mp" in mesh.dim_names else None
    plan: Dict[str, Sequence] = {}
    for name, _p in model.named_parameters():
        pls = [Replicate()] * mesh.ndim
        if mp_axis is not None:
            if any(k in name for k in ("q_proj", "k_proj", "v_proj",
                                       "gate_proj", "up_proj")) and name.endswith("weight"):
                pls[mp_axis] = Shard(1)
            elif any(k in name for k in ("o_proj", "down_proj")) and name.endswith("weight"):
                pls[mp_axis] = Shard(0)
            elif "embed_tokens" in name:
                pls[mp_axis] = Shard(0)
            elif "lm_head" in name and name.endswith("weight"):
                pls[mp_axis] = Shard(1)
        plan[name] = pls
    return plan
