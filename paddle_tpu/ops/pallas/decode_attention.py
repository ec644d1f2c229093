"""Decode (single-token) cache attention — Pallas TPU kernel.

Capability analog of the reference's block_multi_head_attention
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu): at
decode time attention is a bandwidth-bound read of the KV cache. The XLA
path runs ~6 ops per layer (scores einsum, mask, softmax, weighted sum,
plus GQA head repeats that MATERIALIZE the cache rep x); this kernel does
the whole thing in one pass:

- grid (B, KV-heads, L-blocks); the ``rep`` query heads sharing a KV head
  ride one program (GQA without materializing repeated K/V),
- online-softmax accumulation across cache blocks in VMEM scratch,
- a dynamic length bound (``pos``, SMEM scalars — a traced scalar for
  the classic lockstep decode, or a PER-ROW ``(B,)`` vector for the
  chunked/speculative paths where rows sit at different cache offsets):
  blocks past a row's valid prefix skip their compute (``pl.when``), so
  padded cache tails cost DMA only, and masked positions never enter
  the softmax,
- optional int8 cache tiles (the ``int8wk`` decode recipe): K/V stream
  int8 from HBM and dequantize IN VMEM against their per-row scales
  (``k_scale``/``v_scale``, the cache's ``(..., 1)`` scale buffers) —
  the same dequant-inside-the-tile discipline as int8_matmul, so the
  quantized cache's bandwidth win survives into the kernel.

Layouts: q (B, H, D) one token per sequence; kc/vc (B, KV, L, D) padded
cache (head-major, so cache blocks are contiguous (L, D) tiles), f32/bf16
or int8 with (B, KV, L, 1) scales; out (B, H, D). Inference-path only
(no custom VJP).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _routing

__all__ = ["supported", "decode_attention"]


def supported(q, kc) -> bool:
    if q.ndim != 3 or kc.ndim != 4:
        return False
    B, H, D = q.shape
    _, KV, L, _ = kc.shape
    return H % KV == 0 and D % 8 == 0 and L % 128 == 0


def _kernel(pos_ref, q_ref, k_ref, v_ref, *rest, scale, bl, nl, rep, quant):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n_valid = pos_ref[b]                           # THIS row's valid length

    @pl.when(li * bl < n_valid)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)        # (rep, D)
        k = k_ref[0, 0].astype(jnp.float32)        # (bl, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            # dequant in VMEM: int8 rows times their per-row scales —
            # the cache streamed int8 all the way from HBM
            k = k * ks_ref[0, 0].astype(jnp.float32)     # (bl, 1)
            v = v * vs_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        idx = li * bl + jax.lax.broadcasted_iota(jnp.int32, (rep, bl), 1)
        s = jnp.where(idx < n_valid, s, -jnp.inf)
        m_prev = m_scr[:, :1]                      # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, :1] = corr * l_scr[:, :1] + jnp.sum(p, axis=1,
                                                     keepdims=True)
        m_scr[:, :1] = m_new
        acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(li == nl - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_l",))
def decode_attention(q, kc, vc, pos, block_l: int = 256,
                     k_scale=None, v_scale=None):
    """q (B, H, D) x cache (B, KV, L, D), valid length ``pos`` (traced
    scalar, or a per-row ``(B,)`` vector when rows sit at different
    cache offsets; positions >= the row's bound are masked) -> (B, H, D).
    Int8 caches pass their per-row scale buffers via
    ``k_scale``/``v_scale`` ((B, KV, L, 1) f32) and dequantize inside
    the tile."""
    B, H, D = q.shape
    _, KV, L, _ = kc.shape
    rep = H // KV
    bl = min(block_l, L)
    while L % bl:
        bl //= 2
    nl = L // bl
    scale = 1.0 / math.sqrt(D)
    q4 = q.reshape(B, KV, rep, D)
    quant = k_scale is not None
    out_dtype = q.dtype
    pos_b = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, rep, D), lambda b, g, l: (b, g, 0, 0)),
        pl.BlockSpec((1, 1, bl, D), lambda b, g, l: (b, g, l, 0)),
        pl.BlockSpec((1, 1, bl, D), lambda b, g, l: (b, g, l, 0)),
    ]
    args = [pos_b, q4, kc, vc]
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, bl, 1), lambda b, g, l: (b, g, l, 0)),
            pl.BlockSpec((1, 1, bl, 1), lambda b, g, l: (b, g, l, 0)),
        ]
        args += [k_scale, v_scale]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bl=bl, nl=nl, rep=rep,
                          quant=quant),
        grid=(B, KV, nl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rep, D), lambda b, g, l: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, D), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, 128), jnp.float32),
            pltpu.VMEM((rep, 128), jnp.float32),
            pltpu.VMEM((rep, D), jnp.float32),
        ],
        interpret=_routing.use_interpret(),
        name="decode_attention",
    )(*args)
    return out.reshape(B, H, D)
