"""Decode (single-token) cache attention — Pallas TPU kernel.

Capability analog of the reference's block_multi_head_attention
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu): at
decode time attention is a bandwidth-bound read of the KV cache. The XLA
path runs ~6 ops per layer (scores einsum, mask, softmax, weighted sum,
plus GQA head repeats that MATERIALIZE the cache rep x); this kernel does
the whole thing in one pass:

- grid (B, L-blocks): a grid step carries ALL KV heads of one row over one
  block of cache positions, in one batched matmul each way, and the
  ``rep`` query heads sharing a KV head ride with it (GQA without
  materializing repeated K/V). With one KV head a step the kernel was
  bound by the fixed cost of a grid step, not by bytes: 0.41 us a step
  over the 12 288 steps of a 12-layer decode step on the v5e,
- online-softmax accumulation across cache blocks in VMEM scratch,
- a dynamic length bound (``pos`` — a traced scalar for the classic
  lockstep decode, or a PER-ROW ``(B,)`` vector for the
  chunked/speculative paths where rows sit at different cache offsets)
  that the DMA follows: ``pos`` is a scalar-prefetch operand, and the K/V
  (and scale) index maps send every block past a row's valid prefix to
  that row's LAST LIVE block (``_live_block``). The pipeline issues no
  copy for a block index that repeats, so a dead block costs an empty
  grid step (about 0.3 us) and no bytes; its compute is skipped
  (``pl.when``), and masked positions of the last live block never
  enter the softmax. A live step of 1 MiB takes 1.4 us, near what HBM
  gives; at 16 rows of 475 live positions in 2048 the 12 calls of a
  decode step take 0.97 ms where they took 5.0 (PERF.md section 6,
  PR 29),
- optional int8 cache tiles (the ``int8wk`` decode recipe): K/V stream
  int8 from HBM and dequantize IN VMEM against their per-row scales
  (``k_scale``/``v_scale``, the cache's ``(..., 1)`` scale buffers) —
  the same dequant-inside-the-tile discipline as int8_matmul, so the
  quantized cache's bandwidth win survives into the kernel.

The block length is the largest one under the caller's ``block_l`` whose
pipeline buffers fit a scoped-VMEM budget (``_block_len``).

``decode_attention_pair`` is the same pass over TWO cache leaves of a
layer — a second K / V pair with a live length of its own, merged in the
one online softmax (models/evabyte.py: a window of exact positions beside
chunk summaries): the grid's position axis runs over the first leaf's
blocks and then the second's, each leaf's DMA following its own length.

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

__all__ = ["supported", "decode_attention", "supported_pair",
           "decode_attention_pair"]


# What a grid step's pipeline buffers may take of Mosaic's 16 MiB scoped
# VMEM on the v5e. Per cache position of a block and all KV heads: the K
# and V tiles, double-buffered, and for an int8 cache the two (..., 1) f32
# scale tiles, which pad to 128 lanes and are double-buffered too — the
# larger term there. AOT compiles for the v5e (KV 8 and 32, D 128 and 256,
# bf16 / f32 / int8, blocks of 128..2048) passed wherever these came to
# 12 MiB or less and were refused from 16 MiB on; the room left is the
# kernel's own temporaries.
_VMEM_BUDGET = 12 * 1024 * 1024
_BLOCK_L = 256


def _block_len(block_l: int, L: int, KV: int, D: int, itemsize: int,
               quant: bool) -> int:
    """Largest block of cache positions that divides ``L``, reached from
    ``min(block_l, L)`` by halving, whose pipeline buffers fit the VMEM
    budget. 0 = none of at least 32 positions (an int8 tile's sublanes)
    does."""
    per_pos = 2 * 2 * KV * D * itemsize
    if quant:
        per_pos += 2 * 2 * KV * 128 * 4
    bl = min(block_l, L)
    while bl >= 32:
        if L % bl == 0 and bl * per_pos <= _VMEM_BUDGET:
            return bl
        bl //= 2
    return 0


def supported(q, kc) -> bool:
    if q.ndim != 3 or kc.ndim != 4:
        return False
    B, H, D = q.shape
    _, KV, L, _ = kc.shape
    if H % KV or D % 8 or L % 128:
        return False
    return _block_len(_BLOCK_L, L, KV, D, kc.dtype.itemsize,
                      kc.dtype == jnp.int8) > 0


def _live_block(l, n_valid, bl: int):
    """Block index the pipeline fetches at L-step ``l`` of a row with
    ``n_valid`` live positions: ``l`` itself while the block holds a live
    position, else the row's last live block — the index then repeats and
    no copy is issued. Never negative (a row with nothing valid stays on
    block 0)."""
    return jnp.minimum(l, jnp.maximum(n_valid - 1, 0) // bl)


def _kernel(pos_ref, q_ref, k_ref, v_ref, *rest, scale, bl, quant):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n_valid = pos_ref[b]                           # THIS row's valid length

    @pl.when(li * bl < n_valid)
    def _block():
        q = q_ref[0].astype(jnp.float32)           # (KV, rep, D)
        k = k_ref[0].astype(jnp.float32)           # (KV, bl, D)
        v = v_ref[0].astype(jnp.float32)
        if quant:
            # dequant in VMEM: int8 rows times their per-row scales —
            # the cache streamed int8 all the way from HBM
            k = k * ks_ref[0].astype(jnp.float32)  # (KV, bl, 1)
            v = v * vs_ref[0].astype(jnp.float32)
        # all KV heads in one batched matmul each way
        s = jnp.einsum("grd,gld->grl", q, k,
                       preferred_element_type=jnp.float32) * scale
        idx = li * bl + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(idx < n_valid, s, -jnp.inf)
        m_prev = m_scr[:, :, :1]                   # (KV, rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, :, :1] = corr * l_scr[:, :, :1] + jnp.sum(
            p, axis=2, keepdims=True)
        m_scr[:, :, :1] = m_new
        acc_scr[...] = corr * acc_scr[...] + jnp.einsum(
            "grl,gld->grd", p, v, preferred_element_type=jnp.float32)

    @pl.when(li == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / l_scr[:, :, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_l",))
def decode_attention(q, kc, vc, pos, block_l: int = _BLOCK_L,
                     k_scale=None, v_scale=None):
    """q (B, H, D) x cache (B, KV, L, D), valid length ``pos`` (traced
    scalar, or a per-row ``(B,)`` vector when rows sit at different
    cache offsets; positions >= the row's bound are masked and blocks
    past it are not fetched) -> (B, H, D). ``block_l`` bounds the block
    of cache positions a grid step carries. Int8 caches pass their
    per-row scale buffers via ``k_scale``/``v_scale`` ((B, KV, L, 1)
    f32) and dequantize inside the tile."""
    B, H, D = q.shape
    _, KV, L, _ = kc.shape
    rep = H // KV
    quant = k_scale is not None
    bl = _block_len(block_l, L, KV, D, kc.dtype.itemsize, quant)
    if not bl:
        raise ValueError(f"decode_attention: no block of cache positions "
                         f"under block_l={block_l} divides L={L} and fits "
                         f"VMEM at KV={KV}, D={D} (see supported())")
    scale = 1.0 / math.sqrt(D)
    q4 = q.reshape(B, KV, rep, D)
    pos_b = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    def row(b, l, pos_ref):
        return (b, 0, 0, 0)

    def live(b, l, pos_ref):
        return (b, 0, _live_block(l, pos_ref[b], bl), 0)

    in_specs = [
        pl.BlockSpec((1, KV, rep, D), row),
        pl.BlockSpec((1, KV, bl, D), live),
        pl.BlockSpec((1, KV, bl, D), live),
    ]
    args = [pos_b, q4, kc, vc]
    if quant:
        in_specs += [pl.BlockSpec((1, KV, bl, 1), live)] * 2
        args += [k_scale, v_scale]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bl=bl, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, L // bl),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV, rep, D), row),
            scratch_shapes=[
                pltpu.VMEM((KV, rep, 128), jnp.float32),
                pltpu.VMEM((KV, rep, 128), jnp.float32),
                pltpu.VMEM((KV, rep, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, D), q.dtype),
        interpret=_routing.use_interpret(),
        name="decode_attention",
    )(*args)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# two leaves a layer
# ---------------------------------------------------------------------------

def _pair_block_len(block_l: int, L1: int, L2: int, KV: int, D: int,
                    itemsize: int) -> int:
    """``_block_len`` for two K / V pairs in flight at once: a block that
    divides both lengths, the four tiles double-buffered."""
    per_pos = 2 * 2 * 2 * KV * D * itemsize
    bl = min(block_l, L1, L2)
    while bl >= 32:
        if L1 % bl == 0 and L2 % bl == 0 and bl * per_pos <= _VMEM_BUDGET:
            return bl
        bl //= 2
    return 0


def supported_pair(q, k1, k2) -> bool:
    if q.ndim != 3 or k1.ndim != 4 or k2.ndim != 4:
        return False
    B, H, D = q.shape
    _, KV, L1, _ = k1.shape
    if k2.shape[:2] != k1.shape[:2] or k2.shape[3] != D \
            or k1.dtype != k2.dtype or k1.dtype == jnp.int8:
        return False
    if H % KV or D % 8 or L1 % 128 or k2.shape[2] % 128:
        return False
    return _pair_block_len(_BLOCK_L, L1, k2.shape[2], KV, D,
                           k1.dtype.itemsize) > 0


def _pair_kernel(n1_ref, n2_ref, q_ref, k1_ref, v1_ref, k2_ref, v2_ref,
                 o_ref, m_scr, l_scr, acc_scr, *, scale, bl, nb1):
    b = pl.program_id(0)
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(k_ref, v_ref, start, n_valid):
        q = q_ref[0].astype(jnp.float32)           # (KV, rep, D)
        k = k_ref[0].astype(jnp.float32)           # (KV, bl, D)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.einsum("grd,gld->grl", q, k,
                       preferred_element_type=jnp.float32) * scale
        idx = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(idx < n_valid, s, -jnp.inf)
        m_prev = m_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, :, :1] = corr * l_scr[:, :, :1] + jnp.sum(
            p, axis=2, keepdims=True)
        m_scr[:, :, :1] = m_new
        acc_scr[...] = corr * acc_scr[...] + jnp.einsum(
            "grl,gld->grd", p, v, preferred_element_type=jnp.float32)

    n1, n2 = n1_ref[b], n2_ref[b]

    @pl.when(jnp.logical_and(li < nb1, li * bl < n1))
    def _first():
        block(k1_ref, v1_ref, li * bl, n1)

    @pl.when(jnp.logical_and(li >= nb1, (li - nb1) * bl < n2))
    def _second():
        block(k2_ref, v2_ref, (li - nb1) * bl, n2)

    @pl.when(li == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / l_scr[:, :, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_l",))
def decode_attention_pair(q, k1, v1, n1, k2, v2, n2,
                          block_l: int = _BLOCK_L):
    """q (B, H, D) over two cache leaves (B, KV, L1, D) and (B, KV, L2,
    D) with live lengths ``n1`` >= 1 and ``n2`` >= 0 (traced scalars or
    per-row ``(B,)`` vectors), one softmax over both -> (B, H, D). Blocks
    past a leaf's live length are neither fetched nor computed, as in
    ``decode_attention``; while the grid walks one leaf the other's index
    stands still."""
    B, H, D = q.shape
    _, KV, L1, _ = k1.shape
    L2 = k2.shape[2]
    rep = H // KV
    bl = _pair_block_len(block_l, L1, L2, KV, D, k1.dtype.itemsize)
    if not bl:
        raise ValueError(
            f"decode_attention_pair: no block of cache positions under "
            f"block_l={block_l} divides L1={L1} and L2={L2} and fits VMEM "
            f"at KV={KV}, D={D} (see supported_pair())")
    nb1, nb2 = L1 // bl, L2 // bl

    def lengths(n):
        return jnp.broadcast_to(jnp.asarray(n, jnp.int32).reshape(-1), (B,))

    def row(b, l, n1_ref, n2_ref):
        return (b, 0, 0, 0)

    def first(b, l, n1_ref, n2_ref):
        return (b, 0, _live_block(jnp.minimum(l, nb1 - 1), n1_ref[b], bl), 0)

    def second(b, l, n1_ref, n2_ref):
        return (b, 0, _live_block(jnp.maximum(l - nb1, 0), n2_ref[b], bl), 0)

    out = pl.pallas_call(
        functools.partial(_pair_kernel, scale=1.0 / math.sqrt(D), bl=bl,
                          nb1=nb1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nb1 + nb2),
            in_specs=[pl.BlockSpec((1, KV, rep, D), row),
                      pl.BlockSpec((1, KV, bl, D), first),
                      pl.BlockSpec((1, KV, bl, D), first),
                      pl.BlockSpec((1, KV, bl, D), second),
                      pl.BlockSpec((1, KV, bl, D), second)],
            out_specs=pl.BlockSpec((1, KV, rep, D), row),
            scratch_shapes=[
                pltpu.VMEM((KV, rep, 128), jnp.float32),
                pltpu.VMEM((KV, rep, 128), jnp.float32),
                pltpu.VMEM((KV, rep, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, D), q.dtype),
        interpret=_routing.use_interpret(),
        name="decode_attention_pair",
    )(lengths(n1), lengths(n2), q.reshape(B, KV, rep, D), k1, v1, k2, v2)
    return out.reshape(B, H, D)
