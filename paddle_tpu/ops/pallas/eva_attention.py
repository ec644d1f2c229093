"""EVA attention's two kernels of its own — Pallas TPU, forward only: the
prefill's attention and a decode step's chunk pooling.

The attention of an EvaByte prefill from position 0 (ops/eva.py has the
equations): a query sees, under ONE softmax, the exact keys of its own
aligned window up to itself, and the chunk summaries of every window
before its own. One kernel takes both key sets, so the two attentions
never meet as separate outputs joined by their log-sum-exp — the flash
forward kernel's ``(BH, S, 1)`` float32 log-sum-exp pads to 128 lanes in
HBM, 537 MB a call at 32 heads x 32768 positions, beside a carry and an
admission ring that fill the chip (AOT, PERF.md section 6, PR 36).

Grid (BH, q blocks, key steps): a q block lies inside one window ``w``
(``block_q`` divides the window); its key steps run first over the
window's own key blocks, causal — blocks past the q block's diagonal are
neither computed nor fetched (their index map names the diagonal block
again) —, then over the blocks of summaries, of which the first ``w *
per`` are visible (``per`` summaries a window) and the rest likewise
skipped. Online softmax across all steps in VMEM scratch, f32
accumulation, io dtype preserved. Layout (BH, S, D), the flash kernels'.

``eva_chunk_pool`` is a decode step's summary: the chunk a row's new
position lies in, read from the window leaf by a block index from the
row's own position (a scalar-prefetch operand, as the decode attention's
live length is) and pooled in VMEM. As XLA's gather the same read cost a
transposed copy of every window leaf, every layer, every step — 4.8 ms of
a 14.9 ms step on the v5e at 8 slots x 8 layers (PERF.md section 6, PR
36).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _routing
from paddle_tpu.ops.pallas.flash_attention import _NEG_INF, _divisor_block

__all__ = ["supported", "eva_prefill_attention", "pool_supported",
           "eva_chunk_pool"]

_BLOCK = 512


def _blocks(window: int, n_sum: int):
    """(query / key block inside a window, summary block)."""
    return _divisor_block(window, _BLOCK), _divisor_block(n_sum, _BLOCK)


def supported(seq: int, window: int, per: int) -> bool:
    """Routing predicate: ``seq`` whole windows, and blocks the chip's
    tiles take (anything in interpret mode)."""
    if _routing.auto_partitioned() or seq % window or window % per:
        return False
    bq, bs = _blocks(window, seq // window * per)
    return _routing.use_interpret() or (bq % 8 == 0 and bs % 8 == 0)


def _kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, bq, bs, nka, nkb, window, per):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    w = (qi * bq) // window                  # the q block's window
    q0 = qi * bq - w * window                # its first query's place in it

    def attend(k_ref, v_ref, mask):
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask(s.shape), s, _NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jnp.logical_and(j < nka, j * bq < q0 + bq))
    def _own_window():
        def causal(shape):
            rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            return q0 + rows >= j * bq + cols
        attend(k_ref, v_ref, causal)

    @pl.when(jnp.logical_and(j >= nka, (j - nka) * bs < w * per))
    def _summaries():
        def earlier(shape):
            cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            return (j - nka) * bs + cols < w * per
        attend(ks_ref, vs_ref, earlier)

    @pl.when(j == nka + nkb - 1)
    def _finish():
        # every query saw itself: l > 0
        o_ref[0] = (acc_scr[:] / l_scr[:, 0:1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "per"))
def eva_prefill_attention(q, k, v, ks, vs, window: int, per: int):
    """q, k, v (BH, S, D) from position 0, ``S`` whole windows of
    ``window``; ks, vs (BH, S / window * per, D) the summaries, ``per`` a
    window in order -> (BH, S, D)."""
    bh, S, D = q.shape
    N = ks.shape[1]
    if not supported(S, window, per) or N != S // window * per:
        raise ValueError(f"eva_prefill_attention: {q.shape} x {ks.shape} at "
                         f"window={window}, per={per} (see supported())")
    bq, bs = _blocks(window, N)
    nka, nkb = window // bq, N // bs

    def q_map(b, i, j):
        return (b, i, 0)

    def k_map(b, i, j):
        # the window's own blocks up to the diagonal one, which stands in
        # for every later step: an index that repeats is not fetched again
        w = (i * bq) // window
        return (b, w * nka + jnp.minimum(j, i - w * nka), 0)

    def s_map(b, i, j):
        last = jnp.maximum((i * bq) // window * per - 1, 0) // bs
        return (b, jnp.clip(j - nka, 0, last), 0)

    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(D), bq=bq, bs=bs,
                          nka=nka, nkb=nkb, window=window, per=per),
        grid=(bh, S // bq, nka + nkb),
        in_specs=[pl.BlockSpec((1, bq, D), q_map),
                  pl.BlockSpec((1, bq, D), k_map),
                  pl.BlockSpec((1, bq, D), k_map),
                  pl.BlockSpec((1, bs, D), s_map),
                  pl.BlockSpec((1, bs, D), s_map)],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((bh, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_routing.use_interpret(),
        name="eva_prefill_attention",
    )(q, k, v, ks, vs)


# ---------------------------------------------------------------------------
# a decode step's chunk summary
# ---------------------------------------------------------------------------

def pool_supported(leaf, chunk: int) -> bool:
    """A head-major window leaf (B, H, W, D) whose chunks are whole
    sublane tiles of its dtype (anything in interpret mode)."""
    if leaf.ndim != 4 or leaf.shape[2] % chunk:
        return False
    tile = {1: 32, 2: 16, 4: 8}.get(leaf.dtype.itemsize)
    return _routing.use_interpret() or (
        tile is not None and chunk % tile == 0 and leaf.shape[3] % 128 == 0)


def _pool_kernel(blk_ref, k_ref, v_ref, mu_ref, phi_ref, ks_ref, vs_ref, *,
                 scale):
    del blk_ref
    k = k_ref[0].astype(jnp.float32)               # (H, C, D)
    v = v_ref[0].astype(jnp.float32)

    def pool(w_ref, x):
        w = w_ref[...].astype(jnp.float32)         # (H, D)
        sc = jnp.sum(k * w[:, None, :], axis=-1, keepdims=True) * scale
        e = jnp.exp(sc - jnp.max(sc, axis=1, keepdims=True))    # (H, C, 1)
        p = e / jnp.sum(e, axis=1, keepdims=True)
        return jnp.sum(p * x, axis=1, keepdims=True)            # (H, 1, D)
    ks_ref[0] = pool(mu_ref, k).astype(ks_ref.dtype)
    vs_ref[0] = pool(phi_ref, v).astype(vs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def eva_chunk_pool(kw, vw, mu, phi, start, chunk: int):
    """kw, vw (B, H, W, D) window leaves; mu, phi (H, D); ``start`` (B,)
    or a scalar, the first row of each sequence's chunk, a multiple of
    ``chunk`` -> (k~, v~) each (B, H, 1, D): ``ops/eva.py``'s
    ``chunk_summaries`` of rows ``[start, start + chunk)``."""
    B, H, W, D = kw.shape
    if not pool_supported(kw, chunk):
        raise ValueError(f"eva_chunk_pool: leaf {kw.shape} {kw.dtype}, "
                         f"chunk {chunk} (see pool_supported())")
    blk = jnp.broadcast_to(
        jnp.asarray(start, jnp.int32).reshape(-1) // chunk, (B,))

    def rows(b, blk_ref):
        return (b, 0, blk_ref[b], 0)

    def whole(b, blk_ref):
        return (0, 0)

    def out(b, blk_ref):
        return (b, 0, 0, 0)

    one = jax.ShapeDtypeStruct((B, H, 1, D), kw.dtype)
    return pl.pallas_call(
        functools.partial(_pool_kernel, scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[pl.BlockSpec((1, H, chunk, D), rows),
                      pl.BlockSpec((1, H, chunk, D), rows),
                      pl.BlockSpec((H, D), whole),
                      pl.BlockSpec((H, D), whole)],
            out_specs=[pl.BlockSpec((1, H, 1, D), out),
                       pl.BlockSpec((1, H, 1, D), out)]),
        out_shape=[one, one],
        interpret=_routing.use_interpret(),
        name="eva_chunk_pool",
    )(blk, kw, vw, mu, phi)
