"""Decode attention of multi-head latent attention (MLA) — Pallas TPU
kernel.

A decode step of MLA (models/axk1.py) attends in LATENT space: each head's
query is taken into the latent's space (``q~_h = W_UK_h q_nope_h``), so one
cached latent row ``c_kv`` and one rotated key part ``k_pe`` shared by all
heads serve every head as key, and the latent serves as value:
``o~_h = softmax_j(s * (q~_h . c_kv_j + q_pe_h . k_pe_j)) c_kv_j``.
``decode_attention`` at ``rep = heads`` would read the latent twice, as K
and as V; this kernel reads it ONCE:

- grid ``(B, L-blocks)``; a grid step loads one block of a row's latent
  ``(bl, R)`` and rope keys ``(bl, P)`` and scores ALL heads against it in
  one MXU product each ``(H, R) x (R, bl)`` + ``(H, P) x (P, bl)``, then
  accumulates ``p (H, bl) x c_kv (bl, R)`` under an online softmax in
  float32 VMEM scratch (bf16 operands, float32 accumulation),
- the row's live length is a scalar-prefetch operand and the index maps
  send every block past it to the row's last live block
  (``decode_attention._live_block``): a block index that repeats is not
  fetched, and its compute is skipped, so a row's latent is read as far
  as it is live and no further,
- about ``2 H (R + P + R)`` operations a position against ``2 (R + P)``
  bytes: 121 a byte at 64 heads, R 512, P 64 — between the v5e's
  bandwidth and compute roofs, which is why the score and the value
  products run on the MXU in bf16.

Layouts: ``qa`` (B, H, R) the absorbed queries, ``qp`` (B, H, P) the
rotated rope queries (zero-padded to the cache's rope width), ``ckv`` (B,
1, L, R) and ``kpe`` (B, 1, L, P) the cache leaves -> (B, H, R) in
``qa``'s dtype, the latent-space output before ``W_UV``. Inference-path
only (no VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _routing
from paddle_tpu.ops.pallas.decode_attention import _live_block

__all__ = ["supported", "mla_decode_attention"]

_BLOCK_L = 256
# pipeline buffers of a grid step (the two leaves' blocks, double-
# buffered) may take this much of the 16 MiB scoped VMEM; the rest is the
# queries, the (H, bl) scores and the float32 accumulator
_VMEM_BUDGET = 8 * 1024 * 1024


def _block_len(block_l: int, L: int, R: int, P: int, itemsize: int) -> int:
    bl = min(block_l, L)
    while bl >= 128:
        if L % bl == 0 and 2 * bl * (R + P) * itemsize <= _VMEM_BUDGET:
            return bl
        bl //= 2
    return 0


def supported(qa, ckv, kpe) -> bool:
    """Shapes the kernel takes: one query a row, leaves of one "head",
    whole lane tiles of latent and rope width, a length of whole 128-
    position blocks, one float dtype."""
    if qa.ndim != 3 or ckv.ndim != 4 or kpe.ndim != 4:
        return False
    R = qa.shape[2]
    if ckv.shape[:3] != kpe.shape[:3] or ckv.shape[1] != 1 \
            or ckv.shape[3] != R or ckv.dtype != kpe.dtype \
            or ckv.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if R % 128 or kpe.shape[3] % 128:
        return False
    return _block_len(_BLOCK_L, ckv.shape[2], R, kpe.shape[3],
                      ckv.dtype.itemsize) > 0


def _kernel(n_ref, qa_ref, qp_ref, c_ref, p_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, bl):
    b = pl.program_id(0)
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n_valid = n_ref[b]

    @pl.when(li * bl < n_valid)
    def _block():
        c = c_ref[0, 0]                                  # (bl, R)
        dims = (((1,), (1,)), ((), ()))                  # contract the widths
        s = (jax.lax.dot_general(qa_ref[0], c, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qp_ref[0], p_ref[0, 0], dims,
                                   preferred_element_type=jnp.float32)
             ) * scale                                   # (H, bl)
        idx = li * bl + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx < n_valid, s, -jnp.inf)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        pr = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, :1] = corr * l_scr[:, :1] + jnp.sum(pr, axis=1,
                                                     keepdims=True)
        m_scr[:, :1] = m_new
        acc_scr[...] = corr * acc_scr[...] + jnp.dot(
            pr.astype(c.dtype), c, preferred_element_type=jnp.float32)

    @pl.when(li == pl.num_programs(1) - 1)
    def _done():
        # a row with no live position reads zeros, not 0 / 0
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_l"))
def mla_decode_attention(qa, qp, ckv, kpe, n_live, *, scale: float,
                         block_l: int = _BLOCK_L):
    """Latent-space attention of one query a row over the cache leaves'
    first ``n_live`` positions (a traced scalar or a per-row ``(B,)``
    vector; a row with none reads zeros) -> (B, H, R). ``scale``
    multiplies the scores."""
    B, H, R = qa.shape
    _, _, L, P = kpe.shape
    bl = _block_len(block_l, L, R, P, ckv.dtype.itemsize)
    if not bl:
        raise ValueError(f"mla_decode_attention: no block of cache "
                         f"positions under block_l={block_l} divides L={L} "
                         f"(see supported())")
    n_b = jnp.broadcast_to(jnp.asarray(n_live, jnp.int32).reshape(-1), (B,))

    def row(b, l, n_ref):
        return (b, 0, 0)

    def live(b, l, n_ref):
        return (b, 0, _live_block(l, n_ref[b], bl), 0)

    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), bl=bl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, L // bl),
            in_specs=[pl.BlockSpec((1, H, R), row),
                      pl.BlockSpec((1, H, P), row),
                      pl.BlockSpec((1, 1, bl, R), live),
                      pl.BlockSpec((1, 1, bl, P), live)],
            out_specs=pl.BlockSpec((1, H, R), row),
            scratch_shapes=[pltpu.VMEM((H, 128), jnp.float32),
                            pltpu.VMEM((H, 128), jnp.float32),
                            pltpu.VMEM((H, R), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, R), qa.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_routing.use_interpret(),
        name="mla_decode_attention",
    )(n_b, qa, qp.astype(qa.dtype), ckv, kpe)
