"""Fused rotary position embedding — Pallas TPU kernel.

Capability analog of the reference's fused_rope
(paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu, python surface
paddle.incubate.nn.functional.fused_rotary_position_embedding): the
rotation (split halves, multiply by cos/sin tables, re-concat) runs as a
single pass over the activation instead of XLA's slice/mul/concat chain.

Layout: x is (B, S, H, D), tables are (S, D/2), Llama half-split
convention (models/llama.py _rope_op). Grid tiles (batch, seq-blocks);
heads and head_dim stay whole inside a block. The backward is the inverse
rotation (same kernel, negated sin), wired through a custom VJP.

Measured honestly (v5e, 134M Llama, B=8 S=1024): standalone the kernel is
within noise of the XLA chain, but in the full train step the pallas_call
boundary blocks XLA from fusing rope into its neighbors (67.2 -> 73.9
ms/step), so routing defaults OFF (FLAGS_use_fused_rope) and the kernel
remains available for decode/irregular shapes and as the fusion anchor
for the pass framework.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import _routing

__all__ = ["supported", "rope_fused"]


def _seq_block(s: int) -> int:
    for cand in (256, 128, 64, 32, 16, 8):
        if s % cand == 0:
            return cand
    return 0


def supported(x_shape, cos_shape, x_dtype=None, cos_dtype=None) -> bool:
    if _routing.auto_partitioned():
        return False
    if len(x_shape) != 4 or len(cos_shape) != 2:
        return False
    b, s, h, d = x_shape
    if d % 2 != 0 or tuple(cos_shape) != (s, d // 2):
        return False
    # the kernel emits x.dtype; the XLA fallback promotes with the table
    # dtype — only route shapes where the two agree
    if x_dtype is not None and cos_dtype is not None and x_dtype != cos_dtype:
        return False
    return _seq_block(s) > 0


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, d2):
    x = x_ref[0]                       # (BS, H, D)
    c = cos_ref[:]                     # (BS, 1, D/2) — pre-shaped outside
    s = sin_ref[:]
    x1 = x[..., :d2]
    x2 = x[..., d2:]
    o_ref[0] = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _run(x, cos, sin):
    b, s, h, d = x.shape
    d2 = d // 2
    bs = _seq_block(s)
    return pl.pallas_call(
        functools.partial(_rope_kernel, d2=d2),
        grid=(b, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((bs, 1, d2), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((bs, 1, d2), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, h, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=_routing.use_interpret(),
    )(x, cos.reshape(s, 1, d2), sin.reshape(s, 1, d2))


@jax.custom_vjp
def rope_fused(x, cos, sin):
    return _run(x, cos, sin)


def _fwd(x, cos, sin):
    return _run(x, cos, sin), (x, cos, sin)


def _bwd(res, g):
    x, cos, sin = res
    # rotation matrices are orthogonal: dx is the inverse rotation (kernel);
    # table grads are tiny (S, D/2) reductions, left to XLA
    dx = _run(g, cos, -sin)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    g1, g2 = g[..., :d2], g[..., d2:]
    gf1, gf2 = g1.astype(jnp.float32), g2.astype(jnp.float32)
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    dcos = jnp.sum(gf1 * xf1 + gf2 * xf2, axis=(0, 2)).astype(cos.dtype)
    dsin = jnp.sum(gf2 * xf1 - gf1 * xf2, axis=(0, 2)).astype(sin.dtype)
    return dx, dcos, dsin


rope_fused.defvjp(_fwd, _bwd)
