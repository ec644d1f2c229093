"""Fused GroupNorm(+SiLU) — Pallas TPU kernels (forward + backward).

Capability analog of the reference's fused GroupNorm kernels
(paddle/phi/kernels/fusion/gpu/fused_layernorm / add_group_norm_silu —
the SD-UNet serving path). The round-4 UNet device profile
(bench_profile_unet.json) showed the model NORMALIZATION-bound, not
conv-bound: GroupNorm+SiLU chains cost ~60ms of a 207ms step as XLA
elementwise/reduce fusions making 4-5 HBM passes each. This kernel does
one read + one write per direction, f32 statistics in VMEM, and folds
the SiLU (and its backward) into the same pass.

Layout (round 5): 4D conv maps (B, C, H, W) are consumed NATIVELY —
the only pre-kernel reshape is the leading-dim split (B, C, ...) ->
(B*G, C/G, ...), which preserves the (H, W) tiling, so the kernel reads
exactly the layout the surrounding convolutions produce. The round-4
kernel flattened spatial dims to (B*G, C/G, HW), which retiled the
array (HW lanes vs W lanes) and cost a relayout copy on BOTH sides of
every norm — the dominant share of the 37 ms/step of copy/reshape
traffic in the round-4 profile. Full-dim trailing blocks also lift the
HW % 128 restriction, so the 8x8-latent level runs the kernel too.
Non-4D inputs keep the flattened path (HW lane-multiple required).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import _routing

__all__ = ["supported", "gn_fwd", "gn_bwd"]


def _padded_elems(cg: int, spatial) -> int:
    """VMEM footprint in ELEMENTS of one (cg, *spatial) f32 block: VMEM
    buffers live in tiled layout, so the minor dim pads to 128 lanes and
    the second-minor to 8 sublanes."""
    dims = (cg,) + tuple(spatial)
    minor = -(-dims[-1] // 128) * 128
    second = -(-dims[-2] // 8) * 8 if len(dims) >= 2 else 1
    rest = 1
    for d in dims[:-2]:
        rest *= d
    return rest * second * minor


def _layout_for(x_shape, groups: int):
    """'native4d' (no relayout around the kernel, any H/W), 'flat'
    (HW lanes; needs HW % 128), or None (XLA fallback)."""
    if len(x_shape) < 3:
        return None
    c = x_shape[1]
    if c % groups:
        return None
    cg = c // groups
    hw = 1
    for d in x_shape[2:]:
        hw *= d
    # VMEM ceiling: each program holds the full (C/G, spatial) slab (x,
    # out, grad in bwd, plus f32 temporaries) — bound the f32 slab at 4MB
    # so ~4 live copies stay inside ~16MB VMEM. The 4D-native footprint
    # counts LANE PADDING (W rounds to 128): narrow-W levels whose padded
    # slab blows the budget fall back to the flattened layout (one
    # relayout copy each side) rather than to XLA.
    budget = 4 * 1024 * 1024
    if (len(x_shape) == 4
            and _padded_elems(cg, x_shape[2:]) * 4 <= budget):
        return "native4d"
    # interpret mode has no lane-tiling constraint on 'flat'; everything
    # else routes identically so CPU tests exercise the TPU decisions
    if ((hw % 128 == 0 or _routing.use_interpret())
            and cg * hw * 4 <= budget):
        return "flat"
    return None


def supported(x_shape, groups: int) -> bool:
    return (not _routing.auto_partitioned()
            and _layout_for(x_shape, groups) is not None)


def _silu_fwd(y):
    return y * jax.nn.sigmoid(y)


def _silu_bwd(z, g):
    s = jax.nn.sigmoid(z)
    return g * (s * (1.0 + z * (1.0 - s)))


def _block_shapes(x, groups):
    """(blocked x, spatial dims tuple) — 4D keeps (H, W) native when the
    padded block fits VMEM, else flattens (one relayout, still one HBM
    pass inside the kernel)."""
    B, C = x.shape[0], x.shape[1]
    cg = C // groups
    if x.ndim == 4 and _layout_for(x.shape, groups) == "native4d":
        spatial = tuple(x.shape[2:])
    else:
        spatial = (x.size // (B * C),)
    return x.reshape((B * groups, cg) + spatial), cg, spatial


def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, mean_ref, rstd_ref,
                *, eps, act, out_dtype):
    xf = x_ref[0].astype(jnp.float32)              # (Cg, *spatial)
    # pivot-shifted mean: summing (x - x[0]) keeps the accumulation at the
    # activations' SPREAD scale instead of their absolute scale, so a
    # 1000±0.01 block loses no mantissa to the offset
    pivot = xf[(0,) * xf.ndim]
    m = pivot + jnp.mean(xf - pivot)
    # shifted two-pass variance: E[x²]−m² cancels catastrophically for
    # mean-shifted activations (f32 rounding of E[x²] can exceed the true
    # variance, going negative -> rsqrt NaN); the second pass stays in
    # VMEM/registers so it costs VPU time, not HBM traffic
    d = xf - m
    var = jnp.mean(d * d)
    r = jax.lax.rsqrt(var + eps)
    xhat = (xf - m) * r
    y = xhat * w_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    if act == "silu":
        y = _silu_fwd(y)
    o_ref[0] = y.astype(out_dtype)
    # full-block vector stores — Mosaic rejects true scalar stores to VMEM
    mean_ref[0] = jnp.full(mean_ref.shape[1:], m, jnp.float32)
    rstd_ref[0] = jnp.full(rstd_ref.shape[1:], r, jnp.float32)


def gn_fwd(x, w, b, groups: int, eps: float, act=None):
    """Returns (out, mean, rstd); mean/rstd are (B*G, 1...) f32 residuals."""
    B = x.shape[0]
    xb, cg, spatial = _block_shapes(x, groups)
    ones = (1,) * len(spatial)
    zeros = (0,) * len(spatial)
    blk = (1, cg) + spatial
    out, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, act=act, out_dtype=x.dtype),
        grid=(B * groups,),
        in_specs=[
            pl.BlockSpec(blk, lambda i: (i, 0) + zeros),
            pl.BlockSpec((1, cg) + ones,
                         lambda i, g=groups: (i % g, 0) + zeros),
            pl.BlockSpec((1, cg) + ones,
                         lambda i, g=groups: (i % g, 0) + zeros),
        ],
        out_specs=[
            pl.BlockSpec(blk, lambda i: (i, 0) + zeros),
            pl.BlockSpec((1, 1) + ones, lambda i: (i, 0) + zeros),
            pl.BlockSpec((1, 1) + ones, lambda i: (i, 0) + zeros),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * groups, cg) + spatial, x.dtype),
            jax.ShapeDtypeStruct((B * groups, 1) + ones, jnp.float32),
            jax.ShapeDtypeStruct((B * groups, 1) + ones, jnp.float32),
        ],
        interpret=_routing.use_interpret(),
    )(xb, w.reshape((groups, cg) + ones), b.reshape((groups, cg) + ones))
    return out.reshape(x.shape), mean, rstd


def _bwd_kernel(x_ref, w_ref, b_ref, mean_ref, rstd_ref, g_ref,
                dx_ref, dwp_ref, dbp_ref, *, act, x_dtype):
    xf = x_ref[0].astype(jnp.float32)              # (Cg, *spatial)
    m = mean_ref[tuple([0] * mean_ref.ndim)]
    r = rstd_ref[tuple([0] * rstd_ref.ndim)]
    xhat = (xf - m) * r
    w = w_ref[0].astype(jnp.float32)
    gf = g_ref[0].astype(jnp.float32)
    if act == "silu":
        z = xhat * w + b_ref[0].astype(jnp.float32)
        dz = _silu_bwd(z, gf)
    else:
        dz = gf
    sp_axes = tuple(range(1, xf.ndim))
    dwp_ref[0] = jnp.sum(dz * xhat, axis=sp_axes, keepdims=True)
    dbp_ref[0] = jnp.sum(dz, axis=sp_axes, keepdims=True)
    dxhat = dz * w
    mu1 = jnp.mean(dxhat)
    mu2 = jnp.mean(dxhat * xhat)
    dx_ref[0] = (r * (dxhat - mu1 - xhat * mu2)).astype(x_dtype)


def gn_bwd(x, w, b, mean, rstd, g, groups: int, act=None):
    """Returns (dx, dw, db) given the forward residuals."""
    B, C = x.shape[0], x.shape[1]
    xb, cg, spatial = _block_shapes(x, groups)
    gb = g.reshape(xb.shape)
    ones = (1,) * len(spatial)
    zeros = (0,) * len(spatial)
    blk = (1, cg) + spatial
    mean = mean.reshape((B * groups, 1) + ones)
    rstd = rstd.reshape((B * groups, 1) + ones)
    dx, dw_parts, db_parts = pl.pallas_call(
        functools.partial(_bwd_kernel, act=act, x_dtype=x.dtype),
        grid=(B * groups,),
        in_specs=[
            pl.BlockSpec(blk, lambda i: (i, 0) + zeros),
            pl.BlockSpec((1, cg) + ones,
                         lambda i, gr=groups: (i % gr, 0) + zeros),
            pl.BlockSpec((1, cg) + ones,
                         lambda i, gr=groups: (i % gr, 0) + zeros),
            pl.BlockSpec((1, 1) + ones, lambda i: (i, 0) + zeros),
            pl.BlockSpec((1, 1) + ones, lambda i: (i, 0) + zeros),
            pl.BlockSpec(blk, lambda i: (i, 0) + zeros),
        ],
        out_specs=[
            pl.BlockSpec(blk, lambda i: (i, 0) + zeros),
            pl.BlockSpec((1, cg) + ones, lambda i: (i, 0) + zeros),
            pl.BlockSpec((1, cg) + ones, lambda i: (i, 0) + zeros),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * groups, cg) + spatial, x.dtype),
            jax.ShapeDtypeStruct((B * groups, cg) + ones, jnp.float32),
            jax.ShapeDtypeStruct((B * groups, cg) + ones, jnp.float32),
        ],
        interpret=_routing.use_interpret(),
    )(xb, w.reshape((groups, cg) + ones), b.reshape((groups, cg) + ones),
      mean, rstd, gb)
    # per-(b,g) channel partials -> (C,) by summing the batch axis
    dw = jnp.sum(dw_parts.reshape(B, C), axis=0).astype(w.dtype)
    db = jnp.sum(db_parts.reshape(B, C), axis=0).astype(b.dtype)
    return dx.reshape(x.shape), dw, db
