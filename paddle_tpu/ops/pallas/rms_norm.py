"""Fused RMSNorm — Pallas TPU kernels (forward + backward).

Capability analog of the reference's fused norm kernels
(paddle/phi/kernels/fusion/gpu/fused_rms_norm via
paddle.incubate.nn.functional.fused_rms_norm): one pass over HBM per
direction instead of XLA's default elementwise graph, f32 statistics for
bf16 activations, and a backward that recomputes the cheap per-row
statistics instead of spilling them.

Layout: the normalized axis is the last one; leading axes are flattened
to rows. Row blocks ride the VPU sublanes, the hidden dim sits in lanes
(needs H % 128 == 0 on real TPU). The backward emits per-block partial
weight grads (n_blocks, H) reduced outside the kernel — cross-block
accumulation in HBM would serialize the grid.

Routing/eligibility lives in ``supported``; callers (ops/fused_norm.py)
fall back to the lax composition when ineligible. Off-TPU the kernels run
in interpret mode so tests exercise the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _routing

__all__ = ["supported", "rms_fwd", "rms_bwd"]


# Mosaic's default scoped-VMEM limit on v5e. A row block holds its io
# blocks double-buffered (the pipeline) plus the kernel's (rows, h) f32
# temporaries; the temporaries counts below are the smallest that kept
# every AOT compile for v5e inside the limit over h 1024..16384, bf16 and
# f32 (the fixed 256-row block was refused from h=4096 on).
_VMEM_BUDGET = 16 * 1024 * 1024
_FWD_TEMPS = 2
_BWD_TEMPS = 5


def _row_block(rows: int, h: int, io_bytes: int, n_temps: int) -> int:
    """Largest sublane-aligned divisor of ``rows`` (<= 256) whose blocks
    fit the VMEM budget; ``io_bytes`` is the summed itemsize of the
    (rows, h) operands and results. 0 = no block fits."""
    per_row = h * (2 * io_bytes + 4 * n_temps)
    for cand in (256, 128, 64, 32, 16, 8):
        if rows % cand == 0 and cand * per_row <= _VMEM_BUDGET:
            return cand
    return 0


def supported(x_shape, w_shape) -> bool:
    if _routing.auto_partitioned():
        return False
    if len(x_shape) < 2 or len(w_shape) != 1 or x_shape[-1] != w_shape[0]:
        return False
    h = x_shape[-1]
    rows = 1
    for d in x_shape[:-1]:
        rows *= d
    # shapes only, so size for the widest case: f32 x, grad and dx in the
    # backward (the forward's blocks are never larger)
    if _row_block(rows, h, 12, _BWD_TEMPS) == 0:
        return False
    # interpret mode has no lane constraint
    return _routing.use_interpret() or h % 128 == 0


def _fwd_kernel(x_ref, w_ref, o_ref, inv_ref, *, eps, out_dtype):
    xf = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    y = (xf * inv).astype(x_ref.dtype)
    o_ref[:] = (y.astype(jnp.float32)
                * w_ref[:].astype(jnp.float32)).astype(out_dtype)
    inv_ref[:] = inv


def rms_fwd(x, w, eps: float):
    """Returns (out, inv) with inv = rsqrt(mean(x^2, -1) + eps) as (rows, 1)
    f32 residual for the backward."""
    orig_shape = x.shape
    h = orig_shape[-1]
    rows = x.size // h
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    br = _row_block(rows, h, x.dtype.itemsize + jnp.dtype(out_dtype).itemsize,
                    _FWD_TEMPS)
    x2 = x.reshape(rows, h)
    out, inv = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, out_dtype=out_dtype),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, h), out_dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=_routing.use_interpret(),
        name="rms_norm_fwd",
    )(x2, w.reshape(1, h))
    return out.reshape(orig_shape[:-1] + (h,)), inv


def _bwd_kernel(x_ref, w_ref, inv_ref, g_ref, dx_ref, dwp_ref, *, x_dtype,
                block_rows):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dwp_ref[:] = jnp.zeros_like(dwp_ref)

    xf = x_ref[:].astype(jnp.float32)
    inv = inv_ref[:]                                    # (BR, 1) f32
    yn = xf * inv                                       # normalized, f32
    gf = g_ref[:].astype(jnp.float32)
    dy = gf * w_ref[:].astype(jnp.float32)
    dx = inv * (dy - yn * jnp.mean(dy * yn, axis=1, keepdims=True))
    dx_ref[:] = dx.astype(x_dtype)
    # forward quantized yn to x.dtype before the w-multiply; dw sees the same.
    # Partial weight grads keep 8 sublanes (Mosaic tile floor) and accumulate
    # into one revisited output block — the TPU grid runs sequentially.
    yq = yn.astype(x_dtype).astype(jnp.float32)
    h = dwp_ref.shape[-1]
    part = jnp.sum((gf * yq).reshape(8, block_rows // 8, h), axis=1)
    dwp_ref[:] = dwp_ref[:] + part


def rms_bwd(x, w, inv, g):
    """Returns (dx, dw) given the forward residual ``inv``."""
    orig_shape = x.shape
    h = orig_shape[-1]
    rows = x.size // h
    br = _row_block(rows, h, 2 * x.dtype.itemsize + g.dtype.itemsize,
                    _BWD_TEMPS)
    nb = rows // br
    x2 = x.reshape(rows, h)
    g2 = g.reshape(rows, h)
    dx, dw_parts = pl.pallas_call(
        functools.partial(_bwd_kernel, x_dtype=x.dtype, block_rows=br),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((8, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, h), x.dtype),
            jax.ShapeDtypeStruct((8, h), jnp.float32),
        ],
        interpret=_routing.use_interpret(),
        name="rms_norm_bwd",
    )(x2, w.reshape(1, h), inv, g2)
    dw = jnp.sum(dw_parts, axis=0).astype(w.dtype)
    return dx.reshape(orig_shape), dw
