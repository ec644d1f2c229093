"""Weight-only int8 matmul — Pallas TPU kernel (dequant INSIDE the tile).

Capability analog of the reference's ``weight_only_linear``
(paddle/phi/kernels/fusion/gpu/, python API
paddle.nn.quant.weight_only_linear): small-batch decode is bound by
weight HBM bandwidth, so the int8 weight must stream int8 all the way to
VMEM. XLA's ``x @ w_int8.astype(bf16)`` does not deliver that (measured
SLOWER than bf16 on v5e: the convert runs as its own pass); this kernel
loads int8 tiles, converts in VMEM, and feeds the MXU — weight traffic
halves.

Layout: x (B, K) bf16/f32, w (K, N) int8, per-output-channel scale (N,)
f32 -> out (B, N) in x.dtype. 1-D grid over N tiles with the FULL
contraction axis per program (decode cost is per-program latency, not
FLOPs); one dot per program, scale in the epilogue; non-divisible N rides
a padded trailing tile. Inference-path only (no custom VJP; decode runs
under no_grad).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _routing

__all__ = ["supported", "int8_matmul"]


def supported(x, w) -> bool:
    """Decode-shaped only: small row count (the weight-bandwidth-bound
    regime this kernel exists for) and MXU-tileable K/N. Prefill and
    training shapes stay on XLA's dot — they are compute-bound and the
    full-row x tile would not fit VMEM."""
    if x.ndim != 2 or w.ndim != 2 or w.dtype != jnp.int8:
        return False
    K, N = w.shape
    # K is read whole per program: it only needs lane/sublane alignment
    # (128 covers both bf16 lanes and the int8 32-sublane tile)
    return x.shape[0] <= 64 and K % 128 == 0 and N % 128 == 0


def _kernel(x_ref, w_ref, s_ref, o_ref):
    wt = w_ref[...].astype(x_ref.dtype)            # dequant in VMEM
    acc = jax.lax.dot_general(
        x_ref[...], wt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n",))
def int8_matmul(x, w, scale, block_n: int = 1024):
    """x (B, K) @ dequant(w (K, N) int8, scale (N,)) -> (B, N).

    1-D grid over N tiles with the FULL contraction axis per program:
    at decode batch sizes the cost is per-program latency, not FLOPs, so
    fewer/bigger programs win (the K axis of the quantized matrices is at
    most a few thousand — a (K, block_n) int8 tile stays well inside
    VMEM)."""
    B, K = x.shape
    Kw, N = w.shape
    assert K == Kw, (x.shape, w.shape)
    bn = min(block_n, N)
    # keep the double-buffered (K, bn) int8 tile within ~2MB of VMEM
    while K * bn > 2 * 1024 * 1024 and bn > 256:
        bn //= 2
    bn = max(128, (bn // 128) * 128)   # lane alignment
    # non-divisible N keeps the big block: pallas pads the trailing tile
    # (shrinking bn to a divisor fragments the grid — N=5504 would drop
    # to bn=128 and run 6x under HBM bandwidth)
    return pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(N, bn),),
        in_specs=[
            pl.BlockSpec((B, K), lambda j: (0, 0)),
            pl.BlockSpec((K, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((B, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        interpret=_routing.use_interpret(),
        name="int8_matmul",
    )(x, w, scale.astype(jnp.float32).reshape(1, N))
