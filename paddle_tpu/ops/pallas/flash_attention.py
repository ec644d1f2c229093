"""Flash attention — Pallas TPU kernel with custom VJP.

Capability analog of the reference's flash-attn v2 integration
(paddle/phi/kernels/gpu/flash_attn_kernel.cu + third_party/flashattn,
python surface python/paddle/nn/functional/flash_attention.py), built
TPU-native: online-softmax tiling sized to the MXU (128-lane blocks),
VMEM accumulators, causal block skipping, and a two-kernel backward
(dq; dk/dv) using the saved logsumexp — the standard flash-attention-2
recurrence, scheduled for TPU rather than ported from CUDA.

Layouts: public API takes paddle's (batch, seq, heads, head_dim);
kernels run (batch*heads, seq, head_dim). f32 accumulation everywhere
(MXU preferred_element_type), io dtype preserved.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _routing

__all__ = ["supported", "flash_attention_op", "flash_attention_fn",
           "WindowBackwardError"]


class WindowBackwardError(NotImplementedError):
    """A gradient was asked through a windowed flash attention: only the
    forward kernel knows the band."""

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                num_k_blocks, offset=0, window=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visible():  # causal: process only k blocks not fully masked
        q = q_ref[0]                              # (BQ, D) io dtype (bf16 ok)
        k = k_ref[0]                              # (BK, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            # offset = sk - sq: bottom-right-aligned causal (KV-cache
            # chunked prefill; query i sees keys <= i + offset)
            mask = (qi * block_q + rows + offset) >= (ki * block_k + cols)
            if window is not None:
                # the band: a query sees the last `window` keys, itself
                # included
                mask = jnp.logical_and(
                    mask, (qi * block_q + rows + offset)
                    - (ki * block_k + cols) < window)
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, 0:1]                    # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                    # (BQ, BK)
        l_new = alpha * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        # p in io dtype for the MXU (f32 accumulate keeps precision)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal and window is not None:
        # also skip the k blocks wholly below the band of this q block
        @pl.when(jnp.logical_and(
            ki * block_k < (qi + 1) * block_q + offset,
            qi * block_q + offset - ((ki + 1) * block_k - 1) < window))
        def _():
            _visible()
    elif causal:
        @pl.when(ki * block_k < (qi + 1) * block_q + offset)
        def _():
            _visible()
    else:
        _visible()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        l = l_scr[:, 0:1]
        m = m_scr[:, 0:1]
        # Fully-masked rows come in two shapes: a q block whose k blocks
        # were ALL skipped (l == 0, needs the clamp) or a visited block
        # whose row was fully masked (m == _NEG_INF, p == exp(0) == 1 so
        # l == block_k and acc holds a uniform V sum). Zero both.
        safe_l = jnp.maximum(l, 1e-30)
        masked_row = m <= _NEG_INF * 0.5
        o_ref[0] = jnp.where(masked_row, 0.0,
                             acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(masked_row, _NEG_INF, m + jnp.log(safe_l))


def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                       *, scale, causal, offset, window=None):
    """Whole-sequence block: plain softmax attention in VMEM. With one
    (q, k) block the online-softmax merge is pure overhead — no m/l
    scratch round-trips, no acc rescale, no alpha exp. Measured 1.8x the
    merged kernel at the BERT shape (bh=192, S=512, d=64, non-causal)."""
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        mask = rows + offset >= cols
        if window is not None:
            mask = jnp.logical_and(mask, rows + offset - cols < window)
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # A fully-masked row has m == _NEG_INF (finite), so p == 1 everywhere
    # and pv/l would be the uniform V average — zero it instead. Currently
    # defensive: flash_attention_fn rejects causal with sq > sk, the only
    # way such a row arises through the public surface.
    masked_row = m <= _NEG_INF * 0.5
    o_ref[0] = jnp.where(masked_row, 0.0, pv / l).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(masked_row, _NEG_INF, m + jnp.log(l))


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret, window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k
    # window=None passes no keyword: the kernel's partial, and so the
    # compiled text of every caller without a window, stays as it was
    band = {} if window is None else {"window": int(window)}
    if nq == 1 and nk == 1:
        return pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale=scale,
                              causal=causal, offset=sk - sq, **band),
            grid=(bh,),
            in_specs=[pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
                      pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0)),
                      pl.BlockSpec((1, sk, d), lambda b: (b, 0, 0))],
            out_specs=[pl.BlockSpec((1, sq, d), lambda b: (b, 0, 0)),
                       pl.BlockSpec((1, sq, 1), lambda b: (b, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                       jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
            name="flash_fwd",
        )(q, k, v)
    grid = (bh, nq, nk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, offset=sk - sq, **band)

    kv_map = lambda b, i, j: (b, j, 0)  # noqa: E731
    if window is not None:
        # a k block outside the band of q block i is skipped by the kernel:
        # name the nearest block inside it instead, so that the pipeline
        # sees an index it already holds and fetches nothing
        def kv_map(b, i, j):
            lo = jnp.maximum(
                (i * block_q + (sk - sq) - window + 1) // block_k, 0)
            hi = ((i + 1) * block_q + (sk - sq) - 1) // block_k
            return (b, jnp.clip(j, lo, hi), 0)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # a name of its own under a band: a trace tells the two apart
        name="flash_fwd" if window is None else "flash_fwd_band",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dkv kernel (grid over k blocks, scan q blocks) + dq kernel
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, block_q, block_k, num_q_blocks, offset=0):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _visible():
        q = q_ref[0]                                # (BQ, D) io dtype
        k = k_ref[0]                                # (BK, D)
        v = v_ref[0]
        do = do_ref[0]                              # (BQ, D)
        lse = lse_ref[0]                            # (BQ, 1)
        delta = delta_ref[0]                        # (BQ, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            # offset = sk - sq: bottom-right-aligned causal (KV-cache
            # chunked prefill; query i sees keys <= i + offset)
            mask = (qi * block_q + rows + offset) >= (ki * block_k + cols)
            s = jnp.where(mask, s, _NEG_INF)
        # fully-masked rows carry the fwd sentinel lse == _NEG_INF; without
        # the guard p = exp(-1e30 - (-1e30)) == 1 would leak garbage dk/dv
        p = jnp.where(lse <= _NEG_INF * 0.5, 0.0, jnp.exp(s - lse))
        pc = p.astype(do.dtype)
        # dv += p^T do
        dv_scr[:] += jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale)             # (BQ, BK) f32
        dsc = ds.astype(q.dtype)
        # dk += ds^T q
        dk_scr[:] += jax.lax.dot_general(dsc, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal:
        @pl.when((qi + 1) * block_q + offset > ki * block_k)
        def _():
            _visible()
    else:
        _visible()

    @pl.when(qi == num_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr,
               *, scale, causal, block_q, block_k, num_k_blocks, offset=0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _visible():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            # offset = sk - sq: bottom-right-aligned causal (KV-cache
            # chunked prefill; query i sees keys <= i + offset)
            mask = (qi * block_q + rows + offset) >= (ki * block_k + cols)
            s = jnp.where(mask, s, _NEG_INF)
        # masked-row guard: see _dkv_kernel
        p = jnp.where(lse <= _NEG_INF * 0.5, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ki * block_k < (qi + 1) * block_q + offset)
        def _():
            _visible()
    else:
        _visible()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq = sq // block_q
    nk = sk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (bh, sq, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          offset=sk - sq),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          offset=sk - sq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper on (bh, s, d) layout
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, window=None):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                  window)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               window=None):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                    window)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, window, res, do):
    if window is not None:
        raise WindowBackwardError(
            "flash attention with a window has a forward kernel only; "
            "train through the XLA attention with a band mask")
    q, k, v, out, lse = res
    return _bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k,
                interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _divisor_block(s: int, target: int) -> int:
    """Largest divisor of s that is <= target (so any seq length that the
    old fixed-128 default handled still divides cleanly)."""
    b = min(target, s)
    while s % b:
        b -= 1
    return b


def _auto_blocks(sq: int, sk: int):
    """Pick block sizes for the v5e VMEM budget: big blocks amortize grid
    overhead and keep the online-softmax VPU work per MXU op low. Up to
    1024×1024 the whole S×S f32 score tile (4MB) + accumulators fit VMEM,
    so short sequences run single-block (no online-softmax recurrence at
    all); longer sequences tile at <=512 (measured fastest at S>=2048).
    block_k is additionally capped at 1024 so the K/V tiles stay inside
    VMEM for skewed shapes (short query, very long KV). None = no
    TPU-friendly tiling."""
    if sq * sk <= 1024 * 1024 and sk <= 1024:
        return sq, sk
    bq, bk = _divisor_block(sq, 512), _divisor_block(sk, 512)
    if bq % 8 or bk % 8:
        return None   # sublane-unfriendly tiling (odd seq len)
    return bq, bk


def supported(q_shape, k_shape, causal: bool, window=None) -> bool:
    """Routing predicate (nn.functional): (B, S, H, D) shapes the kernel
    takes as they are. Ragged sequence lengths, un-repeated KV heads and
    causal queries with no visible key belong to the XLA sdpa path; so
    does a ``window`` (the last so many keys, the query's own included)
    that is not causal or not positive."""
    if _routing.auto_partitioned():
        return False
    if window is not None and (not causal or int(window) < 1):
        return False
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    sq, sk = q_shape[1], k_shape[1]
    if k_shape[2] != q_shape[2] or (causal and sq > sk):
        return False
    return _auto_blocks(sq, sk) is not None


def flash_attention_fn(q, k, v, causal: bool = False, scale=None,
                       block_q: int = None, block_k: int = None,
                       window: int = None):
    """Pure-jax flash attention on paddle layout (B, S, H, D).

    ``window`` (causal only): query i sees keys ``i + sk - sq - window <
    j <= i + sk - sq``; blocks outside that band are neither computed nor
    fetched. Forward only: its gradient raises ``WindowBackwardError``.

    Falls back to unblocked shapes by shrinking blocks; requires S to be a
    multiple of the (possibly shrunk) block size — callers with ragged
    shapes use the reference sdpa path (nn/functional.py).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if block_q is None or block_k is None:
        auto = _auto_blocks(sq, sk)
        if auto is None:
            raise ValueError(f"flash_attention: no TPU-friendly block "
                             f"tiling for seq ({sq},{sk})")
        abq, abk = auto
    block_q = min(block_q, sq) if block_q else abq
    block_k = min(block_k, sk) if block_k else abk
    if sq % block_q or sk % block_k:
        raise ValueError(f"flash_attention: seq ({sq},{sk}) not divisible by "
                         f"blocks ({block_q},{block_k})")
    if causal and sq > sk:
        # queries with no visible keys (bottom-right alignment needs
        # sk >= sq for every query to see at least one key)
        raise ValueError("flash_attention: causal requires sk >= sq")
    if k.shape[2] != h:
        raise ValueError("flash_attention: repeat kv heads before the kernel")
    if window is not None and (not causal or int(window) < 1):
        raise ValueError("flash_attention: a window is causal and >= 1")
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(x.shape[0] * x.shape[2],
                                             x.shape[1], x.shape[3])

    qb, kb, vb = to_bh(q), to_bh(k), to_bh(v)
    ob = _flash(qb, kb, vb, scale, bool(causal), block_q, block_k,
                _routing.use_interpret(),
                None if window is None else int(window))
    return jnp.swapaxes(ob.reshape(b, h, sq, d), 1, 2)


from paddle_tpu.ops.registry import register_op


@register_op("flash_attention",
             ref="paddle/phi/kernels/gpu/flash_attn_kernel.cu (capability analog)")
def flash_attention_op(q, k, v, causal=False, scale=None):
    return flash_attention_fn(q, k, v, causal=causal, scale=scale)
