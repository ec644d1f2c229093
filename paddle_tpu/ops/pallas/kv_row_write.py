"""The decode step's token-row write — Pallas TPU kernel.

A decode step writes one new key row and one new value row per sequence
into a layer's cache, each sequence at its own position. In XLA that is
``jax.vmap(dynamic_update_slice)``: ONE ``stablehlo.scatter`` (sorted,
unique indices), which the v5e compiler expands into a ``while`` over the
batch — a bounds check, a ``dynamic-slice``, a select and a
``dynamic-update-slice`` a row a buffer, 2.8-3.7 us a trip whatever the
bytes (96 rows x 10 buffers: 3.5 ms of a 17.2 ms serving step). This
kernel writes a layer's K row and V row for all ``B`` sequences in one
call, 0.3-1.0 us a row for both buffers (measured on the v5e at the
serving cells' own cache shapes: PERF.md section 6, PR 34):

- grid ``(B,)``; the write positions ``at`` are the scalar-prefetch
  operand, so the index maps send grid step ``b`` to the block that holds
  position ``at[b]`` of row ``b`` and nothing else of the caches moves,
- both caches are aliased in and out (``input_output_aliases``): the
  buffers stay where they are, as a donated carry's must,
- token-major ``(B, L, KV, D)`` (no decoder builds it: ROADMAP D15): a
  position is a whole ``(KV, D)`` slab, so the block is that slab and
  the body copies the new row into it; the cache is never read (its
  input stays in HBM, ``pl.ANY``),
- head-major ``(B, KV, L, D)`` (every decoder's cache; a rolling buffer
  is the same with the caller's ``pos % L``): a position is ONE ROW of a
  packed sublane tile, so the block is the tile of ``T`` positions around
  it (16 / 8 / 32 for 2- / 4- / 1-byte dtypes), read, the row at
  ``at[b] % T`` replaced by a select, and written back.

``at`` is clamped to ``[0, L - 1]``, which is what the scatter's clip
mode does (a row past its budget sits at ``max_len - 1``). The bytes
written are the bytes the scatter wrote: the result is bit-equal.
Inference-path only (no custom VJP).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _routing

__all__ = ["supported", "kv_row_write"]


# positions in one packed sublane tile, by the cache dtype's byte width
_TILE = {4: 8, 2: 16, 1: 32}
_DTYPES = (jnp.bfloat16, jnp.float32, jnp.int8)


def supported(buf, t, head_major: bool) -> bool:
    """One new row ``t`` a sequence into the 4-D cache buffer ``buf`` of
    its own dtype: bf16 / f32 / int8, a head size of whole lane tiles
    and, head-major, a length of whole sublane tiles. (A quantized
    cache's ``(..., 1)`` scale leaf is not: it keeps XLA's scatter.)"""
    if buf.ndim != 4 or t.ndim != 4 or buf.dtype != t.dtype \
            or buf.dtype not in _DTYPES:
        return False
    B, KV, L, D = _dims(buf.shape, head_major)
    if t.shape != _row_shape(B, KV, D, head_major) or D % 128:
        return False
    return not head_major or L % _TILE[buf.dtype.itemsize] == 0


def _dims(shape, head_major: bool):
    if head_major:
        B, KV, L, D = shape
    else:
        B, L, KV, D = shape
    return B, KV, L, D


def _row_shape(B, KV, D, head_major: bool):
    return (B, KV, 1, D) if head_major else (B, 1, KV, D)


def _copy_kernel(at_ref, k_ref, v_ref, kc_ref, vc_ref, ko_ref, vo_ref):
    ko_ref[...] = k_ref[...]
    vo_ref[...] = v_ref[...]


def _tile_kernel(at_ref, k_ref, v_ref, kc_ref, vc_ref, ko_ref, vo_ref, *, T):
    r = at_ref[pl.program_id(0)] % T
    rows = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[1:], 1)
    ko_ref[0] = jnp.where(rows == r, k_ref[0], kc_ref[0])
    vo_ref[0] = jnp.where(rows == r, v_ref[0], vc_ref[0])


@functools.partial(jax.jit, static_argnames=("head_major",))
def kv_row_write(kc, vc, k, v, at, head_major: bool):
    """Caches ``kc``/``vc`` with row ``b``'s new key ``k[b]`` and value
    ``v[b]`` written at position ``at[b]`` (``(B,)`` int32, clamped to
    the buffer) -> ``(kc, vc)``, in place where the caller gives the
    buffers up. Head-major: caches (B, KV, L, D), rows (B, KV, 1, D);
    token-major: (B, L, KV, D) and (B, 1, KV, D)."""
    if not (supported(kc, k, head_major) and supported(vc, v, head_major)
            and kc.shape == vc.shape and kc.dtype == vc.dtype):
        raise ValueError(f"kv_row_write: caches {kc.shape}/{vc.shape} "
                         f"{kc.dtype}/{vc.dtype} with rows {k.shape}/"
                         f"{v.shape} {k.dtype} are not served (see "
                         f"supported())")
    B, KV, L, D = _dims(kc.shape, head_major)
    at = jnp.clip(jnp.asarray(at, jnp.int32).reshape(B), 0, L - 1)
    new = pl.BlockSpec(_row_shape(1, KV, D, head_major),
                       lambda b, at_ref: (b, 0, 0, 0))
    if head_major:
        T = _TILE[kc.dtype.itemsize]
        blk = pl.BlockSpec((1, KV, T, D),
                           lambda b, at_ref: (b, 0, at_ref[b] // T, 0))
        kernel, held = functools.partial(_tile_kernel, T=T), blk
    else:
        blk = pl.BlockSpec((1, 1, KV, D),
                           lambda b, at_ref: (b, at_ref[b], 0, 0))
        # written whole, never read: the aliased inputs stay in HBM
        kernel, held = _copy_kernel, pl.BlockSpec(memory_space=pl.ANY)
    like = jax.ShapeDtypeStruct(kc.shape, kc.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B,),
            in_specs=[new, new, held, held], out_specs=[blk, blk]),
        out_shape=[like, like],
        input_output_aliases={3: 0, 4: 1},
        interpret=_routing.use_interpret(),
        name="kv_row_write",
    )(at, k, v, kc, vc)
