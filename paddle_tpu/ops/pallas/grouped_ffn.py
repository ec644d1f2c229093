"""The routed experts' SwiGLU feed-forward over rows sorted by expert —
Pallas TPU kernel.

``ops/moe.py:routed_ffn`` sorts a layer's token-expert pairs by held expert
and hands the rows ``x`` (M, H), the per-expert row counts and the held
experts' stacks (gate|up (G, H, 2F), down (G, F, H)) to one grouped
feed-forward. A decode step puts a few rows on each expert (1.5 in the
serving batch of 96 rows at one chip's share of the experts), so the call
is a read of the touched experts' weights and little else. XLA lowers the
pair of ``ragged_dot``s to its own grouped matmul, two calls a layer with
weight tiles of 512 x 512, plus the intermediate ``(M, 2F)`` between them.
This kernel is the whole feed-forward in one call:

- grid ``(G, F / tf)`` over held expert ``g`` and tile ``j`` of the
  expert's width: a step fetches expert ``g``'s gate, up and down tiles
  (``(H, tf)``, ``(H, tf)``, ``(tf, H)``) by index map, so each touched
  expert's weights cross HBM exactly once,
- the group sizes are the scalar-prefetch operand, and an EMPTY group's
  index maps repeat the block of the step before it (the previous live
  group's last tile, or the first live group's first tile where none came
  before): the pipeline issues no copy for a repeated block, and the
  step's compute is skipped (``pl.when``) — ``decode_attention``'s
  ``_live_block`` rule, applied to experts,
- all M rows stay in VMEM for the whole call, beside an f32 accumulator
  of the output: a live step loops over the row tiles of ``tm`` rows that
  hold the group's rows, with the weight tiles resident — the rows times
  the gate and up tiles (f32 accumulation), SwiGLU in VMEM, the product
  (rows of other groups masked to zero) times the down tile added into
  the accumulator; a group of more rows than one tile reads each weight
  tile once all the same,
- the output is written once, in x's dtype, at the last step. Rows past
  ``sum(sizes)`` are in no group and read zero (the caller selects them
  away).

The budgets are shares of one TensorCore's VMEM on the chip at hand
(``_vmem_capacity``): the F tile is the largest under ``_TILE_F`` that
divides F and whose double-buffered weight tiles fit the weight budget
(``_tile_f``); the rows resident take the rest of the plan's budget, so a
call of more rows than that holds is not served (``supported``) and stays
with XLA's pair. Forward only: ``ops/moe.py`` gives it the VJP of XLA's
pair.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _routing

__all__ = ["supported", "grouped_ffn"]


# Of one TensorCore's VMEM: the scoped VMEM the kernel asks Mosaic for
# (7/8), what its buffers may take of it (3/4), and of that what the weight
# tiles may (gate, up, down, double-buffered; at most 40 MiB), the rest
# being the rows resident (x and the output single-buffered, the f32
# accumulator). The v5e's 128 MiB gives 112, 96 and 40 MiB. AOT compiles
# for the v5e at H = F = 3072 in bf16 count the plan to within 1 MiB (the
# loop body's temporaries): plans of up to 120 MiB passed, and one of
# 162 MiB was refused against the chip's 128 MiB.
_WEIGHT_CAP = 40 * 1024 * 1024
_HOST_VMEM = 128 * 1024 * 1024
_TILE_F = 512
_ROW_TILE = 128
_DTYPES = (jnp.bfloat16, jnp.float32)


def _vmem_capacity() -> int:
    """One TensorCore's VMEM on the TPU at hand (``pltpu.get_tpu_info``);
    the v5e's off the TPU, where the kernel runs interpreted or compiles
    for a described v5e; 0 on a TPU that JAX does not describe, where
    nothing is served."""
    if jax.default_backend() != "tpu":
        return _HOST_VMEM
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except (ValueError, NotImplementedError):
        return 0


def _budgets() -> tuple[int, int, int]:
    """``(limit, budget, weights)``: scoped VMEM asked for, the plan's
    budget and the weight tiles' share of it, in bytes."""
    cap = _vmem_capacity()
    return cap * 7 // 8, cap * 3 // 4, min(_WEIGHT_CAP, cap * 5 // 16)


def _tile_f(H: int, F: int, itemsize: int, weights: int) -> int:
    """Largest F tile of whole lane tiles under ``_TILE_F`` that divides F
    and whose double-buffered gate, up and down tiles fit ``weights``
    bytes; 0 = none."""
    tf = _TILE_F
    while tf >= 128:
        if F % tf == 0 and 2 * 3 * H * tf * itemsize <= weights:
            return tf
        tf //= 2
    return 0


def _row_tile(M: int, itemsize: int) -> int:
    """Rows a loop trip computes: ``_ROW_TILE``, or M rounded up to whole
    sublane tiles where that is fewer."""
    sub = 32 // itemsize
    return min(_ROW_TILE, -(-M // sub) * sub)


def _plan(M: int, H: int, F: int, itemsize: int):
    """``(tm, tf, rows, limit)`` — row tile, F tile, the rows held (M
    rounded up to whole row tiles) and the scoped VMEM to ask for — or
    None where the call does not fit this chip's VMEM."""
    limit, budget, weights = _budgets()
    tf = _tile_f(H, F, itemsize, weights)
    if not tf or H % 128 or M < 1:
        return None
    tm = _row_tile(M, itemsize)
    rows = -(-M // tm) * tm
    need = 2 * 3 * H * tf * itemsize + rows * H * (2 * itemsize + 4)
    return (tm, tf, rows, limit) if need <= budget else None


def supported(rows: int, w_gate_up, w_down, dtype) -> bool:
    """``rows`` sorted rows of ``dtype`` through the held experts'
    ``w_gate_up`` (G, H, 2F) and ``w_down`` (G, F, H), all of one dtype,
    bf16 or f32, H and F whole lane tiles, the rows within the VMEM
    plan of the chip at hand."""
    if w_gate_up.ndim != 3 or w_down.ndim != 3:
        return False
    G, H, F2 = w_gate_up.shape
    if F2 % 2 or w_down.shape != (G, F2 // 2, H) or not G:
        return False
    dt = jnp.dtype(dtype)
    if not (dt == w_gate_up.dtype == w_down.dtype) or dt not in _DTYPES:
        return False
    return _plan(int(rows), H, F2 // 2, dt.itemsize) is not None


def _kernel(sizes_ref, offs_ref, grp_ref, frz_ref, x_ref, g_ref, u_ref,
            d_ref, o_ref, acc_ref, *, tm):
    g = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((g == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    size = sizes_ref[g]
    off = offs_ref[g]

    @pl.when(size > 0)
    def _group():
        def tile(t, carry):
            r = pl.multiple_of(t * tm, tm)
            xt = x_ref[pl.ds(r, tm), :]
            a = jnp.dot(xt, g_ref[0], preferred_element_type=jnp.float32)
            b = jnp.dot(xt, u_ref[0], preferred_element_type=jnp.float32)
            h = a * jax.nn.sigmoid(a) * b
            row = r + lax.broadcasted_iota(jnp.int32, h.shape, 0)
            h = jnp.where((row >= off) & (row < off + size), h, 0.0)
            acc_ref[pl.ds(r, tm), :] += jnp.dot(
                h.astype(xt.dtype), d_ref[0],
                preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(off // tm, (off + size + tm - 1) // tm, tile, 0)

    @pl.when((g == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fetch_order(sizes, nj: int):
    """Per group, the expert whose tiles its steps fetch and the F tile
    they stay on (-1: follow the grid's tile): a live group its own; an
    empty one the previous live group's last tile, or, with none before
    it, the first live group's first tile (expert 0's where none is
    live)."""
    G = sizes.shape[0]
    live = sizes > 0
    idx = jnp.arange(G, dtype=jnp.int32)
    last = lax.cummax(jnp.where(live, idx, -1))
    first = jnp.min(jnp.where(live, idx, G))
    first = jnp.where(first == G, 0, first)
    grp = jnp.where(live, idx, jnp.where(last >= 0, last, first))
    frz = jnp.where(live, -1, jnp.where(last >= 0, nj - 1, 0))
    return grp.astype(jnp.int32), frz.astype(jnp.int32)


@jax.jit
def grouped_ffn(x, w_gate_up, w_down, group_sizes):
    """SwiGLU ``(silu(x @ gate[g]) * (x @ up[g])) @ w_down[g]`` for rows
    ``x`` (M, H) sorted by group, ``group_sizes`` (G,) rows each;
    ``w_gate_up`` (G, H, 2F) holds gate|up side by side. Rows past the
    sizes' sum read zero. -> (M, H) in x's dtype."""
    M, H = x.shape
    G, _, F2 = w_gate_up.shape
    F = F2 // 2
    if not supported(M, w_gate_up, w_down, x.dtype):
        raise ValueError(f"grouped_ffn: {M} rows of {x.dtype} through "
                         f"experts {w_gate_up.shape} / {w_down.shape} "
                         f"{w_gate_up.dtype} are not served (see "
                         f"supported())")
    tm, tf, rows, limit = _plan(M, H, F, x.dtype.itemsize)
    nj = F // tf
    sizes = group_sizes.astype(jnp.int32)
    offs = jnp.cumsum(sizes) - sizes
    grp, frz = _fetch_order(sizes, nj)
    if rows != M:
        x = jnp.pad(x, ((0, rows - M), (0, 0)))

    def tile_j(g, j, frz_ref):
        return jnp.where(frz_ref[g] < 0, j, frz_ref[g])

    def gate(g, j, s_ref, o_ref, grp_ref, frz_ref):
        return (grp_ref[g], 0, tile_j(g, j, frz_ref))

    def up(g, j, s_ref, o_ref, grp_ref, frz_ref):
        return (grp_ref[g], 0, nj + tile_j(g, j, frz_ref))

    def down(g, j, s_ref, o_ref, grp_ref, frz_ref):
        return (grp_ref[g], tile_j(g, j, frz_ref), 0)

    def whole(g, j, *refs):
        return (0, 0)

    once = pl.Buffered(1)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(G, nj),
            in_specs=[pl.BlockSpec((rows, H), whole, pipeline_mode=once),
                      pl.BlockSpec((1, H, tf), gate),
                      pl.BlockSpec((1, H, tf), up),
                      pl.BlockSpec((1, tf, H), down)],
            out_specs=pl.BlockSpec((rows, H), whole, pipeline_mode=once),
            scratch_shapes=[pltpu.VMEM((rows, H), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, H), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=limit),
        # as XLA states its own grouped matmul's, every row and expert
        # counted: what the scheduler weighs the call by
        cost_estimate=pl.CostEstimate(
            flops=6 * rows * H * F, transcendentals=rows * F,
            bytes_accessed=(w_gate_up.size + w_down.size
                            + 2 * rows * H) * x.dtype.itemsize),
        interpret=_routing.use_interpret(),
        name="grouped_ffn",
    )(sizes, offs, grp, frz, x, w_gate_up, w_gate_up, w_down)
    return out[:M]
