"""Grouped expert matmuls and sigmoid top-k routing, as pure functions.

One grouped feed-forward (``grouped_ffn``: rows sorted by expert, per-expert
row counts, a gated bias-free SwiGLU) serves, through ``routed_ffn``, the
eager AFMoE model (``models/afmoe.py``) and the serving decoder
(``inference/generate.py:_block_forward``). Where ``kernel_route`` holds
— the kernels' backend, no mesh, shapes the kernel takes — it is one Pallas
call (``ops/pallas/grouped_ffn.py``) that reads each touched expert's
weights once and nothing of an untouched one, differentiable through the
VJP of XLA's form; elsewhere it is XLA's pair of ``lax.ragged_dot``s with
the SwiGLU between them. The training-side ``incubate/nn/moe.py`` keeps
its own ungated, biased pair of ``ragged_dot``s: one helper for both would
branch on its caller. ``routed_ffn`` is the routed
part of an AFMoE feed-forward for ONE CHIP'S SHARE of an expert-parallel
layer: the router scores every published expert, the weights are
normalised over the chosen ``top_k`` whether or not this chip holds them,
and only the held experts' part is computed. Pairs routed to absent
experts (and pairs of rows that are not live) are sorted into a trailing
group that ``grouped_ffn`` does not compute: no capacity, no dropped
token, and nothing that stands in for the absent chips or their exchange.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_ffn", "kernel_route", "sigmoid_topk_route",
           "routed_ffn"]


def kernel_route(rows: int, w_gate_up, w_down, dtype,
                 sharded=False) -> bool:
    """Whether ``grouped_ffn`` over ``rows`` sorted rows of ``dtype``
    through these experts is the Pallas kernel: a backend the kernels run
    on (``_routing.kernel_backend``: a TPU, or
    ``flags.decode_attention_interpret`` for the CPU tests), no mesh (the
    decoder's ``sharded``, or a framework mesh GSPMD would split the
    trace over: a Mosaic kernel cannot be partitioned), and shapes the
    kernel's ``supported`` takes. A trace-time fact of shapes, backend and
    mesh, which the serving engine counts (``moe_ffn_kernel_layers``)."""
    from paddle_tpu.ops.pallas import _routing
    from paddle_tpu.ops.pallas import grouped_ffn as _gf
    return bool(not sharded and _routing.kernel_backend()
                and not _routing.auto_partitioned()
                and _gf.supported(rows, w_gate_up, w_down, dtype))


def _ragged_pair(x, w_gate_up, w_down, group_sizes):
    """XLA's form: two ``lax.ragged_dot``s with the SwiGLU between them."""
    h = lax.ragged_dot(x, w_gate_up, group_sizes)
    f = h.shape[-1] // 2
    return lax.ragged_dot(jax.nn.silu(h[:, :f]) * h[:, f:], w_down,
                          group_sizes)


@jax.custom_vjp
def _kernel_pair(x, w_gate_up, w_down, group_sizes):
    from paddle_tpu.ops.pallas import grouped_ffn as _gf
    return _gf.grouped_ffn(x, w_gate_up, w_down, group_sizes)


def _kernel_pair_fwd(x, w_gate_up, w_down, group_sizes):
    return (_kernel_pair(x, w_gate_up, w_down, group_sizes),
            (x, w_gate_up, w_down, group_sizes))


def _kernel_pair_bwd(res, g):
    """The VJP of XLA's form at the same inputs: the kernel computes the
    same function, forward only."""
    x, w_gate_up, w_down, group_sizes = res
    _, vjp = jax.vjp(lambda a, b, c: _ragged_pair(a, b, c, group_sizes),
                     x, w_gate_up, w_down)
    return (*vjp(g), None)


_kernel_pair.defvjp(_kernel_pair_fwd, _kernel_pair_bwd)


def grouped_ffn(x, w_gate_up, w_down, group_sizes, sharded=False):
    """SwiGLU ``(silu(x @ gate[g]) * (x @ up[g])) @ w_down[g]`` for rows
    ``x`` (M, H) sorted by group, ``group_sizes`` (G,) rows each;
    ``w_gate_up`` (G, H, 2F) holds gate|up side by side. Rows past the
    sizes' sum belong to no group and are not computed (their output is
    unspecified: select it away). The Pallas kernel where
    ``kernel_route`` holds (``sharded``: the caller runs under a mesh),
    else XLA's ``ragged_dot`` pair."""
    if kernel_route(x.shape[0], w_gate_up, w_down, x.dtype, sharded):
        return _kernel_pair(x, w_gate_up, w_down, group_sizes)
    return _ragged_pair(x, w_gate_up, w_down, group_sizes)


def sigmoid_topk_route(x, router_w, expert_bias, top_k: int,
                       route_norm: bool, route_scale: float,
                       n_group: int = 1, topk_group: int = 1):
    """Scores ``sigmoid(x @ router_w)`` in float32 over every expert the
    router knows; the ``top_k`` of ``score + expert_bias`` are chosen (the
    bias selects only; ties go to the lowest index, as ``lax.top_k``
    does); the chosen scores, normalised over the chosen if
    ``route_norm``, times ``route_scale``.

    GROUP-LIMITED (``n_group > 1``, DeepSeek-V3's ``MoEGate``): the
    experts fall into ``n_group`` equal groups in index order, a group
    scores the sum of its two largest ``score + expert_bias``, and the
    ``top_k`` are chosen among the experts of the ``topk_group`` best
    groups only. ``n_group = 1`` traces none of it.
    Returns ``(weights (T, top_k) float32, chosen (T, top_k) int32)``."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    choice = s + expert_bias.astype(jnp.float32)
    if n_group > 1:
        g = choice.reshape(choice.shape[:-1] + (n_group, -1))
        _, best = lax.top_k(jnp.sum(lax.top_k(g, 2)[0], -1), topk_group)
        kept = jnp.any(best[..., None] == jnp.arange(n_group), -2)
        choice = jnp.where(kept[..., None], g, -jnp.inf).reshape(
            choice.shape)
    _, select = lax.top_k(choice, top_k)
    top = jnp.take_along_axis(s, select, axis=-1)
    if route_norm:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return top * route_scale, select.astype(jnp.int32)


# the bytes of a (token-expert pairs, hidden) buffer a routed feed-forward
# may gather at once; past it the tokens go in slabs (``routed_ffn``)
_PAIR_BYTES = 256 << 20


def _slab_tokens(T: int, top_k: int, H: int, itemsize: int) -> int:
    """Tokens a slab of ``routed_ffn``: all ``T`` where their pairs' rows
    fit ``_PAIR_BYTES``, else the largest power of two that does."""
    if T * top_k * H * itemsize <= _PAIR_BYTES:
        return T
    step = 1
    while 2 * step * top_k * H * itemsize <= _PAIR_BYTES:
        step *= 2
    return step


def _held_part(x, w, sel, w_gate_up, w_down, expert_offset, live, sharded):
    """The held experts' weighted sum (T, H) float32 over tokens ``x``
    with routing weights ``w`` and choices ``sel`` (T, top_k), the rows a
    held expert took (held,) and their sum."""
    T, H = x.shape
    top_k = sel.shape[1]
    held = w_gate_up.shape[0]
    local = sel - expert_offset
    here = jnp.logical_and(local >= 0, local < held)
    if live is not None:
        here = jnp.logical_and(here, live[:, None])
    group = jnp.where(here, local, held).reshape(-1)      # (T*K,) by token
    order = jnp.argsort(group)                            # stable
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    xs = x[order // top_k]                                # sorted by expert
    y = grouped_ffn(xs, w_gate_up, w_down, sizes, sharded)
    n_here = jnp.sum(sizes)
    y = jnp.where((jnp.arange(T * top_k) < n_here)[:, None], y, 0)
    y = y[jnp.argsort(order)].reshape(T, top_k, H)
    out = jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)
    return out, sizes, n_here


def routed_ffn(x, router_w, expert_bias, w_gate_up, w_down, *, top_k: int,
               route_norm: bool, route_scale: float, expert_offset: int = 0,
               live=None, sharded=False, n_group: int = 1,
               topk_group: int = 1):
    """The held experts' part of a routed SwiGLU feed-forward over tokens
    ``x`` (T, H). ``w_gate_up`` (held, H, 2F) and ``w_down`` (held, F, H)
    are the experts ``[expert_offset, expert_offset + held)`` of the
    ``router_w.shape[1]`` the router scores, over ``n_group`` groups of
    which ``topk_group`` are kept (``sigmoid_topk_route``). ``live`` (T,)
    bool: rows that are not (a padded tail, a frozen slot) reach no
    expert.

    Returns ``(y (T, H), stats (3,) int32, chosen (T, top_k))``: pairs
    that landed on held experts, held experts with at least one, the
    largest count one took; and what the router chose."""
    T, H = x.shape
    w, sel = sigmoid_topk_route(x, router_w, expert_bias, top_k, route_norm,
                                route_scale, n_group, topk_group)
    step = _slab_tokens(T, top_k, H, x.dtype.itemsize)
    if step >= T:
        out, sizes, n_here = _held_part(x, w, sel, w_gate_up, w_down,
                                        expert_offset, live, sharded)
    else:
        # a long prefill's gathered pairs in slabs of tokens: each slab's
        # (pairs, hidden) buffers are alive alone, not T x top_k rows of
        # them at once (1.9 GB a buffer at 16384 tokens x 8 x 7168)
        parts = [_held_part(
            x[i:i + step], w[i:i + step], sel[i:i + step], w_gate_up,
            w_down, expert_offset,
            None if live is None else live[i:i + step], sharded)
            for i in range(0, T, step)]
        out = jnp.concatenate([o for o, _, _ in parts])
        sizes = sum(z for _, z, _ in parts)
        n_here = jnp.sum(sizes)
    stats = jnp.stack([n_here, jnp.sum(sizes > 0), jnp.max(sizes)])
    return out.astype(x.dtype), stats.astype(jnp.int32), sel
