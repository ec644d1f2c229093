"""Grouped expert matmuls and sigmoid top-k routing, as pure functions.

One grouped-matmul helper (``grouped_ffn``: rows sorted by expert drive
``lax.ragged_dot`` with per-expert row counts, a gated bias-free pair)
serves, through ``routed_ffn``, the eager AFMoE model (``models/afmoe.py``)
and the serving decoder (``inference/generate.py:_block_forward``). The training-side
``incubate/nn/moe.py`` keeps its own ungated, biased pair of
``ragged_dot``s: one helper for both would branch on its caller. ``routed_ffn`` is the routed
part of an AFMoE feed-forward for ONE CHIP'S SHARE of an expert-parallel
layer: the router scores every published expert, the weights are
normalised over the chosen ``top_k`` whether or not this chip holds them,
and only the held experts' part is computed. Pairs routed to absent
experts (and pairs of rows that are not live) are sorted into a trailing
group that ``ragged_dot`` does not compute: no capacity, no dropped
token, and nothing that stands in for the absent chips or their exchange.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["grouped_ffn", "sigmoid_topk_route", "routed_ffn"]


def grouped_ffn(x, w_gate_up, w_down, group_sizes):
    """SwiGLU ``(silu(x @ gate[g]) * (x @ up[g])) @ w_down[g]`` for rows
    ``x`` (M, H) sorted by group, ``group_sizes`` (G,) rows each;
    ``w_gate_up`` (G, H, 2F) holds gate|up side by side. Rows past the
    sizes' sum belong to no group and are not computed (their output is
    unspecified: select it away)."""
    h = lax.ragged_dot(x, w_gate_up, group_sizes)
    f = h.shape[-1] // 2
    return lax.ragged_dot(jax.nn.silu(h[:, :f]) * h[:, f:], w_down,
                          group_sizes)


def sigmoid_topk_route(x, router_w, expert_bias, top_k: int,
                       route_norm: bool, route_scale: float):
    """Scores ``sigmoid(x @ router_w)`` in float32 over every expert the
    router knows; the ``top_k`` of ``score + expert_bias`` are chosen (the
    bias selects only; ties go to the lowest index, as ``lax.top_k``
    does); the chosen scores, normalised over the chosen if
    ``route_norm``, times ``route_scale``.
    Returns ``(weights (T, top_k) float32, chosen (T, top_k) int32)``."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    _, select = lax.top_k(s + expert_bias.astype(jnp.float32), top_k)
    top = jnp.take_along_axis(s, select, axis=-1)
    if route_norm:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return top * route_scale, select.astype(jnp.int32)


def routed_ffn(x, router_w, expert_bias, w_gate_up, w_down, *, top_k: int,
               route_norm: bool, route_scale: float, expert_offset: int = 0,
               live=None):
    """The held experts' part of a routed SwiGLU feed-forward over tokens
    ``x`` (T, H). ``w_gate_up`` (held, H, 2F) and ``w_down`` (held, F, H)
    are the experts ``[expert_offset, expert_offset + held)`` of the
    ``router_w.shape[1]`` the router scores. ``live`` (T,) bool: rows that
    are not (a padded tail, a frozen slot) reach no expert.

    Returns ``(y (T, H), stats (3,) int32, chosen (T, top_k))``: pairs
    that landed on held experts, held experts with at least one, the
    largest count one took; and what the router chose."""
    T, H = x.shape
    held = w_gate_up.shape[0]
    w, sel = sigmoid_topk_route(x, router_w, expert_bias, top_k, route_norm,
                                route_scale)
    local = sel - expert_offset
    here = jnp.logical_and(local >= 0, local < held)
    if live is not None:
        here = jnp.logical_and(here, live[:, None])
    group = jnp.where(here, local, held).reshape(-1)      # (T*K,) by token
    order = jnp.argsort(group)                            # stable
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    xs = x[order // top_k]                                # sorted by expert
    y = grouped_ffn(xs, w_gate_up, w_down, sizes)
    n_here = jnp.sum(sizes)
    y = jnp.where((jnp.arange(T * top_k) < n_here)[:, None], y, 0)
    y = y[jnp.argsort(order)].reshape(T, top_k, H)
    out = jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)
    stats = jnp.stack([n_here, jnp.sum(sizes > 0), jnp.max(sizes)])
    return out.astype(x.dtype), stats.astype(jnp.int32), sel
