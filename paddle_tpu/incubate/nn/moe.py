"""Mixture-of-Experts layers (incubate/distributed/models/moe/moe_layer.py analog).

TPU-native redesign of the reference's MoEScatter/MoEGather dispatch
(moe_layer.py:99): instead of ragged per-expert token counts exchanged by
NCCL all-to-all, tokens are placed into a dense capacity-padded
``(n_experts, capacity, d)`` buffer with a single cumsum-position scatter,
experts run as ONE batched einsum over stacked weights (an MXU-shaped
grouped GEMM), and outputs gather straight back to token order. Every step
is a registered tape op, so the layer trains eagerly AND traces under jit;
with the stacked weights placed ``Shard(0)`` over an ``'ep'`` mesh axis the
einsum compiles to the expert-parallel all-to-all exchange.

``MoEMLP`` is the performance path (stacked expert FFN, no Python loop).
``MoELayer`` keeps the reference's list-of-expert-Layers API for
heterogeneous experts (same one-shot dispatch; per-expert calls remain a
static loop over the capacity buffer).

``MoEMLP(dispatch="ragged")`` selects the DROPLESS grouped-GEMM form:
tokens sorted by expert drive ``lax.ragged_dot`` with per-expert row
counts — no capacity padding, no dropped tokens. Measured (v5e, d=1024
f=4096 E=8 top2, 8k tokens, f32, jit fwd): ragged 15.7ms vs capacity
23.9ms (1.5x). When the active mesh has an ``'ep'`` axis of size > 1 the
ragged path auto-selects the dropless EXPERT-PARALLEL shard_map kernel
(``_make_ragged_ep_ffn``: per-shard ragged_dot over the local experts +
psum combine) — dropless ACROSS ep, the reference's global_scatter
capability. The capacity path remains available as the GSPMD-einsum
fallback form.
"""

from __future__ import annotations

from typing import List, Optional

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.ops.registry import OpDef, apply_op

__all__ = ["MoEMLP", "MoELayer"]


def _make_ragged_ffn(activation: str, top_k: int, n_experts: int):
    """Dropless grouped-GEMM expert FFN over lax.ragged_dot: tokens are
    sorted by expert, per-expert row counts drive the ragged contraction —
    no capacity buffer, no dropped tokens (the megablox/grouped-GEMM form;
    reference capability analog: the NCCL variable-count all-to-all path in
    incubate/distributed/models/moe/moe_layer.py). This is the no-mesh
    form; with an ep>1 mesh _make_ragged_ep_ffn takes over."""
    import jax.numpy as jnp
    from jax import lax

    # the same activation impl the capacity path uses (F.gelu is exact,
    # jax.nn.gelu defaults to the tanh approximation — mixing them skews
    # parity between dispatch modes)
    act_api = getattr(F, activation)
    act = act_api.op.impl if hasattr(act_api, "op") else act_api

    def impl(tokens, gatev, topi, w1, b1, w2, b2):
        T, H = tokens.shape
        e_flat = jnp.transpose(topi).reshape(-1)          # (KT,) k-major
        g_flat = jnp.transpose(gatev).reshape(-1)
        order = jnp.argsort(e_flat)                       # stable
        inv = jnp.argsort(order)
        rep = jnp.tile(tokens, (top_k, 1))[order]         # (KT, H) sorted
        gs = jnp.bincount(e_flat, length=n_experts).astype(jnp.int32)
        e_sorted = e_flat[order]
        h = lax.ragged_dot(rep, w1, gs) + b1.reshape(n_experts, -1)[e_sorted]
        h = act(h)
        y = lax.ragged_dot(h, w2, gs) + b2.reshape(n_experts, -1)[e_sorted]
        y = y[inv] * g_flat[:, None]
        return y.reshape(top_k, T, H).sum(axis=0)

    return impl


_RAGGED_CACHE: dict = {}


def _ragged_ffn_op(activation: str, top_k: int, n_experts: int):
    """Anonymous tape op (not in the public registry: one instance per
    (activation, top_k, E) specialization)."""
    key = (activation, top_k, n_experts)
    if key not in _RAGGED_CACHE:
        opdef = OpDef(f"moe_ragged_ffn<{activation},{top_k},{n_experts}>",
                      _make_ragged_ffn(activation, top_k, n_experts))
        _RAGGED_CACHE[key] = lambda *args: apply_op(opdef, args, {})
    return _RAGGED_CACHE[key]


def _make_ragged_ep_ffn(activation: str, top_k: int, n_experts: int,
                        mesh, ep_axis: str, token_axes: tuple):
    """DROPLESS expert-parallel grouped GEMM (shard_map over the ep axis).

    The reference reaches dropless-EP with variable-count NCCL all-to-all
    (moe_layer.py:99 MoEScatter + global_scatter). XLA wants static
    shapes, so the TPU-native form inverts the exchange: tokens stay
    dp-sharded and REPLICATED over ep (their natural GSPMD state when the
    batch shards over dp), experts stay Shard(0) over ep, and each ep
    shard runs lax.ragged_dot over ONLY the rows routed to its local
    experts — the globally-sorted assignment array is dynamically rolled
    so the local expert region starts at row 0, and group_sizes cover
    just the local experts (trailing rows are outside every group, so the
    kernel skips them). A single psum over ep combines the per-shard
    partial outputs. No capacity buffer, no drops, no padding waste;
    the collectives (implicit replication + psum) ride ICI.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    act_api = getattr(F, activation)
    act = act_api.op.impl if hasattr(act_api, "op") else act_api
    ep = mesh.shape[mesh.dim_names.index(ep_axis)]
    if n_experts % ep:
        raise ValueError(
            f"dropless EP MoE needs n_experts ({n_experts}) divisible by "
            f"the '{ep_axis}' mesh size ({ep})")
    e_local = n_experts // ep
    axes_entry = (token_axes if len(token_axes) > 1 else
                  (token_axes[0] if token_axes else None))
    tok_spec = P(axes_entry, None)

    def local_fn(tokens, gatev, topi, w1, b1, w2, b2):
        T, H = tokens.shape
        g = lax.axis_index(ep_axis)
        e_flat = jnp.transpose(topi).reshape(-1)           # (KT,) global ids
        g_flat = jnp.transpose(gatev).reshape(-1)
        order = jnp.argsort(e_flat)
        inv = jnp.argsort(order)
        rep = jnp.tile(tokens, (top_k, 1))[order]          # sorted by expert
        gs = jnp.bincount(e_flat, length=n_experts).astype(jnp.int32)
        start = (jnp.cumsum(gs) - gs)[g * e_local]         # rows before ours
        gs_local = lax.dynamic_slice(gs, (g * e_local,), (e_local,))
        rolled = jnp.roll(rep, -start, axis=0)
        e_rolled = jnp.roll(e_flat[order], -start) - g * e_local
        e_rolled = jnp.clip(e_rolled, 0, e_local - 1)
        h = lax.ragged_dot(rolled, w1, gs_local) \
            + b1.reshape(e_local, -1)[e_rolled]
        h = act(h)
        y = lax.ragged_dot(h, w2, gs_local) \
            + b2.reshape(e_local, -1)[e_rolled]
        n_local = jnp.sum(gs_local)
        valid = jnp.arange(top_k * T) < n_local
        y = jnp.where(valid[:, None], y, 0.0)              # select: kills NaNs
        y = jnp.roll(y, start, axis=0)[inv] * g_flat[:, None]
        out = y.reshape(top_k, T, H).sum(axis=0)
        return lax.psum(out, ep_axis)

    mapped = shard_map(
        local_fn, mesh=mesh.jax_mesh,
        in_specs=(tok_spec, tok_spec, tok_spec,
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None)),
        out_specs=tok_spec, check_vma=False)

    def impl(tokens, gatev, topi, w1, b1, w2, b2):
        return mapped(tokens, gatev, topi, w1, b1, w2, b2)

    return impl


def _ragged_ep_ffn_op(activation: str, top_k: int, n_experts: int,
                      mesh, ep_axis: str, token_axes: tuple):
    key = (activation, top_k, n_experts, mesh.jax_mesh, ep_axis, token_axes)
    if key not in _RAGGED_CACHE:
        opdef = OpDef(
            f"moe_ragged_ep_ffn<{activation},{top_k},{n_experts},{ep_axis}>",
            _make_ragged_ep_ffn(activation, top_k, n_experts, mesh,
                                ep_axis, token_axes))
        _RAGGED_CACHE[key] = lambda *args: apply_op(opdef, args, {})
    return _RAGGED_CACHE[key]


def _topk_gates(probs, top_k: int, normalize_topk: bool):
    """Shared gating: top-k expert selection + optional renormalization
    (single source for the capacity AND ragged dispatch modes)."""
    gatev, topi = paddle.topk(probs, top_k, axis=-1)      # (T, K) each
    if normalize_topk and top_k > 1:
        gatev = gatev / paddle.sum(gatev, axis=-1, keepdim=True)
    return gatev, topi


def _one_shot_dispatch(tokens, probs, n_experts: int, top_k: int,
                       capacity: int, normalize_topk: bool):
    """Single top-k dispatch shared by both layers — no per-k argsort.

    Returns (buf, slot, keep, gate) where
      buf  (E*C, H)  capacity-padded expert buffers (flat),
      slot (K*T,)    flat buffer slot per assignment (k-major order, so
                     top-1 assignments win capacity over top-2),
      keep (K*T,)    capacity mask,
      gate (K*T, 1)  gate weight per assignment.
    All are graph-connected Tensors (the tape/jit sees one scatter).
    """
    gatev, topi = _topk_gates(probs, top_k, normalize_topk)

    # k-major flatten: assignment order (k=0 tokens..., k=1 tokens...)
    e_flat = paddle.flatten(paddle.transpose(topi, [1, 0]))          # (K*T,)
    gate_flat = paddle.flatten(paddle.transpose(gatev, [1, 0]))      # (K*T,)

    # position bookkeeping in int32: a bf16 cumsum (AMP activations) cannot
    # represent counts above 256 and silently collides capacity slots
    onehot = F.one_hot(e_flat, n_experts).astype("int32")            # (KT, E)
    # 0-based arrival position of each assignment inside its expert
    pos = paddle.sum(paddle.cumsum(onehot, axis=0) * onehot,
                     axis=-1) - 1                                    # (KT,)
    keep = (pos < capacity).astype(tokens.dtype)                     # (KT,)
    slot = e_flat.astype("int32") * capacity + paddle.clip(
        pos, 0, capacity - 1)                                        # (KT,)

    tokens_rep = paddle.tile(tokens, [top_k, 1])                     # (KT, H)
    buf = paddle.scatter_nd_add(
        paddle.zeros([n_experts * capacity, tokens.shape[1]], tokens.dtype),
        paddle.unsqueeze(slot, -1),
        tokens_rep * paddle.unsqueeze(keep, -1))
    return buf, slot, keep, paddle.unsqueeze(gate_flat, -1)


def _one_shot_combine(y_flat, slot, keep, gate, top_k: int, T: int):
    """Gather per-assignment outputs back to token order and mix by gate."""
    picked = paddle.gather(y_flat, slot)                             # (KT, H)
    picked = picked * paddle.unsqueeze(keep, -1) * gate
    per_k = paddle.reshape(picked, [top_k, T, y_flat.shape[-1]])
    return paddle.sum(per_k, axis=0)                                 # (T, H)


def _aux_loss(probs, top1, n_experts: int):
    """GShard load-balancing loss: E * sum_e mean(p_e) * frac(top1 == e)."""
    me = paddle.mean(probs, axis=0)
    ce = paddle.mean(F.one_hot(top1, n_experts).astype("float32"), axis=0)
    return paddle.sum(me * ce) * n_experts


class MoEMLP(nn.Layer):
    """Stacked-expert FFN: ``y = act(x @ w1 + b1) @ w2 + b2`` per expert,
    run as one grouped einsum over weights ``(E, H, F)`` / ``(E, F, H)``.

    Place ``w1/b1/w2/b2`` with ``Shard(0)`` over an ``'ep'`` mesh axis for
    expert parallelism (``ep_plan()`` builds the placement dict). Matches
    the reference's grouped dispatch capability
    (incubate/distributed/models/moe/moe_layer.py:99) in the TPU-native
    stacked form.
    """

    def __init__(self, d_model: int, d_hidden: int, n_experts: int,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 activation: str = "gelu", normalize_topk: bool = True,
                 gate: Optional[nn.Layer] = None,
                 dispatch: str = "capacity", ep_axis: str = "ep"):
        super().__init__()
        if dispatch not in ("capacity", "ragged"):
            raise ValueError("dispatch must be 'capacity' or 'ragged'")
        self.ep_axis = ep_axis
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.n_experts = n_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.normalize_topk = normalize_topk
        self.dispatch = dispatch
        self.gate = gate or nn.Linear(d_model, n_experts, bias_attr=False)
        bound = d_model ** -0.5
        init = nn.initializer.Uniform(-bound, bound)
        self.w1 = self.create_parameter([n_experts, d_model, d_hidden],
                                        default_initializer=init)
        self.b1 = self.create_parameter([n_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter([n_experts, d_hidden, d_model],
                                        default_initializer=init)
        self.b2 = self.create_parameter([n_experts, 1, d_model], is_bias=True)
        self.aux_loss = None

    def _ep_mesh(self):
        """The active mesh when expert parallelism applies (ep axis
        present with size > 1), else None (single-device ragged path)."""
        from paddle_tpu.parallel.mesh import get_mesh
        mesh = get_mesh()
        if (mesh is not None and self.ep_axis in mesh.dim_names
                and mesh.shape[mesh.dim_names.index(self.ep_axis)] > 1):
            return mesh
        return None

    def ep_plan(self, mesh, axis: str = None) -> dict:
        """Param-name -> placements dict for ShardedTrainer: stacked expert
        weights Shard(0) over `axis` (default: this layer's ep_axis),
        everything else replicated."""
        from paddle_tpu.parallel import Replicate, Shard
        idx = mesh.dim_names.index(axis or self.ep_axis)
        plan = {}
        for name, _ in self.named_parameters():
            pls = [Replicate()] * mesh.ndim
            if name.split(".")[-1] in ("w1", "b1", "w2", "b2"):
                pls[idx] = Shard(0)
            plan[name] = pls
        return plan

    def capacity(self, n_tokens: int) -> int:
        c = int(self.capacity_factor * n_tokens * self.top_k / self.n_experts)
        return max(c, self.top_k)

    def forward(self, x):
        B, S, H = x.shape
        T = B * S
        tokens = paddle.reshape(x, [T, H])
        logits = self.gate(tokens)
        probs = F.softmax(logits, axis=-1)
        self.aux_loss = _aux_loss(probs, paddle.argmax(probs, axis=-1),
                                  self.n_experts)

        if self.dispatch == "ragged":
            gatev, topi = _topk_gates(probs, self.top_k, self.normalize_topk)
            mesh = self._ep_mesh()
            if mesh is not None:
                # dropless expert parallelism: per-shard ragged_dot over the
                # ep-sharded stacked weights + psum combine (see
                # _make_ragged_ep_ffn). Token dim stays sharded over dp.
                token_axes = tuple(a for a in ("dp",)
                                   if a in mesh.dim_names
                                   and mesh.shape[mesh.dim_names.index(a)] > 1)
                ffn = _ragged_ep_ffn_op(self.activation, self.top_k,
                                        self.n_experts, mesh, self.ep_axis,
                                        token_axes)
            else:
                ffn = _ragged_ffn_op(self.activation, self.top_k,
                                     self.n_experts)
            out = ffn(tokens, gatev, topi, self.w1, self.b1, self.w2,
                      self.b2)
            return paddle.reshape(out, [B, S, H])

        C = self.capacity(T)
        buf, slot, keep, gate = _one_shot_dispatch(
            tokens, probs, self.n_experts, self.top_k, C,
            self.normalize_topk)

        # grouped GEMMs over the expert axis — exactly the MXU-batched form
        ebuf = paddle.reshape(buf, [self.n_experts, C, H])           # (E,C,H)
        h = paddle.einsum("ech,ehf->ecf", ebuf, self.w1) + self.b1
        h = getattr(F, self.activation)(h)
        y = paddle.einsum("ecf,efh->ech", h, self.w2) + self.b2      # (E,C,H)
        y_flat = paddle.reshape(y, [self.n_experts * C, H])

        out = _one_shot_combine(y_flat, slot, keep, gate, self.top_k, T)
        return paddle.reshape(out, [B, S, H])


class MoELayer(nn.Layer):
    """Reference-API MoE over a list of expert Layers (moe_layer.py analog).

    Uses the same one-shot top-k dispatch as MoEMLP; expert calls are a
    static loop over the dense capacity buffer (tape-recorded Tensor ops
    throughout — traces under jit). For homogeneous FFN experts prefer
    MoEMLP, whose stacked weights shard over 'ep'.
    """

    def __init__(self, d_model: int, experts: List[nn.Layer],
                 gate: Optional[nn.Layer] = None, top_k: int = 2,
                 capacity_factor: float = 1.25, group=None,
                 recompute_interval: int = 0, normalize_topk: bool = False):
        super().__init__()
        self.d_model = d_model
        self.experts = nn.LayerList(experts)
        self.n_experts = len(experts)
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.normalize_topk = normalize_topk
        self.gate = gate or nn.Linear(d_model, self.n_experts, bias_attr=False)
        self.aux_loss = None

    def forward(self, x):
        B, S, H = x.shape
        T = B * S
        tokens = paddle.reshape(x, [T, H])
        logits = self.gate(tokens)
        probs = F.softmax(logits, axis=-1)
        self.aux_loss = _aux_loss(probs, paddle.argmax(probs, axis=-1),
                                  self.n_experts)

        C = max(int(self.capacity_factor * T * self.top_k / self.n_experts),
                self.top_k)
        buf, slot, keep, gate = _one_shot_dispatch(
            tokens, probs, self.n_experts, self.top_k, C,
            self.normalize_topk)

        ebuf = paddle.reshape(buf, [self.n_experts, C, H])
        outs = [self.experts[e](ebuf[e]) for e in range(self.n_experts)]
        y_flat = paddle.reshape(paddle.stack(outs), [self.n_experts * C, -1])

        out = _one_shot_combine(y_flat, slot, keep, gate, self.top_k, T)
        return paddle.reshape(out, [B, S, out.shape[-1]])
