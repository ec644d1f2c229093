"""Ring attention + Ulysses — long-context / context parallelism.

NEW capability relative to the reference (SURVEY §5.7: "No ring attention,
no Ulysses, no blockwise CP exists in this snapshot"); the reference tops
out at Megatron-SP + SEP axis + recompute. TPU-native design:

- **Ring attention** (blockwise context parallel): sequence sharded over a
  mesh axis; each device keeps its q shard and rotates k/v shards around
  the ring with ``jax.lax.ppermute`` — the bidirectional ICI torus makes
  neighbor exchange effectively free, and compute on the current block
  overlaps the DMA of the next. Online-softmax merging keeps only a
  (S/n × S/n) score block alive per step, so max context scales linearly
  with ring size.
- **Ulysses**: all-to-all re-shard seq->heads, local full-seq attention on
  H/n heads, all-to-all back. Better for small rings + many heads; the
  all-to-all also rides ICI.

Both are differentiable through the shard_map (ppermute/all_to_all have
transposes), so they drop into the tape/grad machinery like any op.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.ops.registry import OpDef, apply_op
from paddle_tpu.parallel.mesh import ProcessMesh, get_mesh

__all__ = ["ring_attention", "ulysses_attention", "ring_attention_fn",
           "ulysses_attention_fn"]

_NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """One (Sq_loc x Sk_loc) attention block -> (out, lse). f32 logits."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # fully-masked rows: keep exp() finite
    m_safe = jnp.maximum(m, -1e29)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # normalized block output; _merge re-weights blocks by exp(lse)
    p_norm = (p / jnp.maximum(l, 1e-30)).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p_norm, v)
    lse = jnp.where(m <= -1e29, _NEG_INF, m_safe + jnp.log(jnp.maximum(l, 1e-30)))
    return out, lse[..., 0]  # (b,q,h,d), (b,h,q)


def _merge(acc, out, lse_acc, lse):
    """Numerically-stable online-softmax merge of two partial results."""
    m = jnp.maximum(lse_acc, lse)
    m_safe = jnp.maximum(m, -1e29)
    a1 = jnp.exp(lse_acc - m_safe)
    a2 = jnp.exp(lse - m_safe)
    denom = a1 + a2
    w1 = (a1 / jnp.maximum(denom, 1e-30))
    w2 = (a2 / jnp.maximum(denom, 1e-30))
    # (b,h,q) -> (b,q,h,1) weighting
    def wexp(w):
        return jnp.swapaxes(w, 1, 2)[..., None]
    merged = acc * wexp(w1).astype(acc.dtype) + out * wexp(w2).astype(out.dtype)
    lse_new = m_safe + jnp.log(jnp.maximum(denom, 1e-30))
    lse_new = jnp.where(m <= -1e29, _NEG_INF, lse_new)
    return merged, lse_new


def _ring_local(q, k, v, *, axis, n, scale, causal):
    """Local computation inside shard_map: q stays, k/v rotate the ring.

    Inputs are the local seq shards (B, S/n, H, D); rank r owns global
    block r (contiguous chunking over the sequence).
    """
    r = jax.lax.axis_index(axis)
    b, s_loc, h, d = q.shape
    qf = q.astype(jnp.float32)

    acc = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)

    def step(i, carry):
        acc, lse, k_cur, v_cur = carry
        src_block = (r - i) % n  # which global kv block we now hold
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)
            g_rows = r * s_loc + rows
            g_cols = src_block * s_loc + cols
            mask = (g_rows >= g_cols)[None, None]
        else:
            mask = None
        out_i, lse_i = _block_attn(qf, k_cur.astype(jnp.float32),
                                   v_cur.astype(jnp.float32), scale, mask)
        acc, lse = _merge(acc, out_i, lse, lse_i)
        # rotate kv to the next rank (bidirectional ICI ring)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return acc, lse, k_nxt, v_nxt

    # python loop: n is static (mesh size); lets XLA pipeline ppermute/compute
    carry = (acc, lse, k, v)
    for i in range(n):
        carry = jax.checkpoint(functools.partial(step, i))(carry)
    acc, lse, _, _ = carry
    return acc.astype(q.dtype)


def _head_axis(mesh: ProcessMesh, head_axis):
    """Keep the head dim sharded over tp inside the shard_map (otherwise
    every mp slice would recompute all heads)."""
    if head_axis is None and "mp" in mesh.dim_names and mesh.dim_size("mp") > 1:
        head_axis = "mp"
    if head_axis is not None and (head_axis not in mesh.dim_names
                                  or mesh.dim_size(head_axis) == 1):
        head_axis = None
    return head_axis


def ring_attention_fn(q, k, v, mesh: ProcessMesh, axis: str = "sep",
                      causal: bool = True, scale: Optional[float] = None,
                      head_axis: Optional[str] = None):
    """Pure-jax ring attention over `axis`. Layout (B, S, H, D), S is the
    *global* sequence; the shard_map shards it internally. Heads stay
    sharded over `head_axis` (default: 'mp' when present) so hybrid
    TP + CP does not duplicate head compute."""
    n = mesh.dim_size(axis)
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    if q.shape[1] % n:
        raise ValueError(f"ring_attention: seq {q.shape[1]} % ring {n} != 0")
    head_axis = _head_axis(mesh, head_axis)
    if head_axis is not None and q.shape[2] % mesh.dim_size(head_axis):
        head_axis = None  # heads not divisible: replicate rather than fail
    spec = P(None, axis, head_axis, None)
    fn = shard_map(
        functools.partial(_ring_local, axis=axis, n=n, scale=scale,
                          causal=causal),
        mesh=mesh.jax_mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def _ulysses_local(q, k, v, *, axis, n, scale, causal):
    """all-to-all heads<->seq: local (B, S/n, H, D) -> (B, S, H/n, D)."""
    def seq_to_heads(x):
        # split heads into n groups, exchange so each rank gets full seq of
        # its head group: (b, s/n, h, d) -> (b, s, h/n, d)
        b, s_loc, h, d = x.shape
        x = x.reshape(b, s_loc, n, h // n, d)
        x = jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                               tiled=True)  # (b, s_loc*n, 1, h//n, d)
        return x.reshape(b, s_loc * n, h // n, d)

    def heads_to_seq(x):
        # inverse: (b, s, h/n, d) -> (b, s/n, h, d)
        b, s, hn, d = x.shape
        x = x.reshape(b, n, s // n, hn, d)
        x = jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=3,
                               tiled=True)  # (b, 1, s//n, hn*n, d)
        return x.reshape(b, s // n, hn * n, d)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    s = qh.shape[1]
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        mask = (rows >= cols)[None, None]
    else:
        mask = None
    out, _ = _block_attn(qh.astype(jnp.float32), kh.astype(jnp.float32),
                         vh.astype(jnp.float32), scale, mask)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention_fn(q, k, v, mesh: ProcessMesh, axis: str = "sep",
                         causal: bool = True, scale: Optional[float] = None,
                         head_axis: Optional[str] = None):
    """DeepSpeed-Ulysses-style sequence parallelism (all-to-all head
    exchange). The *local* head count (global / tp shard) must be
    divisible by the axis size."""
    n = mesh.dim_size(axis)
    h = q.shape[2]
    d = q.shape[-1]
    head_axis = _head_axis(mesh, head_axis)
    h_loc = h // mesh.dim_size(head_axis) if head_axis else h
    if head_axis is not None and h % mesh.dim_size(head_axis):
        head_axis = None
        h_loc = h
    if h_loc % n:
        raise ValueError(f"ulysses: local heads {h_loc} % axis {n} != 0")
    if q.shape[1] % n:
        raise ValueError(f"ulysses: seq {q.shape[1]} % axis {n} != 0")
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    spec = P(None, axis, head_axis, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis=axis, n=n, scale=scale,
                          causal=causal),
        mesh=mesh.jax_mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


# -- taped eager wrappers ----------------------------------------------------

def ring_attention(q, k, v, mesh: Optional[ProcessMesh] = None,
                   axis: str = "sep", causal: bool = True, scale=None):
    mesh = mesh or get_mesh()
    if mesh is None:
        raise ValueError("ring_attention needs a mesh")
    opdef = OpDef("ring_attention",
                  lambda q, k, v: ring_attention_fn(q, k, v, mesh, axis,
                                                    causal, scale))
    return apply_op(opdef, (q if isinstance(q, Tensor) else Tensor(q),
                            k if isinstance(k, Tensor) else Tensor(k),
                            v if isinstance(v, Tensor) else Tensor(v)), {})


def ulysses_attention(q, k, v, mesh: Optional[ProcessMesh] = None,
                      axis: str = "sep", causal: bool = True, scale=None):
    mesh = mesh or get_mesh()
    if mesh is None:
        raise ValueError("ulysses_attention needs a mesh")
    opdef = OpDef("ulysses_attention",
                  lambda q, k, v: ulysses_attention_fn(q, k, v, mesh, axis,
                                                       causal, scale))
    return apply_op(opdef, (q if isinstance(q, Tensor) else Tensor(q),
                            k if isinstance(k, Tensor) else Tensor(k),
                            v if isinstance(v, Tensor) else Tensor(v)), {})
