"""EVA chunked linearized attention (EvaByte's ``attention_class: eva``;
Zheng, Wang, Kong, "Efficient Attention via Control Variates", ICLR 2023).

Positions are cut into ALIGNED windows of ``window`` and, inside them,
chunks of ``chunk``. Every complete chunk ``c`` of keys (rotated already)
is summarised once, per head, into one key and one value::

    k~_c = sum_j softmax_j(s * mu . k_j) k_j      s = head_dim ** -0.5
    v~_c = sum_j softmax_j(s * phi . k_j) v_j     j over the chunk

and query ``i`` attends, in ONE softmax of scores ``s * q_i . k``, over
the exact keys ``j <= i`` of its own window and the summaries of every
chunk in an earlier window. Pure ``jax.numpy``, differentiable; what the
eager model runs (``models/evabyte.py``), what ``LlamaDecoder`` runs where
no kernel takes the shape, and what the kernels are compared with.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["chunk_summaries", "eva_attention"]


def chunk_summaries(k, v, mu, phi, chunk: int):
    """k, v (B, H, S, D) head-major, ``S`` a multiple of ``chunk``; mu,
    phi (H, D) -> (k~, v~) each (B, H, S / chunk, D), pooled in float32
    and stored in the inputs' dtype. Written as products and sums over
    the ``chunk`` keys, which fuse with the casts: no float32 copy of the
    keys or values is held."""
    B, H, S, D = k.shape
    kf = k.reshape(B, H, S // chunk, chunk, D).astype(jnp.float32)
    vf = v.reshape(B, H, S // chunk, chunk, D).astype(jnp.float32)
    s = float(D) ** -0.5

    def pool(w, x):
        sc = s * jnp.sum(kf * w.astype(jnp.float32)[None, :, None, None, :],
                         axis=-1)                       # (B, H, N, chunk)
        return jnp.sum(jax.nn.softmax(sc, axis=-1)[..., None] * x, axis=3)
    return pool(mu, kf).astype(k.dtype), pool(phi, vf).astype(v.dtype)


def eva_attention(q, k, v, ks, vs, window: int, chunk: int):
    """q, k, v (B, S, H, D) from position 0; ks, vs (B, H, N, D) the
    summaries of the first ``N`` chunks, head-major as
    ``chunk_summaries`` gives them (those past ``S // chunk`` are seen by
    no query) -> (B, S, H, D). XLA's masked form: an (S, S + N) score
    matrix a head."""
    B, S, H, D = q.shape
    N = ks.shape[2]
    s = jnp.float32(D) ** -0.5
    i = jnp.arange(S)
    local = jnp.logical_and(i[:, None] // window == i[None, :] // window,
                            i[None, :] <= i[:, None])            # (S, S)
    earlier = (jnp.arange(N)[None, :] // (window // chunk)
               < i[:, None] // window)                           # (S, N)
    sc = jnp.concatenate([
        jnp.where(local, jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
            jnp.float32) * s, -jnp.inf),
        jnp.where(earlier, jnp.einsum("bqhd,bhnd->bhqn", q, ks).astype(
            jnp.float32) * s, -jnp.inf)], axis=-1)
    p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    return (jnp.einsum("bhqk,bkhd->bqhd", p[..., :S], v)
            + jnp.einsum("bhqn,bhnd->bqhd", p[..., S:], vs))
