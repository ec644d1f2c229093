"""Operations and bytes one decode step of a looped decoder needs, from
shapes alone (beside ``flops.py``, whose ``layer_params`` and
``least_time_s`` it uses). What the mathematics requires: every weight read
once per pass, every live key and value read once, nothing a compiled
program happens to copy or re-read.
"""

from __future__ import annotations

from benchmark.flops import layer_params


def looped_layer_params(*, hidden: int, ffn: int, heads: int, kv_heads: int,
                        head_dim: int, norms: int = 4) -> int:
    """Parameters of one block: its matmuls and its ``norms`` RMSNorm
    weights (four in a sandwich block, two in a pre-norm one)."""
    return layer_params(hidden, ffn, heads, kv_heads, head_dim) \
        + norms * hidden


def looped_decode_step(*, layers: int, loop_steps: int, hidden: int,
                       ffn: int, heads: int, kv_heads: int, head_dim: int,
                       vocab: int, rows: float, live_positions: float,
                       kv_bytes_per_position: float, norms: int = 4,
                       bytes_per_el: int = 2) -> dict:
    """FLOPs and the least HBM bytes of ONE decode step of ``rows``
    sequences whose caches hold ``live_positions`` token positions in all.

    Bytes: the ``layers`` blocks' weights once per pass (``loop_steps``
    passes over the same weights; a chip with 128 MiB of on-chip memory
    cannot keep a GB of them between passes), the final norm once per
    pass, the untied head once, one embedding row per sequence, and every
    live position's keys and values over all ``loop_steps * layers`` cache
    layers (``kv_bytes_per_position``, which the engine reports from its
    own buffers). Written keys and values and the activations are left
    out: a few rows. FLOPs: 2 per matmul parameter per sequence per pass,
    the head once, and attention's QK^T and PV (2 * head_dim each per head
    per live position per cache layer).
    """
    mats = layer_params(hidden, ffn, heads, kv_heads, head_dim)
    block = looped_layer_params(hidden=hidden, ffn=ffn, heads=heads,
                                kv_heads=kv_heads, head_dim=head_dim,
                                norms=norms)
    weight_bytes = bytes_per_el * (
        loop_steps * (layers * block + hidden) + hidden * vocab
        + rows * hidden)
    kv_bytes = live_positions * kv_bytes_per_position
    flops = (2.0 * rows * (loop_steps * layers * mats + hidden * vocab)
             + 4.0 * heads * head_dim * live_positions * loop_steps * layers)
    return {"flops": flops, "bytes": float(weight_bytes + kv_bytes),
            "weight_bytes": float(weight_bytes), "kv_bytes": float(kv_bytes)}
