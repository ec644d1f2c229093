"""Operations and bytes of what the A.X-K1 configuration adds, from shapes
alone (beside ``flops.py``, whose ``least_time_s`` the readers use): the
absorbed decode attention over the latent cache, the expanded prefill
attention, and one whole decode step of the stage held as one chip's share.
What the mathematics requires: every weight a step's tokens reach read
once, every live latent position read once, nothing a compiled program
copies or re-reads, no padding.
"""

from __future__ import annotations


def mla_params(a: dict) -> dict:
    """Parameters by part, from ``adapters/axk1.py:at_depth``'s dict: one
    layer's latent attention (q_a, its norm, q_b, kv_a, its norm, kv_b,
    o), its two block norms, a dense feed-forward, and of a routed one
    the router (+ its bias), the shared expert and ONE routed expert."""
    h, H = int(a["hidden_size"]), int(a["num_attention_heads"])
    Q, R = int(a["q_lora_rank"]), int(a["kv_lora_rank"])
    dn, dr, dv = (int(a["qk_nope_head_dim"]), int(a["qk_rope_head_dim"]),
                  int(a["v_head_dim"]))
    f, E = int(a["moe_intermediate_size"]), int(a["n_routed_experts"])
    return {"attention": (h * (Q + R + dr) + Q + Q * H * (dn + dr) + R
                          + R * H * (dn + dv) + H * dv * h),
            "norms": 2 * h,
            "dense_ffn": 3 * h * int(a["intermediate_size"]),
            "router": h * E + E,
            "shared": 3 * h * f * int(a["n_shared_experts"]),
            "expert": 3 * h * f}


def mla_decode_attention(*, live_positions: float, heads: int,
                         kv_lora_rank: int, rope_dim: int,
                         bytes_per_position: float) -> dict:
    """FLOPs and the least HBM bytes of the absorbed decode attention over
    ``live_positions`` latent positions (summed over rows and layers):
    each position's stored latent and rope key read once
    (``bytes_per_position`` as the cache stores them), and per head the
    score ``q~ . c_kv + q_pe . k_pe`` and the weighted latent, 2 (R + P +
    R) operations a position."""
    return {"flops": 2.0 * heads * (2 * kv_lora_rank + rope_dim)
            * live_positions,
            "bytes": float(live_positions * bytes_per_position)}


def mla_prefill_attention(*, causal_pairs: float, positions: float,
                          heads: int, qk_head_dim: int, v_head_dim: int,
                          bytes_per_el: int = 2) -> dict:
    """FLOPs and the least HBM bytes of the expanded causal attention of
    prefills whose true lengths give ``causal_pairs`` (query, key) pairs
    (``n (n + 1) / 2`` a row) over ``positions`` positions: QK^T over the
    query/key width and PV over the value width a pair a head; Q and K
    (``qk_head_dim``), V and O (``v_head_dim``) read or written once. The
    bucket's padded tail and any padding of V are not counted."""
    return {"flops": 2.0 * heads * (qk_head_dim + v_head_dim) * causal_pairs,
            "bytes": float(positions * heads * bytes_per_el
                           * (2 * qk_head_dim + 2 * v_head_dim))}


def mla_decode_step(*, arch: dict, rows: float, experts_touched: float,
                    pairs_held: float, live_latent: float,
                    bytes_per_position: float, bytes_per_el: int = 2) -> dict:
    """FLOPs and the least HBM bytes of ONE decode step of ``rows``
    sequences through the stage (``arch`` at its depth).

    ``experts_touched`` / ``pairs_held``: held experts with at least one
    token and token-expert pairs on held experts, summed over the routed
    layers of the step (the engine's ``serving.moe.*`` counters per step).
    ``live_latent``: latent positions the step's attention reads, summed
    over rows and latent layers (``serving.chunk.live_latent_positions``
    per step); ``bytes_per_position`` a position's stored bytes in one
    layer.

    Bytes: every layer's attention, norms, dense or shared feed-forward and
    router once; one expert's weights per touched expert; the final norm,
    the head's slice and one embedding row a sequence; the live latent.
    FLOPs: 2 per matmul parameter per sequence (per pair for the experts;
    the absorbed attention's W_UK and W_UV are kv_b's parameters), the head
    once, and ``mla_decode_attention``'s over the live latent."""
    p = mla_params(arch)
    L = int(arch["num_hidden_layers"])
    dense = int(arch["first_k_dense_replace"])
    h, v = int(arch["hidden_size"]), int(arch["vocab_size"])
    fixed = (L * (p["attention"] + p["norms"]) + dense * p["dense_ffn"]
             + (L - dense) * (p["router"] + p["shared"]) + h + h * v)
    weight_bytes = bytes_per_el * (fixed + rows * h
                                   + experts_touched * p["expert"])
    att = mla_decode_attention(
        live_positions=live_latent, heads=int(arch["num_attention_heads"]),
        kv_lora_rank=int(arch["kv_lora_rank"]),
        rope_dim=int(arch["qk_rope_head_dim"]),
        bytes_per_position=bytes_per_position)
    flops = (2.0 * rows * (fixed - L * p["norms"] - h)
             + 2.0 * pairs_held * p["expert"] + att["flops"])
    return {"flops": flops, "bytes": float(weight_bytes + att["bytes"]),
            "weight_bytes": float(weight_bytes),
            "latent_bytes": att["bytes"],
            "expert_bytes": float(bytes_per_el * experts_touched
                                  * p["expert"])}
