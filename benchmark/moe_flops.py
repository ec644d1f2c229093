"""Operations and bytes of what the AFMoE configuration adds, from shapes
alone (beside ``flops.py``, whose ``least_time_s`` the readers use): one
decode step of a model with routed feed-forwards held as one chip's share,
and one call of the banded flash forward kernel. What the mathematics
requires: every weight that a step's tokens reach read once, every live
key and value read once, nothing a compiled program copies or re-reads.
"""

from __future__ import annotations


def afmoe_params(arch: dict) -> dict:
    """Parameters by part, from the adapter's ``arch`` at its depth
    (``adapters/afmoe.py:at_depth``): one layer's attention (q, k, v,
    gate, o, the two per-head norms), its four block norms, a dense
    feed-forward, and of a routed one the router (+ its bias), the shared
    expert and ONE routed expert."""
    h, d = int(arch["hidden_size"]), int(arch["head_dim"])
    hq = int(arch["num_attention_heads"]) * d
    hk = int(arch["num_key_value_heads"]) * d
    f = int(arch["moe_intermediate_size"])
    return {"attention": h * (3 * hq + 2 * hk) + 2 * d, "norms": 4 * h,
            "dense_ffn": 3 * h * int(arch["intermediate_size"]),
            "router": h * int(arch["num_experts"]) + int(arch["num_experts"]),
            "shared": 3 * h * f * int(arch["num_shared_experts"]),
            "expert": 3 * h * f}


def moe_decode_step(*, arch: dict, rows: float, experts_touched: float,
                    pairs_held: float, live_full: float, live_window: float,
                    bytes_full: float, bytes_window: float,
                    bytes_per_el: int = 2) -> dict:
    """FLOPs and the least HBM bytes of ONE decode step of ``rows``
    sequences.

    ``experts_touched`` / ``pairs_held``: held experts with at least one
    token and token-expert pairs on held experts, summed over the routed
    layers of the step (the engine's ``serving.moe.*`` counters per
    step). ``live_full`` / ``live_window``: positions live in the caches
    that keep all of ``max_len`` and in the rolling buffers, summed over
    the rows; ``bytes_full`` / ``bytes_window`` a position's K and V bytes
    over all layers of each kind (the engine's gauges).

    Bytes: every layer's attention, norms, dense or shared feed-forward and
    router once; one expert's weights per touched expert; the final norm,
    the head's slice and one embedding row a sequence; the live keys and
    values. FLOPs: 2 per matmul parameter per sequence (per pair for the
    experts), the head once, and attention's QK^T and PV over the live
    positions (2 * head_dim each per head per position per layer; a kind's
    layers = its bytes a position over one layer's K and V).
    """
    p = afmoe_params(arch)
    L, dense = int(arch["num_hidden_layers"]), int(arch["num_dense_layers"])
    h, v = int(arch["hidden_size"]), int(arch["vocab_size"])
    heads, d = int(arch["num_attention_heads"]), int(arch["head_dim"])
    fixed = (L * (p["attention"] + p["norms"]) + dense * p["dense_ffn"]
             + (L - dense) * (p["router"] + p["shared"]) + h + h * v)
    weight_bytes = bytes_per_el * (fixed + rows * h
                                   + experts_touched * p["expert"])
    kv_bytes = live_full * bytes_full + live_window * bytes_window
    one_layer_kv = 2 * int(arch["num_key_value_heads"]) * d * bytes_per_el
    flops = (2.0 * rows * (fixed - L * p["norms"] - h)
             + 2.0 * pairs_held * p["expert"]
             + 4.0 * heads * d * (live_full * bytes_full
                                  + live_window * bytes_window) / one_layer_kv)
    return {"flops": flops, "bytes": float(weight_bytes + kv_bytes),
            "weight_bytes": float(weight_bytes), "kv_bytes": float(kv_bytes),
            "expert_bytes": float(bytes_per_el * experts_touched
                                  * p["expert"])}


def banded_flash_fwd(*, seq: int, window: int, heads: int, head_dim: int,
                     batch: int = 1, bytes_per_el: int = 2) -> dict:
    """FLOPs and the least HBM bytes of one call of the causal flash
    forward kernel under a band: query i sees keys ``i - window < j <= i``
    (the K/V heads arrive repeated to ``heads``, as the kernel takes them).
    Visible pairs a head: ``sum_i min(i + 1, window)``; QK^T and PV are 2
    FLOPs each per pair per element of the head. Bytes: Q, K, V read and O
    written once, and the float32 log-sum-exp row."""
    w = min(int(window), int(seq))
    pairs = w * (w + 1) // 2 + (seq - w) * w
    tensor = batch * heads * seq * head_dim * bytes_per_el
    return {"flops": 4.0 * batch * heads * head_dim * pairs,
            "bytes": float(4 * tensor + batch * heads * seq * 4)}
