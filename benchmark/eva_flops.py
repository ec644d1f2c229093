"""Operations and bytes the EVA decoder (EvaByte) needs, from shapes alone
(beside ``flops.py``, whose ``layer_params`` and ``least_time_s`` it uses):
one decode step of a batch, and the attention of one admission prefill.
What the mathematics requires: every weight read once a step, every live
entry of either leaf read once, the causal half of a window and the
summaries a query can see — nothing a compiled program happens to copy,
re-read or pad.
"""

from __future__ import annotations

from benchmark.flops import layer_params


def eva_layer_params(*, hidden: int, ffn: int, heads: int,
                     head_dim: int) -> int:
    """Parameters of one block: its matmuls (MHA), two RMSNorm weights and
    the two pooling vectors a head."""
    return layer_params(hidden, ffn, heads, heads, head_dim) \
        + 2 * hidden + 2 * heads * head_dim


def eva_decode_step(*, layers: int, hidden: int, ffn: int, heads: int,
                    head_dim: int, head_rows: int, chunk: int, rows: float,
                    live_window: float, live_summary: float,
                    bytes_window_row: float, bytes_summary_row: float,
                    bytes_per_el: int = 2) -> dict:
    """FLOPs and the least HBM bytes of ONE decode step of ``rows``
    sequences whose window leaves hold ``live_window`` live rows in all and
    to which ``live_summary`` summaries in all are visible.

    Bytes: the blocks' weights, the final norm, the head of ``head_rows``
    (all ``num_pred_heads`` vocabularies: the program holds and multiplies
    them all) and one embedding row a sequence; every live window row and
    visible summary over all layers (``bytes_*_row``: K and V of one entry
    over the layers, which the engine reports from its own buffers); and
    the rows a step WRITES, one window row and one summary a sequence.
    The re-read of a chunk's keys for pooling and the activations are left
    out: a few rows. FLOPs: 2 per matmul parameter per sequence, the head,
    QK^T and PV over both leaves (2 * head_dim each per head per entry per
    layer) and the pooling of one chunk a sequence a layer (two scores and
    two weighted sums over ``chunk`` keys a head).
    """
    mats = layer_params(hidden, ffn, heads, heads, head_dim)
    block = eva_layer_params(hidden=hidden, ffn=ffn, heads=heads,
                             head_dim=head_dim)
    weight_bytes = bytes_per_el * (layers * block + hidden
                                   + hidden * head_rows + rows * hidden)
    leaf_bytes = (live_window * bytes_window_row
                  + live_summary * bytes_summary_row)
    written = rows * (bytes_window_row + bytes_summary_row)
    flops = (2.0 * rows * (layers * mats + hidden * head_rows)
             + 4.0 * heads * head_dim * (live_window + live_summary) * layers
             + 8.0 * heads * head_dim * chunk * rows * layers)
    return {"flops": flops,
            "bytes": float(weight_bytes + leaf_bytes + written),
            "weight_bytes": float(weight_bytes),
            "leaf_bytes": float(leaf_bytes), "written_bytes": float(written)}


def eva_prefill_pairs(n: int, window: int, chunk: int) -> dict:
    """(query, key) and (query, summary) pairs of a prompt of ``n``
    positions: the causal half of every aligned window, the last one
    partial, and for a query in window ``w`` the ``w * window / chunk``
    summaries of the windows before."""
    full, r = divmod(int(n), int(window))
    local = full * window * (window + 1) // 2 + r * (r + 1) // 2
    per = window // chunk
    summary = per * (window * full * (full - 1) // 2 + r * full)
    return {"local": local, "summary": summary}


def eva_prefill_attention(*, n: int, window: int, chunk: int, heads: int,
                          head_dim: int, layers: int,
                          bytes_per_el: int = 2) -> dict:
    """FLOPs and the least HBM bytes of the attention of ONE admission
    prefill of ``n`` true positions over all ``layers``: QK^T and PV over
    ``eva_prefill_pairs`` (the padded tail of the bucket and the masked
    half of a window are not work), against Q, K, V read and O written
    once and the summaries read once."""
    p = eva_prefill_pairs(n, window, chunk)
    flops = 4.0 * head_dim * heads * (p["local"] + p["summary"]) * layers
    entries = 4 * n + 2 * (n // chunk)
    return {"flops": flops,
            "bytes": float(entries * heads * head_dim * bytes_per_el
                           * layers),
            **p}
