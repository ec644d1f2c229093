"""Operations and bytes the algorithms need, from shapes alone.

Recomputation never counts: these are the operations the mathematics
requires, not what a compiled program happens to execute.
"""

from __future__ import annotations


def layer_params(hidden: int, ffn: int, heads: int, kv_heads: int,
                 head_dim: int) -> int:
    """Matmul parameters of one decoder block (norm weights left out)."""
    attn = hidden * heads * head_dim * 2 + hidden * kv_heads * head_dim * 2
    return attn + 3 * hidden * ffn


def model_train_flops_per_token(*, layers: int, hidden: int, ffn: int,
                                heads: int, kv_heads: int, head_dim: int,
                                vocab: int, seq: int) -> float:
    """Forward + backward FLOPs per trained token of a Llama-style
    decoder under causal attention at sequence length ``seq``.

    Matmuls: 2 FLOPs per parameter per token forward, twice that backward
    (6 N), over the block matmuls and the untied lm-head; the embedding
    lookup is a gather and costs none. Attention: QK^T and PV are each
    2*seq*head_dim FLOPs per head per token over the full square, halved by
    the causal mask, forward; backward is twice the forward (dS, dQ, dK, dV
    = 4 matmuls against the forward's 2). No recomputation.
    """
    n = layers * layer_params(hidden, ffn, heads, kv_heads, head_dim) \
        + hidden * vocab
    attn_fwd = layers * heads * (2 * 2 * seq * head_dim) / 2.0
    return 6.0 * n + 3.0 * attn_fwd


def flash_attention_step(*, batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, layers: int,
                         bytes_per_el: int = 2) -> dict:
    """FLOPs and the least HBM bytes of causal flash attention, forward and
    backward, over all ``layers`` of one training step.

    The trainer repeats K/V to ``heads`` before the kernel (GQA is expanded
    by ``repeat_interleave`` in ``models/llama.py``), so the kernels see
    ``heads`` K/V heads; ``kv_heads`` is accepted so a later grouped kernel
    changes one line here. FLOPs (causal, so half the square): forward 2
    matmuls, backward 5 (recomputed S = QK^T belongs to the algorithm:
    flash attention does not keep P), each 2*seq*seq*head_dim per head.
    Bytes, least possible: forward reads Q, K, V and writes O (+ the f32
    log-sum-exp row); backward reads Q, K, V, O, dO, lse and writes dQ,
    dK, dV.
    """
    del kv_heads
    mm = 2.0 * seq * seq * head_dim / 2.0          # one causal matmul, a head
    per_layer_flops = batch * heads * mm * (2 + 5)
    tensor = batch * heads * seq * head_dim * bytes_per_el
    lse = batch * heads * seq * 4
    per_layer_bytes = (4 * tensor + lse) + (8 * tensor + lse)
    return {"flops": layers * per_layer_flops,
            "bytes": layers * float(per_layer_bytes)}


def least_time_s(flops: float, nbytes: float, peak: dict) -> dict:
    """The roofline's least time and which bound holds."""
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
