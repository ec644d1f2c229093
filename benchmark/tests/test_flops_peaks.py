import pytest

from benchmark import flops as fl
from benchmark.peaks import peaks


def test_v5e_peaks_and_unknown_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and "v5e" in p["source"]
    with pytest.raises(KeyError, match="TPU v9"):
        peaks("TPU v9")


def test_model_flops_per_token_hand_count():
    # one layer, hidden 8, ffn 16, 2 heads of 4, 1 kv head, vocab 10, seq 4
    # block matmuls: q 8*8 + o 8*8 + k 8*4 + v 8*4 + 3*8*16 = 576; head 80
    # 6 * 656 = 3936; attention forward, per token: 2 heads * (2 matmuls *
    # 2 FLOPs * seq 4 * head_dim 4) / 2 (causal) = 64, backward twice that:
    # 3 * 64 = 192
    got = fl.model_train_flops_per_token(
        layers=1, hidden=8, ffn=16, heads=2, kv_heads=1, head_dim=4,
        vocab=10, seq=4)
    assert got == 3936 + 192


def test_mistral_layer_params_as_published():
    # 218.1M a layer (ISSUE 24): 4096*4096*2 + 4096*1024*2 + 3*4096*14336
    assert fl.layer_params(4096, 14336, 32, 8, 128) == 218_103_808


def test_flash_attention_hand_count():
    # B=1, S=4, H=2, D=8, 1 layer, bf16. One causal matmul a head:
    # 2*4*4*8/2 = 128 FLOPs; 7 of them (2 fwd + 5 bwd) over 2 heads = 1792.
    # A tensor is 1*2*4*8*2 = 128 bytes, lse 1*2*4*4 = 32:
    # fwd 4*128+32 = 544, bwd 8*128+32 = 1056 -> 1600
    got = fl.flash_attention_step(batch=1, seq=4, heads=2, kv_heads=1,
                                  head_dim=8, layers=1)
    assert got == {"flops": 1792.0, "bytes": 1600.0}
    lt = fl.least_time_s(197e12, 819e9 / 2, peaks("TPU v5 lite"))
    assert lt == {"seconds": 1.0, "bound": "compute"}
    assert fl.least_time_s(1.0, 819e9, peaks("TPU v5 lite"))["bound"] == \
        "memory"
