"""Pallas fused-kernel parity tests: rms_norm vs the XLA composition.

Reference capability: paddle/phi/kernels/fusion/ fused_rms_norm. Kernels
run in interpret mode on CPU (same code path as TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flags
from paddle_tpu.ops.pallas import rms_norm as prms


def _lax_rms(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w


@pytest.mark.parametrize("dtype,wdtype", [
    (jnp.float32, jnp.float32),
    (jnp.bfloat16, jnp.bfloat16),
    (jnp.bfloat16, jnp.float32),
])
def test_pallas_rms_norm_forward_parity(dtype, wdtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 8, 64)), dtype)
    w = jnp.asarray(rng.normal(size=(64,)), wdtype)
    assert prms.supported(x.shape, w.shape)
    out, inv = prms.rms_fwd(x, w, 1e-6)
    ref = _lax_rms(x, w, 1e-6)
    assert out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-6, atol=1e-6)
    assert inv.shape == (16, 1) and inv.dtype == jnp.float32


def test_pallas_rms_norm_grad_parity():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    from paddle_tpu.ops.fused_norm import rms_norm_fused

    gx0, gw0 = jax.grad(lambda x, w: _lax_rms(x, w, 1e-6).sum(), (0, 1))(x, w)
    gx1, gw1 = jax.grad(
        lambda x, w: rms_norm_fused(x, w, 1e-6).sum(), (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx0), np.asarray(gx1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw0), np.asarray(gw1),
                               rtol=1e-5, atol=1e-5)


def _has_pallas_call(closed) -> bool:
    import jax.extend.core as jex

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                return True
            for v in e.params.values():
                subs = v if isinstance(v, (tuple, list)) else (v,)
                for s in subs:
                    if isinstance(s, jex.ClosedJaxpr) and walk(s.jaxpr):
                        return True
                    if isinstance(s, jex.Jaxpr) and walk(s):
                        return True
        return False

    return walk(closed.jaxpr)


def test_rms_norm_fused_engages_pallas_under_jit():
    # eps is a static custom_vjp arg: were it a traced operand, the
    # concreteness check would silently fall back to lax inside jit
    from paddle_tpu.ops.fused_norm import rms_norm_fused
    x = jnp.ones((2, 8, 64), jnp.float32)
    w = jnp.ones((64,), jnp.float32)
    j = jax.make_jaxpr(lambda x, w: rms_norm_fused(x, w, 1e-6))(x, w)
    assert _has_pallas_call(j)
    jg = jax.make_jaxpr(
        jax.grad(lambda x: rms_norm_fused(x, w, 1e-6).sum()))(x)
    assert _has_pallas_call(jg)


def test_rms_norm_op_routes_to_fused_and_matches_unfused():
    import paddle_tpu.nn.functional as F
    rng = np.random.default_rng(2)
    xv = rng.normal(size=(2, 16, 64)).astype(np.float32)
    wv = rng.normal(size=(64,)).astype(np.float32)
    x, w = paddle.to_tensor(xv), paddle.to_tensor(wv)
    fused = F.rms_norm(x, w)
    paddle.set_flags({"use_fused_rms_norm": False})
    try:
        unfused = F.rms_norm(x, w)
    finally:
        paddle.set_flags({"use_fused_rms_norm": True})
    np.testing.assert_allclose(fused.numpy(), unfused.numpy(),
                               rtol=1e-6, atol=1e-6)


def test_rms_norm_unsupported_shape_falls_back():
    import paddle_tpu.nn.functional as F
    # 7 rows: no row block divides it -> lax fallback must kick in
    x = paddle.to_tensor(np.random.default_rng(3).normal(
        size=(7, 33)).astype(np.float32))
    w = paddle.to_tensor(np.ones((33,), np.float32))
    out = F.rms_norm(x, w)
    assert tuple(out.shape) == (7, 33)


# (hidden, itemsize of x/g/dx) -> (forward, backward) row block. These are the
# largest power-of-two blocks the v5e compiler accepted in an AOT sweep
# (rows 4096, f32 weight; forward h=4096 bf16 would also take 256): the
# fixed 256 of before was refused in the backward from h=4096 on.
_ROW_BLOCKS = {
    (1024, 2): (256, 256), (1024, 4): (256, 256),
    (2048, 2): (256, 256), (2048, 4): (256, 128),
    (4096, 2): (128, 128), (4096, 4): (128, 64),
    (5120, 2): (128, 64), (5120, 4): (128, 64),
    (8192, 2): (64, 64), (8192, 4): (64, 32),
    (16384, 2): (32, 32), (16384, 4): (32, 16),
}


@pytest.mark.parametrize("h,itemsize", sorted(_ROW_BLOCKS))
def test_rms_row_block_follows_hidden_size_and_vmem_budget(h, itemsize):
    fwd = prms._row_block(4096, h, itemsize + 4, prms._FWD_TEMPS)
    bwd = prms._row_block(4096, h, 3 * itemsize, prms._BWD_TEMPS)
    assert (fwd, bwd) == _ROW_BLOCKS[(h, itemsize)]
    assert prms.supported((4096, h), (h,))


def test_rms_supported_refuses_only_what_cannot_fit_or_divide():
    assert prms.supported((8, 4096), (4096,))        # the smallest block
    assert not prms.supported((7, 64), (64,))        # no sublane-aligned rows
    assert not prms.supported((4096, 65536), (65536,))   # 8 rows pass 16 MiB
    # the block still divides the rows it is given
    assert prms._row_block(24, 64, 12, prms._BWD_TEMPS) == 8


def test_llama_fused_rms_norm_vs_unfused_training_parity():
    """One eager train step of the tiny Llama with the fused RMSNorm
    kernel on vs off: losses and a sampled grad must agree."""
    from paddle_tpu.models.llama import TINY_CONFIG, LlamaForCausalLM

    rng = np.random.default_rng(6)
    ids = rng.integers(0, TINY_CONFIG.vocab_size, (2, 16))
    labels = rng.integers(0, TINY_CONFIG.vocab_size, (2, 16))

    def one_loss_and_grad():
        paddle.seed(0)
        m = LlamaForCausalLM(TINY_CONFIG)
        loss = m.loss(paddle.to_tensor(ids), paddle.to_tensor(labels))
        loss.backward()
        g = m.model.layers[0].self_attn.q_proj.weight.grad
        return float(loss.numpy()), np.asarray(g.numpy())

    try:
        paddle.set_flags({"use_fused_rms_norm": True})
        l_fused, g_fused = one_loss_and_grad()
        paddle.set_flags({"use_fused_rms_norm": False})
        l_ref, g_ref = one_loss_and_grad()
    finally:
        paddle.set_flags({"use_fused_rms_norm": True})
    assert abs(l_fused - l_ref) < 1e-5, (l_fused, l_ref)
    np.testing.assert_allclose(g_fused, g_ref, rtol=1e-4, atol=1e-5)


def test_int8_matmul_kernel_matches_dequant():
    """ops/pallas/int8_matmul (weight_only_linear capability): interpret
    mode on CPU; per-channel dequant parity incl. a non-divisible N."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.int8_matmul import int8_matmul, supported

    rng = np.random.default_rng(0)
    for K, N, bn in ((256, 512, 1024), (512, 640, 1024), (512, 640, 512),
                     (5504, 256, 1024)):
        # (512, 640, 512) exercises the padded trailing tile (grid=2,
        # last block 128 wide of a 512 BlockSpec); K=5504 is the 1B
        # down_proj contraction (128-aligned, not 256)
        x = jnp.asarray(rng.standard_normal((8, K)), jnp.float32)
        w = jnp.asarray(rng.integers(-127, 127, (K, N)), jnp.int8)
        s = jnp.asarray(rng.uniform(0.01, 0.02, (N,)), jnp.float32)
        got = np.asarray(int8_matmul(x, w, s, block_n=bn))
        ref = np.asarray((x @ w.astype(jnp.float32)) * s)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # routing guards: big row counts / unaligned shapes are not eligible
    assert not supported(jnp.zeros((128, 256)), jnp.zeros((256, 512), jnp.int8))
    assert not supported(jnp.zeros((8, 200)), jnp.zeros((200, 512), jnp.int8))


def _dense_decode_attention(q, kc, vc, pos):
    """The masked dense reference: softmax over the first ``pos`` (scalar
    or per-row) cache positions, KV heads repeated for GQA."""
    rep = q.shape[1] // kc.shape[1]
    L, D = kc.shape[2], kc.shape[3]
    kk, vv = jnp.repeat(kc, rep, 1), jnp.repeat(vc, rep, 1)
    s = jnp.einsum("bhd,bhkd->bhk", q, kk) / np.sqrt(D)
    bound = jnp.reshape(jnp.asarray(pos), (-1, 1, 1))
    s = jnp.where(jnp.arange(L)[None, None, :] < bound, s, -jnp.inf)
    return np.asarray(jnp.einsum("bhk,bhkd->bhd", jax.nn.softmax(s, -1), vv))


def _decode_attention_inputs(seed, B, KV, H, L, D=8):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((B, KV, L, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((B, KV, L, D)), jnp.float32))


@pytest.mark.parametrize("pos", [1, 100, 256])
@pytest.mark.parametrize("KV,H", [(4, 4), (2, 6)])
def test_decode_attention_kernel_interpret_parity(KV, H, pos):
    """ops/pallas/decode_attention (block_multi_head_attention capability):
    interpret-mode parity with the masked dense reference, incl. GQA
    (``rep`` 1 and 3) and dynamic valid-length masking (scalar ``pos``)."""
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention, supported)

    q, kc, vc = _decode_attention_inputs(0, 2, KV, H, 256)
    assert supported(q, kc)
    got = np.asarray(decode_attention(q, kc, vc, pos, block_l=128))
    np.testing.assert_allclose(got, _dense_decode_attention(q, kc, vc, pos),
                               rtol=2e-5, atol=2e-5)
    assert not supported(jnp.zeros((2, 5, 8)), jnp.zeros((2, 2, 256, 8)))


# per-row valid lengths at block_l=128 over L=512: two rows inside a block
# (the pair the test had), then one row on, before and after every block
# boundary a row can sit at, shortest and longest mixed in one batch
_ROW_LENGTHS = {
    "inside": [100, 37],
    "boundaries": [1, 127, 128, 129, 512],
    "boundaries_reversed": [512, 385, 384, 383, 256, 255, 1],
}


@pytest.mark.parametrize("cache", ["float", "int8"])
@pytest.mark.parametrize("KV,H", [(2, 6), (2, 2)])
@pytest.mark.parametrize("lengths", sorted(_ROW_LENGTHS))
def test_decode_attention_per_row_pos_and_int8_parity(lengths, KV, H, cache):
    """The kernel's per-row valid-length bound ((B,) pos — the chunked
    serving path, where rows sit at different cache offsets) and the
    int8-cache tiles (dequant in VMEM against per-row scales) both match
    the masked dense reference, with rows on and beside the block
    boundaries mixed in one batch (a row's dead blocks are sent to its
    last live one and skipped)."""
    from paddle_tpu.ops.pallas.decode_attention import decode_attention
    from paddle_tpu.quantization.kv_cache import (dequantize_kv,
                                                  quantize_kv_rows)

    pos = jnp.asarray(_ROW_LENGTHS[lengths], jnp.int32)
    q, kc, vc = _decode_attention_inputs(1, pos.shape[0], KV, H, 512)
    if cache == "int8":
        qk, qv = quantize_kv_rows(kc), quantize_kv_rows(vc)
        got = decode_attention(q, qk["q"], qv["q"], pos, block_l=128,
                               k_scale=qk["s"], v_scale=qv["s"])
        kc, vc = (dequantize_kv(qk, jnp.float32),
                  dequantize_kv(qv, jnp.float32))
    else:
        got = decode_attention(q, kc, vc, pos, block_l=128)
    np.testing.assert_allclose(np.asarray(got),
                               _dense_decode_attention(q, kc, vc, pos),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("cache", ["float", "int8"])
def test_decode_attention_ignores_what_lies_past_a_rows_valid_prefix(cache):
    """Large finite values written past each row's valid prefix — what a
    slot's earlier tenant leaves in the cache — change nothing in the
    output: the tail of the last live block is masked, whole dead blocks
    are never read."""
    from paddle_tpu.ops.pallas.decode_attention import decode_attention
    from paddle_tpu.quantization.kv_cache import quantize_kv_rows

    lengths = _ROW_LENGTHS["boundaries"]
    pos = jnp.asarray(lengths, jnp.int32)
    q, kc, vc = _decode_attention_inputs(2, len(lengths), 2, 6, 512)
    dead = jnp.arange(512)[None, None, :, None] >= pos[:, None, None, None]
    kg, vg = jnp.where(dead, 3e4, kc), jnp.where(dead, -3e4, vc)

    def run(k, v):
        if cache == "int8":
            k, v = quantize_kv_rows(k), quantize_kv_rows(v)
            return np.asarray(decode_attention(
                q, k["q"], v["q"], pos, block_l=128,
                k_scale=k["s"], v_scale=v["s"]))
        return np.asarray(decode_attention(q, k, v, pos, block_l=128))

    np.testing.assert_array_equal(run(kg, vg), run(kc, vc))


@pytest.mark.parametrize("bl", [128, 256])
def test_decode_attention_dead_blocks_map_to_the_last_live_block(bl):
    """The clamped block-index rule: L-step ``l`` fetches block ``l`` while
    it holds a live position, else the row's last live block — never one
    past it, never below 0 — so the index of a dead step repeats and the
    pipeline issues no copy for it."""
    from paddle_tpu.ops.pallas.decode_attention import _live_block

    nl = 2048 // bl
    for n_valid in (0, 1, bl - 1, bl, bl + 1, 475, 2047, 2048):
        last_live = max(n_valid - 1, 0) // bl
        got = [int(_live_block(jnp.int32(l), jnp.int32(n_valid), bl))
               for l in range(nl)]
        assert got == [min(l, last_live) for l in range(nl)], n_valid
        assert 0 <= min(got) and max(got) == min(last_live, nl - 1)
        # live steps fetch their own block; the fetches a row causes are
        # the distinct indices: ceil(n_valid / bl), and one for an empty row
        assert len(set(got)) == max(1, -(-n_valid // bl))


# (block_l, L, KV, D, itemsize, int8 cache) -> block length; what the v5e's
# compiler took and refused at these shapes is in the kernel's comment
_BLOCK_LENS = {
    (256, 2048, 8, 128, 2, False): 256,    # the Mistral serving cell
    (256, 4096, 8, 128, 2, False): 256,
    (256, 4096, 8, 128, 1, True): 256,     # int8wk: the scale tiles fit
    (1024, 4096, 8, 128, 1, True): 512,    # ... and bound a larger ask
    (2048, 2048, 8, 128, 2, False): 1024,
    (256, 2048, 32, 128, 2, False): 256,   # MHA-wide rows
    (256, 2048, 32, 128, 1, True): 128,
    (256, 384, 8, 128, 2, False): 128,     # a divisor of L
    (256, 128, 2, 8, 4, False): 128,       # never over L
    (256, 2048, 128, 512, 4, False): 0,    # nothing fits: not supported
}


@pytest.mark.parametrize("case", sorted(_BLOCK_LENS))
def test_decode_attention_block_follows_the_vmem_budget(case):
    from paddle_tpu.ops.pallas.decode_attention import _block_len, supported

    assert _block_len(*case) == _BLOCK_LENS[case]
    block_l, L, KV, D, itemsize, quant = case
    dtype = {1: jnp.int8, 2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    q = jax.ShapeDtypeStruct((2, KV, D), jnp.float32)
    kc = jax.ShapeDtypeStruct((2, KV, L, D), dtype)
    assert supported(q, kc) == (_block_len(256, L, KV, D, itemsize, quant) > 0)


def _parity_decoder(family, quant=None):
    """Tiny decoders of the three cache families the decode kernel reads:
    GQA (2 KV heads for 4), MHA (4 for 4: ``rep = 1``, head-major since
    PR 39) and a looped model (OURO_TINY: MHA, 2 layers x 4 passes = 8
    cache layers). max_len 128: the kernel's ``L % 128 == 0`` bound.
    Flags are read at trace time: build one per flag setting."""
    from paddle_tpu.inference.generate import LlamaDecoder
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.ouro import OURO_TINY, OuroForCausalLM

    paddle.seed(9)
    if family == "looped":
        model = OuroForCausalLM(OURO_TINY)
    else:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2 if family == "gqa" else 4,
            max_position_embeddings=256))
    return model, lambda: LlamaDecoder(model, max_len=128, quant=quant)


@pytest.mark.parametrize("family,quant", [
    ("gqa", None), ("gqa", "int8wk"), ("mha", None), ("looped", None)])
def test_decode_attention_chunked_path_parity(family, quant):
    """The chunked decode path routes the SAME decode-attention kernel
    (per-row pos — no second kernel entry point) behind
    FLAGS_use_decode_attention, GQA and (since PR 39, whose MHA cache is
    head-major) MHA at ``rep = 1`` and a looped model's pass caches alike:
    with the flag on (interpret mode off-TPU via
    FLAGS_decode_attention_interpret) and off, the chunked decode emits
    identical tokens and a carry's logits within the kernel's tolerance."""
    model, build = _parity_decoder(family, quant)
    V = model.config.vocab_size
    ids = np.random.default_rng(5).integers(0, V, (2, 4))
    got = {}
    for on in (True, False):
        paddle.set_flags({"use_decode_attention": on,
                          "decode_attention_interpret": True})
        try:
            dec = build()
            toks = np.asarray(dec.generate(ids, 8, chunk_size=3))
            _, st = dec.decode_chunk(dec.init_decode_state(ids), 5)
            got[on] = toks, np.asarray(st.logits)
        finally:
            paddle.set_flags({"use_decode_attention": True,
                              "decode_attention_interpret": False})
    np.testing.assert_array_equal(got[True][0], got[False][0],
                                  err_msg=f"{family} quant={quant}")
    np.testing.assert_allclose(got[True][1], got[False][1],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("family", ["gqa", "mha", "looped"])
def test_chunk_program_holds_decode_attention_once_a_cache_layer(
        family, monkeypatch):
    """What shows that the route engages (decided at trace time, so in
    every step of a program or in none): the chunk program calls
    ``decode_attention`` once per cache layer with the interpret flag on
    (2 / 2 / 8), and not at all with ``use_decode_attention`` off.
    Counted where the program is traced, by wrapping the kernel."""
    from paddle_tpu.ops.pallas import decode_attention as da

    calls = []
    real = da.decode_attention

    def counted(*a, **k):
        calls.append(a[1].shape)
        return real(*a, **k)

    monkeypatch.setattr(da, "decode_attention", counted)
    model, build = _parity_decoder(family)
    cfg = model.config
    B = 2
    for on, want in ((True, cfg.num_cache_layers), (False, 0)):
        calls.clear()
        paddle.set_flags({"use_decode_attention": on,
                          "decode_attention_interpret": True})
        try:
            dec = build()
            kc, vc = dec._empty_cache(B)
            z = jnp.zeros
            dec._ring_chunk_decode._jitted.lower(
                dec.params, z((B, cfg.vocab_size), jnp.float32), kc, vc,
                z((B,), jnp.int32), z((B, 2), jnp.uint32),
                z((B,), jnp.bool_), z((B,), jnp.int32),
                z((B,), jnp.float32), None, *(None,) * 9, steps=4,
                do_sample=False, top_k=None, top_p=None)
        finally:
            paddle.set_flags({"use_decode_attention": True,
                              "decode_attention_interpret": False})
        assert len(calls) == want, (family, on, calls)
        # every call reads a head-major (B, KV, max_len, D) buffer
        assert set(calls) <= {(B, cfg.num_key_value_heads, 128,
                               cfg.head_dim)}


def test_group_norm_silu_fused_matches_unfused():
    """Round-4 fused GroupNorm+SiLU (ops/pallas/group_norm.py, reference
    add_group_norm_silu): value + grad parity vs the lax composition,
    both act=None (F.group_norm routing) and act='silu' (incubate entry)."""
    import jax
    import numpy as np
    from paddle_tpu.ops.fused_norm import group_norm_fused, group_norm_lax

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 8, 4, 4)).astype(np.float32)
    w = rng.standard_normal(8).astype(np.float32)
    b = rng.standard_normal(8).astype(np.float32)
    for act in (None, "silu"):
        f1 = lambda x, w, b: group_norm_fused(x, w, b, 4, 1e-5, act).sum()
        f0 = lambda x, w, b: group_norm_lax(x, w, b, 4, 1e-5, act).sum()
        v1, g1 = jax.value_and_grad(f1, (0, 1, 2))(x, w, b)
        v0, g0 = jax.value_and_grad(f0, (0, 1, 2))(x, w, b)
        np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
        for a, c in zip(g1, g0):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=1e-4, atol=1e-5, err_msg=str(act))


def test_group_norm_functional_routes_to_fused():
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    x = paddle.to_tensor(np.random.rand(2, 8, 4, 4).astype(np.float32),
                         stop_gradient=False)
    w = paddle.to_tensor(np.ones(8, np.float32), stop_gradient=False)
    b = paddle.to_tensor(np.zeros(8, np.float32), stop_gradient=False)
    out = F.group_norm(x, 4, w, b)
    paddle.set_flags({"use_fused_group_norm": False})
    try:
        ref = F.group_norm(x, 4, w, b)
    finally:
        paddle.set_flags({"use_fused_group_norm": True})
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-5, atol=2e-5)
    out.sum().backward()
    assert x.grad is not None and w.grad is not None and b.grad is not None


def test_adam_non_multi_precision_moments_follow_param_dtype():
    """multi_precision=False + bf16 params -> bf16 moments (reference
    non-MP kernel semantics; halves optimizer HBM traffic on TPU)."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    net = nn.Linear(4, 4)
    for p in net.parameters():
        p._set_value(p.value.astype(jnp.bfloat16))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters(),
                                 multi_precision=False)
    x = paddle.to_tensor(np.random.rand(2, 4).astype(np.float32))
    loss = net(x.astype("bfloat16")).sum()
    loss.backward()
    opt.step()
    st = opt._state[id(net.weight)] if hasattr(opt, "_state") else None
    if st is None:  # accumulator storage is keyed differently
        sd = opt.state_dict()
        moments = [v for k, v in sd.items() if "moment1" in k]
        assert moments, sd.keys()
        assert all(np.asarray(m.value if hasattr(m, 'value') else m).dtype
                   == jnp.bfloat16 for m in moments)
    else:
        assert st["moment1"].dtype == jnp.bfloat16
    # default (multi_precision=True) still keeps f32 moments + master
    net2 = nn.Linear(4, 4)
    for p in net2.parameters():
        p._set_value(p.value.astype(jnp.bfloat16))
    opt2 = paddle.optimizer.AdamW(learning_rate=1e-2,
                                  parameters=net2.parameters())
    loss = net2(x.astype("bfloat16")).sum()
    loss.backward()
    opt2.step()
    sd2 = opt2.state_dict()
    m2 = [v for k, v in sd2.items() if "moment1" in k]
    if m2:
        assert all(np.asarray(m.value if hasattr(m, 'value') else m).dtype
                   == jnp.float32 for m in m2)


def test_group_norm_fused_mean_shifted_no_nan():
    """Review fix: one-pass E[x^2]-m^2 variance cancels catastrophically
    on mean-shifted activations. Judged against the f64 ground truth —
    the round-5 pivot-shifted kernel mean is ~5x MORE accurate here than
    the f32 lax composition, so lax is not a valid oracle."""
    import numpy as np
    from paddle_tpu.ops.fused_norm import group_norm_fused, group_norm_lax

    rng = np.random.default_rng(1)
    x = (1000.0 + 0.01 * rng.standard_normal((2, 8, 4, 4))).astype(np.float32)
    w = np.ones(8, np.float32)
    b = np.zeros(8, np.float32)
    out = np.asarray(group_norm_fused(x, w, b, 4, 1e-5, None))
    ref = np.asarray(group_norm_lax(x, w, b, 4, 1e-5, None))
    x64 = x.astype(np.float64).reshape(2, 4, -1)
    m = x64.mean(-1, keepdims=True)
    v = x64.var(-1, keepdims=True)
    true = ((x64 - m) / np.sqrt(v + 1e-5)).reshape(x.shape)
    assert np.isfinite(out).all()
    kerr = np.abs(out - true).max()
    lerr = np.abs(ref - true).max()
    assert kerr < 0.02, kerr
    assert kerr <= lerr + 1e-3, (kerr, lerr)   # kernel never worse than lax


def test_group_norm_supported_bounds_vmem():
    from paddle_tpu.ops.pallas.group_norm import supported
    assert supported((8, 320, 64, 64), 32)          # SD level-0 slab
    assert not supported((1, 320, 256, 256), 1)     # 84MB slab -> XLA
