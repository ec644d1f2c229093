"""The decode step's token-row write: ``ops/pallas/kv_row_write.py`` in
interpret mode against ``jax.vmap(dynamic_update_slice)``, the form XLA
keeps, and through ``LlamaDecoder`` / ``ServingEngine`` with the kernel
routed (``flags.decode_attention_interpret``) and with the predicate
false. Tiny shapes; a head size of 128 is the least the kernel takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import generate as G
from paddle_tpu.inference.generate import LlamaDecoder
from paddle_tpu.ops.pallas import kv_row_write as kw
from paddle_tpu.serving import ServingEngine

KV, L, D = 2, 64, 128
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32, "int8": jnp.int8}


def _vmapped_dus(buf, t, at, head_major):
    at0 = (lambda p: (0, p, 0)) if head_major else (lambda p: (p, 0, 0))
    return jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(
        c, u, at0(p)))(buf, t, at)


def _shapes(B, head_major, L=L, D=D, S=1):
    return (((B, KV, L, D), (B, KV, S, D)) if head_major
            else ((B, L, KV, D), (B, S, KV, D)))


def _fill(rng, shape, dtype):
    return jnp.asarray(rng.integers(-100, 100, shape), dtype)


def _edges(T):
    """The buffer's ends, a tile's edges, and past the end (clamped, as
    the scatter's clip mode: a row past its budget)."""
    return [0, T - 1, T, L - 1, L, L + 37, 2 * T - 1, 2 * T, 1, L - T]


@pytest.mark.parametrize("B", [1, 16])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("head_major", [True, False],
                         ids=["head_major", "token_major"])
def test_kernel_is_bit_equal_to_the_vmapped_update(head_major, dtype, B):
    dt = DTYPES[dtype]
    T = kw._TILE[jnp.dtype(dt).itemsize]
    rng = np.random.default_rng(B)
    cs, rs = _shapes(B, head_major)
    kc, vc = _fill(rng, cs, dt), _fill(rng, cs, dt)
    assert kw.supported(kc, jnp.zeros(rs, dt), head_major)
    # B == 1 walks its one row over every edge; B == 16 spreads them
    edges = _edges(T)
    ats = [jnp.asarray(a, jnp.int32) for a in (
        [[p] for p in edges[:6]] if B == 1
        else [[edges[(b * 3) % len(edges)] for b in range(B)]])]
    for at in ats:
        k, v = _fill(rng, rs, dt), _fill(rng, rs, dt)
        ko, vo = kw.kv_row_write(kc, vc, k, v, at, head_major=head_major)
        want_k = _vmapped_dus(kc, k, at, head_major)
        want_v = _vmapped_dus(vc, v, at, head_major)
        np.testing.assert_array_equal(np.asarray(ko), np.asarray(want_k))
        np.testing.assert_array_equal(np.asarray(vo), np.asarray(want_v))
        # one position a row changed, everything else byte for byte
        axis = (1, 3) if head_major else (2, 3)
        moved = np.any(np.asarray(ko) != np.asarray(kc), axis=axis)
        assert moved.sum() <= B and (moved.sum(axis=1) <= 1).all()
        rows, where = np.nonzero(moved)
        np.testing.assert_array_equal(
            where, np.minimum(np.asarray(at), L - 1)[rows])
        kc, vc = ko, vo


@pytest.mark.parametrize("head_major", [True, False],
                         ids=["head_major", "token_major"])
def test_rolling_buffer_that_has_wrapped(head_major):
    """A windowed layer's buffer is written at ``pos % L``: the caller's
    arithmetic, the kernel's plain position."""
    B, dt = 4, jnp.float32
    rng = np.random.default_rng(7)
    cs, rs = _shapes(B, head_major)
    kc, vc = _fill(rng, cs, dt), _fill(rng, cs, dt)
    want_k, want_v = kc, vc
    pos = jnp.asarray([L - 2, 3 * L - 1, L + 5, 7], jnp.int32)
    for _ in range(4):                       # rows cross the wrap
        k, v = _fill(rng, rs, dt), _fill(rng, rs, dt)
        kc, vc = kw.kv_row_write(kc, vc, k, v, pos % L,
                                 head_major=head_major)
        want_k = _vmapped_dus(want_k, k, pos % L, head_major)
        want_v = _vmapped_dus(want_v, v, pos % L, head_major)
        pos = pos + 1
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(want_v))


@pytest.mark.parametrize("case,buf,t,head_major", [
    ("two tokens a row", (4, KV, L, D), (4, KV, 2, D), True),
    ("two tokens a row, token-major", (4, L, KV, D), (4, 2, KV, D), False),
    ("a quantized cache's scale leaf", (4, KV, L, 1), (4, KV, 1, 1), True),
    ("a head size off the lane tile", (4, KV, L, 64), (4, KV, 1, 64), True),
    ("a length off the sublane tile", (4, KV, 60, D), (4, KV, 1, D), True),
    ("rows of another batch", (4, KV, L, D), (2, KV, 1, D), True),
], ids=lambda c: c.replace(" ", "_") if isinstance(c, str) else None)
def test_supported_refuses(case, buf, t, head_major):
    s = jax.ShapeDtypeStruct
    assert not kw.supported(s(buf, jnp.float32), s(t, jnp.float32),
                            head_major), case
    with pytest.raises(ValueError, match="not served"):
        kw.kv_row_write(jnp.zeros(buf), jnp.zeros(buf), jnp.zeros(t),
                        jnp.zeros(t), jnp.zeros((buf[0],), jnp.int32),
                        head_major=head_major)


@pytest.mark.parametrize("buf_dt,row_dt", [
    (jnp.bfloat16, jnp.float32),     # the rows' dtype is the buffer's
    (jnp.float16, jnp.float16),      # bf16 / f32 / int8 only
])
def test_supported_refuses_dtypes(buf_dt, row_dt):
    s = jax.ShapeDtypeStruct
    assert not kw.supported(s((4, KV, L, D), buf_dt),
                            s((4, KV, 1, D), row_dt), True)
    # a token-major length needs no sublane tile: a position is a slab
    assert kw.supported(s((4, 60, KV, D), jnp.float32),
                        s((4, 1, KV, D), jnp.float32), False)


# -- through the decoder: the one call site, ``_cache_update``

@pytest.fixture
def kernel_calls(monkeypatch):
    """Route the kernel off the TPU (interpret mode) and count its calls
    at trace time; ``off()`` makes the predicate false again."""
    class Calls(list):
        def off(self):
            paddle.set_flags({"decode_attention_interpret": False})

    calls = Calls()
    real = kw.kv_row_write

    def counted(*a, **k):
        calls.append(k["head_major"])
        return real(*a, **k)

    monkeypatch.setattr(kw, "kv_row_write", counted)
    # the flag routes every kernel of the decoder; hold a cold prefill's
    # attention to XLA's form on both sides, so that what these tests
    # compare bit for bit differs by the row write alone
    from paddle_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(fa, "supported", lambda *a, **k: False)
    paddle.set_flags({"decode_attention_interpret": True})
    yield calls
    calls.off()


def _model(kind):
    """Decoders of the kinds the benchmark's configurations are, at a
    head size the kernel takes (2 heads of 128)."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(11)
    base = dict(vocab_size=96, hidden_size=256, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=2,
                max_position_embeddings=64)
    if kind == "looped":
        from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
        return OuroForCausalLM(OuroConfig(
            **base, num_key_value_heads=2, total_ut_steps=2))
    if kind == "windowed":
        from paddle_tpu.models.afmoe import (FULL, SLIDING, AfmoeConfig,
                                             AfmoeForCausalLM)
        return AfmoeForCausalLM(AfmoeConfig(
            **{**base, "num_hidden_layers": 3}, num_key_value_heads=1,
            head_dim=128, layer_types=(SLIDING, SLIDING, FULL),
            sliding_window=8, num_dense_layers=1, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=32))
    return LlamaForCausalLM(LlamaConfig(
        **base, num_key_value_heads=1 if kind.startswith("gqa") else 2))


def _serve(model, quant=None):
    """Mixed prompts through a three-slot engine, so rows sit at different
    positions; -> (tokens by request, the carry's caches, the decoder)."""
    dec = LlamaDecoder(model, max_len=64, quant=quant)
    eng = ServingEngine(dec, num_slots=3, chunk_size=4)
    rng = np.random.default_rng(2)
    ids = [eng.submit(rng.integers(0, 96, (n,)), m)
           for n, m in ((5, 14), (11, 9), (3, 17), (7, 6))]
    res = eng.drain()
    return ([np.asarray(res[r]) for r in ids],
            jax.tree_util.tree_leaves((eng.state.kc, eng.state.vc)), dec)


@pytest.mark.parametrize("kind", ["gqa", "mha", "looped", "windowed",
                                  "gqa-int8wk"])
def test_decoder_serves_the_same_with_the_kernel_and_without(
        kernel_calls, kind):
    """Greedy tokens and the carry's caches, leaf for leaf, with the
    row write routed to the kernel and with the predicate false: GQA,
    MHA (head-major too since PR 39: every decoder's cache is), a looped
    model's layers x passes buffers, a windowed model's rolling buffers
    (window 8: every row wraps), and an int8 cache, whose int8 leaf takes
    the kernel and whose scale leaf keeps XLA's scatter. The token-major
    form has no decoder caller left; test_other_writes_keep_xlas_form
    and the kernel's own cases hold it."""
    model = _model(kind)
    quant = "int8wk" if kind.endswith("int8wk") else None
    toks_on, caches_on, dec = _serve(model, quant)
    layers = dec.cfg.num_cache_layers
    # once a cache layer in each traced chunk program (steps 4 and fewer),
    # head-major whatever the heads
    assert kernel_calls and len(kernel_calls) % layers == 0
    assert set(kernel_calls) == {True}
    if kind == "windowed":
        assert {b.shape[2] for b in dec._empty_cache(1)[0]} == {8, 64}
    n = len(kernel_calls)
    kernel_calls.off()
    toks_off, caches_off, _ = _serve(model, quant)
    assert len(kernel_calls) == n            # the predicate was false
    for a, b in zip(toks_on, toks_off):
        np.testing.assert_array_equal(a, b)
    assert len(caches_on) == len(caches_off) >= 2 * layers
    for a, b in zip(caches_on, caches_off):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("head_major", [True, False],
                         ids=["head_major", "token_major"])
def test_other_writes_keep_xlas_form(kernel_calls, head_major):
    """Two tokens a row at per-row positions (the speculative verify's
    uneven advance), a scalar position (prefills, the lockstep step) and
    a mesh keep today's code with the kernel's flag on."""
    B, dt = 4, jnp.float32
    rng = np.random.default_rng(5)
    at = jnp.asarray([0, 9, 30, 62], jnp.int32)
    for S, pos, sharded in ((2, at, False), (1, jnp.int32(5), False),
                            (2, jnp.int32(5), False), (1, at, True)):
        cs, rs = _shapes(B, head_major, S=S)
        kc, vc = _fill(rng, cs, dt), _fill(rng, cs, dt)
        k, v = _fill(rng, rs, dt), _fill(rng, rs, dt)
        ko, vo = G._cache_update(kc, vc, k, v, pos, head_major, sharded)
        for got, buf, t in ((ko, kc, k), (vo, vc, v)):
            np.testing.assert_array_equal(
                np.asarray(got),
                np.asarray(G._cache_write(buf, t, pos, head_major)))
    assert kernel_calls == []
    cs, rs = _shapes(B, head_major)
    G._cache_update(jnp.zeros(cs), jnp.zeros(cs), jnp.ones(rs),
                    jnp.ones(rs), at, head_major)
    assert kernel_calls == [head_major]


def test_speculative_chunks_decode_the_same_with_the_kernel(kernel_calls):
    """A speculative engine's draft steps are one token a row (the
    kernel), its verify K+1 (XLA's scatter): the tokens are the plain
    decoder's either way."""
    model = _model("gqa")

    def spec():
        dec = LlamaDecoder(model, max_len=64)
        ids = np.random.default_rng(4).integers(0, 96, (2, 6))
        return np.asarray(dec.generate(ids, 10, draft_model="skip:1",
                                       num_speculative_tokens=2,
                                       chunk_size=3))
    on = spec()
    assert kernel_calls
    kernel_calls.off()
    np.testing.assert_array_equal(on, spec())
