"""The routed experts' feed-forward as one Pallas kernel
(ops/pallas/grouped_ffn.py), interpreted on the CPU: against XLA's pair of
``ragged_dot``s (``ops/moe.py``), its gradient through the custom VJP, what
``supported`` refuses, and the route — a tiny AFMoE decoder's chunk
program holds the kernel once a routed layer where the kernels run, XLA's
pair elsewhere, and the serving engine counts it."""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.generate import LlamaDecoder
from paddle_tpu.models.afmoe import AFMOE_TINY, AfmoeForCausalLM
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas import grouped_ffn as gf
from paddle_tpu.serving import ServingEngine

H, F, G = 128, 256, 8


def _experts(dtype, seed=0):
    rng = np.random.default_rng(seed)
    gu = rng.standard_normal((G, H, 2 * F)) * 0.1
    dn = rng.standard_normal((G, F, H)) * 0.1
    return jnp.asarray(gu, dtype), jnp.asarray(dn, dtype)


# (sizes a group, rows in all): the rows past the sizes' sum are in no group
_GROUPS = {
    "empty_first_middle_last": ([0, 3, 0, 5, 1, 0, 2, 0], 24),
    "one_group_holds_every_row": ([0, 0, 0, 40, 0, 0, 0, 0], 40),
    "no_row_held": ([0] * 8, 12),
    "a_group_longer_than_a_row_tile": ([1, 0, 150, 0, 0, 7, 0, 0], 176),
    "trailing_rows_in_no_group": ([2, 2, 0, 0, 0, 0, 1, 0], 37),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_GROUPS))
def test_kernel_matches_xla_pair(case, dtype):
    sizes, M = _GROUPS[case]
    gu, dn = _experts(dtype)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((M, H)), dtype)
    s = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    assert gf.supported(M, gu, dn, dtype)
    got = np.asarray(gf.grouped_ffn(x, gu, dn, s), np.float32)
    assert got.shape == (M, H)
    f32 = [a.astype(jnp.float32) for a in (x, gu, dn)]
    want = np.asarray(moe._ragged_pair(*f32, s))[:n]
    scale = max(float(np.abs(want).max(initial=0.0)), 1e-6)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert np.abs(got[:n] - want).max(initial=0.0) <= tol * scale
    assert not np.any(got[n:])          # rows in no group read zero
    if dtype == jnp.bfloat16:
        # no further from float32 than XLA's own bf16 pair
        xla = np.asarray(moe._ragged_pair(x, gu, dn, s), np.float32)[:n]
        assert np.abs(got[:n] - want).max(initial=0.0) \
            <= 1.5 * np.abs(xla - want).max(initial=0.0) + 1e-6 * scale


def test_supported_refuses_what_the_kernel_cannot_take():
    s = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    gu, dn = s((G, H, 2 * F), bf), s((G, F, H), bf)
    assert gf.supported(64, gu, dn, bf)
    assert gf.supported(3, gu, dn, bf)               # rows padded to a tile
    assert not gf.supported(64, gu, dn, jnp.float32)     # x's dtype
    assert not gf.supported(64, s((G, H, 2 * F), jnp.int8),
                            s((G, F, H), jnp.int8), jnp.int8)
    assert not gf.supported(64, s((G, 96, 2 * F), bf),   # H off the lanes
                            s((G, F, 96), bf), bf)
    assert not gf.supported(64, s((G, H, 2 * 96), bf),   # F off the lanes
                            s((G, 96, H), bf), bf)
    assert not gf.supported(64, gu, s((G, F, 2 * H), bf), bf)  # down shape
    assert not gf.supported(64, s((H, 2 * F), bf), s((F, H), bf), bf)
    assert not gf.supported(0, gu, dn, bf)
    # the rows resident: the published widths take the serving batch's
    # 384 and a bucket-512 prefill's 2048, not a bucket-8192 one's 32768
    big_gu, big_dn = s((32, 3072, 6144), bf), s((32, 3072, 3072), bf)
    assert gf.supported(384, big_gu, big_dn, bf)
    assert gf.supported(2048, big_gu, big_dn, bf)
    assert not gf.supported(32768, big_gu, big_dn, bf)
    with pytest.raises(ValueError, match="not served"):
        gf.grouped_ffn(jnp.zeros((8, 96), bf), jnp.zeros((G, 96, 256), bf),
                       jnp.zeros((G, 128, 96), bf),
                       jnp.zeros((G,), jnp.int32))


@pytest.mark.parametrize("mib,limit_mib,rows_taken,rows_refused", [
    (128, 112, 2048, 32768),     # the v5e, v6e: what the chip runs
    (64, 56, 384, 2048),         # the v5p, v7x: the decode batch, not a
                                 # bucket-512 prefill
    (0, None, None, 1),          # a TPU that JAX does not describe
], ids=["128MiB", "64MiB", "unknown"])
def test_the_vmem_plan_follows_the_chips_vmem(monkeypatch, mib, limit_mib,
                                              rows_taken, rows_refused):
    monkeypatch.setattr(gf, "_vmem_capacity", lambda: mib << 20)
    s = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    gu, dn = s((32, 3072, 6144), bf), s((32, 3072, 3072), bf)
    if rows_taken:
        assert gf.supported(rows_taken, gu, dn, bf)
        tm, tf, rows, limit = gf._plan(rows_taken, 3072, 3072, 2)
        assert (tf, limit) == (512, limit_mib << 20)
        assert 2 * 3 * 3072 * tf * 2 + rows * 3072 * 8 <= limit
    assert not gf.supported(rows_refused, gu, dn, bf)


@pytest.fixture
def kernels_routed():
    paddle.set_flags({"decode_attention_interpret": True})
    yield
    paddle.set_flags({"decode_attention_interpret": False})


def test_gradient_through_the_kernel_is_the_xla_pairs(kernels_routed):
    gu, dn = _experts(jnp.float32, seed=3)
    sizes, M = _GROUPS["empty_first_middle_last"]
    s = jnp.asarray(sizes, jnp.int32)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((M, H)),
                    jnp.float32)
    cot = jnp.asarray(np.random.default_rng(5).standard_normal((M, H)),
                      jnp.float32)
    assert moe.kernel_route(M, gu, dn, x.dtype)

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c, s)[:sum(sizes)]
                                       * cot[:sum(sizes)])
    got = jax.grad(loss(moe.grouped_ffn), argnums=(0, 1, 2))(x, gu, dn)
    want = jax.grad(loss(moe._ragged_pair), argnums=(0, 1, 2))(x, gu, dn)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_the_route_follows_backend_mesh_and_shape(kernels_routed,
                                                  monkeypatch):
    from paddle_tpu.parallel import mesh as pmesh
    gu, dn = _experts(jnp.float32)
    assert moe.kernel_route(24, gu, dn, jnp.float32)
    assert not moe.kernel_route(24, gu, dn, jnp.float32, sharded=True)
    assert not moe.kernel_route(24, gu, dn, jnp.bfloat16)
    monkeypatch.setattr(pmesh, "_GLOBAL_MESH",
                        pmesh.ProcessMesh(shape=(2, 2),
                                          dim_names=("dp", "mp")))
    assert not moe.kernel_route(24, gu, dn, jnp.float32)
    monkeypatch.setattr(pmesh, "_GLOBAL_MESH", None)
    paddle.set_flags({"decode_attention_interpret": False})
    assert not moe.kernel_route(24, gu, dn, jnp.float32)   # off the TPU


# -- through the decoder: a tiny AFMoE at widths the kernel takes ------------

_CFG = dataclasses.replace(AFMOE_TINY, hidden_size=128,
                           moe_intermediate_size=128, intermediate_size=256,
                           num_attention_heads=4, head_dim=32)
_ROUTED = _CFG.num_hidden_layers - _CFG.num_dense_layers


def _census(dec, B=2):
    """(kernel calls, ``ragged_dot`` mentions) in the chunk program's
    jaxpr — off the TPU the kernel lowers to ordinary HLO, and so does
    ``ragged_dot``, so the program's text could not tell them apart."""
    kc, vc = dec._empty_cache(B)
    z = jnp.zeros
    text = str(dec._ring_chunk_decode._jitted.trace(
        dec.params, z((B, _CFG.vocab_size), jnp.float32), kc, vc,
        z((B,), jnp.int32), z((B, 2), jnp.uint32), z((B,), jnp.bool_),
        z((B,), jnp.int32), z((B,), jnp.float32), None, *(None,) * 9,
        steps=2, do_sample=False, top_k=None, top_p=None).jaxpr)
    return (len(re.findall(r"jit\[name=grouped_ffn", text)),
            len(re.findall(r"ragged_dot", text)))


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    return AfmoeForCausalLM(_CFG)


def test_the_chunk_program_holds_the_kernel_once_a_routed_layer(
        model, kernels_routed):
    dec = LlamaDecoder(model, max_len=64)
    calls, pairs = _census(dec)
    assert (calls, pairs) == (_ROUTED, 0)
    assert dec.moe_ffn_kernel_layers(2) == calls
    eng = ServingEngine(dec, num_slots=2, chunk_size=2)
    assert eng.metrics()["moe_ffn_kernel_layers"] == calls
    assert eng.registry.get("serving.moe.ffn_kernel_layers").value == calls
    # the step's logits are those of XLA's pair
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 12)),
                      jnp.int32)
    kc, vc = dec._empty_cache(2)
    _, kc, vc = dec._prefill(dec.params, ids, kc, vc)
    nxt = ids[:, -1:]
    got, _, _ = dec._step(dec.params, nxt, kc, vc, jnp.int32(12))
    paddle.set_flags({"decode_attention_interpret": False})
    plain = LlamaDecoder(model, max_len=64)
    kc, vc = plain._empty_cache(2)
    _, kc, vc = plain._prefill(plain.params, ids, kc, vc)
    want, _, _ = plain._step(plain.params, nxt, kc, vc, jnp.int32(12))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_off_the_tpu_or_under_a_mesh_the_chunk_program_holds_xlas_pair(
        model):
    from paddle_tpu.parallel import ProcessMesh
    dec = LlamaDecoder(model, max_len=64)
    calls, pairs = _census(dec)
    assert calls == 0 and pairs >= 2 * _ROUTED
    assert ServingEngine(dec, num_slots=2, chunk_size=2).metrics()[
        "moe_ffn_kernel_layers"] == 0
    paddle.set_flags({"decode_attention_interpret": True})
    try:
        sh = LlamaDecoder(model, max_len=64,
                          mesh=ProcessMesh(shape=(2, 2),
                                           dim_names=("dp", "tp")))
        assert sh.moe_ffn_kernel_layers(2) == 0
        calls, pairs = _census(sh)
        assert calls == 0 and pairs >= 2 * _ROUTED
    finally:
        paddle.set_flags({"decode_attention_interpret": False})
