"""Flash attention kernel tests (interpret mode on CPU).

OpTest-style: compare the Pallas kernel against the reference sdpa
(nn/functional.py _sdpa_ref) for outputs and gradients — the reference's
"one schema, N runtimes" cross-check pattern (SURVEY §4a)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention_fn


def _ref_attention(q, k, v, causal):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qT = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kT = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vT = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qT, kT) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vT)
    return jnp.swapaxes(out, 1, 2)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 128, 2, 64), (2, 256, 4, 32)])
def test_forward_matches_reference(shape, causal):
    rng = np.random.default_rng(0)
    b, s, h, d = shape
    q = rng.normal(size=shape).astype(np.float32)
    k = rng.normal(size=shape).astype(np.float32)
    v = rng.normal(size=shape).astype(np.float32)
    out = flash_attention_fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal=causal, block_q=64, block_k=64)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    rng = np.random.default_rng(1)
    shape = (1, 128, 2, 32)
    q = jnp.asarray(rng.normal(size=shape), jnp.float32)
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_fn(q, k, v, causal=causal,
                                          block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attention(q, k, v, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_wired_into_functional():
    """nn.functional.scaled_dot_product_attention uses the kernel when
    shapes allow (FLAGS use_fused_attention + flash_attention_min_seq)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    paddle.set_flags({"flash_attention_min_seq": 64})
    rng = np.random.default_rng(2)
    q = paddle.to_tensor(rng.normal(size=(1, 128, 2, 32)).astype(np.float32),
                         stop_gradient=False)
    k = paddle.to_tensor(rng.normal(size=(1, 128, 2, 32)).astype(np.float32))
    v = paddle.to_tensor(rng.normal(size=(1, 128, 2, 32)).astype(np.float32))
    out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    ref = _ref_attention(q.numpy(), k.numpy(), v.numpy(), True)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=2e-4, atol=2e-4)
    # grads flow through the tape
    paddle.sum(out).backward()
    assert q.grad is not None


@pytest.mark.parametrize("q,k,causal,ok", [
    ((2, 2048, 32, 128), (2, 2048, 32, 128), True, True),
    ((1, 64, 4, 32), (1, 128, 4, 32), True, True),      # chunked prefill
    ((1, 128, 4, 32), (1, 64, 4, 32), True, False),     # a query sees no key
    ((1, 128, 4, 32), (1, 64, 4, 32), False, True),
    ((1, 128, 8, 32), (1, 128, 2, 32), True, False),    # KV heads not repeated
    ((1, 2050, 4, 32), (1, 2050, 4, 32), True, False),  # no 8-aligned tiling
    ((128, 4, 32), (128, 4, 32), False, False),         # not (B, S, H, D)
])
def test_supported_is_the_routing_predicate(q, k, causal, ok):
    """What used to surface as a ValueError out of the kernel's trace (and
    was swallowed) is decided up front; the kernel still raises when it is
    called on such a shape directly."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    assert fa.supported(q, k, causal) is ok
    if not ok and len(q) == 4:
        with pytest.raises(ValueError):
            fa.flash_attention_fn(jnp.zeros(q), jnp.zeros(k), jnp.zeros(k),
                                  causal=causal)


def test_bf16_io():
    rng = np.random.default_rng(3)
    shape = (1, 128, 1, 64)
    q = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    out = flash_attention_fn(q, k, v, causal=True, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    ref = _ref_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


@pytest.mark.parametrize("causal,sq,sk", [(False, 64, 64), (False, 32, 64),
                                          (True, 64, 64), (True, 32, 64)])
def test_single_block_kernel_matches_reference(causal, sq, sk):
    """Round-4 single-block specialization (_fwd_single_kernel): when the
    whole sequence fits one (q,k) block, the merge-free kernel must match
    reference attention for non-causal, causal, and chunked-prefill
    (sq<sk bottom-right-aligned offset) shapes — values AND grads."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    rng = np.random.default_rng(5)
    bh, d = 4, 32
    q = jnp.asarray(rng.standard_normal((bh, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, sk, d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((bh, sq, d)), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def ref(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        if causal:
            rows = jnp.arange(sq)[:, None] + (sk - sq)
            cols = jnp.arange(sk)[None, :]
            s = jnp.where(rows >= cols, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v)

    def kern(q, k, v):
        # block == full seq -> _fwd_single_kernel path
        return fa._flash(q, k, v, scale, causal, sq, sk, fa._routing.use_interpret())

    out_r, vjp_r = jax.vjp(ref, q, k, v)
    out_k, vjp_k = jax.vjp(kern, q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4)
    for gr, gk in zip(vjp_r(do), vjp_k(do)):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3)


def _band_ref(q, k, v, window):
    """Dense banded causal attention: key j visible to query i iff
    0 <= (i + sk - sq) - j < window."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    dist = (jnp.arange(sq)[:, None] + sk - sq) - jnp.arange(sk)[None, :]
    s = jnp.where(jnp.logical_and(dist >= 0, dist < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      v.astype(jnp.float32))


@pytest.mark.parametrize("sq,sk,window,bq,bk", [
    (256, 256, 64, 64, 64),     # the band spans whole blocks
    (256, 256, 100, 64, 64),    # ... and cuts through them
    (256, 256, 1, 64, 64),      # every query sees itself alone
    (256, 256, 300, 64, 64),    # wider than the sequence: plain causal
    (128, 256, 70, 64, 64),     # bottom-right aligned, sq < sk
    (64, 64, 10, 64, 64),       # the single-block kernel
    (256, 256, 96, 32, 128),    # q and k blocks of different sizes
])
def test_banded_forward_matches_a_dense_mask(sq, sk, window, bq, bk):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, sq, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, sk, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, sk, 2, 32)), jnp.float32)
    out = flash_attention_fn(q, k, v, causal=True, block_q=bq, block_k=bk,
                             window=window)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_band_ref(q, k, v, window)),
                               rtol=2e-4, atol=2e-4)


def test_banded_backward_and_non_causal_window_refuse_typed():
    from paddle_tpu.ops.pallas import flash_attention as fa
    x = jnp.ones((1, 128, 2, 32), jnp.float32)
    with pytest.raises(fa.WindowBackwardError):
        jax.grad(lambda q: flash_attention_fn(
            q, x, x, causal=True, window=16).sum())(x)
    with pytest.raises(ValueError, match="window"):
        flash_attention_fn(x, x, x, causal=False, window=16)
    assert fa.supported(x.shape, x.shape, True, window=16)
    assert not fa.supported(x.shape, x.shape, False, window=16)
    assert not fa.supported(x.shape, x.shape, True, window=0)


def test_without_a_window_the_lowered_text_has_no_band():
    """The windowless call lowers exactly as before the window existed:
    no clamp in the k/v index maps, no second comparison in the mask."""
    x = jnp.ones((1, 256, 2, 32), jnp.float32)
    plain = jax.jit(lambda q: flash_attention_fn(
        q, x, x, causal=True, block_q=64, block_k=64)).lower(x).as_text()
    band = jax.jit(lambda q: flash_attention_fn(
        q, x, x, causal=True, block_q=64, block_k=64,
        window=64)).lower(x).as_text()
    assert plain != band
    assert "clamp" not in plain
