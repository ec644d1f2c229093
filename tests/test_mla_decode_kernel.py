"""The latent decode attention kernel (ops/pallas/mla_decode_attention.py)
in Pallas interpret mode against XLA's absorbed form, over ragged live
lengths: none, one position, a block's edge and its next, several blocks,
all of the buffer."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import mla_decode_attention as mla


def _xla(qa, qp, ckv, kpe, n, scale):
    """The decoder's XLA form of the same attention (generate.py)."""
    s = (jnp.einsum("bhr,blr->bhl", qa, ckv[:, 0],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bld->bhl", qp, kpe[:, 0],
                      preferred_element_type=jnp.float32)) * scale
    live = jnp.arange(ckv.shape[2])[None, None, :] < n[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1)
    return jnp.einsum("bhl,blr->bhr", p, ckv[:, 0].astype(jnp.float32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_kernel_matches_the_absorbed_form_over_ragged_lengths(dtype, tol):
    """Rows at 1, 255, 256, 257, 700 and 1024 live positions of 1024 in
    blocks of 256 (float32: summation order only; bfloat16: the kernel's
    probabilities enter the value product in bf16, as flash's do)."""
    rng = np.random.default_rng(0)
    B, H, R, P, L = 6, 16, 128, 128, 1024
    qa, ckv = (jnp.asarray(rng.standard_normal(s), dtype)
               for s in ((B, H, R), (B, 1, L, R)))
    qp = jnp.asarray(rng.standard_normal((B, H, P)) * (
        np.arange(P) < 64), dtype)                  # the rope part: 64 + pad
    kpe = jnp.asarray(rng.standard_normal((B, 1, L, P)) * (
        np.arange(P) < 64), dtype)
    n = jnp.asarray([1, 255, 256, 257, 700, 1024], jnp.int32)
    assert mla.supported(qa, ckv, kpe)
    got = mla.mla_decode_attention(qa, qp, ckv, kpe, n, scale=0.13)
    want = _xla(qa, qp, ckv, kpe, n, 0.13)
    assert got.shape == (B, H, R) and got.dtype == dtype
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want)).max()
    assert err < tol * np.abs(np.asarray(want)).max()


def test_rows_with_nothing_live_read_zeros_and_leave_the_others():
    """A row with no live position (a scalar bound of 0 for all, or one
    row of a batch) reads zeros; its neighbours read what they read
    alone. Positions past a row's bound never enter its softmax: garbage
    there changes nothing."""
    rng = np.random.default_rng(1)
    B, H, R, P, L = 3, 8, 128, 128, 512
    qa = jnp.asarray(rng.standard_normal((B, H, R)), jnp.float32)
    qp = jnp.asarray(rng.standard_normal((B, H, P)), jnp.float32)
    ckv = jnp.asarray(rng.standard_normal((B, 1, L, R)), jnp.float32)
    kpe = jnp.asarray(rng.standard_normal((B, 1, L, P)), jnp.float32)
    n = jnp.asarray([0, 300, 1], jnp.int32)
    got = np.asarray(mla.mla_decode_attention(qa, qp, ckv, kpe, n,
                                              scale=0.1))
    assert np.all(got[0] == 0) and np.all(np.isfinite(got))
    junk = ckv.at[:, :, 300:].set(1e4)
    again = np.asarray(mla.mla_decode_attention(qa, qp, junk, kpe, n,
                                                scale=0.1))
    np.testing.assert_array_equal(got[1:], again[1:])
    zero = mla.mla_decode_attention(qa, qp, ckv, kpe, 0, scale=0.1)
    assert np.all(np.asarray(zero) == 0)


def test_supported_refuses_what_it_does_not_tile():
    def s(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt)
    assert mla.supported(s(4, 64, 512), s(4, 1, 16384, 512),
                         s(4, 1, 16384, 128))
    for qa, c, p in ((s(4, 64, 500), s(4, 1, 1024, 500), s(4, 1, 1024, 128)),
                     (s(4, 64, 512), s(4, 1, 1024, 512), s(4, 1, 1024, 64)),
                     (s(4, 64, 512), s(4, 1, 1000, 512), s(4, 1, 1000, 128)),
                     (s(4, 64, 512), s(4, 2, 1024, 512), s(4, 2, 1024, 128)),
                     (s(4, 64, 512), s(4, 1, 1024, 512, dt=jnp.int8),
                      s(4, 1, 1024, 128, dt=jnp.int8))):
        assert not mla.supported(qa, c, p)
