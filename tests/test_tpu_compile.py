"""AOT compiles of the main path's Pallas kernels for a described TPU.

The TPU's compiler is installed with jax and compiles for a topology that
is described, not attached (`v5e:2x2`), so these run on the CPU harness and
still raise what the chip's compiler would raise: a slice off the tiling,
more scoped VMEM than a kernel may use, a program that does not fit. The
shapes are Llama-2-7B's (hidden 4096, 32 heads of 128, FFN 11008).
Interpret mode is switched off by patching the one routing helper, not by
an option of the program. Nothing runs: a compile that passes says nothing
about results or times.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(scope="module")
def tpu():
    """SingleDeviceSharding on one chip of a described v5e 2x2, with the
    persistent compile cache off around the module (an AOT entry written
    for a described chip cannot be read back without one, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def compiled_not_interpreted(monkeypatch):
    from paddle_tpu.ops.pallas import _routing
    monkeypatch.setattr(_routing, "use_interpret", lambda: False)


def _kernels(fn, *args):
    from paddle_tpu.obs.cost import program_census
    return program_census(jax.jit(fn).lower(*args).compile())["kernels"]


def _s(tpu, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=tpu)


def test_flash_attention_fwd_bwd_compiles(tpu):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fn

    def loss(q, k, v):
        return flash_attention_fn(q, k, v, causal=True).astype(
            jnp.float32).sum()

    qkv = [_s(tpu, (2, 2048, 32, 128))] * 3
    kernels = _kernels(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert kernels == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rms_norm_fwd_bwd_compiles_at_hidden_4096(tpu, dtype):
    """The case a fixed 256-row block failed: at h=4096 the backward's
    blocks and temporaries passed the 16 MiB scoped-VMEM limit."""
    from paddle_tpu.ops.pallas import rms_norm

    rows, h = 4096, 4096
    assert rms_norm.supported((2, 2048, h), (h,))
    x, w = _s(tpu, (rows, h), dtype), _s(tpu, (h,), jnp.float32)
    assert _kernels(lambda x, w: rms_norm.rms_fwd(x, w, 1e-6), x, w) \
        == {"rms_norm_fwd": 1}
    assert _kernels(rms_norm.rms_bwd, x, w, _s(tpu, (rows, 1), jnp.float32),
                    _s(tpu, (rows, h), dtype)) == {"rms_norm_bwd": 1}


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_decode_attention_compiles(tpu, kv_dtype):
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    B, H, KV, L, D = 8, 32, 8, 4096, 128
    q, pos = _s(tpu, (B, H, D)), _s(tpu, (B,), jnp.int32)
    kc = _s(tpu, (B, KV, L, D), kv_dtype)
    if kv_dtype == jnp.int8:
        sc = _s(tpu, (B, KV, L, 1), jnp.float32)
        kernels = _kernels(
            lambda q, k, v, p, ks, vs: decode_attention(
                q, k, v, p, k_scale=ks, v_scale=vs), q, kc, kc, pos, sc, sc)
    else:
        kernels = _kernels(decode_attention, q, kc, kc, pos)
    assert kernels == {"decode_attention": 1}


def test_int8_matmul_compiles(tpu):
    from paddle_tpu.ops.pallas.int8_matmul import int8_matmul

    kernels = _kernels(int8_matmul, _s(tpu, (8, 4096)),
                       _s(tpu, (4096, 11008), jnp.int8),
                       _s(tpu, (11008,), jnp.float32))
    assert kernels == {"int8_matmul": 1}


def test_kernels_give_way_where_gspmd_would_split_them(monkeypatch):
    """Mosaic kernels cannot be partitioned automatically: under a mesh of
    several devices the routing predicates decline (the XLA forms shard),
    inside a fully manual shard_map they do not."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.ops.pallas import _routing, flash_attention, rms_norm
    from paddle_tpu.parallel import mesh as pmesh

    qkv = (2, 2048, 32, 128)
    assert not _routing.auto_partitioned()
    assert rms_norm.supported((4096, 4096), (4096,))
    assert flash_attention.supported(qkv, qkv, True)
    monkeypatch.setattr(pmesh, "_GLOBAL_MESH",
                        pmesh.ProcessMesh(shape=(2, 2),
                                          dim_names=("dp", "mp")))
    assert _routing.auto_partitioned()
    assert not rms_norm.supported((4096, 4096), (4096,))
    assert not flash_attention.supported(qkv, qkv, True)
    seen = []

    def body(x):
        seen.append(_routing.auto_partitioned())
        return x

    m = pmesh.get_mesh().jax_mesh
    jax.jit(jax.shard_map(body, mesh=m, in_specs=P("dp", "mp"),
                          out_specs=P("dp", "mp"), check_vma=False)
            ).lower(jax.ShapeDtypeStruct((4, 4), np.float32))
    assert seen == [False]
