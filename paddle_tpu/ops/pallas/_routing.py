"""What every Pallas kernel's routing asks of the process, in one place.

The kernels compile (Mosaic) for the TPU only. On any other backend they
run in Pallas interpret mode, so the CPU tests execute the same kernel
bodies. Kernels call ``_routing.use_interpret()`` through the module, so
an AOT-compile test that targets a described TPU topology from a CPU
process switches interpret mode off by patching this one function.

A Mosaic kernel is also a single-device program: GSPMD has no rule to
split it ("Mosaic kernels cannot be automatically partitioned"). Each
trainer-side kernel's ``supported()`` asks ``auto_partitioned()`` and
sends a trace that will be split across devices to the XLA form, as the
decoder's ``sharded`` switch does for its kernels; inside a fully manual
``shard_map`` the kernels stay.

The kernels of the decoder and of the routed feed-forward run only where
``kernel_backend()`` holds: off the TPU they stay out of the hundreds of
tests that run a tiny model, unless a test asks for them interpreted.
"""

from __future__ import annotations

import jax

__all__ = ["use_interpret", "auto_partitioned", "kernel_backend"]


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_backend() -> bool:
    """The backend half of the routing of every Pallas kernel the decoder
    calls (attention over the cache, the token-row write, a cold
    prefill's flash forward) and of the routed experts' feed-forward
    (``ops/moe.py``): a TPU, or ``flags.decode_attention_interpret`` for
    the CPU tests — off the TPU a kernel runs interpreted, which the
    hundreds of tests that prefill a tiny decoder should not pay for."""
    from paddle_tpu.flags import flags
    return bool(jax.default_backend() == "tpu"
                or flags.decode_attention_interpret)


def auto_partitioned() -> bool:
    """True when GSPMD will split the trace at hand: the framework's
    active mesh spans several devices and the trace is not inside a
    shard_map that is manual over every mesh axis."""
    from paddle_tpu.parallel.mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return False
    manual = jax.sharding.get_abstract_mesh()
    return manual.empty or set(manual.manual_axes) != set(manual.axis_names)
