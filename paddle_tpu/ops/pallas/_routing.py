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
"""

from __future__ import annotations

import jax

__all__ = ["use_interpret", "auto_partitioned"]


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


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
