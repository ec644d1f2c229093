"""Semi-auto parallel user API: shard_tensor / reshard / shard_layer.

Redesign of the reference's dygraph semi-auto API
(python/paddle/distributed/auto_parallel/api.py: shard_tensor:130,
reshard:346, shard_layer:445, dtensor_from_fn:312) on the GSPMD model:
the *global-view* tensor is a ``jax.Array`` with a ``NamedSharding``; the
per-op SPMD rules + reshard machinery of the reference
(paddle/phi/infermeta/spmd_rules/, .../reshard/) are played by XLA's
sharding propagation — eager ops on sharded arrays follow
computation-follows-data, and ``reshard`` compiles to the minimal
collective (allgather / all-to-all / slice / psum) instead of hand-written
R↔S/P↔R functions.

``Partial`` placements are the one case XLA does not expose publicly, so
they are tracked on the Tensor and materialized with a ``shard_map`` psum
when resharded to Replicate/Shard.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.framework.tensor import Tensor, Parameter
from paddle_tpu.parallel.mesh import ProcessMesh, get_mesh
from paddle_tpu.parallel.placements import Partial, Placement, Replicate, Shard

__all__ = [
    "shard_tensor", "reshard", "dtensor_from_fn", "shard_layer",
    "placements_to_spec", "spec_to_placements", "named_sharding",
    "local_shape", "unshard",
]


def placements_to_spec(placements: Sequence[Placement], mesh: ProcessMesh,
                       ndim: Optional[int] = None) -> P:
    """placements (one per mesh dim) -> PartitionSpec (one entry per tensor dim).

    Multiple mesh axes sharding the same tensor dim become a tuple entry, in
    mesh-dim order (matches the reference's multi-axis Shard semantics).
    """
    dim_axes = {}
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            dim_axes.setdefault(pl.dim, []).append(mesh.dim_names[mesh_dim])
    if not dim_axes:
        return P()
    max_dim = max(dim_axes) if ndim is None else ndim - 1
    entries = []
    for d in range(max_dim + 1):
        axes = dim_axes.get(d)
        if not axes:
            entries.append(None)
        elif len(axes) == 1:
            entries.append(axes[0])
        else:
            entries.append(tuple(axes))
    return P(*entries)


def spec_to_placements(spec: P, mesh: ProcessMesh) -> List[Placement]:
    placements: List[Placement] = [Replicate() for _ in range(mesh.ndim)]
    for tdim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for ax in axes:
            placements[mesh.dim_names.index(ax)] = Shard(tdim)
    return placements


def named_sharding(mesh: ProcessMesh, placements: Sequence[Placement],
                   ndim: Optional[int] = None) -> NamedSharding:
    return NamedSharding(mesh.jax_mesh, placements_to_spec(placements, mesh, ndim))


def _normalize_placements(placements, mesh: ProcessMesh):
    if placements is None:
        return [Replicate() for _ in range(mesh.ndim)]
    pls = list(placements)
    if len(pls) < mesh.ndim:
        pls += [Replicate()] * (mesh.ndim - len(pls))
    return pls


def shard_tensor(data, mesh: Optional[ProcessMesh] = None,
                 placements: Optional[Sequence[Placement]] = None,
                 dtype=None, stop_gradient: Optional[bool] = None) -> Tensor:
    """Create a distributed (global-view) tensor from `data`.

    Reference: python/paddle/distributed/auto_parallel/api.py:130. The data
    is the *global* value; each device materializes only its shard
    (jax.device_put moves per-device slices, the single-process analog of
    every rank holding its local shard in DistTensor).
    """
    mesh = mesh or get_mesh()
    if mesh is None:
        raise ValueError("shard_tensor: no mesh given and no default mesh set")
    placements = _normalize_placements(placements, mesh)
    if any(isinstance(p, Partial) for p in placements):
        raise ValueError("shard_tensor cannot create Partial placements; "
                         "Partial arises from ops (e.g. row-parallel matmul)")
    was_param = isinstance(data, Parameter)
    if isinstance(data, Tensor):
        sg = data.stop_gradient if stop_gradient is None else stop_gradient
        value = data._logical_value()  # never treat a source pad as data
        name = data.name
    else:
        sg = True if stop_gradient is None else stop_gradient
        value = jnp.asarray(data, dtype=dtype)
        name = None
    sharding = named_sharding(mesh, placements, ndim=jnp.ndim(value))
    value, logical = _pad_for_uneven(value, mesh, placements)
    value = jax.device_put(value, sharding)
    if was_param:
        out = Parameter(value, name=name, trainable=not sg)
    else:
        out = Tensor(value, stop_gradient=sg, name=name)
    out._placements = list(placements)
    out._process_mesh = mesh
    out._dist_pad = logical
    return out


def _uneven_logical(shape, mesh: ProcessMesh, placements):
    """The logical shape when `placements` shard `shape` unevenly, else None."""
    counts = {}
    for mesh_dim, p in enumerate(placements):
        if isinstance(p, Shard):
            counts[p.dim] = counts.get(p.dim, 1) * mesh.shape[mesh_dim]
    if any(shape[d] % n for d, n in counts.items()):
        return tuple(shape)
    return None


def _pad_for_uneven(value, mesh: ProcessMesh, placements):
    """Pad-and-mask uneven shards (reference reshard/ uneven handling):
    jax.Array storage requires tile-divisible dims, so non-divisible Shard
    dims are zero-padded up to ``ceil(size/n)*n``. Returns (padded value,
    logical shape or None). The logical view is restored by
    Tensor._logical_value / unshard."""
    shape = list(jnp.shape(value))
    counts = {}
    for mesh_dim, p in enumerate(placements):
        if isinstance(p, Shard):
            counts[p.dim] = counts.get(p.dim, 1) * mesh.shape[mesh_dim]
    pads = [(0, 0)] * len(shape)
    uneven = False
    for dim, n in counts.items():  # dims sharded by several axes need
        rem = shape[dim] % n       # divisibility by the PRODUCT
        if rem:
            pads[dim] = (0, n - rem)
            uneven = True
    if not uneven:
        return value, None
    logical = tuple(shape)
    return jnp.pad(value, pads), logical


def _materialize_partial(t: Tensor, mesh: ProcessMesh):
    """psum pending-partial axes (PToR: reshard/p_to_r_reshard_function.cc)."""
    from jax import shard_map

    partial_axes = tuple(
        mesh.dim_names[i] for i, p in enumerate(t._placements or [])
        if isinstance(p, Partial))
    if not partial_axes:
        return t._value
    cur_spec = placements_to_spec(
        [p if isinstance(p, Shard) else Replicate() for p in t._placements],
        mesh, ndim=t.ndim)

    def local_sum(x):
        return jax.lax.psum(x, partial_axes)

    fn = shard_map(local_sum, mesh=mesh.jax_mesh, in_specs=(cur_spec,),
                   out_specs=cur_spec, check_vma=False)
    return jax.jit(fn)(t._value)


def reshard(x: Tensor, mesh: Optional[ProcessMesh] = None,
            placements: Optional[Sequence[Placement]] = None) -> Tensor:
    """Redistribute `x` to new placements (api.py:346 analog).

    S->R, R->S, S->S' all compile to one XLA collective via device_put with
    the target NamedSharding; P->* first materializes the pending sum.
    """
    mesh = mesh or x._process_mesh or get_mesh()
    if mesh is None:
        raise ValueError("reshard: no mesh available")
    placements = _normalize_placements(placements, mesh)
    value = x._value
    if x._placements and any(isinstance(p, Partial) for p in x._placements):
        value = _materialize_partial(x, x._process_mesh or mesh)
    if any(isinstance(p, Partial) for p in placements):
        raise ValueError("reshard target may not be Partial")
    sharding = named_sharding(mesh, placements, ndim=x.ndim)
    logical = _uneven_logical(x.shape, mesh, placements)
    # run as a taped op so backward reaches x (device_put is differentiable;
    # its transpose moves the cotangent back, i.e. the reverse collective).
    # apply_op feeds the LOGICAL value, and padding happens inside the op,
    # so uneven leaves keep their gradients (the pad's transpose is a slice)
    from paddle_tpu.ops.registry import OpDef, apply_op
    src = x
    if value is not x._value:  # partial was materialized outside the tape
        if x._dist_pad is not None:
            value = value[tuple(slice(0, s) for s in x._dist_pad)]
        src = Tensor(value, stop_gradient=x.stop_gradient, name=x.name)
        src._grad_node = x._grad_node
        src._out_index = x._out_index

    def impl(v):
        pv, _ = _pad_for_uneven(v, mesh, placements)
        return jax.device_put(pv, sharding)

    out = apply_op(OpDef("reshard", impl), (src,), {})
    out._placements = list(placements)
    out._process_mesh = mesh
    out._dist_pad = logical
    return out


def unshard(x: Tensor) -> Tensor:
    """Gather to a fully replicated tensor (get the global value everywhere)."""
    mesh = x._process_mesh or get_mesh()
    if mesh is None or x._placements is None:
        return x
    return reshard(x, mesh, [Replicate()] * mesh.ndim)


def local_shape(global_shape: Sequence[int], mesh: ProcessMesh,
                placements: Sequence[Placement],
                coord: Optional[Sequence[int]] = None) -> tuple:
    """Per-device shard shape, uneven dims included.

    Uneven semantics match the reference's balanced split
    (phi/core/distributed/auto_parallel/reshard/ uneven handling): each
    rank holds ``ceil(size / n)`` rows except the tail, which holds the
    remainder (possibly 0). Without ``coord`` (mesh coordinates, one per
    mesh dim) the maximal (rank-0 / padded-tile) shape is returned — the
    shape XLA actually tiles; with ``coord`` the exact shape at those
    coordinates.
    """
    shape = list(global_shape)
    for mesh_dim, p in enumerate(placements):
        if isinstance(p, Shard):
            n = mesh.shape[mesh_dim]
            tile = -(-shape[p.dim] // n)  # ceil
            if coord is None:
                shape[p.dim] = tile
            else:
                c = coord[mesh_dim]
                shape[p.dim] = max(0, min(tile, shape[p.dim] - c * tile))
    return tuple(shape)


def dtensor_from_fn(fn: Callable, mesh: Optional[ProcessMesh] = None,
                    placements: Optional[Sequence[Placement]] = None,
                    *args, **kwargs) -> Tensor:
    """Build a dist tensor by calling fn then sharding (api.py:312). On TPU
    the interesting optimization is creating big params *already sharded*;
    jit-with-out-sharding makes XLA initialize each shard on-device."""
    t = fn(*args, **kwargs)
    return shard_tensor(t, mesh, placements)


def shard_layer(layer, process_mesh: Optional[ProcessMesh] = None,
                shard_fn: Optional[Callable] = None,
                input_fn: Optional[Callable] = None,
                output_fn: Optional[Callable] = None):
    """Shard every parameter of `layer` in place (api.py:445 analog).

    shard_fn(name, layer, mesh) mutates a sublayer's params; the default
    replicates everything (dp-style).
    """
    mesh = process_mesh or get_mesh()
    if mesh is None:
        raise ValueError("shard_layer: no mesh")

    def default_shard_fn(name, sublayer, mesh):
        for pname, param in list(sublayer._parameters.items()):
            if param is None:
                continue
            sublayer._parameters[pname] = shard_tensor(
                param, mesh, [Replicate()] * mesh.ndim)

    fn = shard_fn or default_shard_fn
    for name, sub in layer.named_sublayers(include_self=True):
        fn(name, sub, mesh)
    if input_fn is not None:
        layer.register_forward_pre_hook(
            lambda lyr, inputs: input_fn(inputs, mesh))
    if output_fn is not None:
        layer.register_forward_post_hook(
            lambda lyr, inputs, outputs: output_fn(outputs, mesh))
    return layer
