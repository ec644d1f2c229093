"""Communication API: groups + eager collectives.

Redesign of python/paddle/distributed/communication/ (all_reduce.py:20,
group.py, collective.py `new_group`) + the C++ ProcessGroup stack
(paddle/fluid/distributed/collective/process_group.h:47) for the
single-controller SPMD model:

- A **Group** names a mesh axis (or an explicit rank subset of the default
  1-D world mesh). There is no per-ring NCCL communicator object — XLA
  compiles the collective over the mesh axis, and ICI/DCN routing follows
  the mesh layout.
- The reference's "every rank holds its local tensor" view maps to a
  *rank-stacked global tensor*: shape ``[group_size, ...]`` sharded
  ``Shard(0)`` over the group's axis. ``all_reduce`` then means
  out[i] = reduce_j in[j] — each rank's slice becomes the reduction —
  which is exactly the reference's in-place collective semantics.
- Collectives are recorded on the autograd tape (shard_map is
  differentiable), so e.g. all_gather backward is reduce-scatter for free;
  the reference needed hand-written PyLayers for that
  (fleet/utils/sequence_parallel_utils.py:85-137).

Plain replicated tensors (no placements) are handled as the trivial
single-shard case so user code runs unchanged on one device.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.ops.registry import OpDef, apply_op
from paddle_tpu.parallel.mesh import ProcessMesh, get_mesh
from paddle_tpu.parallel.placements import Replicate, Shard

__all__ = [
    "ReduceOp", "Group", "new_group", "get_group", "destroy_process_group",
    "all_reduce", "all_gather", "all_gather_object", "reduce",
    "reduce_scatter", "broadcast", "scatter", "gather", "alltoall",
    "all_to_all", "barrier", "send", "recv", "isend", "irecv",
    "stack_for_group", "unstack_from_group",
]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
}


def _reduce_full(x, op: str, axis: str, n: int):
    """Shared per-shard reduction covering every ReduceOp (PROD has no lax
    primitive: all_gather + prod)."""
    if op == ReduceOp.AVG:
        return jax.lax.psum(x, axis) / n
    if op == ReduceOp.PROD:
        return jnp.prod(jax.lax.all_gather(x, axis), axis=0)
    try:
        return _REDUCERS[op](x, axis)
    except KeyError:
        raise ValueError(f"unsupported ReduceOp {op!r}") from None


class Group:
    """A communication group = one (or a tuple of) named mesh axes.

    Reference: communication/group.py `Group`. Single-controller
    semantics: `src`/`dst` arguments to collectives are *group ranks*
    (positions along the group axes, 0..nranks-1), and `ranks` lists them;
    there is no separate global-rank space because one controller owns all
    devices.
    """

    _next_gid = 0

    def __init__(self, mesh: ProcessMesh, axis, ranks: Optional[List[int]] = None):
        self.mesh = mesh
        self.axis = axis  # str or tuple[str, ...]
        self.ranks = (ranks if ranks is not None
                      else list(range(self._axis_size(mesh, axis))))
        self.id = Group._next_gid
        Group._next_gid += 1

    @staticmethod
    def _axis_size(mesh, axis) -> int:
        if isinstance(axis, tuple):
            n = 1
            for a in axis:
                n *= mesh.dim_size(a)
            return n
        return mesh.dim_size(axis)

    @property
    def nranks(self) -> int:
        return self._axis_size(self.mesh, self.axis)

    @property
    def world_size(self) -> int:
        return self.nranks

    @property
    def name(self) -> str:
        return f"group_{self.id}({self.axis})"

    def get_group_rank(self, rank: int) -> int:
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(axis={self.axis!r}, nranks={self.nranks})"


_GROUPS: dict = {}
_DEFAULT_GROUP: Optional[Group] = None


def _default_group() -> Group:
    """World group: every mesh axis (reference: the global default group)."""
    global _DEFAULT_GROUP
    if _DEFAULT_GROUP is None:
        mesh = get_mesh()
        if mesh is None:
            from paddle_tpu.parallel.mesh import init_mesh
            mesh = init_mesh((len(jax.devices()),), ("world",))
        axes = tuple(mesh.dim_names)
        _DEFAULT_GROUP = Group(mesh, axes[0] if len(axes) == 1 else axes)
        _GROUPS[_DEFAULT_GROUP.id] = _DEFAULT_GROUP
    return _DEFAULT_GROUP


def _set_default_group(g: Optional[Group]) -> None:
    global _DEFAULT_GROUP
    _DEFAULT_GROUP = g
    if g is not None:
        _GROUPS[g.id] = g


def new_group(ranks: Optional[Sequence[int]] = None, backend: Optional[str] = None,
              timeout=None, axis: Optional[str] = None,
              mesh: Optional[ProcessMesh] = None) -> Group:
    """Create a group. TPU-native form: name a mesh axis
    (``new_group(axis="mp")``). The rank-list form builds a sub-mesh over
    those devices (single-host analog of the reference's subgroup comm
    rings, collective.py `new_group`)."""
    mesh = mesh or get_mesh()
    if axis is not None:
        if mesh is None:
            raise ValueError("new_group(axis=...) requires an active mesh")
        g = Group(mesh, axis)
    else:
        ranks = list(ranks) if ranks is not None else [d.id for d in jax.devices()]
        sub = ProcessMesh(shape=(len(ranks),), dim_names=("sub",), process_ids=ranks)
        g = Group(sub, "sub", ranks)
    _GROUPS[g.id] = g
    return g


def get_group(gid: int) -> Optional[Group]:
    return _GROUPS.get(gid)


def destroy_process_group(group: Optional[Group] = None) -> None:
    global _DEFAULT_GROUP
    if group is None:
        _GROUPS.clear()
        _DEFAULT_GROUP = None
    else:
        _GROUPS.pop(group.id, None)
        if _DEFAULT_GROUP is group:
            _DEFAULT_GROUP = None


# ---------------------------------------------------------------------------
# rank-stacked view helpers
# ---------------------------------------------------------------------------

def stack_for_group(tensors: Sequence, group: Optional[Group] = None) -> Tensor:
    """Stack per-rank values into the rank-stacked global tensor the eager
    collectives operate on (testing/ergonomics helper)."""
    group = group or _default_group()
    from paddle_tpu.parallel.api import shard_tensor
    vals = [t.value if isinstance(t, Tensor) else jnp.asarray(t) for t in tensors]
    stacked = jnp.stack(vals)
    pls = [Replicate()] * group.mesh.ndim
    axes = group.axis if isinstance(group.axis, tuple) else (group.axis,)
    for ax in axes:
        pls[group.mesh.dim_names.index(ax)] = Shard(0)
    return shard_tensor(stacked, group.mesh, pls)


def unstack_from_group(t: Tensor) -> List[Tensor]:
    import numpy as np
    arr = np.asarray(t.value)
    return [Tensor(jnp.asarray(arr[i])) for i in range(arr.shape[0])]


def _run_collective(name: str, t, group: Group, local_fn, out_specs=None,
                    extra_inputs=()):
    """Apply `local_fn` (per-shard function using lax collectives over
    group.axis) via shard_map on the rank-stacked tensor, through the op
    registry so autograd records it."""
    if not isinstance(t, Tensor):
        t = Tensor(t)
    axis = group.axis
    mesh = group.mesh
    spec_in = P(axis)  # rank-stacked on dim 0
    spec_out = out_specs if out_specs is not None else spec_in

    def impl(*vals):
        fn = shard_map(local_fn, mesh=mesh.jax_mesh,
                       in_specs=tuple(spec_in for _ in vals),
                       out_specs=spec_out, check_vma=False)
        return fn(*vals)

    opdef = OpDef(name, impl)
    return apply_op(opdef, (t, *extra_inputs), {})


def _group_size_check(t, group: Group):
    n = group.nranks
    shape = t.shape if isinstance(t, Tensor) else jnp.shape(t)
    if not shape or shape[0] != n:
        raise ValueError(
            f"eager collective expects the rank-stacked layout [group_size={n}, ...] "
            f"on dim 0 (got shape {tuple(shape)}); build it with "
            "distributed.stack_for_group or shard_tensor(..., [Shard(0)])")


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def all_reduce(tensor: Tensor, op: str = ReduceOp.SUM,
               group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    """out[i] = reduce_j in[j] for every group rank i
    (communication/all_reduce.py:20)."""
    group = group or _default_group()
    _group_size_check(tensor, group)
    axis = group.axis
    red = op

    def local(x):
        return _reduce_full(x, red, axis, group.nranks)

    return _run_collective("all_reduce", tensor, group, local)


def reduce(tensor: Tensor, dst: int = 0, op: str = ReduceOp.SUM,
           group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    """Only group-rank dst receives the reduction; others keep their input
    (communication/reduce.py)."""
    group = group or _default_group()
    _group_size_check(tensor, group)
    _check_group_rank(dst, group, "dst")
    axis = group.axis
    red = op

    def local(x):
        full = _reduce_full(x, red, axis, group.nranks)
        idx = jax.lax.axis_index(axis)
        return jnp.where(idx == dst, full, x)

    return _run_collective("reduce", tensor, group, local)


def all_gather(tensor_or_list, tensor: Optional[Tensor] = None,
               group: Optional[Group] = None, sync_op: bool = True):
    """Both call forms of the reference API
    (communication/all_gather.py): ``all_gather(tensor_list, tensor)``
    appends per-rank tensors to the list; functional form
    ``all_gather(tensor)`` returns the rank-stacked result where every
    rank's slice is the full gather (shape [n, n, ...local])."""
    group = group or _default_group()
    out_list = None
    if isinstance(tensor_or_list, list):
        out_list = tensor_or_list
        src = tensor
    else:
        src = tensor_or_list
    _group_size_check(src, group)
    axis = group.axis

    def local(x):  # x: (1, ...) local block
        return jax.lax.all_gather(x[0], axis)[None]  # (1, n, ...)

    res = _run_collective("all_gather", src, group, local)  # (n, n, ...)
    if out_list is not None:
        import numpy as np
        arr = np.asarray(res.value)[0]  # every rank sees same gather
        out_list.extend(Tensor(jnp.asarray(arr[i])) for i in range(group.nranks))
        return None
    return res


def all_gather_object(object_list: list, obj, group: Optional[Group] = None):
    """Object variant — single-controller: every rank holds `obj` already."""
    group = group or _default_group()
    object_list.extend([obj] * group.nranks)


def reduce_scatter(tensor: Tensor, tensor_list=None, op: str = ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    """Rank i gets the i-th chunk of the elementwise reduction
    (communication/reduce_scatter.py). Rank-stacked in: [n, n*c, ...];
    out: [n, c, ...]."""
    group = group or _default_group()
    src = tensor if tensor_list is None else stack_for_group(tensor_list, group)
    _group_size_check(src, group)
    axis = group.axis
    n = group.nranks
    m = src.shape[1]
    if m % n != 0:
        raise ValueError(f"reduce_scatter: dim1 ({m}) not divisible by group size {n}")
    c = m // n

    def local(x):  # x: (1, m, ...)
        full = _reduce_full(x, op, axis, n)
        i = jax.lax.axis_index(axis)
        return jax.lax.dynamic_slice_in_dim(full, i * c, c, axis=1)

    return _run_collective("reduce_scatter", src, group, local)


def _check_group_rank(r: int, group: Group, what: str) -> None:
    if not 0 <= r < group.nranks:
        raise ValueError(f"{what}={r} out of range for group of size "
                         f"{group.nranks} (src/dst are group ranks)")


def broadcast(tensor: Tensor, src: int = 0, group: Optional[Group] = None,
              sync_op: bool = True) -> Tensor:
    """out[i] = in[src] (communication/broadcast.py)."""
    group = group or _default_group()
    _group_size_check(tensor, group)
    _check_group_rank(src, group, "src")
    axis = group.axis

    def local(x):
        g = jax.lax.all_gather(x, axis)
        return g[src]

    return _run_collective("broadcast", tensor, group, local)


def scatter(tensor: Tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op: bool = True) -> Tensor:
    """Rank i gets tensor_list[i] held by src (communication/scatter.py).
    Single-controller: the scatter of a rank-stacked tensor is the identity
    on placements — provided for API parity."""
    group = group or _default_group()
    if tensor_list is not None:
        return stack_for_group(tensor_list, group)
    _group_size_check(tensor, group)
    return tensor


def gather(tensor: Tensor, gather_list=None, dst: int = 0,
           group: Optional[Group] = None, sync_op: bool = True):
    group = group or _default_group()
    _group_size_check(tensor, group)
    import numpy as np
    arr = np.asarray(tensor.value)
    if gather_list is not None:
        gather_list.extend(Tensor(jnp.asarray(arr[i])) for i in range(group.nranks))
        return None
    return Tensor(jnp.asarray(arr))


def alltoall(out_tensor_list, in_tensor_list=None, group: Optional[Group] = None,
             sync_op: bool = True):
    """out[i][j] = in[j][i] (communication/all_to_all.py). Functional form:
    pass the rank-stacked tensor [n, n, ...] and get its transpose."""
    group = group or _default_group()
    if isinstance(out_tensor_list, Tensor) or not isinstance(out_tensor_list, list):
        t = out_tensor_list
        _group_size_check(t, group)
        axis = group.axis

        def local(x):  # x: (1, n, ...) — rank i sends x[0,j] to rank j
            return jax.lax.all_to_all(x[0], axis, split_axis=0, concat_axis=0,
                                      tiled=True)[None]

        def impl(v):
            fn = shard_map(local, mesh=group.mesh.jax_mesh,
                           in_specs=(P(axis),), out_specs=P(axis),
                           check_vma=False)
            return fn(v)

        return apply_op(OpDef("alltoall", impl), (t,), {})
    src = stack_for_group(in_tensor_list, group)
    res = alltoall(src, group=group)
    import numpy as np
    arr = np.asarray(res.value)
    out_tensor_list.extend(Tensor(jnp.asarray(arr[i])) for i in range(group.nranks))
    return None


all_to_all = alltoall


_BARRIER_CACHE: dict = {}


def barrier(group: Optional[Group] = None) -> None:
    """Device-side sync point (communication/batch_isend_irecv.py barrier
    analog): a tiny psum forces all shards to rendezvous. The jitted
    program is cached per (mesh, axis) — a per-step barrier costs no
    retrace."""
    group = group or _default_group()
    axis = group.axis
    key = (group.mesh.jax_mesh, axis)
    fn = _BARRIER_CACHE.get(key)
    if fn is None:
        def local(x):
            return jax.lax.psum(x, axis)

        fn = jax.jit(shard_map(local, mesh=group.mesh.jax_mesh,
                               in_specs=(P(axis),), out_specs=P(axis),
                               check_vma=False))
        _BARRIER_CACHE[key] = fn
    jax.block_until_ready(fn(jnp.zeros((group.nranks, 1), jnp.float32)))


# -- p2p: ppermute-based send/recv on rank-stacked tensors -------------------

def _shift(tensor: Tensor, src: int, dst: int, group: Group) -> Tensor:
    axis = group.axis

    def local(x):
        return jax.lax.ppermute(x, axis, perm=[(src, dst)])

    return _run_collective("p2p_shift", tensor, group, local)


class _P2PTask:
    def __init__(self, result=None):
        self._result = result

    def wait(self):
        if self._result is not None:
            jax.block_until_ready(self._result.value)
        return self._result

    def is_completed(self):
        return True


import collections as _collections

_PENDING_SENDS: dict = _collections.defaultdict(_collections.deque)


def send(tensor: Tensor, dst: int = 0, group: Optional[Group] = None,
         sync_op: bool = True):
    """P2P on rank-stacked tensors: records the (src-slice -> dst) shift;
    the matching recv returns it. Under single-controller SPMD a lone send
    has no observable effect until the receiver's slice is read, so
    send+recv pairs compile to one collective-permute — the TPU-native
    replacement for ProcessGroup::Send/Recv (process_group.h:205-234).
    Sends queue FIFO per group; each recv consumes the oldest (program-order
    pairing, the SPMD-lockstep discipline the reference's p2p also assumes).
    """
    group = group or _default_group()
    _PENDING_SENDS[group.id].append((dst, tensor))
    return _P2PTask(tensor)


def recv(tensor: Optional[Tensor] = None, src: int = 0,
         group: Optional[Group] = None, sync_op: bool = True):
    group = group or _default_group()
    queue = _PENDING_SENDS.get(group.id)
    if not queue:
        raise RuntimeError("recv without a matching send in this controller")
    dst, t = queue.popleft()
    sent = _shift(t, src, dst, group)
    if tensor is not None:
        tensor._set_value(sent.value)
        return _P2PTask(tensor)
    return sent


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group, sync_op=False)


def irecv(tensor=None, src=0, group=None):
    return recv(tensor, src, group, sync_op=False)


class P2POp:
    """communication/batch_isend_irecv.py P2POp analog."""

    def __init__(self, op, tensor, peer, group=None):
        self.op = op  # isend / irecv callables
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """Run a batch of P2POps; sends are enqueued first so each recv pairs
    FIFO (the reference coalesces these into one NCCL group call — here
    each pair compiles to one collective-permute)."""
    tasks = []
    for op in p2p_op_list:
        if op.op is isend or op.op is send:
            tasks.append(isend(op.tensor, op.peer, op.group))
    for op in p2p_op_list:
        if op.op is irecv or op.op is recv:
            tasks.append(irecv(op.tensor, op.peer, op.group))
    return tasks
