"""Compiled SPMD pipeline parallelism.

Redesign of the reference's pipeline runtime (fleet/meta_parallel/
pipeline_parallel.py 1F1B :459, pp_utils/p2p_communication.py, and the
FleetExecutor interceptor dataflow N21): instead of per-micro-batch NCCL
p2p orchestrated from Python, the whole schedule compiles into ONE SPMD
program over the mesh 'pp' axis:

- stage params live sharded over 'pp' (stage i's weights on ring rank i),
- micro-batches stream through a rotating state buffer moved by
  ``lax.ppermute`` (collective-permute rides ICI),
- the schedule loop is a static Python loop of T = M + S - 1 ticks
  (GPipe-style fill/drain; every device computes every tick, with bubble
  ticks masked), and
- backward is ``jax.grad`` through the loop — XLA reverses the permutes,
  which reproduces the 1F1B-reversed communication pattern without any
  hand-written schedule; per-tick ``jax.checkpoint`` bounds activation
  memory the way recompute_interval does in the reference.

This is the deadlock-free-by-construction answer to SURVEY §7.3 hard
part #1.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel.mesh import ProcessMesh

__all__ = ["spmd_pipeline", "stack_stage_params"]


def stack_stage_params(stage_states: Sequence[dict]) -> dict:
    """Stack per-stage param dicts (same structure) along a leading stage
    axis: the 'pp'-shardable layout (stage i's slice lands on ring rank i)."""
    keys = list(stage_states[0].keys())
    for st in stage_states[1:]:
        if list(st.keys()) != keys:
            raise ValueError("pipeline stages must have identical param structure")
    return {k: jnp.stack([st[k] for st in stage_states]) for k in keys}


def spmd_pipeline(stage_fn: Callable, stacked_params: dict, x,
                  mesh: ProcessMesh, n_micro: int, axis: str = "pp",
                  checkpoint_ticks: bool = True, partial_manual: bool = False,
                  virtual_chunks: int = 1):
    """Run `x` through S pipeline stages as one compiled SPMD program.

    stage_fn(params_slice, microbatch) -> microbatch (same shape/dtype);
    stacked_params[k] has leading dim S (stage axis, sharded over `axis`);
    x has leading dim M = n_micro (micro-batch axis, replicated).

    With ``virtual_chunks = v > 1`` (interleaved VPP,
    pipeline_parallel.py:987 analog) stacked_params[k] has leading dims
    ``(v, S)`` — ``[j, r]`` holds global stage ``j*S + r`` — and the ring
    is traversed v times, cutting the warmup bubble per chunk from
    ``(S-1) * v``-deep to ``(S-1)``-deep stage computes.

    Returns the pipeline output with leading dim M.
    """
    if virtual_chunks > 1:
        return _spmd_pipeline_interleaved(
            stage_fn, stacked_params, x, mesh, n_micro, axis,
            checkpoint_ticks, partial_manual, virtual_chunks)
    S = mesh.dim_size(axis)
    lead = next(iter(stacked_params.values())).shape[0] if stacked_params else S
    if lead != S:
        raise ValueError(f"stacked stage dim {lead} != pp axis size {S}")
    M = x.shape[0]
    if M != n_micro:
        raise ValueError(f"x leading dim {M} != n_micro {n_micro}")

    param_specs = {k: P(axis) for k in stacked_params}
    # per-stage micro-batch IO (the scalability fix): when M divides by S,
    # inputs/outputs are sharded over the pp axis (each rank holds M/S
    # micro-batches) and single micro-batches ride ppermutes to/from the
    # ring ends — per-rank IO memory is M/S x activation, not M x. With
    # M % S != 0 the replicated fallback keeps correctness.
    shard_io = S > 1 and M % S == 0
    per = M // S if shard_io else M
    x_spec = P(axis) if shard_io else P()
    out_spec = P(axis) if shard_io else P()

    def local(params_loc, x_all):
        # params_loc[k]: (1, ...) this rank's stage slice;
        # x_all: (per, ...) local micro-batches (sharded) or (M, ...) (repl)
        r = jax.lax.axis_index(axis)
        p_here = {k: v[0] for k, v in params_loc.items()}
        state = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros((per,) + x_all.shape[1:], x_all.dtype)

        # checkpoint ONLY the stage compute: the accumulator ops (.at.set,
        # where, ppermute) are linear and need no residuals — wrapping the
        # whole tick would keep T copies of the output buffer live
        compute = jax.checkpoint(stage_fn) if checkpoint_ticks else stage_fn

        def tick(t, state, outputs):
            # stage 0 ingests micro-batch t (while t < M); others take the
            # state handed over the ring last tick
            if t < M:
                if shard_io:
                    # owner rank t//per ships micro-batch t to the ring head
                    send = x_all[t % per]
                    inject = jax.lax.ppermute(send, axis, [(t // per, 0)])
                else:
                    inject = x_all[t]
                state = jnp.where(r == 0, inject, state)
            y = compute(p_here, state)
            # last stage emits micro-batch t-(S-1) once the pipe is full
            mb = t - (S - 1)
            if 0 <= mb < M:
                if shard_io:
                    dst = mb // per
                    moved = jax.lax.ppermute(y, axis, [(S - 1, dst)])
                    outputs = outputs.at[mb % per].set(
                        jnp.where(r == dst, moved, outputs[mb % per]))
                else:
                    emit = jnp.where(r == S - 1, y, jnp.zeros_like(y))
                    outputs = outputs.at[mb].set(emit)
            state = jax.lax.ppermute(
                y, axis, [(j, (j + 1) % S) for j in range(S)])
            return state, outputs

        for t in range(M + S - 1):
            state, outputs = tick(t, state, outputs)
        if not shard_io:
            # outputs live on the last ring rank only; share them ringwide
            outputs = jax.lax.psum(outputs, axis)
        return outputs

    kwargs = dict(mesh=mesh.jax_mesh,
                  in_specs=({k: param_specs[k] for k in stacked_params},
                            x_spec),
                  out_specs=out_spec, check_vma=False)
    if partial_manual:
        # manual only over the pp ring; dp/mp/sep stay GSPMD-automatic so
        # hybrid tp/dp sharding inside a stage keeps working
        kwargs["axis_names"] = {axis}
    fn = shard_map(local, **kwargs)
    return fn(stacked_params, x)


def _spmd_pipeline_interleaved(stage_fn, stacked_params, x, mesh, n_micro,
                               axis, checkpoint_ticks, partial_manual, v):
    """Interleaved virtual-pipeline forward (Megatron VPP; reference
    pipeline_parallel.py:987 ``interleave``): global stage ``l = j*S + r``
    runs on rank ``l % S`` with local chunk ``j = l // S``, so each rank
    touches every v-th layer block and micro-batches re-enter the ring v
    times. One compiled SPMD loop of ``M + v*S - 1`` ticks; each tick a
    rank runs (up to) v chunk computes, each cond-skipped when idle."""
    S = mesh.dim_size(axis)
    shapes = {k: p.shape for k, p in stacked_params.items()}
    for k, shp in shapes.items():
        if shp[0] != v or shp[1] != S:
            raise ValueError(
                f"virtual_chunks={v}: stacked param {k} must have leading "
                f"dims (v, S) = ({v}, {S}), got {shp[:2]}")
    M = x.shape[0]
    if M != n_micro:
        raise ValueError(f"x leading dim {M} != n_micro {n_micro}")
    L = v * S
    T = M + L - 1

    param_specs = {k: P(None, axis) for k in stacked_params}
    # same per-stage micro-batch IO as the base pipeline: shard M over the
    # pp axis when divisible (owner rank ships mb t to the ring head at its
    # injection tick; the last global stage ships results back to owners)
    shard_io = S > 1 and M % S == 0
    per = M // S if shard_io else M
    io_spec = P(axis) if shard_io else P()

    def local(params_loc, x_all):
        r = jax.lax.axis_index(axis)
        # params_loc[k]: (v, 1, ...) — this rank's v chunk slices
        p_chunks = [{k: p[j, 0] for k, p in params_loc.items()}
                    for j in range(v)]
        zero = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros((per,) + x_all.shape[1:], x_all.dtype)
        fs = [zero] * v  # per-chunk ring payload

        compute = jax.checkpoint(stage_fn) if checkpoint_ticks else stage_fn

        for t in range(T):
            # global stage 0 (j=0, r=0) consumes micro-batch t this tick
            if shard_io:
                if t < M:
                    send = x_all[t % per]
                    inject_t = jax.lax.ppermute(send, axis, [(t // per, 0)])
                else:
                    inject_t = zero
            ys = []
            for j in range(v):
                # micro-batch at global stage j*S + r this tick
                m = t - j * S - r
                active = (m >= 0) & (m < M)
                # chunk input: ring payload; rank 0 takes the wrapped
                # payload of chunk j-1 (stage (j-1)*S + S-1 -> j*S); the
                # j==0 wrap value is dead — global stage 0 injects x below
                state_in = jnp.where(r == 0, fs[j - 1], fs[j])
                inject = inject_t if shard_io else x_all[jnp.clip(m, 0, M - 1)]
                state_in = jnp.where((r == 0) & (j == 0), inject, state_in)
                if partial_manual:
                    # masked, not cond: GSPMD inserts mp/dp collectives
                    # inside branches and pp-divergent predicates deadlock
                    # the mesh (see pipeline_1f1b.skip_idle)
                    y = jnp.where(active, compute(p_chunks[j], state_in), zero)
                else:
                    y = jax.lax.cond(
                        active,
                        lambda s=state_in, pj=p_chunks[j]: compute(pj, s),
                        lambda: zero)
                ys.append(y)
                # last global stage emits micro-batch m
                if j == v - 1:
                    mb = t - (L - 1)
                    if 0 <= mb < M:
                        if shard_io:
                            dst = mb // per
                            moved = jax.lax.ppermute(y, axis, [(S - 1, dst)])
                            outputs = outputs.at[mb % per].set(
                                jnp.where(r == dst, moved,
                                          outputs[mb % per]))
                        else:
                            emit = jnp.where(r == S - 1, y,
                                             jnp.zeros_like(y))
                            outputs = outputs.at[mb].set(emit)
            # one permute per chunk ring, all ranks, outside the conds
            fs = [jax.lax.ppermute(
                ys[j], axis, [(i, (i + 1) % S) for i in range(S)])
                for j in range(v)]
        if not shard_io:
            outputs = jax.lax.psum(outputs, axis)
        return outputs

    kwargs = dict(mesh=mesh.jax_mesh,
                  in_specs=({k: param_specs[k] for k in stacked_params},
                            io_spec),
                  out_specs=io_spec, check_vma=False)
    if partial_manual:
        kwargs["axis_names"] = {axis}
    fn = shard_map(local, **kwargs)
    return fn(stacked_params, x)
