"""Compiled-SPMD 1F1B pipeline training step.

Redesign of the reference's 1F1B scheduler
(fleet/meta_parallel/pipeline_parallel.py:459 ``forward_backward_pipeline``)
for the XLA/SPMD world: instead of a host loop issuing per-micro-batch NCCL
p2p sends, the WHOLE 1F1B timeline — warmup forwards, steady-state
one-forward-one-backward, drain backwards — compiles into one SPMD program
over the mesh's ``pp`` axis:

- tick ``t``: rank ``r`` forwards micro-batch ``f = t - r`` (when
  ``0 <= f < M``) and backwards micro-batch ``b = t + r - 2S + 1`` (when
  ``0 <= b < M``); both sides are ``lax.cond``-skipped on idle ticks so
  warmup/drain ranks do no wasted compute,
- activations ring forward via ``lax.ppermute`` (r -> r+1) and cotangents
  ring backward (r -> r-1); the loss gradient seeds the cotangent ring at
  the last stage,
- each rank keeps a circular residual buffer of ``2S`` saved stage INPUTS
  (the 1F1B memory bound: ≤ 2S in-flight micro-batches per rank instead of
  GPipe's M + S - 1), and the backward tick recomputes the stage forward
  from the saved input (recompute-style, ``jax.vjp`` at the saved point),
- per-stage parameter gradients accumulate locally and come back stacked
  ``(S, ...)``; the loss comes back psum-reduced.

Total ticks: ``M + 2S - 1`` (vs the compiled GPipe path's ``2(M + S - 1)``
fwd+reversed ticks). No ``(M, ...)`` output buffer is materialized unless
the caller asks for the input cotangents (``return_x_grad`` — needed to
chain an embedding lookup in front of the pipe).

The interleaved virtual-pipeline (VPP) variant of the forward loop lives in
``pipeline_spmd.spmd_pipeline`` via ``virtual_chunks`` (see
pipeline_parallel.py:987 ``interleave`` and
passes/pipeline_scheduler_pass/pipeline_zero_bubble.py for the reference
schedule family).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel.mesh import ProcessMesh

__all__ = ["spmd_pipeline_1f1b"]


def spmd_pipeline_1f1b(stage_fn: Callable, loss_fn: Callable,
                       stacked_params: dict, x, targets,
                       mesh: ProcessMesh, n_micro: int, axis: str = "pp",
                       loss_params: Optional[dict] = None,
                       return_x_grad: bool = False,
                       partial_manual: bool = False,
                       skip_idle: Optional[bool] = None):
    """One 1F1B forward+backward pass.

    stage_fn(params_slice, state) -> state (same shape/dtype);
    loss_fn(final_state, target) -> scalar — or, when ``loss_params`` is
    given, loss_fn(loss_params, final_state, target) -> scalar (the final
    norm / lm-head weights live here; their gradients are returned).
    stacked_params[k]: leading dim S (stage axis, sharded over `axis`);
    x, targets: leading dim M = n_micro.

    Returns ``(loss, grads)`` plus, in order when requested,
    ``loss_param_grads`` and ``x_grad`` (cotangent w.r.t. x, shape like x).
    The loss and all gradients are averaged over the M micro-batches;
    grads[k] has the same stacked (S, ...) layout as stacked_params[k].
    """
    S = mesh.dim_size(axis)
    lead = next(iter(stacked_params.values())).shape[0] if stacked_params else S
    if lead != S:
        raise ValueError(f"stacked stage dim {lead} != pp axis size {S}")
    M = x.shape[0]
    if M != n_micro:
        raise ValueError(f"x leading dim {M} != n_micro {n_micro}")
    W = 2 * S  # residual ring: covers the max fwd->bwd delay 2S-1 (rank 0)
    T = M + 2 * S - 1
    has_lp = loss_params is not None
    lp = loss_params if has_lp else {}
    if skip_idle is None:
        # cond-skipping idle ticks is only safe when the pp axis is the
        # ONLY partitioned axis in the body: under partial-manual hybrid
        # tp/dp, GSPMD inserts mp/dp collectives INSIDE the branches, the
        # pp ranks diverge on the predicate, and the mesh deadlocks
        # (observed: mp all-reduce vs ring collective-permute rendezvous).
        # Masked always-execute keeps collectives uniform across ranks.
        skip_idle = not partial_manual

    param_specs = {k: P(axis) for k in stacked_params}

    def local(params_loc, lp_rep, x_all, tgt_all):
        r = jax.lax.axis_index(axis)
        p_here = {k: v[0] for k, v in params_loc.items()}
        state0 = jnp.zeros_like(x_all[0])

        fs = state0                                   # forward ring payload
        bs = state0                                   # cotangent ring payload
        resid = jnp.zeros((W,) + state0.shape, state0.dtype)
        gacc = {k: jnp.zeros_like(v) for k, v in p_here.items()}
        lp_acc = {k: jnp.zeros_like(v) for k, v in lp_rep.items()}
        xg = (jnp.zeros_like(x_all) if return_x_grad else None)
        loss_acc = jnp.zeros((), jnp.float32)
        inv_m = jnp.float32(1.0 / M)

        def seed_loss(y2, tgt, lp_rep):
            """Loss value + cotangent seed + loss-param grads at rank S-1."""
            if has_lp:
                l, (dlp, dly) = jax.value_and_grad(
                    lambda p, yy: loss_fn(p, yy, tgt).astype(jnp.float32),
                    argnums=(0, 1))(lp_rep, y2)
                return l, dly, dlp
            l, dly = jax.value_and_grad(
                lambda yy: loss_fn(yy, tgt).astype(jnp.float32))(y2)
            return l, dly, {}

        for t in range(T):
            # ---- forward: micro-batch f = t - r (traced, r-dependent) ----
            f = t - r
            has_f = (f >= 0) & (f < M)
            state_in = jnp.where(r == 0, x_all[jnp.clip(f, 0, M - 1)], fs)

            if skip_idle:
                y = jax.lax.cond(
                    has_f,
                    lambda s=state_in: stage_fn(p_here, s),
                    lambda: state0)
            else:
                y = jnp.where(has_f, stage_fn(p_here, state_in), state0)

            # ---- backward: micro-batch b = t + r - 2S + 1 ----------------
            b = t + r - 2 * S + 1
            has_b = (b >= 0) & (b < M)
            # input saved at tick t_w = b + r, delay t - t_w = 2S - 1 - 2r
            slot = jnp.mod(t - (2 * S - 1 - 2 * r), W)
            saved = jax.lax.dynamic_index_in_dim(resid, slot, keepdims=False)
            tgt = tgt_all[jnp.clip(b, 0, M - 1)]

            def do_b(saved=saved, tgt=tgt, bs=bs):
                y2, vjp_fn = jax.vjp(lambda p, s: stage_fn(p, s),
                                     p_here, saved)
                l, dly, dlp = seed_loss(y2, tgt, lp_rep)
                last = r == S - 1
                ct = jnp.where(last, dly.astype(y2.dtype) * inv_m, bs)
                dp, dx = vjp_fn(ct)
                lc = jnp.where(last, l * inv_m, 0.0)
                dlp = {k: jnp.where(last, v * inv_m, 0.0) for k, v in dlp.items()}
                return dp, dx, lc, dlp

            def skip_b():
                return ({k: jnp.zeros_like(v) for k, v in p_here.items()},
                        state0, jnp.zeros((), jnp.float32),
                        {k: jnp.zeros_like(v) for k, v in lp_rep.items()})

            if skip_idle:
                dp, dx, lc, dlp = jax.lax.cond(has_b, do_b, skip_b)
            else:
                live, dead = do_b(), skip_b()
                dp, dx, lc, dlp = jax.tree_util.tree_map(
                    lambda a, z: jnp.where(has_b, a, z), live, dead)
            gacc = {k: gacc[k] + dp[k] for k in gacc}
            lp_acc = {k: lp_acc[k] + dlp[k] for k in lp_acc}
            loss_acc = loss_acc + lc
            if return_x_grad:
                # the cotangent leaving rank 0 is dL/d x[b]
                xg = jnp.where(has_b & (r == 0),
                               xg.at[jnp.clip(b, 0, M - 1)].set(dx), xg)

            # ---- rings + residual save (uniform across ranks) ------------
            resid = jnp.where(has_f,
                              resid.at[jnp.mod(t, W)].set(state_in), resid)
            fs = jax.lax.ppermute(y, axis, [(j, (j + 1) % S) for j in range(S)])
            bs = jax.lax.ppermute(dx, axis,
                                  [(j, (j - 1) % S) for j in range(S)])

        loss = jax.lax.psum(loss_acc, axis)
        grads = {k: v[None] for k, v in gacc.items()}   # (1, ...) per rank
        outs = [loss, grads]
        if has_lp:
            outs.append({k: jax.lax.psum(v, axis) for k, v in lp_acc.items()})
        if return_x_grad:
            outs.append(jax.lax.psum(xg, axis))
        return tuple(outs)

    out_specs = [P(), {k: P(axis) for k in stacked_params}]
    if has_lp:
        out_specs.append({k: P() for k in lp})
    if return_x_grad:
        out_specs.append(P())

    kwargs = dict(mesh=mesh.jax_mesh,
                  in_specs=(param_specs, {k: P() for k in lp}, P(), P()),
                  out_specs=tuple(out_specs), check_vma=False)
    if partial_manual:
        # manual only over the pp ring; dp/mp/sep stay GSPMD-automatic so
        # hybrid tp/dp sharding inside a stage keeps working
        kwargs["axis_names"] = {axis}
    fn = shard_map(local, **kwargs)
    res = fn(stacked_params, lp, x, targets)
    if len(res) == 2:
        return res[0], res[1]
    return res
