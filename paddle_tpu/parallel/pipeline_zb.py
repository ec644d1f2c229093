"""Compiled-SPMD zero-bubble (ZB-H1) pipeline training step.

Redesign of the reference's ZB-H1 scheduler
(python/paddle/distributed/passes/pipeline_scheduler_pass/
pipeline_zero_bubble.py): backward is SPLIT into

- ``dx`` — the input cotangent, which the upstream rank needs on the very
  next tick (it sits on the critical path), computed at the same tick
  1F1B runs its backward, and
- ``dW`` — the parameter gradient, which nothing downstream waits for,
  DEFERRED by ``r`` ticks on rank ``r``: micro-batch ``j``'s dW runs at
  global tick ``j + 2S - 1`` on every rank, which lands the final dWs of
  late stages exactly in the drain ticks where 1F1B leaves them idle
  (the H1 picture: the last stage defers most, stage 0 none).

Schedule (ticks t = 0 .. M + 2S - 2, same grid as 1F1B):

  fwd  f = t - r              (unchanged)
  dx   b = t + r - 2S + 1     (1F1B's backward tick, input-grad only)
  dW   j = t - 2S + 1         (r ticks after j's dx on rank r)

Deferral legality: j's dx runs at tick ``j + 2S - 1 - r``; its dW runs
``r`` ticks later, still within the T = M + 2S - 1 grid (the last dW,
j = M - 1, lands on the final tick for every rank). The saved stage input
(written at tick ``j + r``) is re-read ``2S - 1 - r`` ticks later and the
cotangent ``r`` ticks later — both inside the 2S-slot rings.

Bubble math, stated honestly: in the reference's ASYNC runtime the split
removes (S-1)·t_dW of per-rank idle time from the drain bubble — the
1F1B bubble (S-1)(t_F + t_dx + t_dW) shrinks to (S-1)(t_F + t_dx), the
H1 claim. In this compiled-SPMD form every tick is closed by the
``ppermute`` rendezvous, so wall time is Σ_t max_r cost(r, t) and the
deferral moves dW work between ticks without shortening the synchronous
tick grid — the capability (split backward + H1 placement) is what this
module provides, plus the schedule hook a future async executor would
need. The split pays one extra stage-forward recompute per micro-batch
(dx and dW each re-linearize from the saved input; the reference caches
the linearization instead — with jax.vjp the cache would pin every
micro-batch's intermediates and break the 1F1B memory bound).

``zb_schedule(S, M)`` exposes the static per-rank tick table so the
schedule itself is testable (and documents the accounting above).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel.mesh import ProcessMesh

__all__ = ["spmd_pipeline_zb", "zb_schedule"]


def zb_schedule(S: int, M: int) -> List[Dict[str, List[Tuple[int, int]]]]:
    """Static ZB-H1 tick table: per rank, the list of (tick, micro) for
    each duty. Asserts the schedule invariants the compiled loop relies
    on (dW deferral = r ticks; everything inside the T-tick grid)."""
    T = M + 2 * S - 1
    table = []
    for r in range(S):
        fwd = [(j + r, j) for j in range(M)]
        dx = [(j + 2 * S - 1 - r, j) for j in range(M)]
        dw = [(j + 2 * S - 1, j) for j in range(M)]
        assert all(0 <= t < T for t, _ in fwd + dx + dw), (S, M, r)
        # dW of micro j runs exactly r ticks after its dx on rank r
        assert all(tw - td == r for (td, _), (tw, _) in zip(dx, dw))
        table.append({"fwd": fwd, "dx": dx, "dw": dw})
    return table


def spmd_pipeline_zb(stage_fn: Callable, loss_fn: Callable,
                     stacked_params: dict, x, targets,
                     mesh: ProcessMesh, n_micro: int, axis: str = "pp",
                     loss_params: Optional[dict] = None,
                     return_x_grad: bool = False):
    """One ZB-H1 forward+backward pass. Same contract as
    ``pipeline_1f1b.spmd_pipeline_1f1b`` (losses and grads averaged over
    micro-batches; grads in the stacked (S, ...) layout)."""
    S = mesh.dim_size(axis)
    lead = next(iter(stacked_params.values())).shape[0] if stacked_params else S
    if lead != S:
        raise ValueError(f"stacked stage dim {lead} != pp axis size {S}")
    M = x.shape[0]
    if M != n_micro:
        raise ValueError(f"x leading dim {M} != n_micro {n_micro}")
    W = 2 * S
    T = M + 2 * S - 1
    has_lp = loss_params is not None
    lp = loss_params if has_lp else {}

    param_specs = {k: P(axis) for k in stacked_params}

    def local(params_loc, lp_rep, x_all, tgt_all):
        r = jax.lax.axis_index(axis)
        p_here = {k: v[0] for k, v in params_loc.items()}
        state0 = jnp.zeros_like(x_all[0])

        fs = state0
        bs = state0
        resid = jnp.zeros((W,) + state0.shape, state0.dtype)   # stage inputs
        cts = jnp.zeros((W,) + state0.shape, state0.dtype)     # dx cotangents
        gacc = {k: jnp.zeros_like(v) for k, v in p_here.items()}
        lp_acc = {k: jnp.zeros_like(v) for k, v in lp_rep.items()}
        xg = (jnp.zeros_like(x_all) if return_x_grad else None)
        loss_acc = jnp.zeros((), jnp.float32)
        inv_m = jnp.float32(1.0 / M)

        def seed_loss(y2, tgt, lp_rep):
            if has_lp:
                l, (dlp, dly) = jax.value_and_grad(
                    lambda p, yy: loss_fn(p, yy, tgt).astype(jnp.float32),
                    argnums=(0, 1))(lp_rep, y2)
                return l, dly, dlp
            l, dly = jax.value_and_grad(
                lambda yy: loss_fn(yy, tgt).astype(jnp.float32))(y2)
            return l, dly, {}

        for t in range(T):
            # ---- forward ------------------------------------------------
            f = t - r
            has_f = (f >= 0) & (f < M)
            state_in = jnp.where(r == 0, x_all[jnp.clip(f, 0, M - 1)], fs)
            y = jax.lax.cond(has_f,
                             lambda s=state_in: stage_fn(p_here, s),
                             lambda: state0)

            # ---- dx: input cotangent only (critical path) ---------------
            b = t + r - 2 * S + 1
            has_b = (b >= 0) & (b < M)
            slot_in = jnp.mod(t - (2 * S - 1 - 2 * r), W)
            saved = jax.lax.dynamic_index_in_dim(resid, slot_in,
                                                 keepdims=False)
            tgt = tgt_all[jnp.clip(b, 0, M - 1)]

            def do_dx(saved=saved, tgt=tgt, bs=bs):
                # params are closure constants: the vjp yields ONLY dx
                y2, vjp_fn = jax.vjp(lambda s: stage_fn(p_here, s), saved)
                l, dly, dlp = seed_loss(y2, tgt, lp_rep)
                last = r == S - 1
                ct = jnp.where(last, dly.astype(y2.dtype) * inv_m, bs)
                (dx,) = vjp_fn(ct)
                lc = jnp.where(last, l * inv_m, 0.0)
                dlp = {k: jnp.where(last, v * inv_m, 0.0)
                       for k, v in dlp.items()}
                return dx, ct, lc, dlp

            def skip_dx():
                return (state0, state0, jnp.zeros((), jnp.float32),
                        {k: jnp.zeros_like(v) for k, v in lp_rep.items()})

            dx, ct, lc, dlp = jax.lax.cond(has_b, do_dx, skip_dx)
            lp_acc = {k: lp_acc[k] + dlp[k] for k in lp_acc}
            loss_acc = loss_acc + lc
            # bank the cotangent for the deferred dW (slot by dx tick)
            cts = jnp.where(has_b, cts.at[jnp.mod(t, W)].set(ct), cts)
            if return_x_grad:
                xg = jnp.where(has_b & (r == 0),
                               xg.at[jnp.clip(b, 0, M - 1)].set(dx), xg)

            # ---- dW: deferred r ticks (the ZB split) --------------------
            j = t - 2 * S + 1
            has_w = (j >= 0) & (j < M)
            # j's stage input was saved at tick j + r -> slot (j + r) % W
            slot_w_in = jnp.mod(jnp.clip(j, 0, M - 1) + r, W)
            saved_w = jax.lax.dynamic_index_in_dim(resid, slot_w_in,
                                                   keepdims=False)
            # j's cotangent was banked at its dx tick t - r
            slot_w_ct = jnp.mod(t - r, W)
            ct_w = jax.lax.dynamic_index_in_dim(cts, slot_w_ct,
                                                keepdims=False)

            def do_dw(saved_w=saved_w, ct_w=ct_w):
                _, vjp_fn = jax.vjp(lambda p: stage_fn(p, saved_w), p_here)
                (dp,) = vjp_fn(ct_w)
                return dp

            def skip_dw():
                return {k: jnp.zeros_like(v) for k, v in p_here.items()}

            dp = jax.lax.cond(has_w, do_dw, skip_dw)
            gacc = {k: gacc[k] + dp[k] for k in gacc}

            # ---- rings + residual save ----------------------------------
            resid = jnp.where(has_f,
                              resid.at[jnp.mod(t, W)].set(state_in), resid)
            fs = jax.lax.ppermute(y, axis,
                                  [(i, (i + 1) % S) for i in range(S)])
            bs = jax.lax.ppermute(dx, axis,
                                  [(i, (i - 1) % S) for i in range(S)])

        loss = jax.lax.psum(loss_acc, axis)
        grads = {k: v[None] for k, v in gacc.items()}
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

    fn = shard_map(local, mesh=mesh.jax_mesh,
                   in_specs=(param_specs, {k: P() for k in lp}, P(), P()),
                   out_specs=tuple(out_specs), check_vma=False)
    res = fn(stacked_params, lp, x, targets)
    if len(res) == 2:
        return res[0], res[1]
    return res
