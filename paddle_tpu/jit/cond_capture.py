"""Capture Python ``if tensor:`` branches into ``lax.cond`` under tracing.

Round-4 answer to the reference's first-class IR control flow
(paddle/fluid/pir/dialect/operator/ir/control_flow_op.h) + SOT branch
handling (python/paddle/jit/sot/): when a jit trace hits ``bool()`` on a
traced tensor, instead of graph-breaking to eager, ``to_static`` now
RE-RUNS the function once per outcome of each data-dependent bool — a
decision-tree exploration — and combines the per-path results with
``lax.cond`` on the recorded predicates. The whole function stays one
compiled XLA program with zero graph breaks.

Mechanics. ``Tensor.__bool__`` consults the active :class:`CaptureContext`
when its value is a tracer. If the context has a forced decision for this
bool site, it returns it; otherwise it raises :class:`Fork` carrying the
predicate. :func:`explore` drives the runs depth-first, forcing ``True``
then ``False`` at each newly discovered site, and folds the leaves back
together bottom-up.

Semantics and limits (documented fallback rules — violating any of these
falls back to the round-3 eager graph-break, observable via the
``to_static_graph_breaks`` STAT):

- branch purity: every path is executed during tracing, so branch side
  effects (Python state mutation, appends) happen for ALL paths;
- matching outputs: all paths must produce the same pytree structure,
  shapes and dtypes (:class:`CaptureMismatch` otherwise);
- path budget: at most ``flags.to_static_max_cond_paths`` leaf paths
  (:class:`CaptureOverflow` beyond it) — each data-dependent bool doubles
  the count, so deeply branchy functions belong on
  ``paddle.static.nn.cond`` instead;
- the function must be deterministic across re-runs (same bools hit in
  the same order); the RNG trace key is re-pushed per run so random ops
  replay identically;
- both sides of every branch are computed and the result selected
  (select semantics, like ``paddle.where``) — pick static.nn.cond for
  lazy single-branch execution of expensive branches.
"""

from __future__ import annotations

import sys
from typing import Any, Callable, List

import jax
import jax.numpy as jnp

__all__ = ["explore", "resolve_traced_bool", "CaptureOverflow",
           "CaptureMismatch", "Fork", "opaque_trace_state"]


def opaque_trace_state():
    return jax.core.get_opaque_trace_state()


class Fork(Exception):
    """A new data-dependent bool site was hit; carries the predicate and
    the bool site identity (code object + bytecode offset of the caller)
    so :func:`explore` can recognize a ``while tensor:`` spine — the same
    site forking once per iteration."""

    def __init__(self, pred, site=None):
        super().__init__("data-dependent bool (capture fork)")
        self.pred = pred
        self.site = site


class CaptureOverflow(Exception):
    """More leaf paths than the flags.to_static_max_cond_paths budget."""


class CaptureMismatch(Exception):
    """Paths produced different pytree structures/shapes/dtypes."""


class CaptureContext:
    __slots__ = ("decisions", "cursor", "trace_state")

    def __init__(self, decisions: List[bool]):
        self.decisions = decisions
        self.cursor = 0
        # identity of the trace explore() runs under: bool sites hit in a
        # DEEPER trace (a lax.cond branch / loop body) cannot be captured
        # here — their predicate tracer would be dead at our combine level
        self.trace_state = opaque_trace_state()


_stack: List[CaptureContext] = []


def resolve_traced_bool(value) -> bool:
    """Called by ``Tensor.__bool__`` on a traced value. Returns the forced
    decision for this site, raises :class:`Fork` at a new site, or returns
    ``None`` when no capture is active / the value is not a scalar (the
    caller then falls through to the plain concretization error)."""
    if not _stack:
        return None
    aval = getattr(value, "aval", None)
    if aval is None or getattr(aval, "size", None) != 1:
        return None
    ctx = _stack[-1]
    if opaque_trace_state() != ctx.trace_state:
        # nested traced region: fall through to the ordinary
        # concretization error -> to_static graph-breaks cleanly
        return None
    if ctx.cursor < len(ctx.decisions):
        d = ctx.decisions[ctx.cursor]
        ctx.cursor += 1
        return d
    try:
        # frame 0 = here, 1 = Tensor.__bool__, 2 = the bool() call site
        f = sys._getframe(2)
        site = (id(f.f_code), f.f_lasti)
    except Exception:
        site = None
    raise Fork(jnp.asarray(value).reshape(()).astype(bool), site)


def explore(thunk: Callable[[], Any], max_paths: int = 16,
            max_while_iters: int | None = None):
    """Run ``thunk`` under bool-capture; return its output with every
    data-dependent branch folded into ``lax.cond``.

    ``max_while_iters`` (round 5): a ``while tensor:`` loop forks at the
    SAME bool site once per iteration — an all-True spine that would
    otherwise explore forever and overflow. When a single site has been
    forced True ``max_while_iters`` times along a path, the next fork at
    that site is TRUNCATED: the False branch is taken unconditionally and
    a runtime check (jax.debug.callback) errors if that path is live with
    the predicate still True — so a loop that respects the bound compiles
    exactly (and differentiably, via the lax.cond fold), and one that
    exceeds it at runtime errors loudly instead of silently truncating.

    Zero overhead when no fork occurs (single run, returned as-is)."""

    n_runs = 0
    # a full binary tree with max_paths leaves takes 2*max_paths - 1 runs;
    # bounding RUNS (not just completed leaves) also catches the
    # non-terminating case — a data-dependent `while tensor:` at an
    # unrecognizable site (site=None) forks on an all-True spine forever
    # and never completes a single leaf
    max_runs = 2 * max_paths

    def run(decisions: List[bool]):
        nonlocal n_runs
        n_runs += 1
        if n_runs > max_runs:
            raise CaptureOverflow(
                f"data-dependent branch capture exceeded {max_runs} "
                f"exploration runs (budget {max_paths} paths) — an "
                f"unbounded `while tensor:` loop cannot be captured; "
                f"use paddle.static.nn.while_loop")
        ctx = CaptureContext(list(decisions))
        _stack.append(ctx)
        try:
            return ("leaf", thunk())
        except Fork as f:
            return ("fork", f.pred, f.site)
        finally:
            _stack.pop()

    n_leaves = 0

    def build(prefix: List[bool], spine: dict):
        # spine: per-site count of True decisions along this path
        nonlocal n_leaves
        r = run(prefix)
        if r[0] == "leaf":
            n_leaves += 1
            if n_leaves > max_paths:
                raise CaptureOverflow(
                    f"data-dependent branch capture exceeded "
                    f"{max_paths} paths")
            return r
        pred, site = r[1], r[2]
        from paddle_tpu.framework.monitor import stat_add
        if (max_while_iters is not None and site is not None
                and spine.get(site, 0) >= max_while_iters):
            stat_add("to_static_while_truncations")
            # the forced False is a loop EXIT at this site too: reset its
            # spine count so a later sequential loop at the same site gets
            # a fresh iteration budget instead of truncating at iter 0
            return ("trunc", pred, build(prefix + [False], {**spine, site: 0}))
        stat_add("to_static_cond_captures")
        # True extends this site's spine; False is a loop EXIT at this
        # site — reset its count so a later, sequential loop at the same
        # site gets a fresh iteration budget
        return ("node", pred,
                build(prefix + [True], {**spine, site: spine.get(site, 0) + 1}),
                build(prefix + [False], {**spine, site: 0}))

    return _combine(build([], {}))


def _trunc_check(violation):
    if bool(violation):
        raise RuntimeError(
            "to_static: a captured `while tensor:` loop exceeded the "
            "to_static_max_while_iters bound at runtime — its result was "
            "truncated. Raise paddle.set_flags({'to_static_max_while_iters'"
            ": N}) above the loop's true trip count, or use "
            "paddle.static.nn.while_loop(max_iters=...).")


def _combine(tree, path_pred=None):
    if tree[0] == "leaf":
        return tree[1]
    if tree[0] == "trunc":
        _, pred, sub = tree
        viol = pred if path_pred is None else jnp.logical_and(path_pred, pred)
        jax.debug.callback(_trunc_check, viol)
        return _combine(sub, path_pred)
    _, pred, t, f = tree
    tv, tdef = jax.tree_util.tree_flatten(
        _combine(t, pred if path_pred is None
                 else jnp.logical_and(path_pred, pred)))
    fv, fdef = jax.tree_util.tree_flatten(
        _combine(f, jnp.logical_not(pred) if path_pred is None
                 else jnp.logical_and(path_pred, jnp.logical_not(pred))))
    if tdef != fdef:
        raise CaptureMismatch(
            f"branches produced different pytree structures: {tdef} vs "
            f"{fdef}")
    for a, b in zip(tv, fv):
        sa = (jnp.shape(a), jnp.result_type(a))
        sb = (jnp.shape(b), jnp.result_type(b))
        if sa != sb:
            raise CaptureMismatch(
                f"branches produced mismatched leaves: {sa} vs {sb}")
    try:
        outs = jax.lax.cond(pred, lambda: tuple(tv), lambda: tuple(fv))
    except jax.errors.UnexpectedTracerError as e:
        # the bool site was hit inside an INNER trace (a static.nn.cond
        # branch / lax loop body): its predicate tracer is dead out here.
        # Surface as a capture failure so to_static graph-breaks cleanly.
        raise CaptureMismatch(
            "data-dependent bool inside a nested traced region cannot be "
            f"captured ({e})") from e
    return jax.tree_util.tree_unflatten(tdef, list(outs))
