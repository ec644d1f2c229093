"""Built-in rewrite rules: fusion routing, AMP insertion, decomposition.

These are the three pass families the reference implements over PIR —
fusion patterns (paddle/fluid/pir/transforms/gpu/fused_*_pass.cc), the AMP
pass (python/paddle/distributed/passes/auto_parallel_amp.py), and op
decomposition (python/paddle/decomposition/) — re-expressed as jaxpr
rewrite rules (see passes/rewrite.py for the engine).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import jax.extend.core as jex
from jax import lax

from paddle_tpu.passes.rewrite import EqnRule, MatchInfo, RewriteRule

__all__ = [
    "fuse_rms_norm_rule", "amp_cast_rules", "decompose_rule",
    "DEFAULT_DECOMPOSITIONS", "decomposition_rules",
    "decompose_fused", "FUSED_ROUTING_OFF",
]


# --------------------------------------------------------------------------
# fusion: rms_norm composition -> single custom-vjp unit
# --------------------------------------------------------------------------

def _rms_pattern(x, w):
    # the exact composition nn.functional.rms_norm emits (single source:
    # ops/fused_norm.rms_lax keeps matcher and emitter in sync)
    from paddle_tpu.ops.fused_norm import rms_lax
    return rms_lax(x, w, 1e-6)


def _rms_where(info: MatchInfo) -> bool:
    x_aval = info.captures[0].aval
    red = info.target_eqn("reduce_sum")
    if tuple(red.params.get("axes", ())) != (len(x_aval.shape) - 1,):
        return False
    div = info.target_eqn("div")
    d = div.invars[1]
    if not isinstance(d, jex.Literal):
        return False
    try:
        if float(d.val) != float(x_aval.shape[-1]):
            return False
    except TypeError:
        return False
    add = info.target_eqn("add")
    if not isinstance(add.invars[1], jex.Literal):
        return False
    # structural matching ignores params: the weight's broadcast must map it
    # onto the LAST axis (w[:, None]-style per-row scaling would otherwise
    # match on square activations and silently corrupt numerics)
    w_atom = info.captures[1]
    for _, te in info.eqns:
        if (te.primitive.name == "broadcast_in_dim"
                and any(v is w_atom for v in te.invars)):
            out_ndim = len(te.outvars[0].aval.shape)
            if tuple(te.params.get("broadcast_dimensions", ())) != \
                    (out_ndim - 1,):
                return False
    return True


def _rms_replace(info: MatchInfo) -> Callable:
    from paddle_tpu.ops.fused_norm import rms_norm_fused

    eps = float(info.target_eqn("add").invars[1].val)
    return lambda x, w: rms_norm_fused(x, w, eps)


def fuse_rms_norm_rule(hidden: int = 8) -> RewriteRule:
    """Match x * rsqrt(mean(x^2)+eps) * w (any eps, any trailing width) and
    replace it with ops.fused_norm.rms_norm_fused."""
    f32 = jax.ShapeDtypeStruct((4, hidden), jnp.float32)
    bf16 = jax.ShapeDtypeStruct((4, hidden), jnp.bfloat16)
    wf32 = jax.ShapeDtypeStruct((hidden,), jnp.float32)
    wbf16 = jax.ShapeDtypeStruct((hidden,), jnp.bfloat16)
    return RewriteRule(
        "fuse_rms_norm", _rms_pattern,
        examples=[(bf16, wbf16), (f32, wf32), (bf16, wf32)],
        replace=_rms_replace, where=_rms_where)


# --------------------------------------------------------------------------
# AMP: cast matmul/conv operands to a low-precision compute dtype
# --------------------------------------------------------------------------

def amp_cast_rules(compute_dtype: str = "bfloat16",
                   prims: Sequence[str] = ("dot_general",
                                           "conv_general_dilated")):
    """Rewrite f32 matmuls/convs to compute in ``compute_dtype`` on the MXU
    while keeping the f32 output dtype via preferred_element_type (the
    auto_parallel_amp pass analog; numerics match TPU mixed precision)."""
    dt = jnp.dtype(compute_dtype)

    def make(prim_name: str) -> EqnRule:
        def replace(eqn) -> Optional[Callable]:
            if any(not hasattr(v.aval, "dtype")
                   or v.aval.dtype != jnp.float32 for v in eqn.invars):
                return None
            out_dtype = eqn.outvars[0].aval.dtype
            params = dict(eqn.params)
            params["preferred_element_type"] = jnp.dtype(out_dtype)
            prim = eqn.primitive

            def build(*invals):
                cast = [v.astype(dt) for v in invals]
                out = prim.bind(*cast, **params)
                return out

            return build

        return EqnRule(f"amp_cast_{prim_name}", prim_name, replace)

    return [make(p) for p in prims]


# --------------------------------------------------------------------------
# decomposition: prim -> composition of simpler prims
# --------------------------------------------------------------------------

def decompose_rule(prim_name: str,
                   builder_from_params: Callable[[dict], Callable],
                   name: str = "") -> EqnRule:
    """EqnRule that replaces every ``prim_name`` equation with the traceable
    function ``builder_from_params(eqn.params)`` (python/paddle/decomposition
    analog; used by the ONNX exporter to lower to a portable prim set)."""
    return EqnRule(name or f"decompose_{prim_name}", prim_name,
                   lambda eqn: builder_from_params(dict(eqn.params)))


def _decomp_logistic(params):
    return lambda x: 1.0 / (1.0 + jnp.exp(-x))


def _decomp_softmax(params):
    axis = params.get("axis", (-1,))

    def f(x):
        m = jnp.max(x, axis=axis, keepdims=True)
        e = jnp.exp(x - lax.stop_gradient(m))
        return e / jnp.sum(e, axis=axis, keepdims=True)

    return f


def _decomp_integer_pow(params):
    y = params["y"]

    def f(x):
        if y == 0:
            return jnp.ones_like(x)
        inv = y < 0
        n = -y if inv else y
        out = x
        for _ in range(int(n) - 1):
            out = out * x
        return 1.0 / out if inv else out

    return f


def _decomp_rsqrt(params):
    return lambda x: 1.0 / jnp.sqrt(x)


DEFAULT_DECOMPOSITIONS: Dict[str, Callable[[dict], Callable]] = {
    "logistic": _decomp_logistic,
    "softmax": _decomp_softmax,
    "integer_pow": _decomp_integer_pow,
    "rsqrt": _decomp_rsqrt,
}


def decomposition_rules(table: Optional[Dict[str, Callable]] = None):
    table = DEFAULT_DECOMPOSITIONS if table is None else table
    return [decompose_rule(k, v) for k, v in table.items()]


# --------------------------------------------------------------------------
# fused-op decomposition mode (reference: paddle/fluid/primitive/composite/
# composite.h + python/paddle/decomposition/ — see-through for passes and
# exporters)
# --------------------------------------------------------------------------

# every fused/Pallas routing flag and the value that forces the canonical
# lax composition; plus the decompose_fused_ops master switch consumed by
# entries whose kernel is not flag-gated (chunked fused CE)
FUSED_ROUTING_OFF: Dict[str, object] = {
    "decompose_fused_ops": True,
    "use_fused_rms_norm": False,
    "use_fused_group_norm": False,
    "use_fused_attention": False,
    "use_fused_lm_ce": False,
    "use_decode_attention": False,
}


class decompose_fused:
    """Context manager: inside it, every fused op (fused_rms_norm,
    fused GroupNorm+SiLU, flash/decode attention, chunked
    fused lm-head CE, fused_linear_activation/swiglu) traces as its
    canonical base-prim composition — no pallas_call, no vocab-chunk
    scan. Routing happens at trace time, so wrapping a trace (NOT just a
    call) is what decomposes a jaxpr:

        with passes.decompose_fused():
            jaxpr = jax.make_jaxpr(fn)(*args)

    The ONNX exporter traces under this context; parity tests assert
    decomposed == fused numerics for every entry (test_passes.py).
    """

    def __enter__(self):
        from paddle_tpu.flags import flags, get_flags, set_flags
        self._old = {k: get_flags(k)[k] for k in FUSED_ROUTING_OFF}
        set_flags(dict(FUSED_ROUTING_OFF))
        return self

    def __exit__(self, *exc):
        from paddle_tpu.flags import set_flags
        set_flags(self._old)
        return False
