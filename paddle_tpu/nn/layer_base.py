"""nn.Layer — module base class.

Analog of the reference's ``paddle.nn.Layer`` (python/paddle/nn/layer/layers.py):
parameter/buffer/sublayer registries, forward hooks, state_dict round trip,
train/eval mode, dtype conversion. TPU note: parameters are plain eager
Tensors here; the jit/`to_static` path lifts them into function arguments
(functional_call) so compiled steps never bake weights in as constants.
"""

from __future__ import annotations

import collections
import contextlib
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from paddle_tpu.framework.dtype import convert_dtype, is_floating_point_dtype
from paddle_tpu.framework.tensor import Parameter, Tensor

__all__ = ["Layer", "param_dtype"]


class HookRemoveHelper:
    _next_id = 0

    def __init__(self, hooks: dict):
        self._hooks = hooks
        self._id = HookRemoveHelper._next_id
        HookRemoveHelper._next_id += 1

    def remove(self):
        self._hooks.pop(self._id, None)


_BUILD_DTYPE = None


@contextlib.contextmanager
def param_dtype(dtype):
    """Layers built inside that name no ``dtype`` of their own create
    their parameters in ``dtype`` (an explicit ``dtype=`` still wins): a
    model too large to exist in float32 first and be cast after is born in
    the dtype it runs in."""
    global _BUILD_DTYPE
    was, _BUILD_DTYPE = _BUILD_DTYPE, convert_dtype(dtype)
    try:
        yield
    finally:
        _BUILD_DTYPE = was


class Layer:
    def __init__(self, name_scope: Optional[str] = None, dtype=None):
        self.training = True
        self._dtype = (convert_dtype(dtype) or _BUILD_DTYPE
                       or convert_dtype("float32"))
        self._parameters: "collections.OrderedDict[str, Parameter]" = collections.OrderedDict()
        self._buffers: "collections.OrderedDict[str, Tensor]" = collections.OrderedDict()
        self._sub_layers: "collections.OrderedDict[str, Layer]" = collections.OrderedDict()
        self._forward_pre_hooks: dict = collections.OrderedDict()
        self._forward_post_hooks: dict = collections.OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # -- registration -------------------------------------------------------
    def __setattr__(self, name: str, value: Any) -> None:
        params = self.__dict__.get("_parameters")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__() before assigning parameters")
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            subs = self.__dict__.get("_sub_layers")
            if subs is None:
                raise RuntimeError("call Layer.__init__() before assigning sublayers")
            subs[name] = value
            self.__dict__.pop(name, None)
        else:
            if params is not None and name in params:
                del params[name]
            subs = self.__dict__.get("_sub_layers")
            if subs is not None and name in subs:
                del subs[name]
            object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def add_parameter(self, name: str, parameter: Optional[Parameter]) -> Optional[Parameter]:
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer") -> "Layer":
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True) -> None:
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        if tensor is not None:
            tensor.persistable = persistable
        self._buffers[name] = tensor

    def create_parameter(self, shape, dtype=None, is_bias: bool = False,
                         default_initializer: Optional[Callable] = None,
                         attr=None) -> Parameter:
        """ParamAttr-lite parameter factory (layers.py create_parameter analog)."""
        from paddle_tpu.nn import initializer as init
        dtype = convert_dtype(dtype) or self._dtype
        if default_initializer is None:
            default_initializer = init.Constant(0.0) if is_bias else init.XavierUniform()
        if attr is not None and getattr(attr, "initializer", None) is not None:
            default_initializer = attr.initializer
        value = default_initializer(tuple(shape), dtype)
        p = Parameter(value)
        if attr is not None and getattr(attr, "learning_rate", None) is not None:
            p.optimize_attr["learning_rate"] = attr.learning_rate
        if attr is not None and getattr(attr, "trainable", True) is False:
            p.stop_gradient = True
            p.trainable = False
        return p

    # -- iteration ----------------------------------------------------------
    def named_parameters(self, prefix: str = "", include_sublayers: bool = True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p
            if not include_sublayers:
                break

    def parameters(self, include_sublayers: bool = True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix: str = "") -> Iterator[Tuple[str, Tensor]]:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix, include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b

    def buffers(self) -> List[Tensor]:
        return [b for _, b in self.named_buffers()]

    def named_sublayers(self, prefix: str = "", include_self: bool = False
                        ) -> Iterator[Tuple[str, "Layer"]]:
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from sub.named_sublayers(prefix=sub_prefix, include_self=True)

    def sublayers(self, include_self: bool = False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self) -> Iterator["Layer"]:
        for _, l in self._sub_layers.items():
            if l is not None:
                yield l

    def named_children(self):
        return iter(self._sub_layers.items())

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # -- mode ---------------------------------------------------------------
    def train(self) -> "Layer":
        for _, l in self.named_sublayers(include_self=True):
            l.training = True
        return self

    def eval(self) -> "Layer":
        for _, l in self.named_sublayers(include_self=True):
            l.training = False
        return self

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers: bool = True,
                   structured_name_prefix: str = "", keep_vars: bool = False) -> Dict[str, Tensor]:
        out = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            out[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            if b.persistable:
                out[name] = b
        return out

    def set_state_dict(self, state_dict: Dict[str, Any], use_structured_name: bool = True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                v = state_dict[name]
                if isinstance(v, Tensor):
                    v = v._value
                v = jnp.asarray(np.asarray(v), dtype=t.dtype)
                if tuple(v.shape) != t.shape:
                    raise ValueError(
                        f"shape mismatch for {name}: checkpoint {tuple(v.shape)} vs model {t.shape}")
                t._set_value(v)
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    # -- dtype / device conversion ------------------------------------------
    def to(self, device=None, dtype=None, blocking=None) -> "Layer":
        d = convert_dtype(dtype)
        if d is not None:
            for _, l in self.named_sublayers(include_self=True):
                l._dtype = d
            for p in self.parameters():
                if is_floating_point_dtype(p.dtype):
                    p._set_value(p._value.astype(d))
            for b in self.buffers():
                if is_floating_point_dtype(b.dtype):
                    b._set_value(b._value.astype(d))
        return self

    def astype(self, dtype) -> "Layer":
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        helper = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[helper._id] = hook
        return helper

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        helper = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[helper._id] = hook
        return helper

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).split("\n")
            sub_repr = [sub_repr[0]] + ["  " + l for l in sub_repr[1:]]
            lines.append(f"  ({name}): " + "\n".join(sub_repr))
        main = f"{self.__class__.__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"
