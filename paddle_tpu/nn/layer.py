"""nn.Layer base class.

Reference parity: python/paddle/nn/layer/layers.py:332 (Layer): parameter /
buffer / sublayer registries, forward hooks, train/eval mode, to(), state_dict
/ set_state_dict, named_* traversals, apply(). TPU-native: parameters are
Tensors holding jax.Arrays (possibly sharded — placements attach here for the
auto-parallel path).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Iterator, Optional, Tuple

import numpy as np
from jax import numpy as jnp

from ..core.tensor import Tensor
from ..core import state as core_state
from ..framework import dtype as dtype_mod


class Parameter(Tensor):
    """Trainable tensor (analog of paddle Parameter / EagerParamBase,
    python/paddle/base/framework.py)."""

    # placements/process_mesh live on Tensor as dist-attr properties
    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip", "is_distributed",
                 "sequence_parallel")

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.is_distributed = False


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtype
        self._parameters: "OrderedDict[str, Parameter]" = OrderedDict()
        self._buffers: "OrderedDict[str, Tensor]" = OrderedDict()
        self._sub_layers: "OrderedDict[str, Layer]" = OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._forward_post_hooks: "OrderedDict[int, Callable]" = OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ---- registration ----
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning parameters")
            self.__dict__.pop(name, None)
            params[name] = value
            self._sub_layers.pop(name, None)
            self._buffers.pop(name, None)
            return
        layers = self.__dict__.get("_sub_layers")
        if isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning sublayers")
            self.__dict__.pop(name, None)
            layers[name] = value
            if params is not None:
                params.pop(name, None)
            self._buffers.pop(name, None)
            return
        bufs = self.__dict__.get("_buffers")
        if bufs is not None and name in bufs:
            if value is None or isinstance(value, Tensor):
                bufs[name] = value
                return
        object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + list(self._buffers) + list(self._sub_layers)

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters[str(name)] = None
        else:
            self._parameters[str(name)] = parameter
        return parameter

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable=True):
        self._buffers[str(name)] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(str(name))
        return tensor

    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ) -> Parameter:
        """Analog of Layer.create_parameter (layers.py) using initializers."""
        from .initializer import Constant, XavierUniform, _resolve_attr

        dtype = dtype_mod.convert_dtype(dtype or self._dtype)
        init, name, trainable, lr, reg, need_clip = _resolve_attr(attr, is_bias, default_initializer)
        if init is None:
            # attr=False => no parameter (reference layers.py: bias_attr
            # False skips the bias entirely and forward receives None)
            return None
        value = init(tuple(shape), dtype)
        p = Parameter(value, trainable=trainable, name=name)
        p.optimize_attr = {"learning_rate": lr}
        p.regularizer = reg
        p.need_clip = need_clip
        return p

    def create_tensor(self, name=None, dtype=None):
        return Tensor(jnp.zeros((), dtype_mod.convert_dtype(dtype or self._dtype)), name=name)

    # ---- traversal ----
    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer_prefix, layer in self._walk(prefix):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    yield (layer_prefix + pname, p)
            if not include_sublayers:
                break

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer_prefix, layer in self._walk(prefix):
            for bname, b in layer._buffers.items():
                if b is not None and id(b) not in seen:
                    seen.add(id(b))
                    yield (layer_prefix + bname, b)
            if not include_sublayers:
                break

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix.rstrip("."), self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            sub_prefix = f"{prefix}{name}"
            yield sub_prefix, sub
            yield from sub.named_sublayers(prefix=sub_prefix + ".")

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return [l for l in self._sub_layers.values() if l is not None]

    def named_children(self):
        return [(n, l) for n, l in self._sub_layers.items() if l is not None]

    def _walk(self, prefix=""):
        yield ("", prefix, self)
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            yield from ((n, p, l) for n, p, l in sub._walk(f"{prefix}{name}."))

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # ---- mode ----
    def _set_mode(self, training: bool):
        from ..jit.api import _bump_mode_epoch

        changed = False
        for layer in self.sublayers(include_self=True):
            if layer.training != training:
                layer.training = training
                changed = True
        if changed:  # only invalidate jit guards when a mode actually flipped
            _bump_mode_epoch()
        return self

    def train(self):
        return self._set_mode(True)

    def eval(self):
        return self._set_mode(False)

    # ---- hooks ----
    class _HookHandle:
        _next_id = [0]

        def __init__(self, store):
            self._store = store
            self._id = Layer._HookHandle._next_id[0]
            Layer._HookHandle._next_id[0] += 1

        def remove(self):
            self._store.pop(self._id, None)

    def register_forward_pre_hook(self, hook):
        h = Layer._HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[h._id] = hook
        return h

    def register_forward_post_hook(self, hook):
        h = Layer._HookHandle(self._forward_post_hooks)
        self._forward_post_hooks[h._id] = hook
        return h

    # ---- call ----
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def _name_scopes(self) -> str:
        """Name this layer (a root, or one attached after its root was
        named) by its class, and every layer below by its dotted path under
        the nearest layer above it that is called: a container with no
        forward of its own (LayerList) lends its name to its members
        (`layers.3`). Returns this layer's name."""
        def walk(layer, prefix):
            for name, sub in layer._sub_layers.items():
                if sub is None:
                    continue
                sub._scope_name = prefix + name
                called = type(sub).forward is not Layer.forward
                walk(sub, "" if called else prefix + name + ".")

        self._scope_name = type(self).__name__
        walk(self, "")
        return self._scope_name

    def __call__(self, *inputs, **kwargs):
        # compile-time metadata only: every HLO op traced below carries the
        # path of the layers around it (`.../encoder/layers.3/self_attn/...`)
        with core_state.named_scope(self.__dict__.get("_scope_name") or self._name_scopes()):
            for hook in self._forward_pre_hooks.values():
                out = hook(self, inputs)
                if out is not None:
                    inputs = out if isinstance(out, tuple) else (out,)
            outputs = self.forward(*inputs, **kwargs)
            for hook in self._forward_post_hooks.values():
                res = hook(self, inputs, outputs)
                if res is not None:
                    outputs = res
            return outputs

    # ---- state dict ----
    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix, include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(prefix=structured_name_prefix, include_sublayers=include_sublayers):
            bare = name.rsplit(".", 1)[-1]
            owner = self
            # skip non-persistable buffers
            if bare in self._find_buffer_owner(name)._non_persistable_buffer_names:
                continue
            dest[name] = b
        return dest

    def _find_buffer_owner(self, qualified):
        parts = qualified.split(".")[:-1]
        layer = self
        for p in parts:
            layer = layer._sub_layers.get(p, layer)
        return layer

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Returns (missing_keys, unexpected_keys) like the reference."""
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            target = own[k]
            val = v._value if isinstance(v, Tensor) else jnp.asarray(v)
            if tuple(val.shape) != tuple(target._value.shape):
                raise ValueError(
                    f"shape mismatch for {k}: loaded {tuple(val.shape)} vs param {tuple(target._value.shape)}"
                )
            target._replace_value(val.astype(target._value.dtype))
            if isinstance(target, Parameter):
                target.stop_gradient = not target.trainable
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    # ---- dtype/device movement ----
    def to(self, device=None, dtype=None, blocking=None):
        def move(t: Tensor):
            if t is None:
                return
            new = t
            if dtype is not None:
                d = dtype_mod.convert_dtype(dtype)
                if dtype_mod.is_floating_point_dtype(t.dtype):
                    new = new.astype(d)
            if device is not None:
                new = new.to(device=device)
            if new is not t:
                t._replace_value(new._value)
                if isinstance(t, Parameter):
                    t.stop_gradient = not t.trainable

        for _, p in self.named_parameters():
            move(p)
        for _, b in self.named_buffers():
            move(b)
        if dtype is not None:
            self._dtype = dtype
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def half(self):
        return self.to(dtype="float16")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{self.__class__.__name__}({extra}"]
        for name, sub in self._sub_layers.items():
            sub_repr = repr(sub).replace("\n", "\n  ")
            lines.append(f"  ({name}): {sub_repr}")
        return "\n".join(lines) + ")" if len(lines) > 1 else lines[0] + ")"
