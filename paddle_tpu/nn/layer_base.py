"""nn.Layer — the module system.

Analog of the reference's ``paddle.nn.Layer``
(/root/reference/python/paddle/nn/layer/layers.py:354): a tree of sublayers
holding named Parameters and buffers, with structured-name state_dict,
train/eval mode, and forward hooks.

TPU-native additions: ``raw_state()``/``load_raw_state()`` expose the
parameter+buffer pytree as flat dicts of ``jax.Array`` so jit'd train steps
(paddle_tpu.jit) can functionalize a Layer without copying, and sharded
parameter creation can ``device_put`` into a ``NamedSharding`` at init.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtype import convert_dtype, to_jax_dtype
from ..core.flags import flag
from ..core.tensor import Parameter, Tensor
from . import initializer as I

__all__ = ["Layer", "ParamAttr", "LazyGuard"]

_lazy_mode = False


class LazyGuard:
    """Defer parameter materialization (reference python/paddle/nn/
    initializer/lazy_init.py ``LazyGuard``, used by the semi-auto LLaMA
    harness to build 10B+ models without host OOM): inside the guard,
    ``create_parameter`` records (initializer, shape, dtype) instead of
    allocating. ``dist.shard_tensor``/``shard_layer`` then materialize each
    parameter directly INTO its sharding via ``jax.jit`` with
    ``out_shardings`` — every device allocates only its own shard;
    ``Layer.lazy_materialize()`` materializes unsharded."""

    def __enter__(self):
        global _lazy_mode
        self._saved = _lazy_mode
        _lazy_mode = True
        return self

    def __exit__(self, *exc):
        global _lazy_mode
        _lazy_mode = self._saved


class ParamAttr:
    """Parameter attribute bundle (reference: python/paddle/base/param_attr.py)."""

    def __init__(
        self,
        name=None,
        initializer=None,
        learning_rate=1.0,
        regularizer=None,
        trainable=True,
        need_clip=True,
    ):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        if attr is False:
            return False
        raise TypeError(f"Cannot interpret {attr!r} as ParamAttr")


class HookRemoveHelper:
    _next_id = 0

    def __init__(self, hooks: OrderedDict):
        self._hooks = hooks
        self._id = HookRemoveHelper._next_id
        HookRemoveHelper._next_id += 1
        hooks[self._id] = None  # placeholder replaced by caller

    def remove(self):
        self._hooks.pop(self._id, None)


class Layer:
    def __init__(self, name_scope=None, dtype=None):
        self.training = True
        # parameters are created in ``paddle.get_default_dtype()`` unless
        # the layer names one: ``set_default_dtype("bfloat16")`` builds a
        # model in bf16 directly, with no float32 copy to cast afterwards
        self._dtype = convert_dtype(
            dtype if dtype is not None
            else flag("FLAGS_default_dtype")).name
        self._parameters: OrderedDict[str, Parameter] = OrderedDict()
        self._sub_layers: OrderedDict[str, Layer] = OrderedDict()
        self._buffers: OrderedDict[str, Tensor] = OrderedDict()
        self._non_persistable_buffer_names: set[str] = set()
        self._forward_pre_hooks: OrderedDict = OrderedDict()
        self._forward_post_hooks: OrderedDict = OrderedDict()
        self._name_scope = name_scope or type(self).__name__.lower()

    # ------------------------------------------------ construction helpers

    def create_parameter(
        self,
        shape,
        attr=None,
        dtype=None,
        is_bias=False,
        default_initializer=None,
    ) -> Parameter:
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtype or self._dtype
        init = attr.initializer or default_initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        if _lazy_mode:
            p = Parameter(jnp.zeros((), to_jax_dtype(dtype)), name=attr.name,
                          trainable=attr.trainable)
            p._lazy_init = (init, tuple(shape), dtype)
        else:
            value = init(tuple(shape), dtype=dtype)
            if isinstance(value, Tensor):
                value = value._value
            p = Parameter(value, name=attr.name, trainable=attr.trainable)
        p.optimize_attr["learning_rate"] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.need_clip = getattr(attr, "need_clip", True)
        return p

    def create_tensor(self, shape=None, dtype=None, default_initializer=None):
        dtype = dtype or self._dtype
        if shape is None:
            return Tensor(jnp.zeros((), to_jax_dtype(dtype)))
        init = default_initializer or I.Constant(0.0)
        return Tensor(init(tuple(shape), dtype=dtype))

    def add_parameter(self, name: str, parameter: Parameter | None):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError(f"add_parameter expects Parameter, got {type(parameter)}")
        object.__delattr__(self, name) if name in self.__dict__ else None
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        if sublayer is not None and not isinstance(sublayer, Layer):
            raise TypeError(f"add_sublayer expects Layer, got {type(sublayer)}")
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Tensor | None, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        else:
            self._non_persistable_buffer_names.discard(name)
        return tensor

    # ------------------------------------------------ attribute protocol

    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__() before assigning parameters")
            self.__dict__.pop(name, None)
            params[name] = value
            self._sub_layers.pop(name, None)
            self._buffers.pop(name, None)
            return
        if isinstance(value, Layer):
            subs = self.__dict__.get("_sub_layers")
            if subs is None:
                raise RuntimeError("call Layer.__init__() before assigning sublayers")
            self.__dict__.pop(name, None)
            subs[name] = value
            if params is not None:
                params.pop(name, None)
            self._buffers.pop(name, None)
            return
        bufs = self.__dict__.get("_buffers")
        if bufs is not None and name in bufs:
            if value is None or isinstance(value, Tensor):
                bufs[name] = value
            else:
                bufs[name] = Tensor(value)
            return
        if params is not None and name in params and value is None:
            params[name] = None
            return
        object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + list(self._sub_layers) + list(self._buffers)

    # ------------------------------------------------ traversal

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, l in self.named_children():
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from l.named_sublayers(prefix=sub_prefix, include_self=True, layers_set=layers_set)

    def parameters(self, include_sublayers=True) -> list[Parameter]:
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        layers = (
            self.named_sublayers(prefix=prefix, include_self=True)
            if include_sublayers
            else [(prefix, self)]
        )
        for layer_prefix, layer in layers:
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield layer_prefix + ("." if layer_prefix else "") + name, p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        layers = (
            self.named_sublayers(prefix=prefix, include_self=True)
            if include_sublayers
            else [(prefix, self)]
        )
        for layer_prefix, layer in layers:
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield layer_prefix + ("." if layer_prefix else "") + name, b

    def apply(self, fn: Callable[["Layer"], None]):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    def full_name(self):
        return self._name_scope

    # ------------------------------------------------ train / eval

    def train(self):
        self.training = True
        for l in self.children():
            l.train()
        return self

    def eval(self):
        self.training = False
        for l in self.children():
            l.eval()
        return self

    # ------------------------------------------------ state dict

    def state_dict(self, destination=None, include_sublayers=True, structured_name_prefix="", use_hook=True):
        if destination is None:
            destination = OrderedDict()
        for name, p in self._parameters.items():
            if p is not None:
                destination[structured_name_prefix + name] = p
        for name, b in self._buffers.items():
            if b is not None and name not in self._non_persistable_buffer_names:
                destination[structured_name_prefix + name] = b
        if include_sublayers:
            for name, l in self.named_children():
                l.state_dict(
                    destination=destination,
                    include_sublayers=True,
                    structured_name_prefix=structured_name_prefix + name + ".",
                )
        return destination

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Returns (missing_keys, unexpected_keys) like the reference."""
        own = self.state_dict()
        missing, matched = [], set()
        for key, target in own.items():
            if key in state_dict:
                src = state_dict[key]
                v = src._value if isinstance(src, Tensor) else jnp.asarray(src)
                if tuple(v.shape) != tuple(target._value.shape):
                    raise ValueError(
                        f"state_dict[{key!r}] shape {tuple(v.shape)} does not match "
                        f"parameter shape {tuple(target._value.shape)}"
                    )
                # fresh buffer (astype can alias): compiled train steps donate
                # parameter buffers, so shared storage across models would be
                # invalidated by the first donated step.
                target.set_value(jnp.array(v, dtype=target._value.dtype))
                matched.add(key)
            else:
                missing.append(key)
        unexpected = [k for k in state_dict if k not in own]
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # ------------------------------------------------ raw pytree access (jit path)

    def raw_state(self):
        """(params, buffers): flat name->jax.Array dicts for functional apply."""
        params = {k: p._value for k, p in self.named_parameters()}
        buffers = {k: b._value for k, b in self.named_buffers()}
        return params, buffers

    def load_raw_state(self, params: dict, buffers: dict | None = None):
        """Write jax arrays back into the live Parameters (zero-copy swap)."""
        index = {k: p for k, p in self.named_parameters()}
        for k, v in params.items():
            index[k]._value = v
        if buffers:
            bindex = {k: b for k, b in self.named_buffers()}
            for k, v in buffers.items():
                if k in bindex:
                    bindex[k]._value = v

    # ------------------------------------------------ conversion

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            jdt = to_jax_dtype(dtype)
            for p in self.parameters():
                if jnp.issubdtype(p._value.dtype, jnp.floating):
                    p._value = p._value.astype(jdt)
            for _, b in self.named_buffers():
                if jnp.issubdtype(b._value.dtype, jnp.floating):
                    b._value = b._value.astype(jdt)
            self._dtype = convert_dtype(dtype).name
        if device is not None:
            from ..core.place import Place, CPUPlace, TPUPlace

            if isinstance(device, str):
                place = CPUPlace(0) if device == "cpu" else TPUPlace(0)
            elif isinstance(device, Place):
                place = device
            else:
                place = device
            dev = place.jax_device()
            for p in self.parameters():
                p._value = jax.device_put(p._value, dev)
            for _, b in self.named_buffers():
                b._value = jax.device_put(b._value, dev)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def half(self):
        return self.to(dtype="float16")

    # ------------------------------------------------ hooks & call

    def register_forward_pre_hook(self, hook):
        helper = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[helper._id] = hook
        return helper

    def register_forward_post_hook(self, hook):
        helper = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[helper._id] = hook
        return helper

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(f"{type(self).__name__} must implement forward()")

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            if hook is None:
                continue
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            if hook is None:
                continue
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # ------------------------------------------------ misc

    def lazy_materialize(self):
        """Materialize parameters deferred under LazyGuard (unsharded)."""
        for _, p in self.named_parameters():
            lazy = getattr(p, "_lazy_init", None)
            if lazy is not None:
                init, shape, dtype = lazy
                value = init(shape, dtype=dtype)
                p._value = value._value if isinstance(value, Tensor) else value
                p._lazy_init = None
        return self

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, l in self.named_children():
            body = repr(l).split("\n")
            head = f"({name}): {body[0]}"
            lines.append(head)
            lines.extend("  " + b for b in body[1:])
        main = type(self).__name__ + "("
        if extra and not lines:
            return main + extra + ")"
        if not lines:
            return main + ")"
        out = [main + (extra if extra else "")]
        out.extend("  " + l for l in lines)
        out.append(")")
        return "\n".join(out)
