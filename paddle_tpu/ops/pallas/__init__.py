"""paddle_tpu.ops.pallas — hand-written TPU kernels (Mosaic/Pallas).

The re-emission of the reference's fused kernel set
(/root/reference/paddle/phi/kernels/fusion/gpu/) and its KPS portable
kernel DSL (paddle/phi/kernels/primitive/): flash attention here, with the
XLA-composition fallbacks living in ops/nn_kernels.py. Gated by
FLAGS_use_pallas_kernels.
"""
import contextlib
import math
import threading

import jax
from jax.sharding import PartitionSpec

__all__ = ["interpret", "kernel_mesh", "active_kernel_mesh", "over_mesh"]


def interpret():
    """Whether ``pallas_call`` runs these kernels in the Pallas
    interpreter: true exactly when the default backend is not a TPU, so
    CI on the CPU covers the kernel bodies. The ONE place this is
    decided for every kernel module here — a run that must be on the
    chip (``chip_smoke.py``, ``tests_tpu/``) asserts it is False."""
    return jax.default_backend() != "tpu"


# trace-time stack of (jax Mesh, batch axis names, head axis name), per
# thread: two engines may trace on two dispatcher threads at once
_SCOPES = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh, batch_axes=(), head_axis=None):
    """Scope for TRACING a program that is partitioned over ``mesh``.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" —
    the chip's compiler; the interpreter on virtual CPU devices never
    says so). Inside this scope the attention-family kernels run under
    ``shard_map`` over ``mesh``, their batch dimension split over
    ``batch_axes`` and their head dimension over ``head_axis`` wherever
    those divide it; what does not divide stays whole on every device.
    Set by whoever knows the layout: ``TPShardedEngine`` for its
    programs, ``LlamaAttention`` from the placement stamped on its own
    projection weights."""
    if not hasattr(_SCOPES, "stack"):
        _SCOPES.stack = []
    _SCOPES.stack.append((mesh, tuple(batch_axes), head_axis))
    try:
        yield
    finally:
        _SCOPES.stack.pop()


def active_kernel_mesh():
    """The innermost :func:`kernel_mesh` scope as a hashable ``(mesh,
    batch_axes, head_axis)``, or None. It changes what a kernel call
    traces to, so it belongs in any cache key over traced kernels (the
    per-op jit cache of ``ops/registry.py``)."""
    stack = getattr(_SCOPES, "stack", None)
    return stack[-1] if stack else None


def over_mesh(fn, args, dims, out_dims):
    """``fn(*args)``, under ``shard_map`` when a :func:`kernel_mesh` is
    active. ``dims`` gives one string per argument and ``out_dims`` the
    one for the output, a character per dimension: ``b`` batch, ``h``
    heads, ``.`` neither."""
    scope = active_kernel_mesh()
    if scope is None:
        return fn(*args)
    mesh, batch_axes, head_axis = scope

    def usable(axes, tag):
        n = math.prod(mesh.shape[a] for a in axes)
        sizes = [a.shape[i] for a, d in zip(args, dims)
                 for i, t in enumerate(d) if t == tag]
        return axes if axes and sizes and all(
            s % n == 0 for s in sizes) else None

    split = {"b": usable(batch_axes, "b"),
             "h": usable((head_axis,) if head_axis else (), "h")}

    def spec(d):
        return PartitionSpec(*[split.get(t) for t in d])

    # check_vma=False: pallas_call outputs carry no varying-axes type
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(spec(d) for d in dims),
        out_specs=spec(out_dims), check_vma=False)(*args)


from . import flash_attention  # noqa: E402,F401
