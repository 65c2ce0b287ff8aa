"""The jax SPMD entry points ``ring_attention`` / ``pipeline`` /
``comm_ops`` build on, under the positional call shapes those modules
use: the top-level ``shard_map`` (varying-manual-axes typing, which
types their manual ppermute accumulation patterns), ``lax.pcast`` (marks
a value device-varying over ``axes``) and ``lax.axis_size``. Written for
the one installation there is (JAX 0.9); no older-release branches.
"""
from jax import lax as _lax
from jax import shard_map as _shard_map

__all__ = ["shard_map", "pcast", "axis_size"]


def shard_map(f, mesh, in_specs, out_specs):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def pcast(x, axes, to):
    return _lax.pcast(x, axes, to=to)


axis_size = _lax.axis_size
