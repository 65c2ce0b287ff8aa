"""Global flag registry.

The reference exposes ~184 runtime flags through its own gflags clone
(/root/reference/paddle/common/flags.cc, flags_native.cc) settable via env
vars and ``paddle.set_flags``. This is the same idea natively in Python:
flags are declared with defaults, overridable by ``FLAGS_*`` environment
variables at import and by ``set_flags`` at runtime.
"""
from __future__ import annotations

import os
from typing import Any

__all__ = ["define_flag", "set_flags", "get_flags", "flag"]

_FLAGS: dict[str, Any] = {}
_DOCS: dict[str, str] = {}


def _coerce(value, template):
    if isinstance(template, bool):
        if isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return bool(value)
    if isinstance(template, int):
        return int(value)
    if isinstance(template, float):
        return float(value)
    return value


def define_flag(name: str, default, doc: str = ""):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    env = os.environ.get(name)
    _FLAGS[name] = _coerce(env, default) if env is not None else default
    _DOCS[name] = doc
    return _FLAGS[name]


def set_flags(flags: dict):
    for k, v in flags.items():
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        if k not in _FLAGS:
            raise KeyError(f"Unknown flag {k}; declared flags: {sorted(_FLAGS)}")
        _FLAGS[k] = _coerce(v, _FLAGS[k])


def get_flags(flags=None):
    if flags is None:
        return dict(_FLAGS)
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        if not k.startswith("FLAGS_"):
            k = "FLAGS_" + k
        out[k] = _FLAGS[k]
    return out


def flag(name: str):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    return _FLAGS[name]


# Core flags (analogs of the reference's most-used ones).
define_flag("FLAGS_check_nan_inf", False, "Check outputs of every op for NaN/Inf")
define_flag("FLAGS_eager_op_jit", True, "Compile+cache per-op executables for eager mode")
define_flag("FLAGS_use_pallas_kernels", True, "Use Pallas kernels for fused ops when available")
define_flag("FLAGS_flash_attention_block_size", 256,
            "Preferred q/k block for the Pallas flash-attention kernel "
            "(256 measured fastest on v5e; falls back to 128 when the "
            "sequence is not divisible)")
define_flag("FLAGS_cross_host_device_put", False,
            "Cross-mesh pipeline: use native cross-host device_put (DCN; "
            "requires jax_cross_host_transfer_socket_address) instead of "
            "the coordination-KV host transport")
define_flag("FLAGS_default_dtype", "float32", "Default floating dtype for creation ops")
define_flag("FLAGS_retain_grad_for_all", False, "Retain .grad for non-leaf tensors")
define_flag("FLAGS_log_level", 0, "Framework VLOG level")
