"""paddle_tpu.native — C++ runtime components.

The TPU build keeps the *control plane* native, as the reference does
(SURVEY.md §2.10): TCPStore rendezvous (tcp_store.cpp ←
paddle/phi/core/distributed/store/tcp_store.h:121). Libraries are built on
first use with the system toolchain and cached beside the sources under a
name keyed on a hash of those sources and flags — a binary left over from
other sources (the ``*.so`` files are git-ignored, so a copied checkout
may carry some) is never loaded. ``load_library`` returns None when no
compiler is available and callers fall back to pure-python
implementations; ``chip_smoke.py`` and ``tests_tpu/`` treat that None as
a failure.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import threading

_here = os.path.dirname(os.path.abspath(__file__))
_build_lock = threading.Lock()


def _lib_path(name: str, sources: list[str], flags: list[str]) -> str:
    h = hashlib.sha256("\0".join(flags).encode())
    for s in sources:
        with open(s, "rb") as f:
            h.update(b"\0" + f.read())
    return os.path.join(_here, f"lib{name}_{h.hexdigest()[:12]}.so")


def build_library(name: str, sources: list[str] | None = None,
                  extra_flags: list[str] | None = None) -> str | None:
    """Compile ``name``.cpp into lib``name``_<source hash>.so (cached).
    Returns the path, or None if the toolchain is unavailable or
    compilation fails."""
    sources = sources or [os.path.join(_here, f"{name}.cpp")]
    flags = list(extra_flags or [])
    out = _lib_path(name, sources, flags)
    with _build_lock:
        if os.path.exists(out):
            return out
        # built under a private name and renamed into place: a build that
        # dies midway must not leave a half-written file under the name a
        # later process would trust (still ``*.so``, so git ignores it)
        tmp = f"{out[:-3]}.tmp{os.getpid()}.so"
        cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
               *flags, "-o", tmp, *sources]
        try:
            # blocking UNDER the build lock is the contract here: the
            # lock exists to serialize the one-time g++ build, and a
            # second caller MUST park until the .so exists (tpu-lint's
            # usual "snapshot then block" fix would race the compiler)
            # tpu-lint: disable=lock-blocking-call
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=120)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if proc.returncode != 0:
            import sys

            print(f"[paddle_tpu.native] build of {name} failed:\n"
                  f"{proc.stderr[-2000:]}", file=sys.stderr)
            return None
        os.replace(tmp, out)
        return out


def load_library(name: str):
    """ctypes.CDLL for a native component, building it if needed."""
    import ctypes

    path = build_library(name)
    if path is None:
        return None
    return ctypes.CDLL(path)
