"""Build the native apiserver binary (native/apiserver.cpp).

The C++ core implements the same storage/watch/bind contract as the
Python apiserver (see the header comment in native/apiserver.cpp); the
perf rigs use it because the measured wire ceiling of the Python server
is its GIL.  The binary is never committed: ``native_binary()`` builds it
through ``native/Makefile`` (which also generates ``kinds.inc`` from
``api/types.py``) whenever it is missing or older than its sources, and
raises ``NativeBuildError`` when that fails — the caller chooses its
server explicitly (``toolchain_available()``), nothing falls back to the
Python apiserver on its own.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import threading

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_BINARY = os.path.join(_NATIVE_DIR, "kube-apiserver-native")

_lock = threading.Lock()
_built = False


class NativeBuildError(RuntimeError):
    """``make -C native`` failed or no C++ toolchain is installed."""


def toolchain_available() -> bool:
    """True when this machine can build the native apiserver (make and
    the Makefile's default compiler on PATH)."""
    return shutil.which("make") is not None and \
        shutil.which(os.environ.get("CXX", "g++")) is not None


def native_binary() -> str:
    """Path of the native apiserver, (re)built from source by make when
    missing or out of date.  One make invocation per process."""
    global _built
    with _lock:
        if _built:
            return _BINARY
        if not toolchain_available():
            raise NativeBuildError(
                "no C++ toolchain (make + g++) to build "
                "native/kube-apiserver-native")
        proc = subprocess.run(
            ["make", "-C", _NATIVE_DIR, f"PYTHON={sys.executable}"],
            capture_output=True, text=True, timeout=300)
        if proc.returncode != 0 or not os.path.exists(_BINARY):
            raise NativeBuildError(
                f"make -C {_NATIVE_DIR} failed (rc {proc.returncode}):\n"
                f"{proc.stdout[-2000:]}{proc.stderr[-2000:]}")
        _built = True
        return _BINARY
