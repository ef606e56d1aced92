"""Loader for the native core (csrc/ompitpu_core.c) via ctypes.

Reference rationale: the reference implements its entire runtime in C;
here the Python plane keeps the logic and the native library owns the
two paths where byte movement and memory ordering dominate — the sm
SPSC ring (publish/consume with real acquire/release atomics instead
of the x86-TSO+GIL assumption) and the datatype span gather/scatter
(opal_datatype_pack.c's hot loop).

Build-on-first-use: the library is compiled when it is missing OR
older than ``ompitpu_core.c`` (the .so is git-ignored, so a checkout
switch or a copied tree can leave a stale one beside a newer source).
Every entry point degrades to the pure-Python implementation when no
compiler is available, so the framework stays importable anywhere
(the accelerator/null pattern); :func:`status` says which one a
process got, and why.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from ompi_tpu.core import cvar, output

_out = output.stream("native")

_enabled_var = cvar.register(
    "native", True, bool,
    help="Use the native C core (csrc/) for sm-ring and datatype "
         "pack hot paths when buildable; pure Python otherwise.",
    level=4)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_status = "not loaded yet"

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SRC = os.path.join(_CSRC, "ompitpu_core.c")
_SO = os.path.join(_CSRC, "libompitpu_core.so")


def _stale() -> bool:
    """No library, or one built before the source was last written."""
    try:
        return os.path.getmtime(_SO) < os.path.getmtime(_SRC)
    except OSError:
        return True


def lib() -> Optional[ctypes.CDLL]:
    """The native library, building it on first call; None if disabled
    or unbuildable."""
    global _lib, _tried, _status
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not _enabled_var.get():
            _status = "pure Python: disabled by --mca native 0"
            return None
        built = _stale()
        why = _build() if built else None
        if why is not None:
            _status = f"pure Python: build failed ({why})"
            return None
        try:
            _lib = _bind(ctypes.CDLL(_SO))
        except (OSError, AttributeError) as exc:
            _out.verbose(1, "native core unusable: %s", exc)
            _status = f"pure Python: {_SO} unusable ({exc})"
            return None
        if _lib is None:
            _status = "pure Python: native core ABI mismatch"
        else:
            _status = ("native: built in this run" if built
                       else "native: loaded an up-to-date build")
            _out.verbose(2, "native core loaded: %s", _SO)
        return _lib


def status() -> str:
    """Which core this process runs on after :func:`lib` was tried:
    ``native: ...`` or ``pure Python: <why>``."""
    lib()
    return _status


def _bind(L: ctypes.CDLL) -> Optional[ctypes.CDLL]:
    """Declare signatures; raises AttributeError on missing symbols
    (stale library); returns None on ABI-version mismatch."""
    L.otpu_ring_push.restype = ctypes.c_int
    L.otpu_ring_push.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_char_p,
        ctypes.c_uint32]
    L.otpu_ring_pop.restype = ctypes.c_int64
    L.otpu_ring_pop.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint64]
    L.otpu_ring_readable.restype = ctypes.c_uint64
    L.otpu_ring_readable.argtypes = [ctypes.c_void_p]
    L.otpu_gather_spans.restype = ctypes.c_int64
    L.otpu_gather_spans.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p]
    L.otpu_scatter_spans.restype = ctypes.c_int64
    L.otpu_scatter_spans.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ctypes.c_void_p]
    if L.otpu_abi_version() != 1:
        _out.verbose(1, "native core ABI mismatch; ignoring")
        return None
    return L


def _build() -> Optional[str]:
    """Compile to a private temp file, then atomically publish — N
    ranks may race here on first use and each must either see no .so
    or a complete one (concurrent `make` on a shared output can be
    dlopened half-written)."""
    import tempfile

    cc = os.environ.get("CC", "cc")
    try:
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_CSRC)
        os.close(fd)
        r = subprocess.run(
            [cc, "-O3", "-fPIC", "-std=c11", "-shared", _SRC, "-o", tmp],
            capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            _out.verbose(1, "native build failed:\n%s", r.stderr)
            os.unlink(tmp)
            return f"{cc} exited {r.returncode}"
        os.replace(tmp, _SO)  # atomic: racers each publish a whole file
        return None
    except (OSError, subprocess.TimeoutExpired) as exc:
        _out.verbose(1, "native build unavailable: %s", exc)
        return repr(exc)


def available() -> bool:
    return lib() is not None


def reset_for_testing() -> None:
    global _lib, _tried, _status
    with _lock:
        _lib = None
        _tried = False
        _status = "not loaded yet"
