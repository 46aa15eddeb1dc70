"""Native (C) accelerators with build-on-demand and pure-Python fallback.

The reference is pure Go with no native components (SURVEY.md §2), so
nothing here is a parity obligation — these are host-feed accelerations
for paths the TPU build made hot (quantity parsing on manifest ingest and
pod watch-event re-encode). Every native entry point has a Python oracle
(utils/quantity.py) and parity is fuzz-tested; absence of a C toolchain
degrades to the oracle silently.

Build: compiled once into native/_build/ with the running interpreter's
sysconfig flags. The object's file name carries a hash of its .c source,
so only a binary built from the source on disk is ever loaded: a copied
or stale .so, whatever its mtime, is simply never looked up.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sysconfig
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_lock = threading.Lock()
_kquantity = None
_tried = False


def _compile(src: str, out: str) -> bool:
    include = sysconfig.get_path("include")
    cc = sysconfig.get_config_var("CC") or "cc"
    # compile to a private temp path, then atomically publish: a concurrent
    # or killed compile must never leave a torn .so at the final path
    # (its name would match the source and it would never be rebuilt)
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = [
        *cc.split(),
        "-O2",
        "-fPIC",
        "-shared",
        "-pthread",  # the threaded assignment variant (binpack_kernel.c)
        f"-I{include}",
        src,
        "-o",
        tmp,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
        if proc.returncode != 0 or not os.path.exists(tmp):
            return False
        os.replace(tmp, out)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass


def built_path(src_name: str, stem: str) -> str:
    """native/_build/<stem>-<sha256 of the source, 16 hex>.so."""
    with open(os.path.join(_HERE, src_name), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"{stem}-{digest}.so")


def _build_and_load(src_name: str, stem: str, bind):
    """The shared build-on-demand scaffold: source-keyed lookup, compile,
    bind. Returns the bound handle or None; callers own the caching."""
    try:
        so = built_path(src_name, stem)
        os.makedirs(_BUILD_DIR, exist_ok=True)
        if not os.path.exists(so) and not _compile(
            os.path.join(_HERE, src_name), so
        ):
            return None
        return bind(so)
    except Exception:
        return None


def _bind_extension(so: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location("_kquantity", so)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _bind_ctypes(so: str):
    import ctypes

    lib = ctypes.CDLL(so)
    lib.karpenter_assign.restype = None
    lib.karpenter_shelf_bfd.restype = None
    lib.karpenter_pack_bits.restype = None
    if hasattr(lib, "karpenter_assign_mt"):  # older prebuilt .so lacks it
        lib.karpenter_assign_mt.restype = None
    return lib


def load_kquantity() -> Optional[object]:
    """The _kquantity extension module, building it if needed; None when
    no toolchain is available (callers use the Python path)."""
    global _kquantity, _tried
    with _lock:
        if _kquantity is not None or _tried:
            return _kquantity
        _tried = True
        _kquantity = _build_and_load(
            "quantity.c", "_kquantity", _bind_extension
        )
        return _kquantity


_kbinpack = None
_kbinpack_tried = False
_kbinpack_async_started = False


def load_kbinpack() -> Optional[object]:
    """ctypes handle to the fused assignment kernel (binpack_kernel.c),
    building it on demand; None without a toolchain (callers use the
    numpy path). Plain C, no CPython API — loaded with ctypes.CDLL, and
    the call releases the GIL for its whole O(P*T) worst-case scan."""
    global _kbinpack, _kbinpack_tried
    with _lock:
        if _kbinpack is not None or _kbinpack_tried:
            return _kbinpack
        _kbinpack_tried = True
        _kbinpack = _build_and_load(
            "binpack_kernel.c", "_kbinpack", _bind_ctypes
        )
        return _kbinpack


def peek_kbinpack() -> Optional[object]:
    """The kernel if it has finished loading, else None. Never blocks —
    the degraded-mode solve must not spend its tick budget inside a cc
    subprocess; it runs the numpy stages until the handle appears."""
    return _kbinpack


def ensure_kbinpack_async() -> None:
    """Kick off the kernel build/load in a daemon thread (the
    ensure_kquantity_async pattern)."""
    global _kbinpack_async_started
    with _lock:
        if _kbinpack_async_started or _kbinpack is not None:
            return
        _kbinpack_async_started = True
    threading.Thread(target=load_kbinpack, daemon=True).start()


_async_started = False


def peek_kquantity() -> Optional[object]:
    """The extension if it has finished loading, else None. Never blocks."""
    return _kquantity


def ensure_kquantity_async() -> None:
    """Kick off the build/load in a daemon thread. Callers use the Python
    path until peek_kquantity() turns non-None, so a cold compile never
    blocks a latency-sensitive first request (e.g. an admission webhook)."""
    global _async_started
    with _lock:
        if _async_started or _kquantity is not None:
            return
        _async_started = True
    threading.Thread(target=load_kquantity, daemon=True).start()
