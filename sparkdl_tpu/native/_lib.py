"""Build-on-first-import loaders + ctypes signatures for the native libs.

No pybind11 in the image, so the binding layer is ctypes over a plain C
ABI. Each .so is compiled lazily with g++ and cached under ``_build/``,
named by a hash of its source and build command — a library built from
other source (a stale ``_build/`` riding along in a copied tree, where
mtimes say nothing) is simply never found. Environments without a
toolchain (or without a lib's link dependencies) get ``lib() -> None``
for that library and the pure-Python fallbacks take over — the staging
ring (csrc/sdl_bridge.cc) and the image decoder (csrc/sdl_decode.cc,
links libjpeg/libpng) fail independently. Callers that must not fall
back (``chip_smoke.py``) assert :func:`available` /
:func:`decode_available` themselves.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Callable, Sequence

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")


class NativeLib:
    """One lazily-built native library: compile, cache, declare, fall back."""

    def __init__(self, name: str, source: str,
                 declare: Callable[[ctypes.CDLL], ctypes.CDLL],
                 link_flags: Sequence[str] = ()):
        self._name = name
        self._src = os.path.join(_HERE, "csrc", source)
        self._declare = declare
        self._link_flags = list(link_flags)
        self._lock = threading.Lock()
        self._lib: ctypes.CDLL | None = None
        self._tried = False
        #: True once THIS process compiled the library (vs. found the
        #: hash-named build of the same source already cached).
        self.built_here = False
        #: the hash-named file the library was loaded from (None: not loaded)
        self.path: "str | None" = None

    def _command(self, out: str, src: str) -> "list[str]":
        # libraries after the source: the linker resolves left to right
        return [
            os.environ.get("CXX", "g++"),
            "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread",
            "-o", out, src, *self._link_flags,
        ]

    def so_path(self) -> str:
        """``_build/lib<name>-<hash>.so``: the hash covers the source
        bytes, the compiler and its flags — what the build is made
        from, not where the checkout lives — so the name identifies
        the build."""
        h = hashlib.sha256()
        with open(self._src, "rb") as f:
            h.update(f.read())
        h.update("\0".join(self._command("", "")).encode())
        return os.path.join(
            _BUILD_DIR, f"lib{self._name}-{h.hexdigest()[:16]}.so")

    def _compile(self, so: str) -> bool:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # per-process tmp name: concurrent first imports (several executor
        # processes on one host) must not write through the same tmp inode;
        # whichever os.replace lands last wins, both are valid builds.
        tmp = f"{so}.tmp.{os.getpid()}"
        try:
            subprocess.run(self._command(tmp, self._src), check=True,
                           capture_output=True, timeout=120)
            os.replace(tmp, so)  # atomic publish
            self.built_here = True
            return True
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            logger.warning(
                "%s native build failed (%s); using pure-Python fallback. %s",
                self._name, e, detail.decode(errors="replace")[:500],
            )
            return False

    def lib(self) -> ctypes.CDLL | None:
        """The loaded library, building it if needed; None if unavailable."""
        if self._lib is not None or self._tried:
            return self._lib
        with self._lock:
            if self._lib is not None or self._tried:
                return self._lib
            self._tried = True
            if os.environ.get("SPARKDL_TPU_DISABLE_NATIVE"):
                logger.info(
                    "%s disabled via SPARKDL_TPU_DISABLE_NATIVE", self._name
                )
                return None
            try:
                so = self.so_path()
            except OSError as e:
                logger.warning("%s source unreadable (%s); using "
                               "pure-Python fallback", self._name, e)
                return None
            if not os.path.exists(so) and not self._compile(so):
                return None
            try:
                self._lib = self._declare(ctypes.CDLL(so))
                self.path = so
            except (OSError, AttributeError) as e:
                # a truncated or foreign file under the right name: fall
                # back to pure Python instead of erroring in every batch
                # assembly
                logger.warning("could not load %s: %s", so, e)
                self._lib = None
            return self._lib

    def available(self) -> bool:
        return self.lib() is not None


def _declare_bridge(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.sdl_ring_create.restype = c.c_void_p
    lib.sdl_ring_create.argtypes = [c.c_uint64, c.c_uint32]
    lib.sdl_ring_destroy.argtypes = [c.c_void_p]
    lib.sdl_ring_slot_bytes.restype = c.c_uint64
    lib.sdl_ring_slot_bytes.argtypes = [c.c_void_p]
    lib.sdl_ring_n_slots.restype = c.c_uint32
    lib.sdl_ring_n_slots.argtypes = [c.c_void_p]
    lib.sdl_ring_slot_ptr.restype = c.POINTER(c.c_uint8)
    lib.sdl_ring_slot_ptr.argtypes = [c.c_void_p, c.c_uint32]
    lib.sdl_ring_acquire_write.restype = c.c_int64
    lib.sdl_ring_acquire_write.argtypes = [c.c_void_p, c.c_double]
    lib.sdl_ring_commit_write.argtypes = [c.c_void_p, c.c_uint32, c.c_uint64, c.c_uint64]
    lib.sdl_ring_abort_write.argtypes = [c.c_void_p, c.c_uint32]
    lib.sdl_ring_acquire_read.restype = c.c_int64
    lib.sdl_ring_acquire_read.argtypes = [c.c_void_p, c.c_double]
    lib.sdl_ring_slot_rows.restype = c.c_uint64
    lib.sdl_ring_slot_rows.argtypes = [c.c_void_p, c.c_uint32]
    lib.sdl_ring_slot_used.restype = c.c_uint64
    lib.sdl_ring_slot_used.argtypes = [c.c_void_p, c.c_uint32]
    lib.sdl_ring_release_read.argtypes = [c.c_void_p, c.c_uint32]
    lib.sdl_ring_close.argtypes = [c.c_void_p]
    lib.sdl_ring_closed.restype = c.c_int
    lib.sdl_ring_closed.argtypes = [c.c_void_p]
    lib.sdl_pack_rows.argtypes = [
        c.POINTER(c.c_uint8), c.POINTER(c.POINTER(c.c_uint8)),
        c.POINTER(c.c_uint64), c.c_uint64, c.c_uint64, c.c_uint64,
        c.c_uint64, c.c_uint32,
    ]
    lib.sdl_u8_to_f32.argtypes = [
        c.POINTER(c.c_float), c.POINTER(c.c_uint8), c.c_uint64,
        c.c_float, c.c_float, c.c_uint32,
    ]
    return lib


def _declare_decode(lib: ctypes.CDLL) -> ctypes.CDLL:
    c = ctypes
    lib.sdl_image_info.restype = c.c_int32
    lib.sdl_image_info.argtypes = [
        c.POINTER(c.c_uint8), c.c_uint64,
        c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.POINTER(c.c_int32),
    ]
    lib.sdl_decode_resize.restype = c.c_int32
    lib.sdl_decode_resize.argtypes = [
        c.POINTER(c.c_uint8), c.c_uint64, c.c_int32, c.c_int32,
        c.POINTER(c.c_uint8),
    ]
    lib.sdl_decode_resize_batch.restype = None
    lib.sdl_decode_resize_batch.argtypes = [
        c.c_uint64, c.POINTER(c.POINTER(c.c_uint8)),
        c.POINTER(c.c_uint64), c.c_int32, c.c_int32,
        c.POINTER(c.c_uint8), c.c_int32, c.POINTER(c.c_int32),
    ]
    return lib


_BRIDGE = NativeLib("sdlbridge", "sdl_bridge.cc", _declare_bridge)
_DECODE = NativeLib("sdldecode", "sdl_decode.cc", _declare_decode,
                    link_flags=("-ljpeg", "-lpng"))


def lib() -> ctypes.CDLL | None:
    """The staging-bridge library (back-compat name)."""
    return _BRIDGE.lib()


def available() -> bool:
    return _BRIDGE.available()


def decode_lib() -> ctypes.CDLL | None:
    return _DECODE.lib()


def decode_available() -> bool:
    return _DECODE.available()


def build_report() -> "dict[str, dict]":
    """Per library: loaded?, the hash-named file, and whether this
    process compiled it (what ``chip_smoke.py`` prints)."""
    return {
        n._name: {"loaded": n.available(), "path": n.path,
                  "built_here": n.built_here}
        for n in (_BRIDGE, _DECODE)
    }
