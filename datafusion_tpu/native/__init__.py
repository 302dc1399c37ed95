"""ctypes bindings for the C++ native runtime (`native/`).

The reference engine is fully native (Rust); the rebuild's host-side
runtime components are C++ with a C ABI, loaded here via ctypes
(pybind11 is not available in this environment).  Everything degrades
gracefully: when the shared library is absent and cannot be built, the
engine falls back to the pyarrow-backed readers.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
# search order: a library shipped INSIDE the installed package (wheel
# builds copy it here — scripts/release.sh), then the repo-root
# native/ build tree (development checkouts)
_PKG_LIB = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "libdatafusion_native.so"
)
_LIB_PATH = (
    _PKG_LIB
    if os.path.exists(_PKG_LIB)
    else os.path.join(_NATIVE_DIR, "libdatafusion_native.so")
)

_lib = None
_load_failed = False


def _configure(lib) -> None:
    lib.dtf_csv_open.restype = ctypes.c_void_p
    lib.dtf_csv_open.argtypes = [
        ctypes.c_char_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8),
    ]
    lib.dtf_csv_error.restype = ctypes.c_char_p
    lib.dtf_csv_error.argtypes = [ctypes.c_void_p]
    lib.dtf_csv_next.restype = ctypes.c_int64
    lib.dtf_csv_next.argtypes = [ctypes.c_void_p]
    lib.dtf_csv_col_data.restype = ctypes.c_void_p
    lib.dtf_csv_col_data.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.dtf_csv_col_validity.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.dtf_csv_col_validity.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.dtf_csv_dict_size.restype = ctypes.c_int32
    lib.dtf_csv_dict_size.argtypes = [ctypes.c_void_p, ctypes.c_int32]
    lib.dtf_csv_dict_value.restype = ctypes.c_void_p
    lib.dtf_csv_dict_value.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.dtf_csv_close.restype = None
    lib.dtf_csv_close.argtypes = [ctypes.c_void_p]
    # SQL front-end + plan IR (native/sql_frontend.cpp).  restype is
    # c_void_p (not c_char_p) so the malloc'd pointer survives for
    # string_at + dtf_free instead of being auto-converted and leaked.
    for fn in ("dtf_parse_sql", "dtf_plan_roundtrip", "dtf_plan_repr"):
        f = getattr(lib, fn)
        f.restype = ctypes.c_void_p
        f.argtypes = [ctypes.c_char_p]
    lib.dtf_free.restype = None
    lib.dtf_free.argtypes = [ctypes.c_void_p]


def build_library() -> bool:
    """Compile the shared library (idempotent); True on success."""
    srcs = [
        os.path.join(_NATIVE_DIR, f)
        for f in ("datafusion_native.cpp", "sql_frontend.cpp")
        if os.path.exists(os.path.join(_NATIVE_DIR, f))
    ]
    if not srcs:
        return False

    def built() -> bool:
        return os.path.exists(_LIB_PATH) and os.path.getmtime(
            _LIB_PATH) >= max(os.path.getmtime(s) for s in srcs)

    if built():
        return True
    import fcntl

    try:
        # processes that ask at once (a test run's workers) take turns:
        # one builds, the others wait and find the library made (the
        # Makefile renames it into place whole, so a reader never loads
        # a half-written one)
        with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if built():
                return True
            subprocess.run(
                ["make", "-C", _NATIVE_DIR], check=True,
                capture_output=True, timeout=120,
            )
            return os.path.exists(_LIB_PATH)
    except (OSError, subprocess.SubprocessError):
        # no make / compiler missing / build error or timeout: callers
        # fall back to the pure-Python front-end
        return False


def load_library(build: bool = True):
    """The loaded ctypes library, or None when unavailable.

    Disable entirely with DATAFUSION_TPU_NATIVE=0.
    """
    global _lib, _load_failed
    if os.environ.get("DATAFUSION_TPU_NATIVE", "1") == "0":
        return None
    if _lib is not None or _load_failed:
        return _lib
    if build:
        # always consult the build (idempotent mtime check): a stale .so
        # from an older source set would otherwise load but fail symbol
        # configuration and silently disable every native component
        build_library()
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        _configure(lib)
        _lib = lib
    except (OSError, AttributeError):
        # missing .so, or a stale build missing symbols: fall back to
        # the pyarrow readers rather than crashing datasource setup
        _load_failed = True
    return _lib


def native_available() -> bool:
    return load_library() is not None
