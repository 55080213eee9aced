"""Loader for the single native artifact ``libsrjt.so``.

All C++ components (Parquet footer engine, host JCUDF transcode engine) are
compiled into one shared library, preserving the reference's packaging
invariant of a single JVM-loadable artifact (``CMakeLists.txt:199-208``).
Built lazily with ``make`` on first use; callers degrade gracefully when no
toolchain is present.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

from ..analysis import sanitize

_NATIVE_DIR = os.path.dirname(__file__)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libsrjt.so")
_lock = sanitize.tracked_lock("native.load")
_lib: Optional[ctypes.CDLL] = None
_tried = False
build_error: Optional[str] = None   # why the last make failed, if it did

_c = ctypes

# ``void (*release)(void* ctx)`` of srjt_rows_adopt*: a ctypes callback of
# this type takes the interpreter lock itself, on whichever thread calls it
RELEASE_FN = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


def _build() -> bool:
    """``make`` the library, one builder at a time: test workers and serving
    processes start together on a fresh checkout, and concurrent makes in
    one directory link each other's half-written objects.  The lock is an
    flock on the Makefile itself, so nothing new appears in the tree."""
    global build_error
    import fcntl
    try:
        with open(os.path.join(_NATIVE_DIR, "Makefile")) as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            subprocess.run(["make", "-C", _NATIVE_DIR, "-s"], check=True,
                           capture_output=True, timeout=300)
        return True
    except subprocess.CalledProcessError as e:
        build_error = e.stderr.decode(errors="replace")[-2000:]
    except (subprocess.SubprocessError, OSError) as e:
        build_error = repr(e)
    import warnings
    warnings.warn(f"libsrjt.so build failed, native paths degrade to "
                  f"Python: {build_error}", RuntimeWarning)
    return False


def _sig(lib, name, restype, argtypes):
    fn = getattr(lib, name)
    fn.restype = restype
    fn.argtypes = argtypes
    return fn


def _bind(lib: ctypes.CDLL) -> None:
    i32, i64, u64 = _c.c_int32, _c.c_int64, _c.c_uint64
    p_i32 = _c.POINTER(i32)
    p_i64 = _c.POINTER(i64)
    p_u8 = _c.POINTER(_c.c_uint8)
    pp = _c.POINTER(_c.c_void_p)   # generic pointer-array

    # footer engine (parquet/native/footer_engine.cpp)
    _sig(lib, "srjt_footer_read_and_filter", _c.c_void_p,
         [_c.c_char_p, u64, i64, i64, _c.POINTER(_c.c_char_p), p_i32, p_i32,
          i32, i32, i32, _c.c_char_p, u64])
    _sig(lib, "srjt_footer_num_rows", i64, [_c.c_void_p])
    _sig(lib, "srjt_footer_num_columns", i64, [_c.c_void_p])
    _sig(lib, "srjt_footer_serialize", i64,
         [_c.c_void_p, _c.c_char_p, u64, _c.c_char_p, u64])
    _sig(lib, "srjt_footer_free", None, [_c.c_void_p])

    # rowconv engine (native/rowconv_engine.cpp)
    _sig(lib, "srjt_layout", i32,
         [p_i32, p_i32, i32, p_i32, p_i32, p_i32, p_i32])
    _sig(lib, "srjt_pack_fixed", None,
         [pp, pp, p_i32, p_i32, i32, i64, i32, i32, p_u8])
    _sig(lib, "srjt_unpack_fixed", None,
         [p_u8, i64, i32, p_i32, p_i32, i32, i32, pp, pp])
    _sig(lib, "srjt_var_row_offsets", i64, [pp, i32, i64, i32, p_i64])
    _sig(lib, "srjt_pack_var", None,
         [pp, pp, pp, p_i32, p_i32, p_u8, i32, i64, p_i64, i32, i32, p_u8])
    _sig(lib, "srjt_unpack_var", None,
         [p_u8, p_i64, i64, p_i32, p_i32, p_u8, i32, i32, pp, pp, pp])
    _sig(lib, "srjt_gather_chars", None,
         [p_u8, p_i64, i64, i32, p_i32, p_u8])

    # host table / column ABI (native/host_table.cpp) — the single binding
    # site shared by bridge.py and the test suites; keep in sync with
    # cpp declarations in jni_min.h/host_table.cpp
    vp = _c.c_void_p
    _sig(lib, "srjt_column_fixed", vp, [i32, i32, i64, vp, vp])
    _sig(lib, "srjt_column_string", vp, [i64, vp, vp, vp])
    _sig(lib, "srjt_column_free", None, [vp])
    _sig(lib, "srjt_column_type", i32, [vp])
    _sig(lib, "srjt_column_scale", i32, [vp])
    _sig(lib, "srjt_column_rows", i64, [vp])
    _sig(lib, "srjt_column_data", p_u8, [vp])
    _sig(lib, "srjt_column_data_size", i64, [vp])
    _sig(lib, "srjt_column_offsets", p_i32, [vp])
    _sig(lib, "srjt_column_valid", p_u8, [vp])
    _sig(lib, "srjt_table", vp, [pp, i32])
    _sig(lib, "srjt_table_free", None, [vp])
    _sig(lib, "srjt_table_rows", i64, [vp])
    _sig(lib, "srjt_table_cols", i32, [vp])
    _sig(lib, "srjt_table_column", vp, [vp, i32])
    _sig(lib, "srjt_to_rows", vp, [vp])
    # pointer args typed c_void_p: call sites pass numpy .ctypes pointers
    _sig(lib, "srjt_from_rows", vp, [vp, i32, vp, vp, i32])
    _sig(lib, "srjt_debug_set_max_batch_bytes", None, [i64])
    _sig(lib, "srjt_rows_import", vp, [vp, i64, vp, i64])
    _sig(lib, "srjt_rows_import_append", i32, [vp, vp, i64, vp, i64])
    # adopt: the handle takes the buffers over and hands them back through
    # ``release(ctx)`` once, when it is freed (RELEASE_FN: the callback type)
    _sig(lib, "srjt_rows_adopt", vp, [vp, i64, vp, i64, RELEASE_FN, vp])
    _sig(lib, "srjt_rows_adopt_append", i32,
         [vp, vp, i64, vp, i64, RELEASE_FN, vp])
    _sig(lib, "srjt_rows_free", None, [vp])
    _sig(lib, "srjt_rows_num_batches", i32, [vp])
    _sig(lib, "srjt_rows_batch_rows", i64, [vp, i32])
    _sig(lib, "srjt_rows_batch_data", p_u8, [vp, i32])
    _sig(lib, "srjt_rows_batch_size", i64, [vp, i32])
    _sig(lib, "srjt_rows_batch_offsets", p_i32, [vp, i32])

    # device bridge (native/device_bridge.cpp)
    _sig(lib, "srjt_device_available", i32, [])
    _sig(lib, "srjt_to_rows_device", vp, [vp])
    _sig(lib, "srjt_from_rows_device", vp, [vp, vp, vp, i32])

    # snappy (native/snappy_native.cpp)
    _sig(lib, "srjt_snappy_decompress", _c.c_long,
         [_c.c_char_p, _c.c_long, _c.c_char_p, _c.c_long])
    _sig(lib, "srjt_byte_array_offsets", _c.c_long,
         [_c.c_char_p, _c.c_long, _c.c_long, vp])


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) libsrjt.so; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_LIB_PATH) and not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            # another process may have been mid-link: wait for its make
            # (the lock in _build) and open what it finished
            if not _build():
                return None
            try:
                lib = ctypes.CDLL(_LIB_PATH)
            except OSError:
                return None
        try:
            _bind(lib)
        except AttributeError:
            # stale .so predating a newly-bound symbol: rebuild once and
            # retry — crashing every native consumer is not an option.
            # dlopen caches by pathname, so the stale mapping must be
            # dlclosed first or the retry would rebind the old object.
            try:
                ctypes.CDLL(None).dlclose(ctypes.c_void_p(lib._handle))
            except (OSError, AttributeError):
                return None          # cannot unload — stay unavailable
            del lib
            if not _build():
                return None
            try:
                lib = ctypes.CDLL(_LIB_PATH)
                _bind(lib)
            except (OSError, AttributeError):
                return None
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None
