"""Python side of the embedded-runtime device bridge.

``native/device_bridge.cpp`` forwards host table handles here when the
process hosts a CPython runtime; this module reads the table through
libsrjt's own C accessors, runs the JAX device engine, and imports the
result back through the same C ABI — completing the JNI→device path the
reference gets from ``RowConversionJni.cpp:24-45`` driving CUDA directly.

Every function returns a raw handle as ``int`` (0 = failure); exceptions
never cross the C boundary.  On 0 the C++ caller takes the host engine, so
the exception is logged here first: a device path that silently stopped
serving must be visible to whoever reads the process's stderr.
"""

from __future__ import annotations

import ctypes as C
import logging

import numpy as np

from . import types as T
from .column import Column, Table
from .rowconv import convert_from_rows, convert_to_rows
from .rowconv.convert import RowBatch

_log = logging.getLogger(__name__)


def _load() -> C.CDLL:
    # single shared binding site for the whole libsrjt C ABI
    from . import native
    lib = native.load()
    if lib is None:
        raise OSError("libsrjt.so unavailable")
    return lib


def _np_from_ptr(ptr, n, ctype):
    if not ptr or n == 0:
        return np.zeros(0, dtype=np.ctypeslib.as_ctypes_type(ctype)
                        if not isinstance(ctype, type) else ctype)
    return np.ctypeslib.as_array(ptr, shape=(n,)).copy()


def _table_from_handle(lib, handle: int) -> Table:
    t = C.c_void_p(handle)
    ncols = lib.srjt_table_cols(t)
    n = lib.srjt_table_rows(t)
    cols = []
    for i in range(ncols):
        # srjt_table_column returns a NEW shared handle — freed below once
        # the payloads are copied out, or the column buffers stay pinned
        h = C.c_void_p(lib.srjt_table_column(t, i))
        tid = T.TypeId(lib.srjt_column_type(h))
        scale = lib.srjt_column_scale(h)
        dt = T.DType(tid, scale if tid in (T.TypeId.DECIMAL32,
                                           T.TypeId.DECIMAL64) else 0)
        vptr = lib.srjt_column_valid(h)
        validity = None
        if vptr:
            v = _np_from_ptr(vptr, n, np.uint8).astype(bool)
            validity = None if v.all() else v
        if dt.is_variable_width:
            offs = _np_from_ptr(lib.srjt_column_offsets(h), n + 1, np.int32)
            chars = _np_from_ptr(lib.srjt_column_data(h),
                                 lib.srjt_column_data_size(h), np.uint8)
            import jax.numpy as jnp
            cols.append(Column(dt, jnp.asarray(chars), jnp.asarray(offs),
                               None if validity is None
                               else jnp.asarray(validity)))
        else:
            raw = _np_from_ptr(lib.srjt_column_data(h),
                               lib.srjt_column_data_size(h), np.uint8)
            data = raw.view(dt.storage)
            cols.append(Column.from_numpy(data, dt, validity))
        lib.srjt_column_free(h)
    return Table(cols)


def to_rows_from_handle(table_handle: int) -> int:
    """Host table handle → RowBatches handle via the DEVICE engine."""
    out = None
    lib = None
    try:
        lib = _load()
        table = _table_from_handle(lib, table_handle)
        batches = convert_to_rows(table)
        for b in batches:
            data = np.ascontiguousarray(b.host_bytes())
            offs = np.ascontiguousarray(np.asarray(b.offsets,
                                                   dtype=np.int32))
            nrows = offs.shape[0] - 1
            if out is None:
                out = lib.srjt_rows_import(
                    data.ctypes.data_as(C.c_void_p), data.size,
                    offs.ctypes.data_as(C.c_void_p), nrows)
                if not out:
                    return 0
            else:
                if not lib.srjt_rows_import_append(
                        out, data.ctypes.data_as(C.c_void_p), data.size,
                        offs.ctypes.data_as(C.c_void_p), nrows):
                    lib.srjt_rows_free(out)
                    out = None
                    return 0
        result, out = int(out or 0), None    # ownership passes to caller
        return result
    except Exception:
        _log.exception("to_rows_from_handle: device engine failed; the "
                       "caller falls back to the host engine")
        if out is not None and lib is not None:
            lib.srjt_rows_free(out)          # don't leak a partial import
        return 0


def from_rows_from_handle(rows_handle: int, type_ids_ptr: int,
                          scales_ptr: int, ncols: int) -> int:
    """RowBatches handle + schema arrays → host table handle via the
    DEVICE engine (batch 0, matching the one-batch contract)."""
    handles: list = []
    lib = None
    try:
        import jax.numpy as jnp
        lib = _load()
        h = C.c_void_p(rows_handle)
        if lib.srjt_rows_num_batches(h) < 1:
            return 0
        tids = np.ctypeslib.as_array(
            (C.c_int32 * ncols).from_address(type_ids_ptr)).copy()
        scales = (np.ctypeslib.as_array(
            (C.c_int32 * ncols).from_address(scales_ptr)).copy()
            if scales_ptr else np.zeros(ncols, np.int32))
        schema = [T.DType(T.TypeId(int(t)),
                          int(s) if T.TypeId(int(t)) in
                          (T.TypeId.DECIMAL32, T.TypeId.DECIMAL64) else 0)
                  for t, s in zip(tids, scales)]
        size = lib.srjt_rows_batch_size(h, 0)
        nrows = lib.srjt_rows_batch_rows(h, 0)
        data = _np_from_ptr(lib.srjt_rows_batch_data(h, 0), size, np.uint8)
        offs = _np_from_ptr(lib.srjt_rows_batch_offsets(h, 0), nrows + 1,
                            np.int32)
        batch = RowBatch(jnp.asarray(data), jnp.asarray(offs))
        table = convert_from_rows(batch, schema)

        keepalive = []
        for col in table.columns:
            valid_ptr = None
            if col.validity is not None:
                v = np.ascontiguousarray(
                    np.asarray(col.validity).astype(np.uint8))
                keepalive.append(v)
                valid_ptr = v.ctypes.data_as(C.c_void_p)
            if col.dtype.is_variable_width:
                chars = np.ascontiguousarray(np.asarray(col.data))
                o = np.ascontiguousarray(np.asarray(col.offsets,
                                                    dtype=np.int32))
                keepalive += [chars, o]
                ch = lib.srjt_column_string(
                    col.num_rows, o.ctypes.data_as(C.c_void_p),
                    chars.ctypes.data_as(C.c_void_p), valid_ptr)
            else:
                raw = np.ascontiguousarray(np.asarray(col.data))
                keepalive.append(raw)
                ch = lib.srjt_column_fixed(
                    int(col.dtype.id), col.dtype.scale, col.num_rows,
                    raw.ctypes.data_as(C.c_void_p), valid_ptr)
            if not ch:
                for hh in handles:
                    lib.srjt_column_free(hh)
                return 0
            handles.append(ch)
        arr = (C.c_void_p * len(handles))(*handles)
        out = lib.srjt_table(arr, len(handles))
        for hh in handles:
            lib.srjt_column_free(hh)
        return int(out or 0)
    except Exception:
        _log.exception("from_rows_from_handle: device engine failed; the "
                       "caller falls back to the host engine")
        # free any column handles created before the failure (the to-rows
        # path has the same partial-cleanup contract)
        if lib is not None:
            for hh in handles:
                lib.srjt_column_free(hh)
        return 0
