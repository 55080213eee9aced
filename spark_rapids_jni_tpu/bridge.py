"""Python side of the embedded-runtime device bridge.

``native/device_bridge.cpp`` forwards host table handles here when the
process hosts a CPython runtime; this module reads the table through
libsrjt's own C accessors, runs the JAX device engine, and imports the
result back through the same C ABI — completing the JNI→device path the
reference gets from ``RowConversionJni.cpp:24-45`` driving CUDA directly.

Every function returns a raw handle as ``int`` (0 = failure); exceptions
never cross the C boundary.  On 0 the C++ caller takes the host engine, so
the exception is logged here first and ``bridge.null.<to|from>`` ticks: a
device path that silently stopped serving must be visible to whoever reads
the process's stderr or its counters.

A call is one ``bridge.call`` span (``direction`` ``to`` | ``from``) with the
same leaves both ways: ``bridge.marshal_in`` (handle → host arrays ready to
upload), ``bridge.h2d`` (the upload and its wait), the engine's own spans,
``bridge.d2h`` (the wait for the device and the download),
``bridge.marshal_out`` (host arrays → the handle that goes back).  The
caller's handle outlives the call, so fixed-width buffers are read in place
and every upload is waited for before anything else runs: no transfer reads
the handle's memory after the call has returned.

Ownership of what ``to`` returns: the rows handle ADOPTS the arrays the
download landed in (``srjt_rows_adopt*``), no copy.  They stay in
``_held`` under a token passed as the release context; the library calls
``_release`` exactly once a batch, on whichever thread frees the handle
(ctypes, or the JVM's ``RowConversion.freeRows``), and only then is the
entry dropped.  A rejected adopt never calls it: the arrays stay ours, and
the entry goes at once.  ``bridge.adopted`` − ``bridge.released`` is the
count of batches live handles hold.
"""

from __future__ import annotations

import ctypes as C
import itertools
import logging

import numpy as np

from . import types as T
from .column import Column, Table
from .rowconv import convert_from_rows, convert_to_rows
from .rowconv.convert import RowBatch
from .utils import metrics

_log = logging.getLogger(__name__)

# token -> (data, offsets) of every batch a live rows handle has adopted
_held: dict[int, tuple[np.ndarray, np.ndarray]] = {}
_tokens = itertools.count(1)


def _make_release():
    from . import native
    held, count = _held, metrics.count

    def release(ctx):
        # the interpreter lock is ctypes' to take; the names are this
        # closure's, so it also runs while the modules are torn down at
        # exit (a caller's ``__del__`` freeing its handles)
        held.pop(ctx, None)
        count("bridge.released")
    fn = native.RELEASE_FN(release)
    # the library holds the bare function pointer for as long as any handle
    # lives, which may be past this module's teardown: never freed
    C.pythonapi.Py_IncRef(C.py_object(fn))
    return fn


_release = _make_release()


def _load() -> C.CDLL:
    # single shared binding site for the whole libsrjt C ABI
    from . import native
    lib = native.load()
    if lib is None:
        raise OSError("libsrjt.so unavailable")
    return lib


def _view(ptr, n, dtype=np.uint8) -> np.ndarray:
    """The ``n`` elements at ``ptr`` in place: valid while the handle is."""
    if not ptr or n == 0:
        return np.zeros(0, dtype)
    return np.ctypeslib.as_array(ptr, shape=(n,))


def _dtype_of(tid: int, scale: int) -> T.DType:
    tid = T.TypeId(int(tid))
    return T.DType(tid, int(scale) if tid in (T.TypeId.DECIMAL32,
                                              T.TypeId.DECIMAL64) else 0)


def _nbytes(arrays) -> int:
    return int(sum(a.nbytes for a in arrays))


def _upload(leaves: list) -> list:
    """Host arrays → device arrays, one ``device_put`` for all of them, and
    waited for: the handle's memory is not read after this returns.

    ``leaves`` may be views of a handle's own memory, and on the CPU backend
    an aligned array is aliased, not copied: no device array made here may
    outlive the call that made it.  Each caller drops its ``Table`` /
    ``RowBatch`` before it returns, and nothing may keep one behind its back
    (``hostcache``, a memo keyed on these arrays)."""
    import jax
    nbytes = _nbytes(leaves)
    with metrics.span("bridge.h2d", bytes=nbytes, transfers=len(leaves)):
        on_device = jax.block_until_ready(jax.device_put(leaves))
    metrics.count("bridge.bytes.h2d", nbytes)
    metrics.annotate(bytes_in=nbytes)                # on ``bridge.call``
    return on_device


def _download(leaves: list) -> list[np.ndarray]:
    """Device arrays → host arrays: every copy started, then each waited
    for, so the transfers queue behind the program instead of each paying
    a round trip of its own."""
    with metrics.span("bridge.d2h", transfers=len(leaves)) as sp:
        for a in leaves:
            a.copy_to_host_async()
        host = [np.asarray(a) for a in leaves]
        # a 2-D leaf can come back F-ordered; a bool leaf is one byte a row
        host = [np.ascontiguousarray(a).view(np.uint8)
                if a.dtype == np.bool_ else np.ascontiguousarray(a)
                for a in host]
        nbytes = _nbytes(host)
        if sp is not None:
            sp.annotate(bytes=nbytes)
    metrics.count("bridge.bytes.d2h", nbytes)
    metrics.annotate(bytes_out=nbytes)               # on ``bridge.call``
    return host


def _table_from_handle(lib, handle: int) -> Table:
    t = C.c_void_p(handle)
    n = lib.srjt_table_rows(t)
    dtypes, leaves, copied = [], [], 0
    with metrics.span("bridge.marshal_in") as sp:
        for i in range(lib.srjt_table_cols(t)):
            # srjt_table_column returns a NEW shared handle, freed once the
            # buffers are in hand; the table keeps the column alive
            h = C.c_void_p(lib.srjt_table_column(t, i))
            dt = _dtype_of(lib.srjt_column_type(h), lib.srjt_column_scale(h))
            validity = None
            vptr = lib.srjt_column_valid(h)
            if vptr:
                # one byte a row, any non-zero byte valid: made bools here
                v = _view(vptr, n) != 0
                copied += v.nbytes
                validity = None if v.all() else v
            data = _view(lib.srjt_column_data(h),
                         lib.srjt_column_data_size(h))
            if dt.is_variable_width:
                # strings cross the bridge in no cell yet: their buffers are
                # copied out as before, until a reading says what that costs
                offs = _view(lib.srjt_column_offsets(h), n + 1,
                             np.int32).copy()
                data = data.copy()
                copied += offs.nbytes + data.nbytes
                leaves.append([data, offs, validity])
            else:
                data = data.view(dt.storage)
                if dt.id == T.TypeId.FLOAT64:
                    from .utils import f64bits
                    data = f64bits.np_to_bits(data)   # exact host-side view
                leaves.append([data, None, validity])
            dtypes.append(dt)
            lib.srjt_column_free(h)
        flat = [a for col in leaves for a in col if a is not None]
        if sp is not None:
            sp.annotate(bytes=_nbytes(flat), copied_bytes=copied)
    metrics.count("bridge.host_copied_bytes", copied)
    on_device = iter(_upload(flat))
    cols = []
    for dt, col in zip(dtypes, leaves):
        data, offs, validity = (None if a is None else next(on_device)
                                for a in col)
        cols.append(Column(dt, data, offs, validity))
    return Table(cols)


def _to_rows(lib, table_handle: int) -> int:
    table = _table_from_handle(lib, table_handle)
    batches = convert_to_rows(table)
    host = _download([leaf for b in batches for leaf in (b.data, b.offsets)])
    shape = dict(rows=table.num_rows, cols=len(table.columns),
                 batches=len(batches))
    del table, batches               # the device's copies go before the handle is made
    out = None
    try:
        with metrics.span("bridge.marshal_out") as sp:
            adopted = copied = 0
            for data, offs in zip(host[0::2], host[1::2]):
                data = data.view(np.uint8)
                as_i32 = np.ascontiguousarray(offs, dtype=np.int32)
                if as_i32 is not offs:
                    copied += as_i32.nbytes
                got = _adopt(lib, out, data, as_i32)
                if not got:
                    return 0                 # a partial set is freed below
                out = got
                adopted += data.nbytes + as_i32.nbytes
            if sp is not None:
                sp.annotate(bytes=adopted, copied_bytes=copied)
        metrics.count("bridge.host_copied_bytes", copied)
        metrics.count("bridge.adopted_bytes", adopted)
        metrics.annotate(**shape)
        result, out = int(out or 0), None    # ownership passes to caller
        return result
    finally:
        if out is not None:
            lib.srjt_rows_free(out)          # don't leak a partial batch set


def _adopt(lib, out, data: np.ndarray, offs: np.ndarray) -> int:
    """``data`` and ``offs`` as one more batch of ``out`` (a new handle when
    ``out`` is None), taken over without a copy: the handle, or 0 where the
    library rejected them, and they stay ours."""
    token = next(_tokens)
    _held[token] = (data, offs)
    args = (data.ctypes.data_as(C.c_void_p), data.size,
            offs.ctypes.data_as(C.c_void_p), offs.shape[0] - 1, _release,
            token)
    got = 0
    try:
        if out is None:
            got = lib.srjt_rows_adopt(*args) or 0
        elif lib.srjt_rows_adopt_append(out, *args):
            got = out
    finally:
        if not got:
            _held.pop(token, None)
    if got:
        metrics.count("bridge.adopted")
    return got


def _from_rows(lib, rows_handle: int, type_ids_ptr: int, scales_ptr: int,
               ncols: int) -> int:
    h = C.c_void_p(rows_handle)
    if lib.srjt_rows_num_batches(h) < 1:
        return 0
    with metrics.span("bridge.marshal_in") as sp:
        tids = np.ctypeslib.as_array(
            (C.c_int32 * ncols).from_address(type_ids_ptr))
        scales = (np.ctypeslib.as_array(
            (C.c_int32 * ncols).from_address(scales_ptr))
            if scales_ptr else np.zeros(ncols, np.int32))
        schema = [_dtype_of(t, s) for t, s in zip(tids, scales)]
        nrows = lib.srjt_rows_batch_rows(h, 0)
        data = _view(lib.srjt_rows_batch_data(h, 0),
                     lib.srjt_rows_batch_size(h, 0))
        offs = _view(lib.srjt_rows_batch_offsets(h, 0), nrows + 1, np.int32)
        copied = 0
        if any(dt.is_variable_width for dt in schema):
            # a batch with strings: copied out as before (see above)
            data, offs = data.copy(), offs.copy()
            copied = data.nbytes + offs.nbytes
        elif data.size % 4 == 0 and data.ctypes.data % 4 == 0:
            # fixed-width rows are 8-byte aligned: the uint32 words the
            # fixed engine decodes, in place
            data = data.view(np.uint32)
        if sp is not None:
            sp.annotate(bytes=data.nbytes + offs.nbytes, copied_bytes=copied)
    metrics.count("bridge.host_copied_bytes", copied)
    batch = RowBatch(*_upload([data, offs]))
    table = convert_from_rows(batch, schema)

    leaves, specs = [], []
    for col in table.columns:
        leaves += [a for a in (col.data, col.offsets, col.validity)
                   if a is not None]
        specs.append((col.dtype, col.num_rows, col.validity is not None))
    host = iter(_download(leaves))
    del batch, table, leaves         # the device's copies go before the host's are made
    handles: list = []
    try:
        with metrics.span("bridge.marshal_out") as sp:
            copied = 0
            for dt, n, has_validity in specs:
                raw = next(host)
                o = (np.ascontiguousarray(next(host), dtype=np.int32)
                     if dt.is_variable_width else None)
                valid = next(host) if has_validity else None
                valid_ptr = (None if valid is None
                             else valid.ctypes.data_as(C.c_void_p))
                if o is not None:
                    ch = lib.srjt_column_string(
                        n, o.ctypes.data_as(C.c_void_p),
                        raw.ctypes.data_as(C.c_void_p), valid_ptr)
                else:
                    ch = lib.srjt_column_fixed(
                        int(dt.id), dt.scale, n,
                        raw.ctypes.data_as(C.c_void_p), valid_ptr)
                if not ch:
                    return 0
                handles.append(ch)
                copied += sum(a.nbytes for a in (raw, o, valid)
                              if a is not None)
            arr = (C.c_void_p * len(handles))(*handles)
            out = lib.srjt_table(arr, len(handles))
            if sp is not None:
                sp.annotate(bytes=copied, copied_bytes=copied)
        metrics.count("bridge.host_copied_bytes", copied)
        metrics.annotate(rows=nrows, cols=ncols,
                         batches=lib.srjt_rows_num_batches(h))
        return int(out or 0)
    finally:
        # the table shares the columns; these handles were the way in (and
        # on a failure, all there is to free)
        for hh in handles:
            lib.srjt_column_free(hh)


def _serve(direction: str, fn, *args) -> int:
    """One call through ``fn`` under its ``bridge.call`` span; 0 and a
    logged exception on any failure, counted either way."""
    out = 0
    try:
        with metrics.span("bridge.call", direction=direction):
            out = fn(_load(), *args)
    except Exception:
        _log.exception("%s_rows_from_handle: device engine failed; the "
                       "caller falls back to the host engine", direction)
    metrics.count(f"bridge.calls.{direction}")
    if not out:
        metrics.count(f"bridge.null.{direction}")
    return out


def to_rows_from_handle(table_handle: int) -> int:
    """Host table handle → RowBatches handle via the DEVICE engine."""
    return _serve("to", _to_rows, table_handle)


def from_rows_from_handle(rows_handle: int, type_ids_ptr: int,
                          scales_ptr: int, ncols: int) -> int:
    """RowBatches handle + schema arrays → host table handle via the
    DEVICE engine (batch 0, matching the one-batch contract; the JNI
    wrapper sends any other batch to the host engine)."""
    return _serve("from", _from_rows, rows_handle, type_ids_ptr, scales_ptr,
                  ncols)
