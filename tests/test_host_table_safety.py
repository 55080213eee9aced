"""Safety/regression tests for the C++ host table engine (host_table.cpp).

Round-1 advisor findings: (a) ``batch_bounds`` infinite-looped when a single
row exceeded the batch byte limit instead of failing like the Python engine
(layout.build_batches raises ValueError); (b) ``srjt_rows_import`` /
``srjt_from_rows`` trusted shuffle-received bytes — offsets and row-embedded
string slots — without bounds checks, allowing out-of-bounds reads.
"""

import ctypes as C

import numpy as np
import pytest

from spark_rapids_jni_tpu import native as _native

# single shared binding site (native/__init__.py); load() builds the
# library on a fresh checkout, one process at a time
lib = _native.load()
if lib is None:
    pytest.skip(f"libsrjt.so unavailable: {_native.build_error}",
                allow_module_level=True)

INT32, STRING = 3, 24


def _np_ptr(a):
    return a.ctypes.data_as(C.c_void_p)


def _string_table(chars_per_row: int, n: int):
    """One int32 col + one string col with constant-length strings."""
    ints = np.arange(n, dtype=np.int32)
    offs = (np.arange(n + 1, dtype=np.int32) * chars_per_row)
    chars = np.full(offs[-1], ord("x"), dtype=np.uint8)
    h_int = lib.srjt_column_fixed(INT32, 0, n, _np_ptr(ints), None)
    h_str = lib.srjt_column_string(n, _np_ptr(offs), _np_ptr(chars), None)
    arr = (C.c_void_p * 2)(h_int, h_str)
    t = lib.srjt_table(arr, 2)
    lib.srjt_column_free(h_int)
    lib.srjt_column_free(h_str)
    return t


def test_oversized_row_fails_instead_of_hanging():
    lib.srjt_debug_set_max_batch_bytes(64)
    try:
        t = _string_table(chars_per_row=200, n=4)  # each row > 64B limit
        rows = lib.srjt_to_rows(t)
        assert not rows  # nullptr: conversion rejected, not an infinite loop
        lib.srjt_table_free(t)
    finally:
        lib.srjt_debug_set_max_batch_bytes(0)


def test_small_limit_still_batches_normal_rows():
    lib.srjt_debug_set_max_batch_bytes(256)
    try:
        t = _string_table(chars_per_row=8, n=64)
        rows = lib.srjt_to_rows(t)
        assert rows
        lib.srjt_rows_free(rows)
        lib.srjt_table_free(t)
    finally:
        lib.srjt_debug_set_max_batch_bytes(0)


def _import(data: np.ndarray, offsets: np.ndarray, n: int):
    return lib.srjt_rows_import(_np_ptr(data), len(data), _np_ptr(offsets), n)


@pytest.mark.parametrize("how", ["import", "adopt"])
def test_import_rejects_bad_offsets(how):
    # srjt_rows_adopt (the device bridge's hand-off) checks as import does;
    # a rejected adopt never calls its release, an accepted one once
    released = []
    release = _native.RELEASE_FN(released.append)

    def make(data, offsets, n):
        if how == "import":
            return _import(data, offsets, n)
        return lib.srjt_rows_adopt(_np_ptr(data), len(data),
                                   _np_ptr(offsets), n, release, 5)
    data = np.zeros(64, dtype=np.uint8)
    # non-monotonic
    assert not make(data, np.array([0, 40, 20, 64], dtype=np.int32), 3)
    # does not start at zero
    assert not make(data, np.array([8, 32, 64], dtype=np.int32), 2)
    # does not end at data_size
    assert not make(data, np.array([0, 32, 48], dtype=np.int32), 2)
    # negative
    assert not make(data, np.array([0, -4, 64], dtype=np.int32), 2)
    assert released == []
    # well-formed accepted
    offsets = np.array([0, 32, 64], dtype=np.int32)
    h = make(data, offsets, 2)
    assert h
    lib.srjt_rows_free(h)
    assert released == ([] if how == "import" else [5])


def _from_rows(rows_handle, type_ids):
    tids = np.asarray(type_ids, dtype=np.int32)
    return lib.srjt_from_rows(rows_handle, 0, _np_ptr(tids), None, len(tids))


def test_from_rows_rejects_short_rows():
    # schema int32+string: fixed area = 4(int)+4(pad)+8(slot)+1(validity)->24B
    data = np.zeros(16, dtype=np.uint8)  # one 16B row: too short
    h = _import(data, np.array([0, 16], dtype=np.int32), 1)
    assert h
    assert not _from_rows(h, [INT32, STRING])
    lib.srjt_rows_free(h)


def test_from_rows_rejects_out_of_row_string_slot():
    # Build a legitimate row, then corrupt the string slot to point past the
    # row's end (the shuffle-corruption case): must fail, not read OOB.
    t = _string_table(chars_per_row=8, n=1)
    rows = lib.srjt_to_rows(t)
    assert rows
    size = lib.srjt_rows_batch_size(rows, 0)
    buf = np.ctypeslib.as_array(lib.srjt_rows_batch_data(rows, 0),
                                shape=(size,)).copy()
    lib.srjt_rows_free(rows)
    lib.srjt_table_free(t)

    # round-trips clean before corruption
    offs = np.array([0, size], dtype=np.int32)
    h = _import(buf, offs, 1)
    back = _from_rows(h, [INT32, STRING])
    assert back
    lib.srjt_table_free(back)
    lib.srjt_rows_free(h)

    # The string (offset,len) slot lives at bytes 4..12 of the row for this
    # schema (int32 at 0, slot 4-aligned after it): offset at 4..8, length
    # at 8..12.  Corrupt the length to something huge:
    bad = buf.copy()
    bad[8:12] = np.frombuffer(np.int32(2**31 - 1).tobytes(), dtype=np.uint8)
    h = _import(bad, offs, 1)
    assert not _from_rows(h, [INT32, STRING])
    lib.srjt_rows_free(h)

    # corrupt the slot offset to point before the fixed area
    bad2 = buf.copy()
    bad2[4:8] = np.frombuffer(np.int32(2).tobytes(), dtype=np.uint8)
    h = _import(bad2, offs, 1)
    assert not _from_rows(h, [INT32, STRING])
    lib.srjt_rows_free(h)


def test_from_rows_rejects_overlapping_string_slots():
    """Two string columns whose slots both claim the same row tail must be
    rejected: JCUDF chars are concatenated in column order, so each slot's
    offset must equal the running cursor.  Overlap would let one crafted row
    amplify the chars allocation once per string column."""
    # schema: string + string → slots at 0..8 and 8..16, validity 16, fpv 17,
    # rows padded to 8 → 24B fixed area
    n = 1
    chars = np.frombuffer(b"abcdabcd", dtype=np.uint8).copy()
    offs = np.array([0, 4], dtype=np.int32)
    h1 = lib.srjt_column_string(n, _np_ptr(offs), _np_ptr(chars), None)
    offs2 = np.array([4, 8], dtype=np.int32) - 4
    h2 = lib.srjt_column_string(n, _np_ptr(offs2), _np_ptr(chars[4:].copy()),
                                None)
    arr = (C.c_void_p * 2)(h1, h2)
    t = lib.srjt_table(arr, 2)
    lib.srjt_column_free(h1)
    lib.srjt_column_free(h2)
    rows = lib.srjt_to_rows(t)
    assert rows
    size = lib.srjt_rows_batch_size(rows, 0)
    buf = np.ctypeslib.as_array(lib.srjt_rows_batch_data(rows, 0),
                                shape=(size,)).copy()
    lib.srjt_rows_free(rows)
    lib.srjt_table_free(t)

    offsets = np.array([0, size], dtype=np.int32)
    h = _import(buf, offsets, 1)
    back = _from_rows(h, [STRING, STRING])
    assert back                       # clean bytes round-trip
    lib.srjt_table_free(back)
    lib.srjt_rows_free(h)

    # make the SECOND slot's offset point back at the first column's chars
    bad = buf.copy()
    bad[8:12] = bad[0:4]              # slot2.offset := slot1.offset
    h = _import(bad, offsets, 1)
    assert not _from_rows(h, [STRING, STRING])
    lib.srjt_rows_free(h)
