"""Tests for the dtype system and the JCUDF row-layout calculator.

The layout expectations are the worked examples from the reference's format
spec (RowConversion.java:60-90) — computed by hand here, not copied.
"""

import numpy as np
import pytest

import spark_rapids_jni_tpu as sr
from spark_rapids_jni_tpu.rowconv import layout as L


def test_dtype_itemsize_and_alignment():
    assert sr.int8.itemsize == 1 and sr.int8.row_alignment == 1
    assert sr.int64.itemsize == 8 and sr.int64.row_alignment == 8
    assert sr.bool8.itemsize == 1
    assert sr.timestamp_days.storage == np.dtype(np.int32)
    assert sr.timestamp_ms.storage == np.dtype(np.int64)
    # string slot: 8 bytes, 4-byte aligned (row_conversion.cu:1342-1350)
    assert sr.string.itemsize == 8 and sr.string.row_alignment == 4
    assert sr.decimal32(-2).storage == np.dtype(np.int32)
    assert sr.decimal64(-4).storage == np.dtype(np.int64)


def test_dtype_scale_only_for_decimals():
    with pytest.raises(ValueError):
        sr.DType(sr.TypeId.INT32, scale=-2)


def test_layout_javadoc_example_bool_int16_int32():
    # | A_0 | P | B_0 B_1 | C_0..C_3 | V0 | P*7 |  → 16 bytes
    lay = L.compute_row_layout([sr.bool8, sr.int16, sr.int32])
    assert lay.column_starts == (0, 2, 4)
    assert lay.validity_offset == 8
    assert lay.validity_bytes == 1
    assert lay.fixed_row_size == 16


def test_layout_javadoc_example_reordered():
    # C, B, A → | C*4 | B*2 | A | V0 | = 8 bytes, no padding
    lay = L.compute_row_layout([sr.int32, sr.int16, sr.bool8])
    assert lay.column_starts == (0, 4, 6)
    assert lay.validity_offset == 7
    assert lay.fixed_row_size == 8


def test_layout_string_slot_alignment():
    # int8 at 0, string slot aligned to 4 → starts at 4, occupies 8
    lay = L.compute_row_layout([sr.int8, sr.string, sr.int64])
    assert lay.column_starts == (0, 4, 16)
    assert lay.variable_column_indices == (1,)
    assert not lay.fixed_width_only


def test_layout_validity_byte_per_8_columns():
    lay = L.compute_row_layout([sr.int8] * 9)
    assert lay.validity_bytes == 2
    assert lay.validity_offset == 9
    assert lay.fixed_row_size == 16


def test_row_size_limit_enforced():
    # no 1 KB limit on the path that stands for the reference's
    # convert_to_rows: its own benchmark's 212-column "Fixed Width Only"
    # table (1160 B rows) and 200 x int64 lay out, and round-trip equal to
    # the plain packer
    import numpy as np
    from spark_rapids_jni_tpu import (Column, Table, convert_from_rows,
                                      convert_to_rows)
    from spark_rapids_jni_tpu.rowconv import reference as ref
    cycle = [sr.int8, sr.int32, sr.int16, sr.int64, sr.int32, sr.bool8,
             sr.uint16, sr.uint8, sr.uint64]
    wide = L.compute_row_layout([cycle[i % 9] for i in range(212)])
    assert wide.fixed_row_size == 1160 and wide.fixed_width_only
    assert L.compute_row_layout([sr.int64] * 200).fixed_row_size == 1632
    rng = np.random.default_rng(212)
    n = 1000
    cols = []
    for i, dt in enumerate(wide.schema):
        st = np.dtype(dt.storage)
        values = (rng.integers(0, 2, n, dtype=np.uint8) if dt == sr.bool8
                  else rng.integers(np.iinfo(st).min // 2,
                                    np.iinfo(st).max // 2, n, dtype=st))
        cols.append(Column.from_numpy(
            values, dt, rng.random(n) < 0.9 if i % 3 == 0 else None))
    table = Table(cols)
    (batch,) = convert_to_rows(table)
    want_bytes, want_offsets = ref.to_rows_np(table)
    np.testing.assert_array_equal(batch.host_bytes(), want_bytes)
    np.testing.assert_array_equal(np.asarray(batch.offsets), want_offsets)
    back = convert_from_rows(batch, table.schema)
    for sent, came in zip(table.columns, back.columns):
        np.testing.assert_array_equal(np.asarray(came.data),
                                      np.asarray(sent.data))
        np.testing.assert_array_equal(np.asarray(came.validity_or_true()),
                                      np.asarray(sent.validity_or_true()))


def test_build_batches_single():
    b = L.build_batches(np.full(100, 16, dtype=np.int64))
    assert b.num_batches == 1
    assert b.row_boundaries == (0, 100)
    assert b.batch_bytes == (1600,)
    np.testing.assert_array_equal(
        b.row_offsets_within_batch[0], np.arange(101) * 16)


def test_build_batches_splits_on_limit_and_32_row_multiple():
    # 100 rows × 16B with a 1000-byte cap → 62-row capacity, rounded down to 32
    b = L.build_batches(np.full(100, 16, dtype=np.int64), max_batch_bytes=1000)
    assert b.row_boundaries[1] % 32 == 0
    assert all(x <= 1000 for x in b.batch_bytes)
    assert b.row_boundaries[-1] == 100
    assert sum(b.batch_bytes) == 1600


def test_build_batches_row_too_big():
    with pytest.raises(ValueError):
        L.build_batches(np.asarray([10, 2000, 10]), max_batch_bytes=1000)
