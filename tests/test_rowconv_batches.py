"""A fixed-width table that takes more than one row batch: the batch's rows
are cut inside its program (one launch a batch, nothing else on the device),
the boundaries are the reference's, and nothing breaks at a batch just under
2**31 bytes.  The cell that runs this at full size is chipbench's
``fixed212_roundtrip``."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_jni_tpu as sr
from spark_rapids_jni_tpu import Column, Table, convert_from_rows, convert_to_rows
from spark_rapids_jni_tpu.rowconv import convert, reference as ref
from spark_rapids_jni_tpu.rowconv.layout import (MAX_BATCH_BYTES,
                                                 compute_row_layout)
from spark_rapids_jni_tpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import (datagen, guards, references,  # noqa: E402
                       references_batches)

ROW = 1160            # the 212-column table's JCUDF row
N = 1000


def _table(columns):
    return Table([Column.from_numpy(values, getattr(sr, name), valid)
                  for name, values, valid in columns])


@pytest.fixture(scope="module")
def wide():
    columns = datagen.nvbench_columns(N, 212, 34)
    table = _table(columns)
    oracle, _ = ref.to_rows_np(table)
    plain = references.pack_rows_fixed(columns).reshape(-1)
    # the program's oracle and the benchmark's plain packer are two
    # independent writings of the format
    np.testing.assert_array_equal(oracle, plain)
    return columns, table, oracle


@pytest.mark.parametrize("cap", [
    64 << 10,            # 56 rows fit: 32 a batch
    200 << 10,           # 176 fit: 160 a batch
    64 * ROW,            # exactly 64 rows: 64 a batch
    64 * ROW - 1,        # one byte under: 63 fit, 32 a batch
    500 * ROW,           # 500 fit: 480, then the 520 left do not fit: 480, 40
    N * ROW,             # the whole table fits exactly: one batch
], ids=["64KiB", "200KiB", "64rows", "64rows-1", "500rows", "whole"])
def test_wide_table_batches_are_the_references(wide, cap):
    columns, table, oracle = wide
    batches = convert_to_rows(table, max_batch_bytes=cap)
    bounds = references_batches.plain_batch_boundaries(ROW, N, cap)
    assert [b.num_rows for b in batches] == np.diff(bounds).tolist()
    assert all(b.num_bytes <= cap for b in batches)
    for b in batches:
        assert b.offsets.dtype == jnp.int32
        assert references_batches.offset_mismatches(
            np.asarray(b.offsets), b.num_rows, ROW) == 0
    np.testing.assert_array_equal(
        np.concatenate([b.host_bytes() for b in batches]), oracle)
    for b, lo, hi in zip(batches, bounds[:-1], bounds[1:]):
        back = convert_from_rows(b, table.schema)
        for (_, values, valid), col in zip(columns, back.columns):
            # every payload bit, null slots included, and every validity bit
            np.testing.assert_array_equal(
                np.asarray(col.data).view(np.uint8),
                values[lo:hi].view(np.uint8))
            np.testing.assert_array_equal(
                np.asarray(col.validity),
                np.ones(hi - lo, bool) if valid is None else valid[lo:hi])


def _compiles() -> float:
    """Programs JAX has handed to the backend's compiler (or fetched from
    its persistent cache) since the benchmark's guard began to count."""
    return guards.compiles()["jax.backend_compile"]


def test_a_batch_is_one_launch_and_nothing_else_on_the_device(monkeypatch):
    metrics.set_enabled(True)

    def no_slices(*a, **k):
        raise AssertionError("_slice_column reached for a fixed-width table")
    monkeypatch.setattr(convert, "_slice_column", no_slices)
    # shapes no other test has compiled: an eager slice would compile too
    n = 777
    columns = datagen.nvbench_columns(n, 21, 5)
    table = _table(columns)
    jax.block_until_ready(jax.tree_util.tree_leaves(table))
    row = compute_row_layout(table.schema).fixed_row_size
    guards.watch_xla_compiles()
    before_call = _compiles()
    batches = convert_to_rows(table, max_batch_bytes=300 * row)
    assert [b.num_rows for b in batches] == [288, 288, 201]
    # one program a batch, and no other
    assert _compiles() == before_call + 3
    root = [t for t in metrics.span_roots()
            if t["name"] == "convert_to_rows"][-1]
    spans = root["children"]
    assert [s["name"] for s in spans] == ["rowconv.fixed.prepare"] + [
        "rowconv.fixed.launch"] * 3
    assert spans[0]["attrs"] == {"batches": 3, "eager_ops": 0}
    assert [s["attrs"] for s in spans[1:]] == [
        {"direction": "to", "batch": i, "rows": r, "bytes": r * row}
        for i, r in enumerate([288, 288, 201])]
    before = metrics.counter_value("rowconv.fixed.batches.from")
    to_before = metrics.counter_value("rowconv.fixed.batches.to")
    convert_to_rows(table, max_batch_bytes=300 * row)
    assert metrics.counter_value("rowconv.fixed.batches.to") == to_before + 3
    assert _compiles() == before_call + 3    # the second compiles nothing
    back = convert_from_rows(batches[2], table.schema)
    assert metrics.counter_value("rowconv.fixed.batches.from") == before + 1
    root = [t for t in metrics.span_roots()
            if t["name"] == "convert_from_rows"][-1]
    assert [(s["name"], s["attrs"]) for s in root["children"]] == [
        ("rowconv.fixed.launch",
         {"direction": "from", "rows": 201, "bytes": 201 * row})]
    np.testing.assert_array_equal(np.asarray(back[3].data),
                                  columns[3][1][576:])


def test_eager_ops_counts_arguments_the_table_does_not_hold(monkeypatch):
    """What ``eager_ops`` reads: program arguments that are not the table's
    own resident arrays (each was made by a device op outside the batch
    programs).  A column whose payload is made anew on every access shows."""
    metrics.set_enabled(True)
    columns = datagen.nvbench_columns(64, 4, 1)
    table = _table(columns)

    class Copies(Column):
        @property
        def data(self):
            return self.__dict__["data"] + 0

        @data.setter
        def data(self, value):
            self.__dict__["data"] = value
    c = table.columns[1]
    table.columns[1] = Copies(c.dtype, c.data, validity=c.validity)
    convert_to_rows(table)
    root = [t for t in metrics.span_roots()
            if t["name"] == "convert_to_rows"][-1]
    assert root["children"][0]["attrs"]["eager_ops"] == 1


def _old_to_rows_fixed_full(layout, has_valid, datas, valids):
    """``_to_rows_fixed_full`` as it stood before it took ``lo`` and ``hi``
    (PR 33's), to compare what the whole-table call lowers to."""
    n = datas[0].shape[0]
    vi = iter(valids)
    cols_valid = [next(vi) if hv else jnp.ones((n,), dtype=jnp.bool_)
                  for hv in has_valid]
    valid = jnp.stack(cols_valid, axis=1)
    flat = convert._to_rows_fixed_words(layout, datas, valid)
    offsets = jnp.arange(n + 1, dtype=jnp.int32) * layout.fixed_row_size
    return flat, offsets


@pytest.mark.parametrize("n,lo,hi", [(1024, 128, 896), (1000, 32, 992)])
def test_whole_table_lowers_to_the_program_it_was(n, lo, hi):
    columns = datagen.nvbench_columns(n, 30, 2)
    table = _table(columns)
    layout = compute_row_layout(table.schema)
    has_valid = tuple(c.validity is not None for c in table.columns)
    datas = tuple(c.data for c in table.columns)
    valids = tuple(c.validity for c in table.columns
                   if c.validity is not None)
    old = jax.jit(_old_to_rows_fixed_full, static_argnums=(0, 1))
    old.__wrapped__.__name__ = "_to_rows_fixed_full"
    was = old.lower(layout, has_valid, datas, valids).as_text()
    now = convert._to_rows_fixed_full.lower(layout, has_valid, 0, n, datas,
                                            valids).as_text()
    assert now == was
    # and a batch of it differs by its cuts alone: one slice an argument
    cut = convert._to_rows_fixed_full.lower(layout, has_valid, lo, hi,
                                            datas, valids).as_text()
    assert cut != was
    assert (cut.count("stablehlo.slice") - was.count("stablehlo.slice")
            == len(datas) + len(valids))


def test_offsets_of_the_largest_batch_stay_int32():
    """The first batch of the cell: ``(2**31 - 1) // 1160`` rows rounded
    down to 32.  Nothing of that size is allocated here."""
    rows = MAX_BATCH_BYTES // ROW // 32 * 32
    assert rows == 1851264 and rows * ROW == 2147466240 < 2**31
    schema = [getattr(sr, name) for name in
              (datagen.NVBENCH_CYCLE[i % 9] for i in range(212))]
    layout = compute_row_layout(schema)
    assert layout.fixed_row_size == ROW
    n = 2 << 20
    datas = tuple(jax.ShapeDtypeStruct((n,), dt.storage) for dt in schema)
    has_valid = tuple(i % 3 == 0 for i in range(212))
    valids = tuple(jax.ShapeDtypeStruct((n,), jnp.bool_)
                   for hv in has_valid if hv)
    words, offsets = jax.eval_shape(
        functools.partial(convert._to_rows_fixed_full, layout, has_valid, 0,
                          rows), datas, valids)
    assert (words.shape, words.dtype) == ((rows * ROW // 4,), jnp.uint32)
    assert (offsets.shape, offsets.dtype) == ((rows + 1,), jnp.int32)
    # the program's expression, in its dtype, at the far end
    tail = np.arange(rows - 3, rows + 1, dtype=np.int32) * np.int32(ROW)
    assert tail.dtype == np.int32 and tail[-1] == 2147466240
    assert (np.diff(tail.astype(np.int64)) == ROW).all()
    batch = convert.RowBatch(words, offsets)
    assert batch.num_rows == rows and batch.num_bytes == 2147466240
    # the second batch, and the byte view's shape
    words1, offsets1 = jax.eval_shape(
        functools.partial(convert._to_rows_fixed_full, layout, has_valid,
                          rows, n), datas, valids)
    assert convert.RowBatch(words1, offsets1).num_bytes == 285230080
    u8 = jax.eval_shape(convert._words_to_bytes, words)
    assert (u8.shape, u8.dtype) == ((2147466240,), jnp.uint8)
    assert jax.eval_shape(convert._bytes_to_words, u8).shape == words.shape


@pytest.mark.parametrize("n", [0, 1, 127, 128, 1000, 4097])
def test_byte_view_round_trips_at_any_length(n):
    words = np.random.default_rng(n).integers(0, 2**32, n, dtype=np.uint32)
    as_bytes = np.asarray(convert._words_to_bytes(jnp.asarray(words)))
    assert as_bytes.dtype == np.uint8
    np.testing.assert_array_equal(as_bytes, words.view(np.uint8))
    back = np.asarray(convert._bytes_to_words(jnp.asarray(as_bytes)))
    assert back.dtype == np.uint32
    np.testing.assert_array_equal(back, words)
