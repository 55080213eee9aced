"""Row↔column conversion tests.

Mirrors the reference test strategy (SURVEY §4, tests/row_conversion.cpp):
- differential testing: JAX device path vs the NumPy oracle (the reference
  uses its legacy CUDA path as oracle, tests/row_conversion.cpp:49-58)
- round-trip testing: to_rows → from_rows → table equality (:204-218)
- shape/stress sweep incl. non-power-of-2 sizes (:221-437)
- type-matrix with validity patterns all/none/most/few (:546-707)
- string tests (:62-200, 825-1023)
"""

import numpy as np
import pytest

import spark_rapids_jni_tpu as sr
from spark_rapids_jni_tpu import Column, Table, convert_to_rows, convert_from_rows
from spark_rapids_jni_tpu.rowconv import reference as ref
from spark_rapids_jni_tpu.rowconv.convert import (
    convert_to_rows_fixed_width_optimized,
    convert_from_rows_fixed_width_optimized,
)

RNG = np.random.default_rng(42)


def random_validity(n, pattern):
    if pattern == "all":
        return None
    if pattern == "none":
        return np.zeros(n, dtype=bool)
    if pattern == "most":
        return RNG.random(n) < 0.9
    return RNG.random(n) < 0.1  # "few"


def random_column(dtype, n, validity="all"):
    v = random_validity(n, validity)
    if dtype.id == sr.TypeId.STRING:
        words = ["", "a", "spark", "tpu-native", "longer string payload 🎉",
                 "x" * 37]
        strs = [words[i % len(words)] for i in range(n)]
        col = Column.strings_from_list(strs)
        if v is not None:
            import jax.numpy as jnp
            col = Column(col.dtype, col.data, col.offsets, jnp.asarray(v))
        return col
    if dtype.id == sr.TypeId.BOOL8:
        arr = RNG.integers(0, 2, n).astype(np.uint8)
    elif dtype.storage.kind == "f":
        arr = RNG.standard_normal(n).astype(dtype.storage)
    else:
        info = np.iinfo(dtype.storage)
        arr = RNG.integers(info.min // 2, info.max // 2, n,
                           dtype=dtype.storage)
    return Column.from_numpy(arr, dtype, v)


def assert_tables_equal(a: Table, b: Table):
    assert a.num_columns == b.num_columns
    assert a.num_rows == b.num_rows
    for i, (ca, cb) in enumerate(zip(a.columns, b.columns)):
        assert ca.dtype == cb.dtype, f"col {i}"
        va = np.asarray(ca.validity_or_true())
        vb = np.asarray(cb.validity_or_true())
        np.testing.assert_array_equal(va, vb, err_msg=f"col {i} validity")
        if ca.dtype.id == sr.TypeId.STRING:
            # compare only valid rows' payloads
            la, lb = ca.to_pylist(), cb.to_pylist()
            assert [x for x, ok in zip(la, va) if ok] == \
                   [x for x, ok in zip(lb, vb) if ok], f"col {i}"
        else:
            da, db = np.asarray(ca.data), np.asarray(cb.data)
            np.testing.assert_array_equal(da[va], db[vb], err_msg=f"col {i}")


def roundtrip_and_differential(table: Table):
    """JAX path bytes == NumPy oracle bytes, and round-trip == identity."""
    batches = convert_to_rows(table)
    oracle_bytes, oracle_offsets = ref.to_rows_np(table)
    got = np.concatenate([b.host_bytes() for b in batches])
    np.testing.assert_array_equal(got, oracle_bytes)

    assert len(batches) == 1
    back = convert_from_rows(batches[0], table.schema)
    assert_tables_equal(table, back)

    # oracle round-trip too (the spec must be self-consistent)
    back_np = ref.from_rows_np(oracle_bytes, oracle_offsets, list(table.schema))
    assert_tables_equal(table, back_np)


# ---- fixed width ----------------------------------------------------------

def test_single_int64_column():
    roundtrip_and_differential(Table([random_column(sr.int64, 17)]))


def test_simple_mixed_fixed_width():
    t = Table([random_column(sr.int8, 31), random_column(sr.int32, 31),
               random_column(sr.float64, 31), random_column(sr.bool8, 31)])
    roundtrip_and_differential(t)


def test_tall_narrow():
    # Tall: 4096 × 1 (tests/row_conversion.cpp Tall analog)
    roundtrip_and_differential(Table([random_column(sr.int32, 4096)]))


@pytest.mark.slow
def test_wide_256_columns():
    t = Table([random_column(sr.int8, 13) for _ in range(256)])
    roundtrip_and_differential(t)


def test_non_power_of_two_shape():
    # alignment edge cases: 557 rows × 131 cols of cycling types
    kinds = [sr.int8, sr.int16, sr.int32, sr.int64, sr.float32]
    t = Table([random_column(kinds[i % len(kinds)], 557) for i in range(131)])
    roundtrip_and_differential(t)


@pytest.mark.parametrize("pattern", ["all", "none", "most", "few"])
def test_type_matrix_with_validity(pattern):
    n = 97
    dtypes = [sr.int8, sr.int16, sr.int32, sr.int64, sr.float32, sr.float64,
              sr.bool8, sr.timestamp_ms, sr.timestamp_days,
              sr.decimal32(-2), sr.decimal64(-4)]
    t = Table([random_column(dt, n, pattern) for dt in dtypes])
    roundtrip_and_differential(t)


def test_fixed_width_optimized_parity():
    t = Table([random_column(sr.int32, 64), random_column(sr.int64, 64)])
    a = convert_to_rows(t)[0]
    b = convert_to_rows_fixed_width_optimized(t)[0]
    np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
    back = convert_from_rows_fixed_width_optimized(b, t.schema)
    assert_tables_equal(t, back)


def test_multi_batch_splitting():
    # force multiple ≤2GB-style batches with a tiny cap (Biggest analog)
    t = Table([random_column(sr.int64, 200)])
    batches = convert_to_rows(t, max_batch_bytes=1024)
    assert len(batches) > 1
    oracle_bytes, _ = ref.to_rows_np(t)
    got = np.concatenate([b.host_bytes() for b in batches])
    np.testing.assert_array_equal(got, oracle_bytes)
    # each batch independently converts back; rows concatenate in order
    lay_rows = []
    for b in batches:
        back = convert_from_rows(b, t.schema)
        lay_rows.append(back[0].to_numpy())
    np.testing.assert_array_equal(np.concatenate(lay_rows), t[0].to_numpy())


# ---- strings --------------------------------------------------------------

@pytest.mark.slow
def test_simple_string():
    t = Table([random_column(sr.int32, 11), random_column(sr.string, 11)])
    roundtrip_and_differential(t)


@pytest.mark.slow
def test_two_string_columns():
    t = Table([random_column(sr.string, 29), random_column(sr.int64, 29),
               random_column(sr.string, 29)])
    roundtrip_and_differential(t)


@pytest.mark.parametrize("pattern", ["most", "few"])
@pytest.mark.slow
def test_strings_with_nulls(pattern):
    t = Table([random_column(sr.string, 53, pattern),
               random_column(sr.int16, 53, pattern)])
    roundtrip_and_differential(t)


@pytest.mark.slow
def test_many_strings_mixed():
    n = 512
    cols = []
    for i in range(10):
        cols.append(random_column(sr.string if i % 3 == 0 else sr.int32, n,
                                  "most" if i % 2 else "all"))
    roundtrip_and_differential(Table(cols))


def test_empty_strings_only():
    c = Column.strings_from_list(["", "", ""])
    roundtrip_and_differential(Table([c, random_column(sr.int8, 3)]))


def test_zero_row_roundtrip():
    # empty partitions are routine in Spark shuffles
    t = Table([Column.from_numpy(np.zeros(0, np.int32)),
               Column.from_numpy(np.zeros(0, np.int64))])
    batches = convert_to_rows(t)
    back = convert_from_rows(batches[0], t.schema)
    assert back.num_rows == 0
    ts = Table([Column.strings_from_list([]),
                Column.from_numpy(np.zeros(0, np.int16))])
    batches = convert_to_rows(ts)
    back = convert_from_rows(batches[0], ts.schema)
    assert back.num_rows == 0


def test_fixed_batches_are_u32_words():
    # Fixed-width batches carry the JCUDF byte stream as u32 words (rows are
    # 8-byte aligned, so the view is exact); host_bytes() is the canonical
    # byte materialization and must match the scalar oracle.
    import jax.numpy as jnp
    t = Table([Column.from_numpy(np.arange(100, dtype=np.int32)),
               Column.from_numpy(np.arange(100, dtype=np.int16))])
    b = convert_to_rows(t)[0]
    assert b.data.dtype == jnp.uint32
    ob, _ = ref.to_rows_np(t)
    np.testing.assert_array_equal(b.host_bytes(), ob)
    # from_rows accepts the byte view of the same batch too
    from spark_rapids_jni_tpu.rowconv.convert import RowBatch
    back = convert_from_rows(RowBatch(b.device_u8(), b.offsets), t.schema)
    for a, c in zip(back.columns, t.columns):
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(c.data))


@pytest.mark.slow
def test_xpack_geometry_not_reused_across_layouts():
    """Round-4 regression: the xpack geometry memo is keyed on the string
    column's offsets arrays — REUSING the same string Column under a
    different fixed-width layout (different fpv → different row sizes)
    must re-plan, not hit a stale geometry and emit corrupt rows."""
    import os
    rng = np.random.default_rng(5)
    n = 3000
    strs = [("v" * int(k)) if k else "" for k in rng.integers(0, 9, n)]
    str_col = Column.strings_from_list(strs)
    t1 = Table([Column.from_numpy(
        rng.integers(0, 100, n, dtype=np.int32)), str_col])
    t2 = Table([Column.from_numpy(
        rng.integers(0, 100, n, dtype=np.int64)), str_col,
        Column.from_numpy(rng.integers(0, 2, n).astype(np.uint8),
                          sr.bool8)])
    for t in (t1, t2):
        got = convert_to_rows(t)[0].host_bytes()
        os.environ["SRJT_XPACK"] = "0"
        try:
            want = convert_to_rows(t)[0].host_bytes()
        finally:
            os.environ["SRJT_XPACK"] = "1"
        np.testing.assert_array_equal(got, want)


# ---- inverse xpack engine (round 5) ---------------------------------------

def _xpack_off():
    import contextlib, os

    @contextlib.contextmanager
    def ctx():
        os.environ["SRJT_XPACK"] = "0"
        try:
            yield
        finally:
            os.environ["SRJT_XPACK"] = "1"
    return ctx()


@pytest.mark.slow
def test_from_rows_xpack_differential():
    """The fused inverse engine must byte-match the non-xpack from_rows
    path (which matches the NumPy oracle) across geometries that stress
    the bucket planner: many short strings, a long outlier, nulls."""
    from spark_rapids_jni_tpu.rowconv import xpack
    rng = np.random.default_rng(11)
    for n in (5, 257, 4096):
        strs = [("s" * int(k)) if k else "" for k in rng.integers(0, 40, n)]
        strs[n // 2] = "y" * 300                  # Lw outlier
        t = Table([
            Column.strings_from_list(strs),
            random_column(sr.int64, n, "most"),
            Column.strings_from_list([s[::-1] for s in strs]),
            random_column(sr.int16, n, "few"),
        ])
        b = convert_to_rows(t)[0]
        layout_got = convert_from_rows(b, t.schema)
        with _xpack_off():
            want = convert_from_rows(b, t.schema)
        assert_tables_equal(layout_got, want)


@pytest.mark.slow
def test_from_rows_xpack_engages():
    """Regression: the engine must actually run (not silently fall back)
    on the bench-shaped geometry."""
    from spark_rapids_jni_tpu.rowconv import xpack
    rng = np.random.default_rng(3)
    n = 2048
    words = ["", "tpu", "spark-rapids", "columnar row transcode",
             "x" * 24, "payload"]
    t = Table([
        Column.from_numpy(rng.integers(0, 99, n, dtype=np.int32)),
        Column.strings_from_list(
            [words[j] for j in rng.integers(0, len(words), n)]),
    ])
    b = convert_to_rows(t)[0]
    layout = sr.rowconv.convert.compute_row_layout(t.schema)
    res = xpack.from_rows_var_x(layout, b)
    assert res is not None
    datas, valid, chars, out_offs = res
    np.testing.assert_array_equal(np.asarray(chars[0]),
                                  np.asarray(t[1].data))
    np.testing.assert_array_equal(np.asarray(out_offs[0]),
                                  np.asarray(t[1].offsets))


@pytest.mark.slow
def test_from_rows_xpack_corrupt_slot_raises():
    """Shuffle-received rows with an out-of-row slot must raise, not read
    out of bounds (host_table.cpp srjt_from_rows hardening parity)."""
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.rowconv.convert import RowBatch
    n = 64
    t = Table([Column.from_numpy(np.arange(n, dtype=np.int32)),
               Column.strings_from_list(["abcd"] * n)])
    b = convert_to_rows(t)[0]
    u8 = np.array(b.host_bytes())
    # row 0: string slot starts at byte 8 (after i32 + slot... layout:
    # i32 @0, slot @8? — just blast the len field of the first slot huge
    layout = sr.rowconv.convert.compute_row_layout(t.schema)
    ci = layout.variable_column_indices[0]
    slot_start = layout.column_starts[ci]
    u8[slot_start + 4:slot_start + 8] = np.frombuffer(
        np.uint32(1 << 20).tobytes(), dtype=np.uint8)
    bad = RowBatch(jnp.asarray(u8), b.offsets)
    with pytest.raises(ValueError, match="corrupt row"):
        convert_from_rows(bad, t.schema)


@pytest.mark.slow
def test_xpack_fallback_accounting():
    """A geometry outside the packing caps must fall back AND say why."""
    from spark_rapids_jni_tpu.rowconv import xpack
    before = sum(xpack.fallback_counts.values())
    n = 40
    # 600-char strings (608 B rows; a row has no size cap of its own, only
    # the engines' geometry caps, each with its counted fallback): a group of
    # 8 rows spans ~4.8KB of chars -> the from_rows dst-span bucket (Bd)
    # exceeds its 512-word cap and the engine must degrade with accounting
    strs = [("q" * 600) for _ in range(n)]
    t = Table([Column.strings_from_list(strs),
               Column.from_numpy(np.arange(n, dtype=np.int8), sr.int8)])
    b = convert_to_rows(t)[0]
    back = convert_from_rows(b, t.schema)
    np.testing.assert_array_equal(np.asarray(back[0].data),
                                  np.asarray(t[0].data))
    after = sum(xpack.fallback_counts.values())
    assert after > before, "fallback happened but was not accounted"


_TEST_CYCLE = (sr.int8, sr.int16, sr.int32, sr.int64, sr.float32, sr.float64,
               sr.bool8)
# spark-rapids-jni benchmarks/row_conversion.cpp: the cycle of its 155-column
# axis (chipbench's fixed155_roundtrip) and of its 212-column "Fixed Width
# Only" table
_NVBENCH_CYCLE = (sr.int8, sr.int32, sr.int16, sr.int64, sr.int32, sr.bool8,
                  sr.uint16, sr.uint8, sr.uint64)


def _fixed_table(kind, n):
    """Nulls (~10%) on every third column; null slots keep their payload."""
    cycle, n_cols = {"one": ((sr.int64,), 1),
                     "mixed12_dec_f64": (_TEST_CYCLE, 12),
                     "nvbench155": (_NVBENCH_CYCLE, 155),
                     "nvbench212": (_NVBENCH_CYCLE, 212)}[kind]
    cols = [random_column(cycle[i % len(cycle)], n,
                          "most" if i % 3 == 0 else "all")
            for i in range(n_cols)]
    if kind == "mixed12_dec_f64":
        # the cycle has no decimal128: the 16-byte quad compose/decode
        import jax.numpy as jnp
        lanes = RNG.integers(-2**62, 2**62, (n, 2), dtype=np.int64)
        cols.append(Column(sr.types.decimal128(-2), jnp.asarray(lanes),
                           validity=jnp.asarray(RNG.random(n) < 0.9)))
    return Table(cols)


@pytest.mark.parametrize("n", [1, 127, 1000])
@pytest.mark.parametrize("kind", ["one", "mixed12_dec_f64", "nvbench155",
                                  "nvbench212"])
def test_fixed_word_major_roundtrip(kind, n):
    """The fixed path's one engine each way (word compose + interleave,
    deinterleave + word-row decode): the batch's bytes equal the NumPy
    packer's, and the table comes back bit for bit, null slots' payload
    included; n off a multiple of 128 leaves the last lane tile ragged."""
    table = _fixed_table(kind, n)
    (batch,) = convert_to_rows(table)
    want_bytes, want_offsets = ref.to_rows_np(table)
    np.testing.assert_array_equal(batch.host_bytes(), want_bytes)
    np.testing.assert_array_equal(np.asarray(batch.offsets), want_offsets)
    back = convert_from_rows(batch, table.schema)
    assert back.num_columns == table.num_columns and back.num_rows == n
    for i, (sent, came) in enumerate(zip(table.columns, back.columns)):
        assert came.dtype == sent.dtype, f"col {i}"
        assert np.asarray(came.data).dtype == np.asarray(sent.data).dtype
        np.testing.assert_array_equal(np.asarray(came.data),
                                      np.asarray(sent.data),
                                      err_msg=f"col {i} payload")
        np.testing.assert_array_equal(np.asarray(came.validity_or_true()),
                                      np.asarray(sent.validity_or_true()),
                                      err_msg=f"col {i} validity")
