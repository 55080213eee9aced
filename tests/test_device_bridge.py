"""Device-bridge tests: bytes entering through the C/JNI surface are
transcoded by the DEVICE engine (VERDICT round-1 item 6; the reference's
JNI drives its device engine directly, RowConversionJni.cpp:24-45).

The pytest process hosts CPython, so ``srjt_device_available()`` is true
and ``srjt_to_rows_device`` round-trips through
``spark_rapids_jni_tpu.bridge`` → JAX engine → ``srjt_rows_import``.  The
host C++ engine output is the byte-exact oracle.
"""

import ctypes as C

import numpy as np
import pytest

# the bridge module must resolve the SAME library instance
import spark_rapids_jni_tpu  # noqa: F401  (initializes jax/x64)

from spark_rapids_jni_tpu import native as _native

# single shared binding site (native/__init__.py); load() builds the
# library on a fresh checkout, one process at a time
lib = _native.load()
if lib is None:
    pytest.skip(f"libsrjt.so unavailable: {_native.build_error}",
                allow_module_level=True)

INT32, INT64, STRING = 3, 4, 24


def _np_ptr(a):
    return a.ctypes.data_as(C.c_void_p)


def _mixed_table(n=257):
    rng = np.random.default_rng(5)
    ints = rng.integers(-1000, 1000, n).astype(np.int32)
    longs = rng.integers(-10**12, 10**12, n).astype(np.int64)
    lens = rng.integers(0, 9, n).astype(np.int64)
    offs = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lens, out=offs[1:])
    chars = rng.integers(97, 123, int(offs[-1])).astype(np.uint8)
    valid = (rng.random(n) < 0.9).astype(np.uint8)
    h1 = lib.srjt_column_fixed(INT32, 0, n, _np_ptr(ints), _np_ptr(valid))
    h2 = lib.srjt_column_string(n, _np_ptr(offs), _np_ptr(chars), None)
    h3 = lib.srjt_column_fixed(INT64, 0, n, _np_ptr(longs), None)
    arr = (C.c_void_p * 3)(h1, h2, h3)
    t = lib.srjt_table(arr, 3)
    for h in (h1, h2, h3):
        lib.srjt_column_free(h)
    return t, (ints, offs, chars, valid, longs)


def _batch_bytes(rows):
    size = lib.srjt_rows_batch_size(rows, 0)
    return np.ctypeslib.as_array(lib.srjt_rows_batch_data(rows, 0),
                                 shape=(size,)).copy()


def test_device_available_in_python_process():
    assert lib.srjt_device_available() == 1


@pytest.mark.slow
def test_to_rows_device_matches_host_engine():
    t, _ = _mixed_table()
    host = lib.srjt_to_rows(t)
    dev = lib.srjt_to_rows_device(t)
    assert host and dev, "both engines must produce rows"
    assert lib.srjt_rows_num_batches(dev) == lib.srjt_rows_num_batches(host)
    np.testing.assert_array_equal(_batch_bytes(dev), _batch_bytes(host))
    lib.srjt_rows_free(host)
    lib.srjt_rows_free(dev)
    lib.srjt_table_free(t)


@pytest.mark.slow
def test_from_rows_device_roundtrip():
    t, (ints, offs, chars, valid, longs) = _mixed_table()
    rows = lib.srjt_to_rows_device(t)
    assert rows
    tids = np.asarray([INT32, STRING, INT64], dtype=np.int32)
    scales = np.zeros(3, dtype=np.int32)
    back = lib.srjt_from_rows_device(rows, _np_ptr(tids), _np_ptr(scales), 3)
    assert back
    assert lib.srjt_table_cols(back) == 3
    assert lib.srjt_table_rows(back) == len(ints)
    # int32 column payload must round-trip byte-exactly
    c0 = C.c_void_p(lib.srjt_table_column(back, 0))
    raw = np.ctypeslib.as_array(lib.srjt_column_data(c0),
                                shape=(lib.srjt_column_data_size(c0),))
    np.testing.assert_array_equal(raw.view(np.int32), ints)
    # string chars round-trip
    c1 = C.c_void_p(lib.srjt_table_column(back, 1))
    raw1 = np.ctypeslib.as_array(lib.srjt_column_data(c1),
                                 shape=(lib.srjt_column_data_size(c1),))
    np.testing.assert_array_equal(raw1, chars)
    lib.srjt_rows_free(rows)
    lib.srjt_table_free(t)
    lib.srjt_table_free(back)


@pytest.mark.slow
def test_srjt_device_kill_switch(monkeypatch):
    # SRJT_DEVICE=0 is the operator escape hatch forcing the host engine
    # (same convention as the SRJT_PALLAS dispatch toggle); getenv is read
    # per call, so flipping the env var takes effect immediately
    assert lib.srjt_device_available() == 1
    monkeypatch.setenv("SRJT_DEVICE", "0")
    assert lib.srjt_device_available() == 0
    t, _ = _mixed_table(16)
    assert not lib.srjt_to_rows_device(t)
    monkeypatch.delenv("SRJT_DEVICE")
    assert lib.srjt_device_available() == 1
    rows = lib.srjt_to_rows_device(t)
    assert rows
    lib.srjt_rows_free(rows)
    lib.srjt_table_free(t)


def test_bridge_failure_is_logged_before_null(monkeypatch, caplog):
    # a null handle sends the C++ caller to the host engine; the Python
    # exception behind it must reach the log, not vanish
    from spark_rapids_jni_tpu import bridge

    def boom(_table):
        raise RuntimeError("engine down")
    monkeypatch.setattr(bridge, "convert_to_rows", boom)
    t, _ = _mixed_table(16)
    with caplog.at_level("ERROR"):
        assert not lib.srjt_to_rows_device(t)
    assert "engine down" in caplog.text
    lib.srjt_table_free(t)
