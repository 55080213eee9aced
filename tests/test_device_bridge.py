"""Device-bridge tests: bytes entering through the C/JNI surface are
transcoded by the DEVICE engine (VERDICT round-1 item 6; the reference's
JNI drives its device engine directly, RowConversionJni.cpp:24-45).

The pytest process hosts CPython, so ``srjt_device_available()`` is true
and ``srjt_to_rows_device`` round-trips through
``spark_rapids_jni_tpu.bridge`` → JAX engine → ``srjt_rows_adopt``.  The
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


# --- PR 36: the bridge measured (spans, counters) and called from four
# task threads at once, as fixed155_cabi_t4 calls it ------------------------

def _fixed_table(seed, n=700, ncols=20):
    """A fixed-width table of the nvbench cycle behind a handle, with the
    arrays it was made of (chipbench's generator and driver: what the cell
    sends)."""
    from chipbench import datagen
    from chipbench.drivers import transcode_cabi
    columns = datagen.nvbench_columns(n, ncols, seed)
    tids = np.asarray([transcode_cabi.TYPE_IDS[name]
                       for name, _, _ in columns], np.int32)
    return transcode_cabi.build_handle(lib, columns), columns, tids


def _roundtrip(t, tids):
    rows = lib.srjt_to_rows_device(t)
    assert rows
    scales = np.zeros_like(tids)
    back = lib.srjt_from_rows_device(rows, _np_ptr(tids), _np_ptr(scales),
                                     len(tids))
    assert back
    got = [_batch_bytes(rows)]
    for i in range(lib.srjt_table_cols(back)):
        h = C.c_void_p(lib.srjt_table_column(back, i))
        got.append(np.ctypeslib.as_array(
            lib.srjt_column_data(h),
            shape=(lib.srjt_column_data_size(h),)).copy())
        got.append(np.ctypeslib.as_array(
            lib.srjt_column_valid(h),
            shape=(lib.srjt_column_rows(h),)).copy())
        lib.srjt_column_free(h)
    lib.srjt_rows_free(rows)
    lib.srjt_table_free(back)
    return got


def test_four_threads_at_once_answer_as_one_thread_and_the_plain_packer():
    import threading
    from chipbench import references
    tables = [_fixed_table(seed) for seed in (11, 12, 13, 14)]
    alone = [_roundtrip(t, tids) for t, _, tids in tables]
    for (_, columns, _), got in zip(tables, alone):
        np.testing.assert_array_equal(
            got[0], references.pack_rows_fixed(columns).reshape(-1))
        for ci, (_, values, valid) in enumerate(columns):
            np.testing.assert_array_equal(
                got[1 + 2 * ci], np.ascontiguousarray(values).view(np.uint8))
            np.testing.assert_array_equal(
                got[2 + 2 * ci].astype(bool),
                np.ones(len(values), bool) if valid is None else valid)
    together, errors = [[] for _ in tables], []

    def task(i):
        try:
            for _ in range(3):
                together[i].append(_roundtrip(tables[i][0], tables[i][2]))
        except Exception as e:  # noqa: BLE001 — re-raised on the test's thread
            errors.append(e)
    threads = [threading.Thread(target=task, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errors and not any(th.is_alive() for th in threads)
    for want, trips in zip(alone, together):
        assert len(trips) == 3
        for got in trips:
            assert len(got) == len(want)
            for a, b in zip(want, got):
                np.testing.assert_array_equal(a, b)
    for t, _, _ in tables:
        lib.srjt_table_free(t)


def test_bridge_spans_carry_their_attrs_under_the_callers_root():
    from spark_rapids_jni_tpu.utils import metrics
    was = metrics.enabled()
    metrics.set_enabled(True)
    try:
        t, columns, tids = _fixed_table(21)
        before = {k: metrics.counter_value(k) for k in (
            "bridge.calls.to", "bridge.calls.from", "bridge.bytes.h2d",
            "bridge.bytes.d2h", "bridge.host_copied_bytes",
            "bridge.null.to", "bridge.null.from", "bridge.adopted_bytes",
            "bridge.adopted", "bridge.released")}
        with metrics.span("task", rid="task-7") as root:
            _roundtrip(t, tids)
        tree = root.as_dict()
        calls = tree["children"]
        assert [(c["name"], c["attrs"]["direction"]) for c in calls] == [
            ("bridge.call", "to"), ("bridge.call", "from")]
        moved = {k: metrics.counter_value(k) - v for k, v in before.items()}
        assert moved["bridge.calls.to"] == moved["bridge.calls.from"] == 1
        assert moved["bridge.null.to"] == moved["bridge.null.from"] == 0
        for c in calls:
            assert c["rid"] == "task-7"
            assert set(c["attrs"]) == {"direction", "rows", "cols",
                                       "batches", "bytes_in", "bytes_out"}
            assert (c["attrs"]["rows"], c["attrs"]["cols"],
                    c["attrs"]["batches"]) == (700, 20, 1)
            leaves = [s for s in c["children"]
                      if s["name"].startswith("bridge.")]
            assert [s["name"] for s in leaves] == [
                "bridge.marshal_in", "bridge.h2d", "bridge.d2h",
                "bridge.marshal_out"]
            assert all(s["rid"] == "task-7" for s in leaves)
            assert all(set(s["attrs"]) == (
                {"bytes", "transfers"} if s["name"][-3:] in ("h2d", "d2h")
                else {"bytes", "copied_bytes"}) for s in leaves)
        assert moved["bridge.bytes.h2d"] == sum(
            c["attrs"]["bytes_in"] for c in calls)
        assert moved["bridge.bytes.d2h"] == sum(
            c["attrs"]["bytes_out"] for c in calls)
        assert moved["bridge.host_copied_bytes"] == sum(
            s["attrs"]["copied_bytes"] for c in calls for s in c["children"]
            if "copied_bytes" in s.get("attrs", {}))
        # the batch that came down is the rows handle's, not a copy of it;
        # ``_roundtrip`` freed the handle, so the buffer is given back
        to_out = [s["attrs"] for s in calls[0]["children"]
                  if s["name"] == "bridge.marshal_out"][0]
        assert to_out["copied_bytes"] == 0
        assert moved["bridge.adopted_bytes"] == to_out["bytes"] == (
            calls[0]["attrs"]["bytes_out"])
        assert moved["bridge.adopted"] == moved["bridge.released"] == 1
        lib.srjt_table_free(t)
    finally:
        metrics.set_enabled(was)


@pytest.mark.parametrize("direction", ["to", "from"])
def test_null_handle_ticks_its_counter(monkeypatch, direction):
    from spark_rapids_jni_tpu import bridge
    from spark_rapids_jni_tpu.utils import metrics
    was = metrics.enabled()
    metrics.set_enabled(True)
    try:
        t, columns, tids = _fixed_table(31, n=64, ncols=9)
        rows = lib.srjt_to_rows_device(t)
        assert rows

        def boom(*_):
            raise RuntimeError("engine down")
        monkeypatch.setattr(bridge, f"convert_{direction}_rows", boom)
        other = "from" if direction == "to" else "to"
        nulls = metrics.counter_value(f"bridge.null.{direction}")
        calls = metrics.counter_value(f"bridge.calls.{direction}")
        others = metrics.counter_value(f"bridge.null.{other}")
        scales = np.zeros_like(tids)
        out = (lib.srjt_to_rows_device(t) if direction == "to" else
               lib.srjt_from_rows_device(rows, _np_ptr(tids),
                                         _np_ptr(scales), len(tids)))
        assert not out
        assert metrics.counter_value(f"bridge.null.{direction}") == nulls + 1
        assert metrics.counter_value(f"bridge.calls.{direction}") == calls + 1
        assert metrics.counter_value(f"bridge.null.{other}") == others
        lib.srjt_rows_free(rows)
        lib.srjt_table_free(t)
    finally:
        metrics.set_enabled(was)


def test_from_rows_uploads_the_words_the_resident_cells_decode(monkeypatch):
    # the batch goes up as the uint32 words the fixed engine decodes, read
    # in place: no bytes -> words pass on the device, no host copy
    from spark_rapids_jni_tpu import bridge
    seen = []
    real = bridge.convert_from_rows

    def spy(batch, schema):
        seen.append((batch.data.dtype, batch.data.shape, batch.num_rows))
        return real(batch, schema)
    monkeypatch.setattr(bridge, "convert_from_rows", spy)
    t, columns, tids = _fixed_table(41, n=96, ncols=11)
    _roundtrip(t, tids)
    from chipbench import references
    row = references.jcudf_fixed_layout([c[0] for c in columns])[4]
    assert seen == [(np.dtype("uint32"), (96 * row // 4,), 96)]
    lib.srjt_table_free(t)
