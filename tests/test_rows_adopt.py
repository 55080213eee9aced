"""The rows handle that ``srjt_to_rows_device`` returns adopts the host
arrays the download landed in (``srjt_rows_adopt*``, ``bridge._to_rows``)
instead of copying them into fresh pages (PR 37).

The contract under test: the handle reads the caller's buffers in place;
``release(ctx)`` runs exactly once a buffer, when the handle is freed, on
any thread, at interpreter exit too; a rejected adopt never runs it; the
host engine reads an adopted batch as it reads an imported one.
"""

import ctypes as C
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401  (initializes jax/x64)

from spark_rapids_jni_tpu import native as _native

lib = _native.load()
if lib is None:
    pytest.skip(f"libsrjt.so unavailable: {_native.build_error}",
                allow_module_level=True)

INT32, INT64, STRING = 3, 4, 24
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ptr(a):
    return a.ctypes.data_as(C.c_void_p)


def _addr(p) -> int:
    return C.cast(p, C.c_void_p).value or 0


def _fixed_table(seed, n=300, ncols=12):
    from chipbench import datagen
    from chipbench.drivers import transcode_cabi
    columns = datagen.nvbench_columns(n, ncols, seed)
    tids = np.asarray([transcode_cabi.TYPE_IDS[name]
                       for name, _, _ in columns], np.int32)
    return transcode_cabi.build_handle(lib, columns), tids


class _Releases:
    """A release callback that records every context it is called with."""

    def __init__(self):
        self.seen = []
        self.fn = _native.RELEASE_FN(lambda ctx: self.seen.append(ctx))


@pytest.fixture
def counting():
    from spark_rapids_jni_tpu.utils import metrics
    was = metrics.enabled()
    metrics.set_enabled(True)
    names = ("bridge.adopted", "bridge.released", "bridge.adopted_bytes",
             "bridge.host_copied_bytes")
    before = {k: metrics.counter_value(k) for k in names}
    yield lambda: {k: metrics.counter_value(k) - v for k, v in before.items()}
    metrics.set_enabled(was)


def test_the_handle_reads_the_downloaded_arrays_in_place(monkeypatch):
    from spark_rapids_jni_tpu import bridge
    landed = []
    real = bridge._download

    def spy(leaves):
        host = real(leaves)
        landed.append(host)
        return host
    monkeypatch.setattr(bridge, "_download", spy)
    t, _ = _fixed_table(3)
    rows = lib.srjt_to_rows_device(t)
    assert rows and len(landed) == 1
    data, offs = landed[0]
    assert _addr(lib.srjt_rows_batch_data(rows, 0)) == data.ctypes.data
    assert _addr(lib.srjt_rows_batch_offsets(rows, 0)) == offs.ctypes.data
    assert lib.srjt_rows_batch_size(rows, 0) == data.nbytes
    assert lib.srjt_rows_batch_rows(rows, 0) == offs.shape[0] - 1 == 300
    lib.srjt_rows_free(rows)
    lib.srjt_table_free(t)


def test_a_to_call_adopts_the_batch_and_copies_nothing(counting):
    from spark_rapids_jni_tpu.utils import metrics
    t, _ = _fixed_table(4)
    with metrics.span("task") as root:
        rows = lib.srjt_to_rows_device(t)
    assert rows
    size = lib.srjt_rows_batch_size(rows, 0)
    (call,) = root.as_dict()["children"]
    leaves = {s["name"]: s["attrs"] for s in call["children"]}
    want = size + 301 * 4
    assert leaves["bridge.marshal_out"] == {"bytes": want, "copied_bytes": 0}
    moved = counting()
    assert moved["bridge.adopted_bytes"] == want
    assert moved["bridge.adopted"] == 1 and moved["bridge.released"] == 0
    assert moved["bridge.host_copied_bytes"] == (
        leaves["bridge.marshal_in"]["copied_bytes"])
    lib.srjt_rows_free(rows)
    assert counting()["bridge.released"] == 1
    lib.srjt_table_free(t)


def test_release_runs_once_a_buffer_when_the_handle_is_freed():
    rel = _Releases()
    a = np.arange(64, dtype=np.uint8)
    b = np.arange(32, dtype=np.uint8)
    oa = np.asarray([0, 32, 64], np.int32)
    ob = np.asarray([0, 32], np.int32)
    h = lib.srjt_rows_adopt(_ptr(a), 64, _ptr(oa), 2, rel.fn, 11)
    assert h
    assert lib.srjt_rows_adopt_append(h, _ptr(b), 32, _ptr(ob), 1, rel.fn, 12)
    assert lib.srjt_rows_num_batches(h) == 2 and rel.seen == []
    assert _addr(lib.srjt_rows_batch_data(h, 1)) == b.ctypes.data
    lib.srjt_rows_free(h)
    assert sorted(rel.seen) == [11, 12]


@pytest.mark.parametrize("data_size, offsets, n_rows, with_release", [
    (64, [0, 40, 20, 64], 3, True),      # non-monotonic
    (64, [8, 32, 64], 2, True),          # does not start at zero
    (64, [0, 32, 48], 2, True),          # does not end at the size
    (64, [0, 32, 64], 2, False),         # no release callback
])
def test_a_rejected_adopt_leaves_ownership_with_the_caller(
        data_size, offsets, n_rows, with_release):
    rel = _Releases()
    data = np.zeros(data_size, np.uint8)
    offs = np.asarray(offsets, np.int32)
    fn = rel.fn if with_release else _native.RELEASE_FN()
    assert not lib.srjt_rows_adopt(_ptr(data), data_size, _ptr(offs), n_rows,
                                   fn, 21)
    good = np.asarray([0, 64], np.int32)
    h = lib.srjt_rows_adopt(_ptr(data), 64, _ptr(good), 1, rel.fn, 22)
    assert h
    assert not lib.srjt_rows_adopt_append(h, _ptr(data), data_size,
                                          _ptr(offs), n_rows, fn, 23)
    assert lib.srjt_rows_num_batches(h) == 1 and rel.seen == []
    lib.srjt_rows_free(h)
    assert rel.seen == [22]


def _mixed_rows():
    """Host-engine rows of an int32 (with nulls), string, int64 table."""
    n = 97
    rng = np.random.default_rng(9)
    ints = rng.integers(-99, 99, n).astype(np.int32)
    longs = rng.integers(-10**12, 10**12, n).astype(np.int64)
    offs = np.zeros(n + 1, np.int32)
    np.cumsum(rng.integers(0, 7, n), out=offs[1:])
    chars = rng.integers(97, 123, int(offs[-1])).astype(np.uint8)
    valid = (rng.random(n) < 0.8).astype(np.uint8)
    cols = [lib.srjt_column_fixed(INT32, 0, n, _ptr(ints), _ptr(valid)),
            lib.srjt_column_string(n, _ptr(offs), _ptr(chars), None),
            lib.srjt_column_fixed(INT64, 0, n, _ptr(longs), None)]
    t = lib.srjt_table((C.c_void_p * 3)(*cols), 3)
    for h in cols:
        lib.srjt_column_free(h)
    rows = lib.srjt_to_rows(t)
    size = lib.srjt_rows_batch_size(rows, 0)
    data = np.ctypeslib.as_array(lib.srjt_rows_batch_data(rows, 0),
                                 shape=(size,)).copy()
    roffs = np.ctypeslib.as_array(lib.srjt_rows_batch_offsets(rows, 0),
                                  shape=(n + 1,)).copy()
    lib.srjt_rows_free(rows)
    lib.srjt_table_free(t)
    return data, roffs, n


def _columns_of(table):
    out = []
    for i in range(lib.srjt_table_cols(table)):
        h = C.c_void_p(lib.srjt_table_column(table, i))
        n = lib.srjt_column_rows(h)
        data = np.ctypeslib.as_array(lib.srjt_column_data(h), shape=(
            lib.srjt_column_data_size(h),)).copy()
        offs = lib.srjt_column_offsets(h)
        out.append((data, None if not offs else
                    np.ctypeslib.as_array(offs, shape=(n + 1,)).copy(),
                    np.ctypeslib.as_array(lib.srjt_column_valid(h),
                                          shape=(n,)).copy()))
        lib.srjt_column_free(h)
    return out


def test_the_host_engine_reads_an_adopted_batch_as_an_imported_one():
    data, offs, n = _mixed_rows()
    rel = _Releases()
    tids = np.asarray([INT32, STRING, INT64], np.int32)
    imported = lib.srjt_rows_import(_ptr(data), data.size, _ptr(offs), n)
    adopted = lib.srjt_rows_adopt(_ptr(data), data.size, _ptr(offs), n,
                                  rel.fn, 31)
    assert imported and adopted
    got = []
    for h in (imported, adopted):
        table = lib.srjt_from_rows(h, 0, _ptr(tids), None, 3)
        assert table
        got.append(_columns_of(table))
        lib.srjt_table_free(table)
    for a, b in zip(*got):
        for x, y in zip(a, b):
            if x is None:
                assert y is None
            else:
                np.testing.assert_array_equal(x, y)
    assert got[0][1][1] is not None            # the string column's offsets
    lib.srjt_rows_free(imported)
    lib.srjt_rows_free(adopted)
    assert rel.seen == [31]


def test_four_threads_three_round_trips_give_every_buffer_back(counting):
    from spark_rapids_jni_tpu import bridge
    tables = [_fixed_table(seed, n=200, ncols=9) for seed in (41, 42, 43, 44)]
    before = set(bridge._held)
    errors = []

    def task(t, tids):
        try:
            scales = np.zeros_like(tids)
            for _ in range(3):
                rows = lib.srjt_to_rows_device(t)
                assert rows
                back = lib.srjt_from_rows_device(rows, _ptr(tids),
                                                 _ptr(scales), len(tids))
                assert back
                lib.srjt_rows_free(rows)
                lib.srjt_table_free(back)
        except Exception as e:  # noqa: BLE001 — re-raised on the test's thread
            errors.append(e)
    threads = [threading.Thread(target=task, args=tt) for tt in tables]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errors and not any(th.is_alive() for th in threads)
    moved = counting()
    assert moved["bridge.adopted"] == moved["bridge.released"] == 12
    from chipbench import datagen, references
    row = references.jcudf_fixed_layout(
        [name for name, _, _ in datagen.nvbench_columns(1, 9, 0)])[4]
    assert moved["bridge.adopted_bytes"] == 12 * (200 * row + 201 * 4)
    assert set(bridge._held) <= before
    for t, _ in tables:
        lib.srjt_table_free(t)


def test_a_process_that_exits_with_adopted_handles_alive_returns_0():
    # a caller's ``__del__`` frees its answers while the interpreter tears
    # the modules down, and one handle is never freed at all
    script = textwrap.dedent("""
        from spark_rapids_jni_tpu import native
        from spark_rapids_jni_tpu.utils import metrics
        from chipbench import datagen
        from chipbench.drivers import transcode_cabi
        metrics.set_enabled(True)
        lib = native.load()
        table = transcode_cabi.build_handle(
            lib, datagen.nvbench_columns(64, 9, 5))
        callers = [transcode_cabi.Caller(lib) for _ in range(3)]
        for c in callers:
            c.rows = lib.srjt_to_rows_device(table)
            assert c.rows
        callers[0].table = table
        never_freed = lib.srjt_to_rows_device(table)
        assert never_freed
        print("adopted", metrics.counter_value("bridge.adopted"), flush=True)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [REPO] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    done = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    assert "adopted 4" in done.stdout
    assert "Exception ignored" not in done.stderr, done.stderr[-2000:]


def test_adopts_and_releases_from_more_threads_than_cores_balance(counting):
    # the registry and the counters are shared by every caller: adopt and
    # free from more threads than cores, switching often, and nothing held
    # may be lost or left behind
    from spark_rapids_jni_tpu import bridge
    before = set(bridge._held)
    n_threads, rounds = (os.cpu_count() or 4) + 2, 50
    errors = []

    def task():
        try:
            for _ in range(rounds):
                data = np.zeros(16, np.uint8)
                offs = np.asarray([0, 8, 16], np.int32)
                h = bridge._adopt(lib, None, data, offs)
                assert h
                assert bridge._adopt(lib, h, data[:8], offs[:2])
                lib.srjt_rows_free(h)
        except Exception as e:  # noqa: BLE001 — re-raised on the test's thread
            errors.append(e)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=task) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not errors and not any(th.is_alive() for th in threads)
    moved = counting()
    assert moved["bridge.adopted"] == moved["bridge.released"] == (
        2 * n_threads * rounds)
    assert set(bridge._held) <= before
