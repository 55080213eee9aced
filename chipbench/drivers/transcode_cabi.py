"""Closed-loop JCUDF round trips through the C ABI, from host buffers and
back: every caller is a thread that owns one table behind a libsrjt table
handle and sends it through ``srjt_to_rows_device`` and its batch through
``srjt_from_rows_device``, the symbols ``RowConversion.convertToRows`` /
``convertFromRows`` reach through ``jni_bridge.cpp``.  Nothing stays on the
chip between calls.  Work is counted in JCUDF row bytes, produced by the one
direction plus consumed by the other, as in ``transcode``.

Of the program this file takes ``native.load()`` (the library and its
ctypes signatures) and the metrics store; the conversions are reached
through the C symbols alone."""

from __future__ import annotations

import ctypes as C
import threading
import types

import numpy as np

from .. import datagen, references

# TypeId of each type name at the C ABI (spark_rapids_jni_tpu/types.py and
# native/host_table.cpp type_size): part of the ABI, so written out here
TYPE_IDS = {"int8": 1, "int16": 2, "int32": 3, "int64": 4, "uint8": 5,
            "uint16": 6, "uint32": 7, "uint64": 8, "float32": 9,
            "float64": 10, "bool8": 11}
NULL_COUNTERS = ("bridge.null.to", "bridge.null.from")


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(C.c_void_p)


def _nulls() -> float:
    from spark_rapids_jni_tpu.utils import metrics
    return float(sum(metrics.counter_value(k) for k in NULL_COUNTERS))


def build_handle(lib, columns) -> int:
    """A table handle of ``[(type_name, values, validity | None)]``: one
    ``srjt_column_fixed`` a column (the library copies the buffers in, as
    ``HostColumn`` does), one validity byte a row where the column has
    nulls, then ``srjt_table``."""
    handles = []
    try:
        for name, values, valid in columns:
            data = np.ascontiguousarray(values)
            v = None if valid is None else valid.astype(np.uint8)
            h = lib.srjt_column_fixed(
                TYPE_IDS[name], 0, data.shape[0], _ptr(data),
                None if v is None else _ptr(v))
            if not h:
                raise RuntimeError(f"srjt_column_fixed({name}) gave null")
            handles.append(h)
        table = lib.srjt_table((C.c_void_p * len(handles))(*handles),
                               len(handles))
    finally:
        for h in handles:
            lib.srjt_column_free(h)
    if not table:
        raise RuntimeError("srjt_table gave null")
    return table


class Caller:
    """One task thread's handles: its table, and the answers of its last
    round trip, freed with the caller."""

    def __init__(self, lib):
        self.lib, self.columns = lib, None
        self.table = self.rows = self.back = None

    def free_answers(self) -> None:
        rows, back, self.rows, self.back = self.rows, self.back, None, None
        if rows:
            self.lib.srjt_rows_free(rows)
        if back:
            self.lib.srjt_table_free(back)

    def free_table(self) -> None:
        table, self.table = self.table, None
        if table:
            self.lib.srjt_table_free(table)

    def __del__(self):
        self.free_answers()
        self.free_table()


def _together(state, rec) -> None:
    """One round trip of every caller, all at once; a failure of any is
    raised here."""
    errors = []

    def one(c):
        try:
            call(state, c, -1, rec)
        except Exception as e:  # noqa: BLE001 — raised again below, on the caller's thread
            errors.append(e)
    threads = [threading.Thread(target=one, args=(c,))
               for c in range(len(state.callers))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def setup(config: dict, traffic: dict, seed: int, rec):
    from spark_rapids_jni_tpu import native
    lib = native.load()
    if lib is None or lib.srjt_device_available() != 1:
        raise RuntimeError("libsrjt.so or its device bridge is unavailable")
    n_callers = int(traffic["callers"])
    cycle = config["type_cycle"]
    names = [cycle[i % len(cycle)] for i in range(config["columns"])]
    tids = np.asarray([TYPE_IDS[n] for n in names], np.int32)
    state = types.SimpleNamespace(
        lib=lib, tids=tids, scales=np.zeros_like(tids), nulls0=_nulls(),
        callers=[Caller(lib) for _ in range(n_callers)],
        facts={"row_bytes": 0})

    def build(c):
        mine = state.callers[c]
        mine.columns = datagen.nvbench_columns(
            config["rows"], config["columns"], [seed, c],
            config["null_every"], config["valid_share"], cycle)
        mine.table = build_handle(lib, mine.columns)
    builders = [threading.Thread(target=build, args=(c,))
                for c in range(n_callers)]
    for t in builders:
        t.start()
    for t in builders:
        t.join()
    if not all(mine.table for mine in state.callers):
        raise RuntimeError("a caller's table was not built")
    _together(state, rec)             # the warm-up: all callers at once
    state.facts["row_bytes"] = int(
        lib.srjt_rows_batch_size(state.callers[0].rows, 0))
    return state


def call(state, caller: int, i: int, rec) -> float:
    from spark_rapids_jni_tpu.utils import metrics
    lib, mine = state.lib, state.callers[caller]
    mine.free_answers()               # a caller drops its last answers first
    # one root span a round trip: the bridge's spans of both directions
    # share its request id, which is what ``total_per_call`` sums over
    with metrics.span("chipbench.roundtrip"):
        with rec.span("to_rows"):
            mine.rows = lib.srjt_to_rows_device(mine.table)
        if not mine.rows:
            raise RuntimeError("srjt_to_rows_device gave a null handle")
        with rec.span("from_rows"):
            mine.back = lib.srjt_from_rows_device(
                mine.rows, _ptr(state.tids), _ptr(state.scales),
                len(state.tids))
        if not mine.back:
            raise RuntimeError("srjt_from_rows_device gave a null handle")
    return 2.0 * sum(lib.srjt_rows_batch_size(mine.rows, b)
                     for b in range(lib.srjt_rows_num_batches(mine.rows)))


def _view(ptr, n: int, dtype=np.uint8) -> np.ndarray:
    if not ptr or n == 0:
        return np.zeros(0, dtype)
    return np.ctypeslib.as_array(ptr, shape=(n,))


def answers(state):
    """Every caller's last round trip as views of the handles' own memory
    (the handles stay with ``state``): the batches a rows handle holds, the
    first batch's bytes and offsets, and each returned column's payload
    bytes and validity bytes.  The tables that went in are freed: the
    comparison reads the arrays they were made of."""
    lib, got = state.lib, []
    for mine in state.callers:
        mine.free_table()
        if not (mine.rows and mine.back):
            got.append(None)          # the call failed: counted elsewhere
            continue
        n = lib.srjt_rows_batch_rows(mine.rows, 0)
        returned = []
        for ci in range(lib.srjt_table_cols(mine.back)):
            h = C.c_void_p(lib.srjt_table_column(mine.back, ci))
            returned.append((
                _view(lib.srjt_column_data(h), lib.srjt_column_data_size(h)),
                _view(lib.srjt_column_valid(h), lib.srjt_column_rows(h))))
            # the table keeps the column alive; this handle was only a way in
            lib.srjt_column_free(h)
        got.append((lib.srjt_rows_num_batches(mine.rows),
                    _view(lib.srjt_rows_batch_data(mine.rows, 0),
                          lib.srjt_rows_batch_size(mine.rows, 0)),
                    _view(lib.srjt_rows_batch_offsets(mine.rows, 0), n + 1,
                          np.int32),
                    returned))
    return got


def control_answers(state, got):
    """The packer that ignores nulls, in the program's place."""
    return [None if g is None else
            (g[0], references.pack_rows_fixed(
                mine.columns, ignore_nulls=True).reshape(-1), g[2], g[3])
            for mine, g in zip(state.callers, got)]


def compare(state, got) -> dict:
    """Every caller's last round trip against its own table: row bytes
    against the plain packer, row offsets against int32 ``arange x row
    size``, the batches of the rows handle against one, the payload and
    validity bytes of the returned table against what went in, and the null
    handles of the whole run.  All exact."""
    row_diff = offset_diff = back_diff = batch_diff = 0
    for mine, g in zip(state.callers, got):
        if g is None:
            continue
        batches, rows, offsets, returned = g
        batch_diff += abs(batches - 1)
        want = references.pack_rows_fixed(mine.columns)
        n, row_size = want.shape
        want = want.reshape(-1)
        row_diff += (int(np.count_nonzero(rows != want))
                     if rows.shape == want.shape
                     else max(rows.size, want.size))
        del want
        want_offsets = np.arange(n + 1, dtype=np.int64) * row_size
        offset_diff += (int(np.count_nonzero(offsets != want_offsets))
                        if offsets.shape == want_offsets.shape
                        and offsets.dtype == np.int32
                        else max(offsets.size, want_offsets.size))
        back_diff += abs(len(returned) - len(mine.columns))
        for (name, values, valid), (data, validity) in zip(mine.columns,
                                                           returned):
            sent = np.ascontiguousarray(values).view(np.uint8)
            if data.shape != sent.shape:
                back_diff += n
                continue
            width = sent.size // n
            back_diff += int(np.count_nonzero(
                (sent != data).reshape(n, width).any(axis=1)))
            sent_valid = (np.ones(n, np.uint8) if valid is None
                          else valid.astype(np.uint8))
            # a returned column without a validity buffer is all valid
            came = validity if validity.size else np.ones(n, np.uint8)
            back_diff += int(np.count_nonzero(sent_valid != came))
    return {"row_byte_mismatches": {"value": row_diff, "limit": 0},
            "row_offset_mismatches": {"value": offset_diff, "limit": 0},
            "roundtrip_mismatches": {"value": back_diff, "limit": 0},
            "batch_count_mismatches": {"value": batch_diff, "limit": 0},
            "null_handles": {"value": _nulls() - state.nulls0, "limit": 0}}
