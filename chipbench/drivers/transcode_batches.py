"""Closed-loop JCUDF round trips of a table that takes more than one row
batch: ``convert_to_rows(table)`` yields the batches, then
``convert_from_rows(batch, schema)`` of every batch in order, as the
source's "from row" loop walks the batches its "to row" made.  The table
stays resident on the chip.  Work is counted in JCUDF row bytes, produced by
the one direction plus consumed by the other, over all batches, as in
``transcode``: the cells' GB/s mean the same thing."""

from __future__ import annotations

import types

import numpy as np

from .. import datagen, references, references_batches
from .transcode import _block


def setup(config: dict, traffic: dict, seed: int, rec):
    import spark_rapids_jni_tpu as sr
    from spark_rapids_jni_tpu import Column, Table
    columns = datagen.nvbench_columns(
        config["rows"], config["columns"], seed,
        config["null_every"], config["valid_share"], config["type_cycle"])
    table = Table([Column.from_numpy(values, getattr(sr, name), valid)
                   for name, values, valid in columns])
    _block(table)
    state = types.SimpleNamespace(
        table=table, schema=table.schema, columns=columns, last=None,
        cap=int(config["max_batch_bytes"]), want=None,
        row_size=references.jcudf_fixed_layout([c[0] for c in columns])[4],
        facts={"row_bytes": 0, "batches": 0})
    for i in range(int(traffic.get("warmup_calls", 2))):
        call(state, 0, i, rec)
    state.facts["row_bytes"] = sum(b.num_bytes for b, _ in state.last)
    state.facts["batches"] = len(state.last)
    return state


def call(state, caller: int, i: int, rec) -> float:
    from spark_rapids_jni_tpu import convert_from_rows, convert_to_rows
    from spark_rapids_jni_tpu.utils import metrics
    state.last = None                 # a caller drops its last answer first
    # one root span a round trip: the program's spans of both directions and
    # of every batch share its request id, which ``total_per_call`` sums over
    with metrics.span("chipbench.roundtrip"):
        with rec.span("to_rows"):
            batches = convert_to_rows(state.table,
                                      max_batch_bytes=state.cap)
            _block(batches)
        with rec.span("from_rows"):
            backs = [convert_from_rows(b, state.schema) for b in batches]
            _block(backs)
    state.last = list(zip(batches, backs))
    return 2.0 * sum(b.num_bytes for b in batches)


def answers(state):
    """The window's last call, on the host, a batch at a time: its row bytes
    and offsets, then the payload and validity of the table that came back
    from it, each freed on the device before the next is fetched.  The table
    is freed first.  The host then holds the rows twice (these and the plain
    packer's) and the columns twice: ~11 GB at the cell's size with the
    comparison's temporaries."""
    last, state.last, state.table = state.last, None, None
    got = []
    while last:
        batch, back = last.pop(0)
        rows, offsets = batch.host_bytes(), np.asarray(batch.offsets)
        del batch
        cols = list(back.columns)
        del back
        returned = []
        while cols:
            c = cols.pop(0)
            returned.append((np.ascontiguousarray(np.asarray(c.data)),
                             np.asarray(c.validity_or_true())))
        got.append((rows, offsets, returned))
    return got


def _want(state) -> np.ndarray:
    """The plain packer's rows of the whole table, packed once a run."""
    if state.want is None:
        state.want = references.pack_rows_fixed(state.columns)
    return state.want


def _recut(got, bounds):
    """The same bytes and the same returned rows in other batches: cut at
    the row boundaries ``bounds``."""
    rows = np.concatenate([g[0].reshape(-1) for g in got])
    row_size = rows.size // bounds[-1]
    ncols = len(got[0][2])
    datas = [np.concatenate([g[2][ci][0] for g in got]) for ci in range(ncols)]
    valids = [np.concatenate([g[2][ci][1] for g in got])
              for ci in range(ncols)]
    return [(rows[lo * row_size:hi * row_size],
             (np.arange(hi - lo + 1, dtype=np.int64)
              * row_size).astype(np.int32),
             [(d[lo:hi], v[lo:hi]) for d, v in zip(datas, valids)])
            for lo, hi in zip(bounds[:-1], bounds[1:])]


def control_unrounded(state, got):
    """The boundary rule without its rounding to 32 rows, in the program's
    place: the same bytes, every batch but the last filled to the last row
    that fits."""
    n = state.columns[0][1].shape[0]
    return _recut(got, references_batches.plain_batch_boundaries(
        state.row_size, n, state.cap, round_to_32=False))


def control_ignores_nulls(state, got):
    """The packer that ignores nulls, in the program's place, cut where the
    program cut."""
    low = references.pack_rows_fixed(state.columns,
                                     ignore_nulls=True).reshape(-1)
    out, lo = [], 0
    for rows, offsets, returned in got:
        out.append((low[lo:lo + rows.size], offsets, returned))
        lo += rows.size
    return out


# each control with the one compared number it has to move, and alone
# (``python3 -m chipbench.control_batches``); ``chipbench.control`` runs the
# first
CONTROLS = {
    "unrounded_boundaries": (control_unrounded, "batch_boundary_mismatches"),
    "ignores_nulls": (control_ignores_nulls, "row_byte_mismatches")}
control_answers = control_unrounded


def compare(state, got) -> dict:
    """Against the plain boundary rule, the plain packer and the input, all
    exact: batch boundaries that differ (or a batch an int32 cannot
    address), row offsets that differ, row bytes that differ over all
    batches in order, and rows of batch k's answer whose payload or
    validity bits differ from rows [lo_k, hi_k) of the table that went
    in."""
    n = state.columns[0][1].shape[0]
    row_size = state.row_size
    want = _want(state).reshape(-1)
    boundary = references_batches.boundary_mismatches(
        [g[1].shape[0] - 1 for g in got], row_size, n, state.cap)
    offset_diff = row_diff = back_diff = lo = 0
    for rows, offsets, returned in got:
        k = offsets.shape[0] - 1
        hi = lo + k
        offset_diff += references_batches.offset_mismatches(offsets, k,
                                                            row_size)
        rows = rows.reshape(-1)
        ref = want[lo * row_size:hi * row_size]
        row_diff += (int(np.count_nonzero(rows != ref))
                     if rows.shape == ref.shape
                     else max(rows.size, ref.size))
        back_diff += abs(len(returned) - len(state.columns))
        for (name, values, valid), (data, validity) in zip(state.columns,
                                                           returned):
            sent = np.ascontiguousarray(values[lo:hi]).view(np.uint8)
            came = data.view(np.uint8)
            sent_valid = (np.ones(k, bool) if valid is None
                          else valid[lo:hi])
            if came.size != sent.size or validity.shape != sent_valid.shape:
                back_diff += max(k, 1)
                continue
            back_diff += int(np.count_nonzero(
                (sent.reshape(k, -1) != came.reshape(k, -1)).any(axis=1)))
            back_diff += int(np.count_nonzero(sent_valid != validity))
        lo = hi
    # rows of the table that no batch carried
    row_diff += max(n - lo, 0) * row_size
    return {"batch_boundary_mismatches": {"value": boundary, "limit": 0},
            "row_offset_mismatches": {"value": offset_diff, "limit": 0},
            "row_byte_mismatches": {"value": row_diff, "limit": 0},
            "roundtrip_mismatches": {"value": back_diff, "limit": 0}}
