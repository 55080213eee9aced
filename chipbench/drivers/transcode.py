"""Closed-loop JCUDF round trips: ``convert_to_rows(table)`` then
``convert_from_rows(batch, schema)``, every leaf blocked, on a table that
stays resident on the chip.  Work is counted in JCUDF row bytes: produced
by the one direction plus consumed by the other."""

from __future__ import annotations

import types

import numpy as np

from .. import datagen, references


def _block(tree):
    import jax
    jax.block_until_ready(jax.tree_util.tree_leaves(tree))


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
        facts={"row_bytes": 0})
    for i in range(int(traffic.get("warmup_calls", 2))):
        call(state, 0, i, rec)
    state.facts["row_bytes"] = state.last[0].num_bytes
    return state


def call(state, caller: int, i: int, rec) -> float:
    from spark_rapids_jni_tpu import convert_from_rows, convert_to_rows
    state.last = None                 # a caller drops its last answer first
    with rec.span("to_rows"):
        batches = convert_to_rows(state.table)
        _block(batches)
    if len(batches) != 1:
        raise RuntimeError(f"{len(batches)} batches: the cell's table has "
                           f"to fit one")
    with rec.span("from_rows"):
        back = convert_from_rows(batches[0], state.schema)
        _block(back)
    state.last = (batches[0], back)
    return 2.0 * batches[0].num_bytes


def answers(state):
    """The window's last round trip, on the host: the batch's bytes and the
    payload and validity of the table that came back.  Frees the device.
    Earlier answers are not held, by choice: since PR 29 ``from_rows``
    reserves 1.85 GiB of temporaries and earlier answers (1.50 GiB each)
    would fit beside it (PERF.md)."""
    last, state.last, state.table = state.last, None, None
    if last is None:
        return []
    batch, back = last
    del last
    return [(batch.host_bytes(),
             [(np.ascontiguousarray(np.asarray(c.data)),
               np.asarray(c.validity_or_true()))
              for c in back.columns])]


def control_answers(state, got):
    """The packer that ignores nulls, in the program's place."""
    low = references.pack_rows_fixed(state.columns, ignore_nulls=True)
    return [(low, returned) for _, returned in got]


def compare(state, got) -> dict:
    """Against the plain packer and the input: row bytes that differ, and
    rows of the table that came back whose payload or validity bits differ
    from the table that went in.  Both exact."""
    want = references.pack_rows_fixed(state.columns).reshape(-1)
    row_diff = back_diff = 0
    for rows, returned in got:
        rows = rows.reshape(-1)
        row_diff += (int(np.count_nonzero(rows != want))
                     if rows.shape == want.shape
                     else max(rows.size, want.size))
        back_diff += abs(len(returned) - len(state.columns))
        for (name, values, valid), (data, validity) in zip(state.columns,
                                                           returned):
            n = values.shape[0]
            sent = np.ascontiguousarray(values).view(np.uint8).reshape(n, -1)
            came = data.view(np.uint8).reshape(n, -1)
            back_diff += int(np.count_nonzero((sent != came).any(axis=1)))
            sent_valid = np.ones(n, bool) if valid is None else valid
            back_diff += int(np.count_nonzero(sent_valid != validity))
    return {"row_byte_mismatches": {"value": row_diff, "limit": 0},
            "roundtrip_mismatches": {"value": back_diff, "limit": 0}}
