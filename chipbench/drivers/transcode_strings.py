"""Closed-loop JCUDF round trips of a table with string columns:
``convert_to_rows(table)`` then ``convert_from_rows(batch, schema)``, every
leaf blocked (data, validity, offsets, chars), on a table that stays
resident on the chip.  Work is counted in JCUDF row bytes, produced by the
one direction plus consumed by the other, as in ``transcode``: the two
cells' GB/s mean the same thing."""

from __future__ import annotations

import types

import numpy as np

from .. import datagen_strings, references_strings


def _block(tree):
    import jax
    jax.block_until_ready(jax.tree_util.tree_leaves(tree))


def build_table(columns):
    """The resident ``Table`` of ``datagen_strings.strings_columns``' output:
    a string column straight from its arrays."""
    import jax.numpy as jnp
    import spark_rapids_jni_tpu as sr
    from spark_rapids_jni_tpu import Column, Table
    cols = []
    for name, values, valid in columns:
        if name == "string":
            cols.append(Column(sr.string, jnp.asarray(values[1]),
                               jnp.asarray(values[0]),
                               None if valid is None else jnp.asarray(valid)))
        else:
            cols.append(Column.from_numpy(values, getattr(sr, name), valid))
    return Table(cols)


def setup(config: dict, traffic: dict, seed: int, rec):
    columns = datagen_strings.strings_columns(
        config["rows"], config["columns"], seed, config["null_every"],
        config["valid_share"], config["string_len"], config["type_cycle"])
    table = build_table(columns)
    _block(table)
    state = types.SimpleNamespace(
        table=table, schema=table.schema, columns=columns, last=None,
        facts={"row_bytes": 0,
               "char_bytes": int(sum(v[1].size for name, v, _ in columns
                                     if name == "string"))})
    for i in range(int(traffic.get("warmup_calls", 2))):
        call(state, 0, i, rec)
    state.facts["row_bytes"] = state.last[0].num_bytes
    return state


def call(state, caller: int, i: int, rec) -> float:
    from spark_rapids_jni_tpu import convert_from_rows, convert_to_rows
    from spark_rapids_jni_tpu.utils import metrics
    state.last = None                 # a caller drops its last answer first
    # one root span a round trip: the program's spans of both directions
    # share its request id, which is what ``total_per_call`` sums over
    with metrics.span("chipbench.roundtrip"):
        with rec.span("to_rows"):
            batches = convert_to_rows(state.table)
            _block(batches)
        if len(batches) != 1:
            raise RuntimeError(f"{len(batches)} batches: the cell's table "
                               f"has to fit one")
        with rec.span("from_rows"):
            back = convert_from_rows(batches[0], state.schema)
            _block(back)
    state.last = (batches[0], back)
    return 2.0 * batches[0].num_bytes


def answers(state):
    """The window's last round trip, on the host: the batch's bytes and
    offsets, and every leaf of the table that came back.  Frees the
    device."""
    last, state.last, state.table = state.last, None, None
    if last is None:
        return []
    batch, back = last
    del last
    returned = []
    for c in back.columns:
        leaf = {"data": np.ascontiguousarray(np.asarray(c.data)),
                "valid": np.asarray(c.validity_or_true())}
        if c.offsets is not None:
            leaf["offsets"] = np.asarray(c.offsets)
        returned.append(leaf)
    return [(batch.host_bytes(), np.asarray(batch.offsets), returned)]


def control_answers(state, got):
    """The packer whose string slots count from the chars region, in the
    program's place."""
    low, offsets = references_strings.pack_rows_strings(
        state.columns, slots_from_chars=True)
    return [(low, offsets, returned) for _, _, returned in got]


def _differ(a: np.ndarray, b: np.ndarray) -> int:
    a, b = np.asarray(a).reshape(-1), np.asarray(b).reshape(-1)
    if a.shape != b.shape:
        return max(a.size, b.size, 1)
    return int(np.count_nonzero(a != b))


def compare(state, got) -> dict:
    """Against the plain packer and the input, all exact: row bytes that
    differ, row offsets that differ, and what came back differing from what
    went in — rows of a fixed column by payload, validity bits, string
    offsets, chars bytes."""
    want, want_offsets = (references_strings.pack_rows_strings(state.columns)
                          if got else (None, None))
    row_diff = offset_diff = back_diff = 0
    for rows, offsets, returned in got:
        row_diff += _differ(rows, want)
        offset_diff += _differ(offsets, want_offsets)
        back_diff += abs(len(returned) - len(state.columns))
        for (name, values, valid), leaf in zip(state.columns, returned):
            if name == "string":
                n = values[0].shape[0] - 1
                back_diff += _differ(leaf.get("offsets", ()), values[0])
                back_diff += _differ(leaf["data"], values[1])
            else:
                n = values.shape[0]
                sent = np.ascontiguousarray(values).view(np.uint8)
                came = leaf["data"].view(np.uint8)
                back_diff += (int(np.count_nonzero(
                    (sent.reshape(n, -1) != came.reshape(n, -1)).any(axis=1)))
                    if sent.size == came.size else n)
            back_diff += _differ(leaf["valid"],
                                 np.ones(n, bool) if valid is None else valid)
    return {"row_byte_mismatches": {"value": row_diff, "limit": 0},
            "row_offset_mismatches": {"value": offset_diff, "limit": 0},
            "roundtrip_mismatches": {"value": back_diff, "limit": 0}}
