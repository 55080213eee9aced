"""The reference benchmark's strings table (155 columns cycling nine
fixed types and STRING, rows over 1 KB) through ``convert_to_rows`` /
``convert_from_rows`` at small sizes: bytes and offsets against two plain
packers written apart, the round trip leaf by leaf, no fallback, and the
``rowconv.var.*`` spans and counters."""

import numpy as np
import pytest

from spark_rapids_jni_tpu import convert_from_rows, convert_to_rows
from spark_rapids_jni_tpu.rowconv import reference as ref
from spark_rapids_jni_tpu.rowconv import xpack, xpallas, xtile
from spark_rapids_jni_tpu.rowconv.layout import compute_row_layout
from spark_rapids_jni_tpu.utils import metrics

from chipbench import datagen_strings, references_strings
from chipbench.drivers import transcode_strings

COLUMNS = 155
DRAWN = {"dist": "normal", "lo": 0, "hi": 32}
CASES = {
    "all_empty": {"dist": "constant", "lo": 0, "hi": 0},
    "all_32_bytes": {"dist": "constant", "lo": 0, "hi": 32},
    "null_string_column": DRAWN,
    "drawn": DRAWN,
}


def make(n, case, seed=28):
    columns = datagen_strings.strings_columns(n, COLUMNS, seed, 3, 0.9,
                                              CASES[case])
    if case == "null_string_column":
        name, values, _ = columns[9]
        assert name == "string"
        columns[9] = (name, values, np.zeros(n, bool))
    return columns, transcode_strings.build_table(columns)


def fallbacks():
    return (sum(xpack.fallback_counts.values()),
            xpallas._counts["fallbacks"])


def find(trees, name):
    out, todo = [], list(trees)
    while todo:
        node = todo.pop()
        if node["name"] == name:
            out.append(node)
        todo.extend(node.get("children", ()))
    return out


def round_trip_checked(n, case):
    columns, table = make(n, case)
    layout = compute_row_layout(table.schema)
    assert layout.fixed_plus_validity == 888 and xtile.serves(layout)
    metrics.set_enabled(True)
    metrics.reset()
    before = fallbacks()
    batches = convert_to_rows(table)
    assert len(batches) == 1
    batch = batches[0]
    back = convert_from_rows(batch, table.schema)
    assert fallbacks() == before

    # two plain packers written apart, the program, and each other
    oracle_bytes, oracle_offsets = ref.to_rows_np(table)
    plain_bytes, plain_offsets = references_strings.pack_rows_strings(columns)
    got = batch.host_bytes()
    np.testing.assert_array_equal(plain_bytes, oracle_bytes)
    np.testing.assert_array_equal(plain_offsets, oracle_offsets)
    np.testing.assert_array_equal(got, oracle_bytes)
    np.testing.assert_array_equal(np.asarray(batch.offsets), oracle_offsets)
    sizes = np.diff(oracle_offsets)
    assert sizes.min() >= 888 and sizes.max() <= 888 + 15 * 32 + 7

    for sent, came in zip(table.columns, back.columns):
        np.testing.assert_array_equal(np.asarray(came.data),
                                      np.asarray(sent.data))
        np.testing.assert_array_equal(np.asarray(came.validity_or_true()),
                                      np.asarray(sent.validity_or_true()))
        if sent.dtype.is_variable_width:
            np.testing.assert_array_equal(np.asarray(came.offsets),
                                          np.asarray(sent.offsets))
    return table, batch, metrics.span_roots()


@pytest.mark.parametrize("n", [257, 4096])
@pytest.mark.parametrize("case", list(CASES))
def test_strings155_round_trip(n, case):
    table, batch, roots = round_trip_checked(n, case)
    to_rows = find(roots, "convert_to_rows")[0]
    from_rows = find(roots, "convert_from_rows")[0]
    sizes = find([to_rows], "rowconv.var.sizes")[0]
    assert sizes["attrs"]["rows"] == n and sizes["attrs"]["batches"] == 1
    for root, direction in ((to_rows, "to"), (from_rows, "from")):
        plan = find([root], "rowconv.var.plan")[0]["attrs"]
        assert plan["memo_hit"] == 0
        assert plan["Mw"] * 4 >= np.diff(np.asarray(batch.offsets)).max()
        assert plan["tiles"] == -(-n // xtile.tile_rows(n, plan["Mw"]))
        launches = find([root], "rowconv.var.launch")
        assert launches and all(s["attrs"]["direction"] == direction
                                for s in launches)
    sync = find([from_rows], "rowconv.var.totals_sync")
    assert len(sync) == 1 and sync[0]["attrs"]["bytes"] > 0
    chars = sum(c.data.shape[0] for c in table.columns
                if c.dtype.is_variable_width)
    assert metrics.counter_value("rowconv.var.engine.to.xpack") == 1
    assert metrics.counter_value("rowconv.var.engine.from.xpack") == 1
    for other in ("dma", "gather"):
        assert metrics.counter_value(f"rowconv.var.engine.to.{other}") == 0
        assert metrics.counter_value(f"rowconv.var.engine.from.{other}") == 0
    assert metrics.counter_value("rowconv.var.chars_bytes") == 2 * chars

    # the table again: the geometry memo of its offset arrays hits
    convert_to_rows(table)
    plan = find(metrics.span_roots()[-1:], "rowconv.var.plan")[0]["attrs"]
    assert plan["memo_hit"] == 1


def test_strings155_batch_over_several_row_tiles(monkeypatch):
    # 1000 rows of up to 320 words in tiles of 256 rows: the last tile is
    # part rows of the batch, part padding
    monkeypatch.setattr(xtile, "TILE_WORDS", 320 * 256)
    _, _, roots = round_trip_checked(1000, "drawn")
    for name in ("convert_to_rows", "convert_from_rows"):
        plan = find(find(roots, name), "rowconv.var.plan")[0]["attrs"]
        assert plan["tiles"] == 4
