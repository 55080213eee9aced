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


# row tiles of 256 rows (``TILE_WORDS`` patched to 320 * 256): what a tile's
# strings look like, by row, for the bodies that keep the strings on the
# lanes.  ``lengths(rng, n)`` redraws every string column's lengths.
TILE = 256


def _drawn_lengths(rng, n):
    return datagen_strings.string_lengths(rng, n, DRAWN)


def _one_tile(value):
    def lengths(rng, n):
        ln = _drawn_lengths(rng, n)
        ln[TILE:2 * TILE] = value
        return ln
    return lengths


def _rare_byte(rng, n):
    ln = np.zeros(n, np.int64)
    ln[1::4] = 1
    return ln


TILE_CASES = {
    # case: (rows, lengths, rows a from_rows group holds)
    "second_tile_all_empty": (1000, _one_tile(0), 8),
    "second_tile_all_32_bytes": (1000, _one_tile(32), 8),
    # five live rows in the last tile: its first group is part padding
    "last_tile_five_rows": (3 * TILE + 5, _drawn_lengths, 8),
    # short strings: a 512 B stretch of a char stream holds more groups of
    # 8 rows than the combine takes, so the groups grow to 32 and 128 rows
    # and a group's rows spread over that many sublanes of the lane order
    "one_byte_strings": (1000, lambda rng, n: np.ones(n, np.int64), 32),
    "rare_one_byte_strings": (2100, _rare_byte, 128),
    "null_string_columns": (1000, _drawn_lengths, 8),
}


def make(n, case, seed=28):
    spec = CASES.get(case, DRAWN)
    columns = datagen_strings.strings_columns(n, COLUMNS, seed, 3, 0.9, spec)
    if case == "null_string_column":
        name, values, _ = columns[9]
        assert name == "string"
        columns[9] = (name, values, np.zeros(n, bool))
    if case in TILE_CASES:
        rng = np.random.default_rng(seed + 1)
        lengths = TILE_CASES[case][1]
        for ci, (name, _, valid) in enumerate(columns):
            if name != "string":
                continue
            offsets = np.zeros(n + 1, np.int32)
            np.cumsum(lengths(rng, n), out=offsets[1:])
            chars = rng.integers(32, 127, int(offsets[-1]), dtype=np.uint8)
            if case == "null_string_columns" and ci in (9, 39, 69):
                # all null, every other row null, drawn: the bytes travel
                valid = {9: np.zeros(n, bool), 39: np.arange(n) % 2 == 0,
                         69: valid}[ci]
                assert valid is not None
            columns[ci] = (name, (offsets, chars), valid)
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


@pytest.mark.parametrize("case", list(TILE_CASES))
def test_strings155_tiles_of_unlike_strings(case, monkeypatch):
    """What the lane-ordered bodies can get wrong and row-ordered ones
    could not: a tile that differs from its neighbours, a group that is
    part padding, groups of 32 and 128 rows, null strings — bytes against
    the plain packers, then the round trip."""
    monkeypatch.setattr(xtile, "TILE_WORDS", 384 * TILE)
    planned = []
    plan = xtile.plan_from_rows_chars
    monkeypatch.setattr(
        xtile, "plan_from_rows_chars",
        lambda *a: planned.append(plan(*a)) or planned[-1])
    n, _, group = TILE_CASES[case]
    _, _, roots = round_trip_checked(n, case)
    for name in ("convert_to_rows", "convert_from_rows"):
        attrs = find(find(roots, name), "rowconv.var.plan")[0]["attrs"]
        assert attrs["tiles"] == -(-n // TILE)
    (geom,) = planned
    assert geom[2] == TILE and geom[5] == group
