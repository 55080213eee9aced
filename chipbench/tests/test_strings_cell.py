"""The strings cell's own tests (``strings155_roundtrip``), on the CPU at a
tiny size: ``JAX_PLATFORMS=cpu python3 -m pytest
chipbench/tests/test_strings_cell.py -q``.  Not collected by tier-1."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import (datagen_strings, harness, references_strings,  # noqa: E402
                       rooflines_strings)

CELL = "strings155_roundtrip"
FAKE_CHIP = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny(cell):
    return {**cell.config, "rows": 3000}


def passes(compared):
    return all(c["value"] <= c["limit"] for c in compared.values())


@pytest.fixture(autouse=True)
def own_trace_dir(monkeypatch, tmp_path):
    """A traced run empties ``harness.TRACE_DIR`` first: two xdist workers
    tracing into the checkout's one directory empty each other's."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


def test_driver_agrees_with_reference_and_control_fails():
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)
    cell = harness.Cell(CELL)
    rec = harness.Recorder()
    state = cell.driver.setup(tiny(cell), cell.traffic, 2**31 + 28, rec)
    state.errors = []
    lat, _, work, failed, _ = harness.drive(cell, state, rec, 0.5)
    assert lat and not failed
    assert work == 2.0 * state.facts["row_bytes"] * len(lat)
    assert state.facts["char_bytes"] > 0
    got = cell.driver.answers(state)
    program = cell.driver.compare(state, got)
    assert set(program) == {"row_byte_mismatches", "row_offset_mismatches",
                            "roundtrip_mismatches"}
    assert passes(program), program
    control = cell.driver.compare(
        state, cell.driver.control_answers(state, got))
    assert control["row_byte_mismatches"]["value"] > 0
    assert control["row_offset_mismatches"]["value"] == 0
    assert not passes(control)


def test_planted_fault_in_one_strings_chars_reads_one(monkeypatch):
    import spark_rapids_jni_tpu as sr
    from spark_rapids_jni_tpu import Column, Table
    real = sr.convert_from_rows

    def from_rows(batch, schema):
        cols = list(real(batch, schema).columns)
        c = cols[9]
        assert c.dtype.is_variable_width
        cols[9] = Column(c.dtype, c.data.at[5].set(c.data[5] ^ 1), c.offsets,
                         c.validity)
        return Table(cols)
    monkeypatch.setattr(sr, "convert_from_rows", from_rows)
    cell = harness.Cell(CELL)
    r = harness.run_cell(cell, 9, 0.3, False, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert r["calls"] > 0 and r["correct"] is False
    assert r["compared"]["roundtrip_mismatches"]["value"] == 1
    assert r["compared"]["row_byte_mismatches"]["value"] == 0


def test_whole_run_reports_the_cells_lines():
    cell = harness.Cell(CELL)
    # a call takes ~0.3 s here: the window has to hold several, since the
    # span store reckons "began in the window" from a clock read of its own
    # and a pause between that and the reader's can drop the window's first
    r = harness.run_cell(cell, 2**31 + 5, 1.5, True, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert r["correct"] and r["calls"] >= 2 and list(r)[-1] == "compared"
    assert set(r["end_to_end_traced"]) == {"transcode_gbps", "setup_s"}
    # on the CPU the trace has no device plane: the roofline is left out
    assert set(r["metrics"]) == set(cell.per_layer) - {"strings_roofline"}
    assert all(c["limit"] == 0 for c in r["compared"].values())


def test_per_layer_is_the_nine_names():
    assert set(harness.Cell(CELL).per_layer) == {
        "to_rows_ms", "from_rows_ms", "to_rows_dispatch_ms",
        "from_rows_dispatch_ms", "strings_sizes_ms", "strings_plan_ms",
        "strings_launch_ms", "strings_totals_sync_ms", "strings_roofline"}


def test_plain_packer_by_hand_and_roofline_hand_count():
    # 4 rows of (int8, string, int32): slots at 0, 4, 12; validity byte 16;
    # chars from byte 17; rows padded to 8
    offsets = np.array([0, 3, 3, 8, 9], np.int32)
    chars = np.frombuffer(b"abcdefghi", np.uint8)
    columns = [("int8", np.array([1, 2, 3, 4], np.int8), None),
               ("string", (offsets, chars),
                np.array([True, False, True, True])),
               ("int32", np.array([10, 20, 30, 40], np.int32), None)]
    assert references_strings.jcudf_layout(
        ["int8", "string", "int32"]) == ([0, 4, 12], [1, 8, 4], 16, 1, 17)
    rows, offs = references_strings.pack_rows_strings(columns)
    assert offs.tolist() == [0, 24, 48, 72, 96]
    row0 = np.zeros(24, np.uint8)
    row0[0] = 1
    row0[4:12] = np.array([17, 3], np.uint32).view(np.uint8)
    row0[12:16] = np.array([10], np.int32).view(np.uint8)
    row0[16] = 0b111
    row0[17:20] = chars[:3]
    np.testing.assert_array_equal(rows[:24], row0)
    assert rows[24 + 16] == 0b101 and rows[24 + 8] == 0     # null, empty
    np.testing.assert_array_equal(rows[48 + 17:48 + 22], chars[3:8])
    low, _ = references_strings.pack_rows_strings(columns,
                                                  slots_from_chars=True)
    assert int(np.count_nonzero(low != rows)) == 4         # one byte a row

    config = {"rows": 4, "columns": 3, "null_every": 3,
              "type_cycle": ["int8", "string", "int32"]}
    facts = {"row_bytes": 96, "char_bytes": 9}
    # payload 4 x 5, one nullable column (the first), one string column's
    # offsets and the row offsets (5 x 4 B each), chars, rows; both ways
    assert rooflines_strings.strings_roundtrip(config, facts) == 2 * (
        4 * 5 + 4 * 1 + 4 * 5 * 2 + 9 + 96)


def test_lengths_follow_the_configurations_rule():
    cell = harness.Cell(CELL)
    rng = np.random.default_rng(3)
    lens = datagen_strings.string_lengths(rng, 200_000,
                                          cell.config["string_len"])
    assert lens.min() == 0 and lens.max() == 32
    assert abs(lens.mean() - 16) < 0.1 and abs(lens.std() - 32 / 6) < 0.1
    cols = datagen_strings.strings_columns(100, 20, 2**31 + 7, 3, 0.9,
                                           cell.config["string_len"])
    assert [c[0] for c in cols[:10]] == cell.config["type_cycle"]
    assert cols[9][2] is not None and cols[19][2] is None
