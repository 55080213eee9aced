"""The C ABI cell's own tests (``fixed155_cabi_t4``), on the CPU at a tiny
size: ``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_cabi_cell.py
-q``.  Tier-1 collects them through ``tests/test_instrument_cabi_cell.py``."""

import ctypes as C
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, references, rooflines  # noqa: E402

CELL = "fixed155_cabi_t4"
FAKE_CHIP = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TINY = {"rows": 1200}
NUMBERS = {"row_byte_mismatches", "row_offset_mismatches",
           "roundtrip_mismatches", "batch_count_mismatches", "null_handles"}
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def tiny(cell):
    return {**cell.config, **TINY}


def moved(compared):
    return sorted(k for k, c in compared.items() if c["value"] > c["limit"])


@pytest.fixture(autouse=True)
def own_trace_dir(monkeypatch, tmp_path):
    """A traced run empties ``harness.TRACE_DIR`` first: two xdist workers
    tracing into the checkout's one directory empty each other's."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


@pytest.fixture
def driven():
    """The cell set up at the tiny size and driven for a short window."""
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)
    cell = harness.Cell(CELL)
    rec = harness.Recorder()
    state = cell.driver.setup(tiny(cell), cell.traffic, 2**31 + 36, rec)
    state.errors = []
    lat, _, work, failed, _ = harness.drive(cell, state, rec, 0.4)
    assert len(lat) >= 4 and not failed
    # every caller ends on a whole sequence of three round trips
    assert len(lat) % cell.traffic["cycle_calls"] == 0
    assert work == 2.0 * state.facts["row_bytes"] * len(lat)
    yield cell, state


def test_driver_agrees_with_reference_and_the_control_moves_row_bytes(driven):
    cell, state = driven
    assert state.facts == {"row_bytes": 1200 * 848}
    got = cell.driver.answers(state)
    assert len(got) == cell.traffic["callers"] == 4
    # every caller has a table of its own
    assert not np.array_equal(state.callers[0].columns[1][1],
                              state.callers[1].columns[1][1])
    program = cell.driver.compare(state, got)
    assert set(program) == NUMBERS and moved(program) == [], program
    control = cell.driver.compare(
        state, cell.driver.control_answers(state, got))
    assert moved(control) == ["row_byte_mismatches"], control


def _flip_row_byte(cell, state):
    mine = state.callers[2]
    data = np.ctypeslib.as_array(
        state.lib.srjt_rows_batch_data(mine.rows, 0),
        shape=(state.lib.srjt_rows_batch_size(mine.rows, 0),))
    data[848 * 7 + 5] ^= 1
    return "row_byte_mismatches"


def _flip_validity_byte(cell, state):
    mine = state.callers[1]
    h = C.c_void_p(state.lib.srjt_table_column(mine.back, 4))
    np.ctypeslib.as_array(state.lib.srjt_column_valid(h),
                          shape=(1200,))[11] ^= 1
    state.lib.srjt_column_free(h)
    return "roundtrip_mismatches"


def _append_a_batch(cell, state):
    mine = state.callers[3]
    data, offs = np.zeros(8, np.uint8), np.asarray([0, 8], np.int32)
    assert state.lib.srjt_rows_import_append(
        mine.rows, data.ctypes.data_as(C.c_void_p), 8,
        offs.ctypes.data_as(C.c_void_p), 1)
    return "batch_count_mismatches"


@pytest.mark.parametrize("plant", [_flip_row_byte, _flip_validity_byte,
                                   _append_a_batch])
def test_planted_fault_moves_its_own_number_and_no_other(driven, plant):
    cell, state = driven
    name = plant(cell, state)
    compared = cell.driver.compare(state, cell.driver.answers(state))
    assert moved(compared) == [name], compared
    assert compared[name]["value"] == 1


def test_forced_null_handle_is_counted_and_fails_the_run(monkeypatch):
    from spark_rapids_jni_tpu import bridge
    real, calls = bridge.convert_from_rows, []

    def from_rows(batch, schema):
        calls.append(1)
        if len(calls) == 8:           # past set-up's four round trips
            raise RuntimeError("planted: the device engine is down")
        return real(batch, schema)
    monkeypatch.setattr(bridge, "convert_from_rows", from_rows)
    cell = harness.Cell(CELL)
    r = harness.run_cell(cell, 9, 0.3, False, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert r["correct"] is False and r["failed"] == 1
    # the driver's own number, and the harness's guard on the failed call
    assert moved(r["compared"]) == ["failed_calls", "null_handles"]
    assert r["compared"]["null_handles"]["value"] == 1


def test_whole_run_reports_the_cells_lines():
    cell = harness.Cell(CELL)
    r = harness.run_cell(cell, 2**31 + 5, 0.6, True, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert r["correct"] and r["calls"] >= 4 and list(r)[-1] == "compared"
    assert set(r["end_to_end_traced"]) == {"transcode_gbps", "setup_s"}
    # on the CPU the trace has no device plane: the roofline is left out
    assert set(r["metrics"]) == set(cell.per_layer) - {"transcode_roofline"}
    assert set(r["compared"]) == NUMBERS | {
        "fallbacks_moved", "compiles_in_window", "failed_calls"}
    assert all(c["limit"] == 0 and c["value"] == 0
               for c in r["compared"].values())
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert all(m[k] > 0 for k in ("cabi_marshal_in_ms", "cabi_h2d_ms",
                                  "cabi_d2h_ms", "cabi_marshal_out_ms"))
    # what no leaf accounts for: a direction's span less its leaves, and the
    # whole round trip less them (the wait to enter the interpreter too)
    assert 0 < m["cabi_unattributed_ms"] < m["cabi_roundtrip_unattributed_ms"]
    assert m["cabi_roundtrip_unattributed_ms"] < (m["to_rows_ms"]
                                                  + m["from_rows_ms"])
    # the leaves of both directions lie inside the two C calls
    assert (m["cabi_marshal_in_ms"] + m["cabi_h2d_ms"] + m["cabi_d2h_ms"]
            + m["cabi_marshal_out_ms"]) <= 1.5 * (m["to_rows_ms"]
                                                  + m["from_rows_ms"])


def test_bridge_spans_of_a_call_hang_under_the_callers_root(driven):
    from spark_rapids_jni_tpu.utils import metrics
    from chipbench.readers import program_span
    cell, state = driven
    rec = harness.Recorder()
    cell.driver.call(state, 1, 0, rec)
    # the newest root of the store: no window to cut it by (the store reads a
    # clock of its own, and a pause between the two reads drops a lone call)
    root = [t for t in metrics.span_roots()
            if t["name"] == "chipbench.roundtrip"][-1]
    calls = [c for c in root["children"] if c["name"] == "bridge.call"]
    assert [c["attrs"]["direction"] for c in calls] == ["to", "from"]
    rows_bytes = 1200 * 848
    for c in calls:
        assert c["rid"] == root["rid"] and c["tid"] == root["tid"]
        a = c["attrs"]
        assert (a["rows"], a["cols"], a["batches"]) == (1200, 155, 1)
        names = [s["name"] for s in c["children"]]
        engine = "convert_%s_rows" % a["direction"]
        assert names == ["bridge.marshal_in", "bridge.h2d", engine,
                         "bridge.d2h", "bridge.marshal_out"]
        leaves = {s["name"]: s["attrs"] for s in c["children"]}
        assert set(leaves["bridge.marshal_in"]) == {"bytes", "copied_bytes"}
        assert set(leaves["bridge.marshal_out"]) == {"bytes", "copied_bytes"}
        assert set(leaves["bridge.h2d"]) == {"bytes", "transfers"}
        assert set(leaves["bridge.d2h"]) == {"bytes", "transfers"}
        assert a["bytes_in"] == leaves["bridge.h2d"]["bytes"]
        assert a["bytes_out"] == leaves["bridge.d2h"]["bytes"]
    to, back = (
        {s["name"]: s["attrs"] for s in c["children"]} for c in calls)
    payload = 1200 * 532
    # up: 155 payloads and 52 validity vectors, read in place but for the
    # bytes -> bools of the validity; down: one batch and its offsets
    assert to["bridge.h2d"] == {"bytes": payload + 52 * 1200,
                                "transfers": 155 + 52}
    assert to["bridge.marshal_in"]["copied_bytes"] == 52 * 1200
    assert to["bridge.d2h"] == {"bytes": rows_bytes + 1201 * 4,
                                "transfers": 2}
    # back up: the batch as words, in place; down: every column's payload
    # and its validity vector
    assert back["bridge.marshal_in"] == {"bytes": rows_bytes + 1201 * 4,
                                         "copied_bytes": 0}
    assert back["bridge.h2d"]["transfers"] == 2
    assert back["bridge.d2h"] == {"bytes": payload + 155 * 1200,
                                  "transfers": 310}
    assert back["bridge.marshal_out"]["copied_bytes"] == payload + 155 * 1200
    flat = program_span.flatten([root])
    assert {"convert_to_rows", "convert_from_rows", "rowconv.fixed.launch"
            } <= {s["name"] for s in flat}


def test_configuration_states_what_the_references_derive():
    cell = harness.Cell(CELL)
    cfg = cell.config
    names = rooflines.schema(cfg)
    starts, sizes, voff, vbytes, row = references.jcudf_fixed_layout(names)
    assert (sum(sizes), row) == (cfg["payload_bytes_a_row"],
                                 cfg["row_bytes"]) == (532, 848)
    assert rooflines.row_bytes(cfg) == cfg["batch_bytes"] == 889192448
    assert set(names) <= set(cell.driver.TYPE_IDS)
    assert cfg["reduced"] == [] and cell.traffic["callers"] == 4
    assert cell.chips == 1 and "tail" not in cell.traffic
    assert cell.traffic["cycle_calls"] == 3
    # the shapes are the resident cell's own
    resident = harness.Cell("fixed155_roundtrip").config
    for key in ("rows", "columns", "type_cycle", "null_every", "valid_share"):
        assert cfg[key] == resident[key], key


def test_type_ids_are_the_programs():
    from spark_rapids_jni_tpu import types as T
    cell = harness.Cell(CELL)
    for name, tid in cell.driver.TYPE_IDS.items():
        assert int(getattr(T.TypeId, name.upper())) == tid, name


def test_cell_adds_data_files_and_one_driver_only():
    mine = [m for m in BENCH["per_layer"] if m["name"].startswith("cabi_")]
    assert sorted(m["name"] for m in mine) == [
        "cabi_d2h_ms", "cabi_h2d_ms", "cabi_marshal_in_ms",
        "cabi_marshal_out_ms", "cabi_roundtrip_unattributed_ms",
        "cabi_unattributed_ms"]
    for m in mine:
        assert (m["layer"], m["moves"], m["workloads"], m["source"]) == (
            "bridge", "transcode_gbps", [CELL], "program_span")
        data = json.load(open(os.path.join(
            ROOT, "chipbench", "metrics", m["name"] + ".json")))
        assert data["reader"] == "program_span"
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == "cabi_fixed155_1m"
    text = open(os.path.join(ROOT, "chipbench", "drivers",
                             "transcode_cabi.py")).read()
    assert "convert_to_rows" not in text and "import jax" not in text
