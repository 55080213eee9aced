"""The batches cell's own tests (``fixed212_roundtrip``), on the CPU at a
tiny size: ``JAX_PLATFORMS=cpu python3 -m pytest
chipbench/tests/test_batches_cell.py -q``.  Tier-1 collects them through
``tests/test_instrument_batches_cell.py``."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import (harness, references, references_batches,  # noqa: E402
                       rooflines)

CELL = "fixed212_roundtrip"
FAKE_CHIP = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
# 1200 rows of 1160 B under a cap of 1000 rows: the plain rule cuts at 992
TINY = {"rows": 1200, "max_batch_bytes": 1000 * 1160}


def tiny(cell):
    return {**cell.config, **TINY}


def passes(compared):
    return all(c["value"] <= c["limit"] for c in compared.values())


def moved(compared):
    return [k for k, c in compared.items() if c["value"] > c["limit"]]


@pytest.fixture(autouse=True)
def own_trace_dir(monkeypatch, tmp_path):
    """A traced run empties ``harness.TRACE_DIR`` first: two xdist workers
    tracing into the checkout's one directory empty each other's."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


def test_driver_agrees_with_reference_and_each_control_moves_its_number():
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)
    cell = harness.Cell(CELL)
    rec = harness.Recorder()
    state = cell.driver.setup(tiny(cell), cell.traffic, 2**31 + 34, rec)
    state.errors = []
    lat, _, work, failed, _ = harness.drive(cell, state, rec, 0.5)
    assert lat and not failed
    assert state.facts == {"row_bytes": 1200 * 1160, "batches": 2}
    assert work == 2.0 * state.facts["row_bytes"] * len(lat)
    got = cell.driver.answers(state)
    assert [g[1].shape[0] - 1 for g in got] == [992, 208]
    program = cell.driver.compare(state, got)
    assert set(program) == {"batch_boundary_mismatches",
                            "row_offset_mismatches", "row_byte_mismatches",
                            "roundtrip_mismatches"}
    assert passes(program), program
    for name, (answers, moves) in cell.driver.CONTROLS.items():
        control = cell.driver.compare(state, answers(state, got))
        assert moved(control) == [moves], (name, control)
    # the one ``chipbench.control`` runs is the rule without its rounding
    control = cell.driver.compare(
        state, cell.driver.control_answers(state, got))
    assert control["batch_boundary_mismatches"]["value"] == 1
    assert control["row_byte_mismatches"]["value"] == 0


def _run_broken(monkeypatch, patch):
    cell = harness.Cell(CELL)
    patch(monkeypatch)
    r = harness.run_cell(cell, 9, 0.3, False, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert r["calls"] > 0 and r["correct"] is False
    return r["compared"]


def test_planted_fault_in_the_second_batchs_bytes_reads_one(monkeypatch):
    import spark_rapids_jni_tpu as sr
    real = sr.convert_to_rows

    def to_rows(table, *a, **k):
        batches = real(table, *a, **k)
        b = batches[1]
        batches[1] = type(b)(b.data.at[7].set(b.data[7] ^ 1), b.offsets)
        return batches
    compared = _run_broken(
        monkeypatch, lambda m: m.setattr(sr, "convert_to_rows", to_rows))
    assert compared["row_byte_mismatches"]["value"] == 1
    assert compared["batch_boundary_mismatches"]["value"] == 0
    assert compared["row_offset_mismatches"]["value"] == 0
    # the flipped byte is column 1's (int32 at bytes 4..8), which comes back
    assert compared["roundtrip_mismatches"]["value"] == 1


def test_planted_fault_in_the_second_answer_reads_one(monkeypatch):
    import spark_rapids_jni_tpu as sr
    from spark_rapids_jni_tpu import Column, Table
    real = sr.convert_from_rows

    def from_rows(batch, schema):
        back = real(batch, schema)
        if batch.num_rows != 208:
            return back
        cols = list(back.columns)
        c = cols[2]
        cols[2] = Column(c.dtype, c.data.at[5].add(1), validity=c.validity)
        return Table(cols)
    compared = _run_broken(
        monkeypatch, lambda m: m.setattr(sr, "convert_from_rows", from_rows))
    assert moved(compared) == ["roundtrip_mismatches"]
    assert compared["roundtrip_mismatches"]["value"] == 1


def test_one_batch_where_two_are_due_is_caught(monkeypatch):
    import spark_rapids_jni_tpu as sr
    real = sr.convert_to_rows
    compared = _run_broken(
        monkeypatch, lambda m: m.setattr(
            sr, "convert_to_rows", lambda table, *a, **k: real(table)))
    assert compared["batch_boundary_mismatches"]["value"] > 0
    assert compared["row_byte_mismatches"]["value"] == 0


def test_whole_run_reports_the_cells_lines():
    cell = harness.Cell(CELL)
    r = harness.run_cell(cell, 2**31 + 5, 1.0, True, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert r["correct"] and r["calls"] >= 2 and list(r)[-1] == "compared"
    assert set(r["end_to_end_traced"]) == {"transcode_gbps", "setup_s"}
    # on the CPU the trace has no device plane: the roofline is left out
    assert set(r["metrics"]) == set(cell.per_layer) - {"transcode_roofline"}
    assert all(c["limit"] == 0 and c["value"] == 0
               for c in r["compared"].values())
    # one prepare and one launch a batch each way, inside every call
    assert r["metrics"]["batch_launch_ms"]["value"] > 0
    assert (r["metrics"]["batch_launch_ms"]["value"]
            <= r["metrics"]["to_rows_ms"]["value"]
            + r["metrics"]["from_rows_ms"]["value"])


def test_program_spans_of_a_call(monkeypatch):
    from spark_rapids_jni_tpu.utils import metrics
    from chipbench.readers import program_span
    metrics.set_enabled(True)
    cell = harness.Cell(CELL)
    rec = harness.Recorder()
    state = cell.driver.setup(tiny(cell), cell.traffic, 7, rec)
    t0 = time.monotonic()
    cell.driver.call(state, 0, 0, rec)
    trees = metrics.span_roots(window_s=time.monotonic() - t0)
    call = [t for t in trees if t["name"] == "chipbench.roundtrip"][-1]
    spans = program_span.flatten([call])
    prepare = [s for s in spans if s["name"] == "rowconv.fixed.prepare"]
    launch = [s for s in spans if s["name"] == "rowconv.fixed.launch"]
    assert [p["attrs"] for p in prepare] == [{"batches": 2, "eager_ops": 0}]
    assert sorted((s["attrs"]["direction"], s["attrs"]["rows"],
                   s["attrs"]["bytes"]) for s in launch) == [
        ("from", 208, 208 * 1160), ("from", 992, 992 * 1160),
        ("to", 208, 208 * 1160), ("to", 992, 992 * 1160)]
    assert sorted(s["attrs"]["batch"] for s in launch
                  if s["attrs"]["direction"] == "to") == [0, 1]


def test_per_layer_is_the_seven_names():
    assert set(harness.Cell(CELL).per_layer) == {
        "to_rows_ms", "from_rows_ms", "to_rows_dispatch_ms",
        "transcode_roofline", "batch_prepare_ms", "batch_launch_ms",
        "from_rows_dispatch_total_ms"}


@pytest.mark.parametrize("row_size,n,cap,want", [
    # the cell: (2**31 - 1) // 1160 = 1851279, down to 1851264
    (1160, 2 << 20, 2**31 - 1, [0, 1851264, 2097152]),
    # the source's own larger axis value: three batches
    (1160, 4 << 20, 2**31 - 1, [0, 1851264, 3702528, 4194304]),
    (1160, 1 << 20, 2**31 - 1, [0, 1048576]),                # one batch
    (1160, 1851279, 2**31 - 1, [0, 1851279]),   # fits whole: not rounded
    (1160, 1851280, 2**31 - 1, [0, 1851264, 1851280]),
    (8, 100, 8 * 40, [0, 32, 64, 100]),         # 36 rows left fit
    (8, 100, 8 * 32, [0, 32, 64, 96, 100]),     # exactly one multiple fits
    (8, 100, 8 * 32 - 1, [0, 31, 62, 93, 100]),  # under one: not rounded
    (8, 64, 8 * 32, [0, 32, 64]),
    (8, 0, 100, [0, 0]),
])
def test_plain_boundary_rule_by_hand(row_size, n, cap, want):
    assert references_batches.plain_batch_boundaries(row_size, n,
                                                     cap) == want
    assert references_batches.boundary_mismatches(
        np.diff(want).tolist(), row_size, n, cap) == 0


def test_unrounded_rule_and_the_mismatch_counts_by_hand():
    assert references_batches.plain_batch_boundaries(
        1160, 2 << 20, 2**31 - 1, round_to_32=False) == [0, 1851279, 2097152]
    # one boundary off
    assert references_batches.boundary_mismatches(
        [1851279, 245873], 1160, 2 << 20, 2**31 - 1) == 1
    # one batch where two are due: a batch too few, a boundary off, and the
    # batch is one an int32 offset cannot address
    assert references_batches.boundary_mismatches(
        [2 << 20], 1160, 2 << 20, 2**31 - 1) == 3
    with pytest.raises(ValueError):
        references_batches.plain_batch_boundaries(1160, 5, 1000)
    good = (np.arange(5, dtype=np.int64) * 1160).astype(np.int32)
    assert references_batches.offset_mismatches(good, 4, 1160) == 0
    assert references_batches.offset_mismatches(good + 1, 4, 1160) == 5
    assert references_batches.offset_mismatches(good.astype(np.int64), 4,
                                                1160) == 5
    assert references_batches.offset_mismatches(good[:-1], 4, 1160) == 5


def test_configuration_states_what_the_references_derive():
    cell = harness.Cell(CELL)
    cfg, derived = cell.config, cell.config["derived"]
    names = rooflines.schema(cfg)
    starts, sizes, voff, vbytes, row = references.jcudf_fixed_layout(names)
    assert (row, row // 4) == (derived["row_bytes"], derived["row_words"])
    assert sum(sizes) == derived["payload_bytes_per_row"]
    assert len(range(0, cfg["columns"], cfg["null_every"])) == derived[
        "nullable_columns"]
    bounds = references_batches.plain_batch_boundaries(
        row, cfg["rows"], cfg["max_batch_bytes"])
    assert [{"rows": [lo, hi], "bytes": (hi - lo) * row}
            for lo, hi in zip(bounds[:-1], bounds[1:])] == derived["batches"]
    assert rooflines.row_bytes(cfg) == derived["table_row_bytes"]
    assert rooflines.transcode_roundtrip(cfg) == derived[
        "least_hbm_bytes_per_call"]
    assert cfg["max_batch_bytes"] == 2**31 - 1 and cfg["reduced"] == ["rows"]


def test_references_import_nothing_of_the_program():
    for fn in ("references_batches.py",):
        text = open(os.path.join(ROOT, "chipbench", fn)).read()
        assert "spark_rapids_jni_tpu" not in text and "import jax" not in text
