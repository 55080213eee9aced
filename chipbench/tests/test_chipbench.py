"""chipbench's own tests: ``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q``.

Tier-1 (``pytest tests/``) does not collect this file.  Everything here runs
on the CPU at a tiny size: it shows that the harness, the drivers, the
comparison and the reducers are right, never how fast the chip is.
"""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, references, rooflines, trace  # noqa: E402

TINY = {"nvbench_fixed155_1m": {"rows": 3000},
        "tpch_q6_sf1": {"rows": 50_000, "row_group_rows": 16_384},
        "tpcds_star_10m": {"sales_rows": 30_000, "items": 2000,
                           "stores": 12}}
CELLS = ["fixed155_roundtrip", "q6_scan", "star_streams4"]
# the shape the recorded trace was taken at (PR 25's first chip calls; no
# cell of BENCHMARK.json: no public source has it)
FIXED12 = {"rows": 1_000_000, "columns": 12, "null_every": 3,
           "type_cycle": ["int8", "int16", "int32", "int64", "float32",
                          "float64", "bool8"]}
FAKE_CHIP = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TRACE = os.path.join(ROOT, "chipbench", "testdata",
                     "fixed12_quarter_second.xplane.pb")


def tiny(cell):
    return {**cell.config, **TINY[cell.config["name"]]}


def passes(compared):
    return all(c["value"] <= c["limit"] for c in compared.values())


@pytest.fixture(autouse=True)
def own_trace_dir(monkeypatch, tmp_path):
    """A traced run empties ``harness.TRACE_DIR`` first: two xdist workers
    tracing into the checkout's one directory empty each other's."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


# --- (a) every driver: setup -> call -> check, and its control ---------------

@pytest.mark.parametrize("name", [c for c in CELLS if c in
                                  {w["name"] for w in BENCH["workloads"]}])
def test_driver_agrees_with_reference_and_control_fails(name):
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)
    cell = harness.Cell(name)
    rec = harness.Recorder()
    state = cell.driver.setup(tiny(cell), cell.traffic, 2**31 + 11, rec)
    state.errors = []
    lat, _, work, failed, elapsed = harness.drive(cell, state, rec, 0.5)
    assert lat and not failed and work > 0 and elapsed >= 0.5
    # every stream ended on a whole pass over its queries
    assert len(lat) % cell.traffic.get("cycle_calls", 1) == 0
    got = cell.driver.answers(state)
    program = cell.driver.compare(state, got)
    assert passes(program), program
    control = cell.driver.compare(
        state, cell.driver.control_answers(state, got))
    assert not passes(control), control


@pytest.mark.skipif("star_streams4" not in
                    {w["name"] for w in BENCH["workloads"]},
                    reason="the served-SQL cell is not in BENCHMARK.json")
@pytest.mark.parametrize("data_seed", [7, 2**31 + 3])
def test_sql_answers_agree_with_the_twins_on_other_data_sets(data_seed):
    """The cell's data set is the configuration's (``data_seed``), so its
    runs compare one data set in many row orders.  Other data sets, at a
    tiny size on the CPU: join and group sizes differ with each."""
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)
    cell = harness.Cell("star_streams4")
    rec = harness.Recorder()
    state = cell.driver.setup({**tiny(cell), "data_seed": data_seed},
                              cell.traffic, 3, rec)
    state.errors = []
    lat, _, _, failed, _ = harness.drive(cell, state, rec, 0.3)
    got = cell.driver.answers(state)
    assert lat and not failed and passes(cell.driver.compare(state, got))
    assert not passes(cell.driver.compare(
        state, cell.driver.control_answers(state, got)))


def test_whole_run_traced_on_cpu_reports_spans_and_no_device_share():
    cell = harness.Cell("fixed155_roundtrip")
    r = harness.run_cell(cell, 5, 0.3, True, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert r["correct"] and list(r)[-1] == "compared"
    # host-clock and program spans are read; what needs a device plane (the
    # roofline) is left out, whatever later PRs add beside it
    assert {"to_rows_ms", "from_rows_ms"} <= set(r["metrics"])
    assert not any(cell.per_layer[name]["source"] == "device_trace"
                   for name in r["metrics"])
    assert "busy_s" not in r["device"] and "breakdown" not in r


def test_end_to_end_line_has_the_cells_metrics():
    cell = harness.Cell("fixed155_roundtrip")
    r = harness.run_cell(cell, 6, 0.3, False, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert set(r["metrics"]) == {"transcode_gbps", "call_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] == r["calls"] and r["failed"] == 0


def test_every_cell_reports_the_end_to_end_metrics_benchmark_json_lists():
    for w in BENCH["workloads"]:
        traffic = harness.Cell(w["name"]).traffic
        reported = {traffic["rate"]["metric"], "setup_s"} | (
            {traffic["tail"]["metric"]} if "tail" in traffic else set())
        listed = {m["name"] for m in BENCH["end_to_end"]
                  if w["name"] in m.get("workloads", [w["name"]])}
        assert reported == listed, w["name"]


def test_command_fails_without_a_chip_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(BENCH["command"] + ["--workload", "fixed155_roundtrip",
                                           "--seed", "1", "--seconds", "1",
                                           "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(BENCH["command"] + ["--workload", "fixed155_roundtrip",
                                           "--seed", "1", "--seconds", "1",
                                           "--trace", "0"],
                       cwd=tmp_path, env={**os.environ,
                                          "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout.strip() == ""


# --- the timed path broken underneath: correct comes out false ----------------

def _run_broken(name, monkeypatch, patch):
    cell = harness.Cell(name)
    patch(monkeypatch)
    r = harness.run_cell(cell, 9, 0.3, False, time.time(), FAKE_CHIP,
                         config=tiny(cell))
    assert r["calls"] > 0 and r["correct"] is False
    return r["compared"]


def test_altered_row_byte_is_caught(monkeypatch):
    import spark_rapids_jni_tpu as sr
    real = sr.convert_to_rows

    def to_rows(table, *a, **k):
        batches = real(table, *a, **k)
        b = batches[0]
        batches[0] = type(b)(b.data.at[7].set(b.data[7] ^ 1), b.offsets)
        return batches
    compared = _run_broken(
        "fixed155_roundtrip", monkeypatch,
        lambda m: m.setattr(sr, "convert_to_rows", to_rows))
    assert compared["row_byte_mismatches"]["value"] == 1


def test_altered_column_on_the_way_back_is_caught(monkeypatch):
    import spark_rapids_jni_tpu as sr
    from spark_rapids_jni_tpu import Column, Table
    real = sr.convert_from_rows

    def from_rows(batch, schema):
        back = real(batch, schema)
        cols = list(back.columns)
        c = cols[2]
        cols[2] = Column(c.dtype, c.data.at[5].add(1), validity=c.validity)
        return Table(cols)
    compared = _run_broken(
        "fixed155_roundtrip", monkeypatch,
        lambda m: m.setattr(sr, "convert_from_rows", from_rows))
    assert compared["roundtrip_mismatches"]["value"] == 1
    assert compared["row_byte_mismatches"]["value"] == 0


def test_altered_scan_answer_is_caught(monkeypatch):
    from spark_rapids_jni_tpu.models import q6
    real = q6.run

    def run(raw, lo, hi):
        revenue, matched = real(raw, lo, hi)
        return revenue * (1 + 1e-9), matched
    compared = _run_broken("q6_scan", monkeypatch,
                           lambda m: m.setattr(q6, "run", run))
    assert compared["revenue_rel_gap"]["value"] > 5e-10
    assert compared["matched_rows_gap"]["value"] == 0


@pytest.mark.skipif("star_streams4" not in
                    {w["name"] for w in BENCH["workloads"]},
                    reason="the served-SQL cell is not in BENCHMARK.json")
def test_altered_sql_answer_is_caught(monkeypatch):
    from chipbench.drivers import sql
    real = sql._host_answer

    def host_answer(out, n_keys):
        keys, sums = real(out, n_keys)
        return keys, sums * (1 + 1e-9)
    # the driver's own read-back is the nearest seam that is the same for
    # every query: the answer is altered before anything compares it
    compared = _run_broken(
        "star_streams4", monkeypatch,
        lambda m: m.setattr(sql, "_host_answer", host_answer))
    assert compared["sum_rel_gap"]["value"] > 5e-10


def test_a_program_compiled_inside_the_window_is_caught(monkeypatch):
    import jax
    import jax.numpy as jnp
    from chipbench.drivers import transcode
    real = transcode.call

    def call(state, caller, i, rec):
        if i == 3:                       # a shape the warm-up never saw
            jax.jit(lambda x: x * 3 + i)(jnp.ones(17)).block_until_ready()
        return real(state, caller, i, rec)
    compared = _run_broken("fixed155_roundtrip", monkeypatch,
                           lambda m: m.setattr(transcode, "call", call))
    assert compared["compiles_in_window"]["value"] >= 1
    assert compared["row_byte_mismatches"]["value"] == 0


# --- (b) the trace reducer on a recorded trace ---------------------------------

def test_trace_reducer_on_recorded_chip_trace():
    """A 12-column round trip (FIXED12), 0.25 s, one v5e (PR 25's chip call 2).  The numbers
    were worked out apart, by a plain sweep over the 1586 events of the
    device plane's 'XLA Ops' line clipped to the cb:window span."""
    r = trace.reduce_trace(TRACE)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.214389299, abs=1e-9)
    assert r["window_s"] == pytest.approx(0.267246052, abs=1e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.19778310, abs=1e-7)
    (op1, t1), (op2, t2) = r["device_ops"][:2]
    assert op1.startswith("%fusion.33 = (u32[1000000,1]")
    assert t1 == pytest.approx(0.060581107, abs=1e-9)
    assert op2.startswith("%slice.28 = u32[1000000,2]")
    assert t2 == pytest.approx(0.020257616, abs=1e-9)
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert all(len(name) <= trace.NAME_CHARS for name, _ in r["device_ops"])
    # every long gap of this run lay inside a from_rows or to_rows span
    assert {g[0] for g in r["idle_gaps"]} <= {"from_rows", "to_rows"}
    gaps = [g[1] for g in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] < 0.01


def test_union_of_nested_and_overlapping_intervals():
    merged = trace.union_intervals(np.array([0., 1, 5, 6, 20]),
                                   np.array([10., 2, 12, 7, 25]))
    assert merged.tolist() == [[0, 12], [20, 25]]


def test_roofline_reader_from_the_recorded_trace():
    from chipbench.readers import roofline, trace_busy
    ctx = {"trace": trace.reduce_trace(TRACE), "calls": 13,
           "config": FIXED12, "facts": {}, "device_kind": "TPU v5 lite"}
    busy_ms = trace_busy.read(ctx, {})
    assert busy_ms == pytest.approx(214.389299 / 13)
    # 214 MB least (2 x 1M x 107 B) over 819 GB/s = 0.2613 ms against 16.49 ms busy a call
    share = roofline.read(ctx, {"bytes": "transcode_roundtrip"})
    assert share == pytest.approx(100 * (214e6 / 819e9) / (busy_ms / 1e3))
    assert 1.5 < share < 1.6
    ctx["device_kind"] = "TPU v9"
    with pytest.raises(KeyError):
        roofline.read(ctx, {"bytes": "transcode_roundtrip"})
    ctx["trace"] = {"busy_s": None}
    assert roofline.read(ctx, {"bytes": "transcode_roundtrip"}) is None


# --- (c) bytes of the rooflines, worked by hand from the shapes ----------------

def test_layout_and_roofline_bytes_by_hand():
    cfg = {c["name"]: json.load(open(os.path.join(ROOT, c["file"])))
           for c in BENCH["configs"]}
    # 12 columns of the recorded trace's shape: i8@0 i16@2 i32@4 i64@8
    # f32@16 f64@24 b8@32 i8@33 i16@34 i32@36 i64@40 f32@48 -> data ends at
    # 52, 2 validity bytes, row 56
    starts, sizes, voff, vbytes, row = references.jcudf_fixed_layout(
        rooflines.schema(FIXED12))
    assert starts == [0, 2, 4, 8, 16, 24, 32, 33, 34, 36, 40, 48]
    assert (voff, vbytes, row) == (52, 2, 56)
    # payload 28 + (1+2+4+8+4) = 47 B, nullable columns 0,3,6,9 = 4 B a row
    assert rooflines.row_bytes(FIXED12) == 56_000_000
    assert rooflines.transcode_roundtrip(FIXED12) == 2 * 1_000_000 * (
        47 + 4 + 56)
    # 155 columns of the source's cycle: i8@0 i32@4 i16@8 i64@16 i32@24
    # b8@28 u16@30 u8@32 u64@40, the next cycle at 48: 17 cycles = 816 B,
    # then i8@816 i32@820 -> data ends at 824, 20 validity bytes, row 848;
    # payload 17 * 31 + 1 + 4 = 532 B, nullable columns 0,3,..,153 = 52
    f155 = cfg["nvbench_fixed155_1m"]
    starts, sizes, voff, vbytes, row = references.jcudf_fixed_layout(
        rooflines.schema(f155))
    assert starts[:10] == [0, 4, 8, 16, 24, 28, 30, 32, 40, 48]
    assert starts[-2:] == [816, 820] and sum(sizes) == 532
    assert (voff, vbytes, row) == (824, 20, 848)
    assert len(range(0, 155, 3)) == 52 and f155["rows"] == 1 << 20
    assert rooflines.row_bytes(f155) == 848 * (1 << 20) == 889_192_448
    assert rooflines.transcode_roundtrip(f155) == 2 * (1 << 20) * (
        532 + 52 + 848)
    # the source's 212-column table: 23 cycles = 1104 B, five more columns
    # end at 1132, 27 validity bytes -> 1160 B (over the 1 KB limit the
    # program had until PR 28)
    assert references.jcudf_fixed_layout(
        [f155["type_cycle"][i % 9] for i in range(212)])[4] == 1160
    if "tpch_q6_sf1" in cfg:
        assert rooflines.q6_scan(cfg["tpch_q6_sf1"],
                                 {"parquet_bytes": 75_000_000}) == (
            75_000_000 + 2 * 6_000_000 * 28)


# --- (d) a later PR adds files and entries, and edits no file that is there ----

def test_new_config_cell_and_metric_as_files_only(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "chipbench").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    cfg = json.load(open(os.path.join(
        ROOT, "chipbench/configs/nvbench_fixed155_1m.json")))
    cfg.update(name="nvbench_fixed30_4k", columns=30, rows=4096)
    (tmp_path / "chipbench/configs/nvbench_fixed30_4k.json").write_text(
        json.dumps(cfg))
    bench["configs"].append({
        "name": "nvbench_fixed30_4k", "source": cfg["source"],
        "file": "chipbench/configs/nvbench_fixed30_4k.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "fixed30_roundtrip", "config": "nvbench_fixed30_4k",
        "traffic": "roundtrip_c1", "chips": 1, "why": "test"})
    # the new cell joins a metric that is there (its own file names it) and a
    # new metric joins the new cell (the metric's file names the cell)
    (tmp_path / "chipbench/workloads").mkdir(exist_ok=True)
    (tmp_path / "chipbench/workloads/fixed30_roundtrip.json").write_text(
        json.dumps({"metrics": ["to_rows_ms"]}))
    (tmp_path / "chipbench/metrics/window_span_ms.json").write_text(
        json.dumps({"reader": "span", "params": {"span": "window"},
                    "unit": "ms", "workloads": ["fixed30_roundtrip"]}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.Cell("fixed30_roundtrip", root=str(tmp_path))
    assert set(cell.per_layer) == {"to_rows_ms", "window_span_ms"}
    r = harness.run_cell(cell, 3, 0.3, True, time.time(), FAKE_CHIP)
    assert r["correct"] and set(r["metrics"]) == {"to_rows_ms",
                                                 "window_span_ms"}
    after = {p: p.read_bytes() for p in before}
    assert after == before


# --- (e) BENCHMARK.json against the contract's rules and the files -------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_the_contracts_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(_line(w) for w in BENCH["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("chipbench/") and len(c["reduced"]) <= 16
        data = json.load(open(os.path.join(ROOT, c["file"])))
        assert data["source"] == c["source"] and data["reduced"] == c["reduced"]
        assert all(NAME.match(k) and k in data for k in c["reduced"])
    configs = {c["name"] for c in BENCH["configs"]}
    assert len(configs) == len(BENCH["configs"])
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(set(cells)) == len(cells) and 1 <= len(cells) <= 24
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(cells)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "traffic", w["traffic"] + ".json"))
    assert {w["config"] for w in BENCH["workloads"]} == configs
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert _line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert m["workloads"] and set(m["workloads"]) <= set(moved)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_benchmark_json_agrees_with_the_files_the_harness_reads():
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    found = {}
    for w in BENCH["workloads"]:
        cell = harness.Cell(w["name"])
        rate = cell.traffic["rate"]
        e2e = {m["name"]: m for m in BENCH["end_to_end"]}
        assert w["name"] in e2e[rate["metric"]]["workloads"]
        assert e2e[rate["metric"]]["unit"] == rate["unit"]
        assert cell.per_layer, "every cell reports a per-layer metric"
        for name, metric in cell.per_layer.items():
            found.setdefault(name, set()).add(w["name"])
            assert metric["unit"] == listed[name]["unit"]
            assert metric["moves"] == listed[name]["moves"] == rate["metric"]
            assert metric["layer"] == listed[name]["layer"]
    assert {k: sorted(v) for k, v in found.items()} == {
        k: sorted(m["workloads"]) for k, m in listed.items()}


def test_chipbench_imports_nothing_of_the_old_benchmarks():
    bad = re.compile(r"^\s*(from|import)\s+(benchmarks|tools|bench|chip_smoke)\b",
                     re.M)
    for dirpath, _, files in os.walk(os.path.join(ROOT, "chipbench")):
        for fn in files:
            if fn.endswith(".py") and "tests" not in dirpath:
                text = open(os.path.join(dirpath, fn)).read()
                assert not bad.search(text), fn
    for fn in ("references.py", "datagen.py", "peaks.py", "rooflines.py"):
        text = open(os.path.join(ROOT, "chipbench", fn)).read()
        assert "spark_rapids_jni_tpu" not in text, fn
