"""The readers of the program's own spans: ``program_span`` on a synthetic
span store, ``idle_under_spans`` and ``idle_report`` on the recorded chip
trace, and every metric file against ``BENCHMARK.json``.

``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q``; tier-1 does not
collect this file.
"""

import glob
import json
import os
import statistics
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, idle_report, trace  # noqa: E402
from chipbench.readers import idle_under_spans, program_span  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TRACE = os.path.join(ROOT, "chipbench", "testdata",
                     "fixed12_quarter_second.xplane.pb")
NEW = ["scan_footer_ms", "scan_walk_ms", "scan_decompress_ms",
       "scan_walk_wait_ms", "scan_stage_ms", "scan_upload_ms",
       "scan_launch_ms", "scan_answer_wait_ms", "scan_unattributed_ms",
       "scan_idle_attributed", "to_rows_dispatch_ms",
       "from_rows_dispatch_ms", "sql_frontend_ms"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(autouse=True)
def own_trace_dir(monkeypatch, tmp_path):
    """A traced run empties ``harness.TRACE_DIR`` first: two xdist workers
    tracing into the checkout's one directory empty each other's."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


# --- program_span on a synthetic store ---------------------------------------

def _span(name, start, dur, tid=1, attrs=None, children=(), rid=None):
    d = {"name": name, "start_ms": start, "dur_ms": dur, "tid": tid,
         "rid": rid}
    if attrs:
        d["attrs"] = attrs
    if children:
        d["children"] = list(children)
    return d


def _call(t, stall=0):
    """One call of 100 ms at ``t``, request id ``t``: 10 footer, a walker
    thread (tid 2) of 30 + 20 under the scan, the caller waiting 25 + 15 and
    staging 2 x 5, 20 answer; 7 ms of the scan and 17 ms of the call under
    no leaf.  ``stall`` lengthens the answer, and the call."""
    scan = _span("scan", t, 70, children=[
        _span("footer", t, 10),
        _span("walk", t + 10, 30, tid=2, attrs={"decompress_ms": 18.0}),
        _span("walk", t + 40, 20, tid=2, attrs={"decompress_ms": 12.0}),
        _span("walk_wait", t + 12, 25),
        _span("stage", t + 37, 5),
        _span("walk_wait", t + 42, 15),
        _span("stage", t + 57, 5),
        _span("unlabelled", t + 62, 6, children=[_span("leaf", t + 63, 3)])])
    call = _span("call", t, 100 + stall, children=[
        scan, _span("answer", t + 75, 20 + stall)])
    for s in program_span.flatten([call]):
        s["rid"] = t
    return call


def _store(trees):
    def span_roots(window_s=None):
        span_roots.asked = window_s
        return trees
    return types.SimpleNamespace(span_roots=span_roots)


def test_program_span_stats_on_a_synthetic_store():
    store = _store([_call(0.0), _call(200.0), _call(400.0, stall=300),
                    _span("other", 900, 1, rid=9)])
    ctx = {"program_metrics": store, "calls": 3,
           "window_start_monotonic": time.monotonic() - 7.0}

    def read(span, stat, **more):
        return program_span.read(ctx, {"span": span, "stat": stat, **more})

    assert read("footer", "total_per_call") == 10
    assert store.span_roots.asked == pytest.approx(7.0, abs=0.5)
    assert read("walk", "total_per_call") == 50          # the other thread's
    assert read("walk", "total_per_call", attr="decompress_ms") == 30
    assert read("walk_wait", "total_per_call") == 40
    assert read("walk_wait", "median") == 20
    assert read("answer", "median") == 20            # one call stalled
    assert read("answer", "total_per_call") == 20
    # 100 less footer 10, waits 40, stages 10, the leaf 3, answer 20: the
    # walker's spans, on another thread, cover nothing of the caller's time
    assert read("call", "self") == pytest.approx(17.0)
    assert read("scan", "self") == pytest.approx(7.0)
    assert read("no.such.span", "median") is None
    assert read("walk_wait", "median", attr="not_there") is None
    with pytest.raises(ValueError):
        read("footer", "mode")


def test_program_span_gives_nothing_for_a_store_without_windows_or_tids():
    ctx = {"calls": 3, "window_start_monotonic": time.monotonic()}
    old = types.SimpleNamespace(span_roots=lambda: [_call(0.0)])
    assert program_span.read(dict(ctx, program_metrics=old),
                             {"span": "call", "stat": "median"}) is None
    bare = {"name": "call", "start_ms": 0.0, "dur_ms": 5.0}
    assert program_span.read(dict(ctx, program_metrics=_store([bare])),
                             {"span": "call", "stat": "median"}) is None


def test_program_span_reads_the_programs_store_from_the_windows_start():
    from spark_rapids_jni_tpu.utils import metrics
    was = metrics.enabled()
    metrics.set_enabled(True)
    try:
        with metrics.span("warmup.only"):
            pass
        time.sleep(0.02)
        ctx = {"program_metrics": metrics, "calls": 2,
               "window_start_monotonic": time.monotonic()}
        for _ in range(2):
            with metrics.span("a.call"):
                for _ in range(2):
                    with metrics.span("a.part", n=4):
                        time.sleep(0.01)
        total = program_span.read(ctx, {"span": "a.part",
                                        "stat": "total_per_call"})
        assert 20 <= total < 60
        assert program_span.read(ctx, {"span": "a.part", "attr": "n",
                                       "stat": "total_per_call"}) == 8
        assert 0 <= program_span.read(ctx, {"span": "a.call",
                                            "stat": "self"}) < 5
        assert program_span.read(ctx, {"span": "warmup.only",
                                       "stat": "median"}) is None
    finally:
        metrics.set_enabled(was)


# --- idle_under_spans and idle_report on the recorded trace ------------------

def _by_hand():
    """Idle time of the recorded trace inside its window, and the idle time
    under the program's one span kind of that run (``convert_*``, not yet
    prefixed then), by a plain sweep."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(TRACE)
    ops, spans, window = [], [], None
    for plane in data.planes:
        for ln in plane.lines:
            for e in ln.events:
                if plane.name == "/device:TPU:0" and ln.name == "XLA Ops":
                    ops.append((e.start_ns, e.start_ns + e.duration_ns))
                elif e.name == "cb:window":
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith("convert_"):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
    lo, hi = window
    step = 1000.0                                   # ns: a 1 us raster
    grid = np.arange(lo, hi, step) + step / 2
    busy = np.zeros(grid.size, bool)
    for a, b in ops:
        busy[np.searchsorted(grid, a):np.searchsorted(grid, b)] = True
    under = np.zeros(grid.size, bool)
    for a, b in spans:
        under[np.searchsorted(grid, a):np.searchsorted(grid, b)] = True
    return ((~busy).sum() * step, (~busy & under).sum() * step)


@pytest.fixture()
def recorded(monkeypatch, tmp_path):
    """The recorded trace where the harness would have left a run's, read
    as if its program had prefixed its spans."""
    monkeypatch.setattr(idle_under_spans, "PROGRAM", "convert_")
    idle_under_spans.load.cache_clear()
    day = tmp_path / "plugins" / "profile" / "2026_01_01"
    day.mkdir(parents=True)
    os.symlink(TRACE, day / "host.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    yield str(day / "host.xplane.pb")
    idle_under_spans.load.cache_clear()


def test_idle_under_spans_on_the_recorded_trace(recorded):
    idle_ns, under_ns = _by_hand()
    reduced = trace.reduce_trace(TRACE)
    assert idle_ns == pytest.approx(
        (reduced["window_s"] - reduced["busy_s"]) * 1e9, rel=2e-3)
    # the spans of that run are convert_to_rows / convert_from_rows: "to_rows"
    # and "from_rows" once the prefix is cut; both lie on the caller's line
    share = idle_under_spans.read({}, {"root": "to_rows"})
    assert share == pytest.approx(100 * under_ns / idle_ns, abs=0.3)
    assert 5 < share < 95
    assert idle_under_spans.read({}, {"root": "no.such.root"}) is None
    loaded = idle_under_spans.load(recorded)
    assert idle_under_spans.load(recorded) is loaded        # parsed once
    assert sum(idle_under_spans.measure(p) for p in loaded["idle"]) == \
        pytest.approx((reduced["window_s"] - reduced["busy_s"]) * 1e9)


def test_idle_under_spans_gives_nothing_without_a_trace_or_a_device(
        monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "none"))
    assert idle_under_spans.read({}, {"root": "q6.run"}) is None


def test_idle_report_table_on_the_recorded_trace(recorded, capsys):
    rows, window_s, idle_s, named_s = idle_report.table(recorded)
    reduced = trace.reduce_trace(TRACE)
    assert window_s == pytest.approx(reduced["window_s"])
    assert idle_s == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    by = {r[0]: r for r in rows}
    assert set(by) == {"to_rows", "from_rows"}
    for name, count, total, own, idle, idle_own in rows:
        assert count == 13 and 0 < idle <= total
        assert own == total and idle_own == idle       # no spans inside
    _, under_ns = _by_hand()
    assert named_s == pytest.approx(under_ns / 1e9, abs=2e-4)
    assert sum(r[4] for r in rows) == pytest.approx(named_s)
    assert idle_report.main([recorded]) == 0
    out = capsys.readouterr().out
    assert "to_rows" in out and "(idle under no program span)" in out
    assert idle_report.main([]) == 2


def test_nesting_and_interval_measures():
    spans = idle_under_spans.nest([("b", 2, 5), ("a", 0, 10), ("c", 3, 4),
                                   ("d", 6, 9), ("e", 12, 13)])
    assert [(n, kids) for n, _, _, kids in spans] == [
        ("a", [1, 3]), ("b", [2]), ("c", []), ("d", []), ("e", [])]
    merged = np.array([[0., 4], [10, 12]])
    got = idle_under_spans.overlap(merged, [-1, 2, 3, 11, 20], [1, 3, 11, 30,
                                                                 21])
    assert got.tolist() == [1, 1, 2, 1, 0]
    assert idle_under_spans.overlap(np.zeros((0, 2)), [0], [5]).tolist() == [0]


# --- the traced run on the CPU: the span metrics read, the device one absent --

def test_traced_scan_on_cpu_reports_the_span_metrics_and_they_add_up():
    cell = harness.Cell("q6_scan")
    config = {**cell.config, "rows": 400_000, "row_group_rows": 131_072}
    fake = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    r = harness.run_cell(cell, 2**31 + 11, 1.0, True, time.time(), fake,
                         config=config)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(m) == set(n for n in NEW if n.startswith("scan_")) - {
        "scan_idle_attributed"}                   # no device plane on the CPU
    assert all(v >= 0 for v in m.values())
    assert m["scan_decompress_ms"] <= m["scan_walk_ms"]
    # what holds however loaded the machine is (the sum of the parts' medians
    # against the median call does not): in every call the leaves under
    # q6.run on its thread cover no more than q6.run, which lies inside
    # chipbench's call
    from spark_rapids_jni_tpu.utils import metrics
    runs = sorted((s for s in program_span.flatten(metrics.span_roots())
                   if s["name"] == "q6.run"), key=lambda s: s["start_ms"])
    assert len(runs) >= r["calls"] > 0
    for s in runs:
        assert 0 <= program_span.unattributed_ms(s) <= s["dur_ms"]
    assert statistics.median(s["dur_ms"] for s in runs[-r["calls"]:]) <= \
        r["median_call_ms"]
    path = trace.find_xplane(harness.TRACE_DIR)
    from jax.profiler import ProfileData
    names = {e.name for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events}
    assert {"srjt:q6.run", "srjt:q6.answer", "srjt:parquet.scan.walk",
            "srjt:parquet.scan.upload", "cb:scan"} <= names


# --- every new metric file against BENCHMARK.json ----------------------------

@pytest.mark.parametrize("name", NEW)
def test_new_metric_file_agrees_with_benchmark_json(name):
    metric = json.load(open(os.path.join(ROOT, "chipbench", "metrics",
                                         name + ".json")))
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert set(metric) == {"reader", "params", "unit", "better", "source",
                           "layer", "moves", "workloads"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert metric[key] == entry[key], key
    assert metric["source"] in SOURCES
    assert os.path.exists(os.path.join(ROOT, "chipbench", "readers",
                                       metric["reader"] + ".py"))
    # the cells that report it, as harness.Cell resolves them: the file's own
    # list and every workloads/<cell>.json that names the metric
    cells = [harness.Cell(w["name"]) for w in BENCH["workloads"]]
    reporting = [cell for cell in cells if name in cell.per_layer]
    assert set(metric["workloads"]) <= {cell.name for cell in reporting}
    assert sorted(cell.name for cell in reporting) == sorted(
        entry["workloads"])
    for cell in reporting:
        assert cell.traffic["rate"]["metric"] == metric["moves"]
    layers = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in NEW}
    assert metric["layer"] in layers          # a layer the benchmark names


def test_new_entries_stand_at_the_end_and_the_old_ones_are_untouched():
    # the order of NEW is not held: every PR that appends an entry moves it
    names = [m["name"] for m in BENCH["per_layer"]]
    assert set(NEW) <= set(names) and len(set(names)) == len(names)
    assert sorted(glob.glob(os.path.join(ROOT, "chipbench", "metrics",
                                         "*.json"))) == sorted(
        os.path.join(ROOT, "chipbench", "metrics", n + ".json")
        for n in names)
