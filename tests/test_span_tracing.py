"""One span primitive, two sinks (utils/metrics.py): every span is a node of
the in-memory tree (parent, request id) AND a ``srjt:<name>`` annotation on
the profiler's clock; the scan's spans partition a ``models.q6.run`` call."""

import glob
import io
import os
import threading

import numpy as np
import pytest

import jax
import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_jni_tpu.models import q6
from spark_rapids_jni_tpu.parquet import decode, device_scan
from spark_rapids_jni_tpu.utils import metrics, tracing

N = 240_000                     # a call long enough to dwarf a thread start
SCAN_SPANS = ("parquet.scan.footer", "parquet.scan.walk",
              "parquet.scan.walk_wait", "parquet.scan.stage",
              "parquet.scan.upload", "parquet.scan.decode")


@pytest.fixture(autouse=True)
def _metrics_on():
    metrics.set_enabled(True)
    metrics.reset()
    yield
    metrics.reset()
    metrics.set_enabled(None)      # back to the env default (off)


@pytest.fixture(scope="module")
def lineitem() -> bytes:
    """q6's four columns, snappy, four row groups."""
    rng = np.random.default_rng(41)
    t = pa.table({
        "l_quantity": pa.array(rng.integers(1, 51, N).astype(np.float64)),
        "l_extendedprice": pa.array(rng.uniform(900, 105_000, N)),
        "l_discount": pa.array(rng.integers(0, 11, N) / 100.0),
        "l_shipdate": pa.array(rng.integers(8036, 10_562, N)
                               .astype(np.int32)),
    })
    buf = io.BytesIO()
    pq.write_table(t, buf, compression="snappy", row_group_size=N // 4,
                   use_dictionary=False)
    return buf.getvalue()


def _flat(trees):
    out = []
    for t in trees:
        out.append(t)
        out.extend(_flat(t.get("children", [])))
    return out


# --- the primitive -----------------------------------------------------------


def test_span_on_another_thread_hangs_under_its_parent_with_its_rid():
    def work(parent):
        with metrics.span("walker", parent=parent):
            with metrics.span("walker.inner"):
                pass

    with metrics.span("call") as call:
        th = threading.Thread(target=work, args=(call,))
        th.start()
        th.join()
        with metrics.span("own"):
            pass
    (root,) = metrics.span_roots()      # the walker made no root of its own
    kids = {c["name"]: c for c in root["children"]}
    assert set(kids) == {"walker", "own"}
    assert kids["walker"]["tid"] != root["tid"] == kids["own"]["tid"]
    inner = kids["walker"]["children"][0]
    assert inner["name"] == "walker.inner"
    assert {root["rid"], kids["walker"]["rid"], inner["rid"],
            kids["own"]["rid"]} == {root["rid"]}


def test_children_from_many_threads_all_arrive():
    import sys
    workers, each = 16, 200
    start = threading.Barrier(workers + 1)

    def work(parent):
        start.wait(timeout=30)
        for _ in range(each):
            with metrics.span("theirs", parent=parent):
                pass

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with metrics.span("call") as call:
            threads = [threading.Thread(target=work, args=(call,))
                       for _ in range(workers)]
            for t in threads:
                t.start()
            start.wait(timeout=30)
            for _ in range(each):
                with metrics.span("own"):
                    pass
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    (root,) = metrics.span_roots()
    names = [c["name"] for c in root["children"]]
    assert names.count("theirs") == workers * each
    assert names.count("own") == each
    assert {c["rid"] for c in root["children"]} == {root["rid"]}


def test_root_draws_a_fresh_rid_unless_given_one():
    with metrics.span("a"):
        pass
    with metrics.span("b"):
        pass
    with metrics.span("c", rid="q3#17") as c:
        with metrics.span("c.child") as child:
            pass
    a, b, c_dict = metrics.span_roots()
    assert a["rid"] != b["rid"]
    assert c.rid == child.rid == c_dict["rid"] == "q3#17"
    assert "rid" not in c_dict.get("attrs", {})     # a field, not an attribute
    assert child.parent is c and c.parent is None


def test_roots_stay_at_their_bound():
    for i in range(metrics._ROOTS_MAX + 50):
        with metrics.span("r", i=i):
            pass
    roots = metrics.span_roots()
    assert len(roots) == metrics._ROOTS_MAX
    assert roots[-1]["attrs"] == {"i": metrics._ROOTS_MAX + 49}   # the newest
    assert roots[0]["attrs"] == {"i": 50}


def test_span_roots_of_a_window_leaves_older_roots_out():
    import time
    with metrics.span("old"):
        pass
    time.sleep(0.05)
    t0 = time.monotonic()
    with metrics.span("new"):
        pass
    got = metrics.span_roots(window_s=time.monotonic() - t0)
    assert [r["name"] for r in got] == ["new"]
    assert len(metrics.span_roots()) == 2


def test_metrics_off_scan_opens_no_span(lineitem, monkeypatch):
    metrics.set_enabled(False)
    assert metrics.span("a") is metrics.span("b", parent=None, rid=3)
    opened = []
    real = metrics.Span.__init__
    monkeypatch.setattr(
        metrics.Span, "__init__",
        lambda self, *a, **k: (opened.append(a[0]), real(self, *a, **k))[1])
    q6.run(lineitem, 8766, 9131)
    assert opened == [] and metrics.span_roots() == []
    assert metrics.snapshot()["counters"] == {}
    metrics.set_enabled(True)
    q6.run(lineitem, 8766, 9131)
    assert "q6.run" in opened                    # the probe does see spans


# --- the profiler's clock ----------------------------------------------------


def _profile(tmp_path, fn):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.duration_ns / 1e6)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_profile_holds_every_scan_span_with_the_stores_durations(
        lineitem, tmp_path):
    device_scan.scan_table(lineitem)             # compile outside the trace
    metrics.reset()
    events = _profile(tmp_path, lambda: device_scan.scan_table(lineitem))
    spans = _flat(metrics.span_roots())
    assert [s["name"] for s in spans if s["name"] ==
            "parquet_scan_table_device"] == ["parquet_scan_table_device"]
    for name in SCAN_SPANS + ("parquet_scan_table_device",):
        noted = sorted(d for n, d in events if n == metrics.PREFIX + name)
        stored = sorted(s["dur_ms"] for s in spans if s["name"] == name)
        assert noted and len(noted) == len(stored), name
        assert np.abs(np.array(noted) - np.array(stored)).max() < 1.0, name
    # @traced opens one annotation, prefixed: none under the bare name
    assert not [n for n, _ in events if n == "parquet_scan_table_device"]


def test_traced_entry_is_prefixed_with_metrics_off_too(lineitem, tmp_path):
    device_scan.scan_table(lineitem)
    metrics.set_enabled(False)
    assert tracing.enabled()
    events = _profile(tmp_path, lambda: device_scan.scan_table(lineitem))
    names = [n for n, _ in events]
    assert names.count("srjt:parquet_scan_table_device") == 1
    assert "parquet_scan_table_device" not in names
    assert not [n for n in names if n.startswith("srjt:parquet.scan.")]


# --- the scan's partition ----------------------------------------------------


@pytest.mark.parametrize("pipeline", ["1", "0"])
def test_calling_thread_spans_cover_the_q6_call(lineitem, monkeypatch,
                                                pipeline):
    monkeypatch.setenv("SRJT_STAGE_PIPELINE", pipeline)
    q6.run(lineitem, 8766, 9131)                 # compile
    best = float("inf")
    for _ in range(5):                           # a tiny call: take the best
        metrics.reset()
        q6.run(lineitem, 8766, 9131)
        (root,) = metrics.span_roots()
        assert root["name"] == "q6.run"
        assert root["attrs"] == {"file_bytes": len(lineitem), "rows": N}
        spans = _flat([root])
        assert {s["rid"] for s in spans} == {root["rid"]}
        leaves = [s for s in spans if s["tid"] == root["tid"]
                  and not any(c["tid"] == root["tid"]
                              for c in s.get("children", []))]
        best = min(best, root["dur_ms"] - sum(s["dur_ms"] for s in leaves))
    # milliseconds outside every leaf, not a share of a ~25 ms call: a loaded
    # host stretched the share past its limit (0.9468 under six workers). This
    # test's own readings: 0.36-0.52 ms unpipelined and 1.0-1.5 ms pipelined
    # beside the six workers of a whole tier-1 run, up to 2.7 ms beside twelve
    # processes; a leaf of a few ms lost or moved off this thread trips it.
    assert best < 4.0, best
    names = [s["name"] for s in spans]
    walks = [s for s in spans if s["name"] == "parquet.scan.walk"]
    assert names.count("parquet.scan.stage") == len(walks) == 4
    assert names.count("q6.answer") == names.count("parquet.scan.upload") == 1
    if pipeline == "1":
        assert {w["tid"] for w in walks} != {root["tid"]}
        assert names.count("parquet.scan.walk_wait") == 4
    else:
        assert {w["tid"] for w in walks} == {root["tid"]}
        assert "parquet.scan.walk_wait" not in names
    upload = next(s for s in spans if s["name"] == "parquet.scan.upload")
    staged = sum(s["attrs"]["bytes"] for s in spans
                 if s["name"] == "parquet.scan.stage")
    assert upload["attrs"]["bytes"] == staged == N * 28
    assert upload["attrs"]["transfers"] >= 1
    assert 0 <= upload["attrs"]["pack_ms"] <= upload["dur_ms"]


def test_device_path_counts_the_bytes_the_host_decoder_counts(lineitem):
    device_scan.scan_table(lineitem)
    dev = metrics.snapshot()["counters"]
    spans = _flat(metrics.span_roots())
    walks = [s["attrs"] for s in spans if s["name"] == "parquet.scan.walk"]
    metrics.reset()
    decode.read_table(lineitem)
    host = metrics.snapshot()["counters"]
    meta = pq.ParquetFile(io.BytesIO(lineitem)).metadata
    # uncompressed page bytes: the column chunks' sizes less their page headers
    chunks = [meta.row_group(g).column(c) for g in range(meta.num_row_groups)
              for c in range(meta.num_columns)]
    assert dev["parquet.bytes.uncompressed"] >= N * 28
    assert dev["parquet.bytes.uncompressed"] < sum(
        c.total_uncompressed_size for c in chunks)
    for name in ("parquet.bytes.uncompressed", "parquet.bytes.compressed",
                 "parquet.pages.data"):
        assert dev[name] == host[name] > 0, name
    assert dev["parquet.bytes.compressed"] == sum(
        c.total_compressed_size for c in chunks)
    assert sum(w["bytes_uncompressed"] for w in walks) == \
        dev["parquet.bytes.uncompressed"]
    assert sum(w["pages"] for w in walks) == dev["parquet.pages.data"]
    assert dev["parquet.decompress_ms"] == pytest.approx(
        sum(w["decompress_ms"] for w in walks), abs=0.01)
    assert dev["parquet.decompress_ms"] > 0


# --- served SQL --------------------------------------------------------------


def test_submit_sql_observes_its_front_end_and_query_spans_take_the_rid():
    from spark_rapids_jni_tpu import exec as xc
    from spark_rapids_jni_tpu.column import Column, Table
    t = Table([Column.from_numpy(np.arange(100, dtype=np.int64)),
               Column.from_numpy(np.arange(100, dtype=np.int64) % 7)])
    schemas = {"t": ["a", "b"]}
    with xc.QueryScheduler(workers=1, max_batch=1) as sched:
        tickets = [sched.submit_sql(
            "SELECT b, SUM(a) AS s FROM t GROUP BY b ORDER BY b",
            {"t": t}, schemas=schemas) for _ in range(2)]
        for tk in tickets:
            tk.result()
    h = metrics.snapshot()["histograms"]["exec.stage.frontend_ms"]
    assert h["count"] == 2 and h["min"] >= 0
    assert all(tk.timings["frontend_s"] >= 0 for tk in tickets)
    queries = [r for r in metrics.span_roots()
               if r["name"].startswith("query:")]
    assert sorted(q["rid"] for q in queries) == sorted(
        tk.rid for tk in tickets)
