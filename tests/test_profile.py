"""EXPLAIN ANALYZE / per-plan-node profiling: differential sweep.

The profiler must be observation-only: profiled execution bit-identical
to unprofiled, per-node observed row counts exact against a pandas
oracle evaluating the same optimized tree, disabled mode one bool check
(node_enter must return before touching any other state), and
capture/replay must take identical branches with ``SRJT_PROFILE=1`` —
including the ``SRJT_PROFILE_VALIDITY`` scalar syncs, which land on the
tape in the same order on capture and replay.
"""

import io
import json

import numpy as np
import pandas as pd
import pytest

from spark_rapids_jni_tpu.column import Column, Table, force_column
from spark_rapids_jni_tpu.models import tpcds_plans
from spark_rapids_jni_tpu.plan import ir, lower, profile
from spark_rapids_jni_tpu.plan import stats as plan_stats
from spark_rapids_jni_tpu.utils import flight, metrics

QUERIES = ("q3", "q52", "q55")         # 3 TPC-DS plan queries (oracle)


def _col(a, validity=None):
    return Column.from_numpy(np.asarray(a), validity=validity)


def _assert_tables_equal(a, b):
    """Bit-identical: same columns, same payload arrays (no reordering
    slack — profiling must be observation-only)."""
    A = [np.asarray(force_column(c).data) for c in a.columns]
    B = [np.asarray(force_column(c).data) for c in b.columns]
    assert len(A) == len(B)
    for i, (x, y) in enumerate(zip(A, B)):
        np.testing.assert_array_equal(x, y, err_msg=f"col {i}")


@pytest.fixture
def prof_on():
    profile.set_enabled(True)
    profile.reset()
    yield profile
    profile.set_enabled(None)


@pytest.fixture(scope="module")
def tpcds():
    """Small TPC-DS tables, device + pandas twins."""
    from benchmarks import tpcds_data
    from spark_rapids_jni_tpu.models import tpcds as M
    files = tpcds_data.generate(n_sales=20_000, n_items=300, seed=11)
    tables = M.load_tables(files)
    pdt = {k: pd.read_parquet(io.BytesIO(v)) for k, v in files.items()}
    return tables, pdt


# --- pandas plan evaluator (row-count oracle) --------------------------------


def _pd_expr(e, df):
    if isinstance(e, ir.Col):
        return df[e.name]
    if isinstance(e, ir.Lit):
        return e.value
    if isinstance(e, ir.Mul):
        return _pd_expr(e.left, df) * _pd_expr(e.right, df)
    if isinstance(e, ir.ScalarAgg):
        s = _pd_expr(e.arg, df)
        return s.mean() if e.fn == "mean" else s.sum()
    raise NotImplementedError(type(e).__name__)


def _pd_mask(p, df):
    if isinstance(p, ir.And):
        m = np.ones(len(df), bool)
        for q in p.parts:
            m &= np.asarray(_pd_mask(q, df))
        return m
    if isinstance(p, ir.Or):
        m = np.zeros(len(df), bool)
        for q in p.parts:
            m |= np.asarray(_pd_mask(q, df))
        return m
    if isinstance(p, ir.Cmp):
        a, b = _pd_expr(p.left, df), _pd_expr(p.right, df)
        import operator as op
        f = {"==": op.eq, "!=": op.ne, "<": op.lt, "<=": op.le,
             ">": op.gt, ">=": op.ge}[p.op]
        return np.asarray(f(a, b))
    if isinstance(p, ir.Between):
        v = _pd_expr(p.col, df)
        m = np.ones(len(df), bool)
        if p.lo is not None:
            m &= np.asarray(v >= p.lo)
        if p.hi is not None:
            m &= np.asarray(v < p.hi if p.hi_strict else v <= p.hi)
        return m
    if isinstance(p, ir.IsIn):
        return np.asarray(_pd_expr(p.col, df).isin(list(p.values)))
    raise NotImplementedError(type(p).__name__)


def _pd_agg(df, keys, aggs):
    g = df.groupby(list(keys), sort=True)
    out = {}
    for src, fn, name in aggs:
        out[name] = g[src].mean() if fn == "mean" else g[src].sum()
    return pd.DataFrame(out).reset_index()


def _pd_eval(node, pdt):
    """Pandas twin of ``lower._apply_node`` — row counts must match the
    profiled execution node for node."""
    if isinstance(node, ir.Scan):
        df = pdt[node.table]
        if node.columns is not None:
            df = df[list(node.columns)]
        if node.predicate is not None:
            df = df[_pd_mask(node.predicate, df)]
        return df.reset_index(drop=True)
    if isinstance(node, ir.Filter):
        df = _pd_eval(node.child, pdt)
        return df[_pd_mask(node.predicate, df)].reset_index(drop=True)
    if isinstance(node, ir.Project):
        return _pd_eval(node.child, pdt)[list(node.columns)]
    if isinstance(node, ir.Join):
        lt, rt = _pd_eval(node.left, pdt), _pd_eval(node.right, pdt)
        return lt.merge(rt, left_on=list(node.left_on),
                        right_on=list(node.right_on), how=node.how)
    if isinstance(node, ir.FusedJoinAggregate):
        lt, rt = _pd_eval(node.left, pdt), _pd_eval(node.right, pdt)
        j = lt.merge(rt, left_on=list(node.left_on),
                     right_on=list(node.right_on), how=node.how)
        return _pd_agg(j, node.keys, node.aggs)
    if isinstance(node, ir.Aggregate):
        return _pd_agg(_pd_eval(node.child, pdt), node.keys, node.aggs)
    if isinstance(node, ir.Sort):
        return _pd_eval(node.child, pdt)
    if isinstance(node, ir.Limit):
        return _pd_eval(node.child, pdt).head(node.n)
    raise NotImplementedError(type(node).__name__)


# --- differential sweep ------------------------------------------------------


@pytest.mark.parametrize("qname", QUERIES)
def test_profiled_bit_identical_and_rows_match_oracle(tpcds, prof_on,
                                                      qname):
    tables, pdt = tpcds
    tree = tpcds_plans.optimized(qname).tree
    cat = lower.TableCatalog(tables, tpcds_plans.TABLE_SCHEMAS)

    profile.set_enabled(False)
    plain = lower.execute(tree, cat, record_stats=False)
    profile.set_enabled(True)
    with profile.query(qname, ir.fingerprint(tree)) as pr:
        got = lower.execute(
            tree, lower.TableCatalog(tables, tpcds_plans.TABLE_SCHEMAS),
            record_stats=False)

    _assert_tables_equal(got, plain)           # bit-identical

    # profile tree mirrors the executed tree; every node's observed rows
    # must equal the pandas evaluation of the same subtree
    assert len(pr.roots) == 1

    def check(rec, node):
        kids = ir.children(node)
        assert rec.op == type(node).__name__
        assert rec.node_id == ir.fingerprint(node)
        assert rec.out_rows == len(_pd_eval(node, pdt)), rec.line
        assert len(rec.children) == len(kids)
        for r, k in zip(rec.children, kids):
            check(r, k)

    check(pr.roots[0], tree)
    assert pr.finished and pr.wall_ms > 0


def test_disabled_mode_is_one_bool_check(monkeypatch):
    """With the gate off, node_enter/op_event/at_node_output must return
    before touching ANY other state — enforced by poisoning every module
    attribute they would consult next."""
    profile.set_enabled(False)

    class Boom:
        def __getattribute__(self, name):
            if name.startswith("__"):          # monkeypatch plumbing
                return object.__getattribute__(self, name)
            raise AssertionError("disabled path touched profiler state")

    monkeypatch.setattr(profile, "_tls", Boom())
    assert profile.node_enter(ir.Scan("t")) is None
    profile.op_event("x", rows=1)          # no-op, no state touched
    profile.annotate_node(engine="dense")
    profile.at_node_output(None)           # never inspects the table
    metrics.profile_op("x", rows=1)        # hook gates before _tls too


def test_disabled_execution_records_nothing(tpcds):
    tables, _ = tpcds
    profile.set_enabled(False)
    profile.reset()
    tree = tpcds_plans.optimized("q55").tree
    lower.execute(tree,
                  lower.TableCatalog(tables, tpcds_plans.TABLE_SCHEMAS),
                  record_stats=False)
    assert profile.completed() == []
    with profile.query("nope") as pr:
        assert pr is None                  # query() is a no-op when off
    assert profile.completed() == []


def test_capture_replay_identical_branches(tpcds, prof_on, monkeypatch):
    """SRJT_PROFILE=1 (+ validity syncs) through compile_query: the
    eager capture and the jitted replay must resolve the same tape —
    including the per-node validity scalars — and return bit-identical
    results.  A nullable column makes the validity sync real."""
    from spark_rapids_jni_tpu.models.compiled import compile_query
    monkeypatch.setenv("SRJT_PROFILE", "1")
    monkeypatch.setenv("SRJT_PROFILE_VALIDITY", "1")
    profile.set_enabled(None)              # re-read both knobs
    assert profile._validity

    rng = np.random.default_rng(7)
    n = 3000
    valid = rng.random(n) > 0.25
    tables = {
        "fact": Table([_col(rng.integers(0, 50, n).astype(np.int64)),
                       _col(rng.integers(1, 9, n).astype(np.int64),
                            validity=valid)]),
        "dim": Table([_col(np.arange(50, dtype=np.int64)),
                      _col((np.arange(50) % 5).astype(np.int32))]),
    }
    schemas = {"fact": ["f_sk", "f_qty"], "dim": ["d_sk", "d_tag"]}
    tree = ir.Sort(ir.Aggregate(
        ir.Join(ir.Scan("fact"), ir.Scan("dim"), ("f_sk",), ("d_sk",)),
        ("d_tag",), (("f_qty", "sum", "total"),)), ("d_tag",))
    qfn = lower.compile_plan(tree, schemas)

    cq = compile_query(qfn, tables)        # capture (validity syncs taped)
    out = cq.run(tables)                   # replay re-trace + dispatch
    _assert_tables_equal(out, cq.expected)
    out2 = cq.run_unchecked(tables)
    _assert_tables_equal(out2, cq.expected)


def test_validity_density_recorded(prof_on, monkeypatch):
    monkeypatch.setenv("SRJT_PROFILE", "1")
    monkeypatch.setenv("SRJT_PROFILE_VALIDITY", "1")
    profile.set_enabled(None)
    n = 1000
    valid = np.zeros(n, bool)
    valid[: n // 4] = True                 # 25% valid
    tables = {"t": Table([_col(np.arange(n, dtype=np.int64)),
                          _col(np.arange(n, dtype=np.int64),
                               validity=valid)])}
    schemas = {"t": ["a", "b"]}
    tree = ir.Filter(ir.Scan("t"), ir.Cmp("<", ir.Col("a"), ir.Lit(n)))
    with profile.query("validity") as pr:
        lower.execute(tree, lower.TableCatalog(tables, schemas),
                      record_stats=False)
    fracs = [r.valid_frac for r in pr.nodes() if r.valid_frac is not None]
    # density counts NULLABLE columns only: col "a" (validity=None) is
    # skipped, col "b" is 25% valid
    assert fracs and all(abs(f - 0.25) < 1e-9 for f in fracs)


def test_mispredict_flag_and_stats_feedback(prof_on):
    n = 2000
    tables = {"t": Table([_col(np.arange(n, dtype=np.int64))])}
    schemas = {"t": ["a"]}
    tree = ir.Filter(ir.Scan("t"), ir.Cmp("<", ir.Col("a"), ir.Lit(10)))
    fp = ir.fingerprint(tree)
    plan_stats.GLOBAL.observe(fp, 2000)    # stale prior: 2000 rows
    with profile.query("mis") as pr:
        lower.execute(tree, lower.TableCatalog(tables, schemas),
                      record_stats=True)
    root = pr.roots[0]
    assert root.est_rows == 2000 and root.out_rows == 10
    assert root.mispredicted()
    assert "mispredict" in json.dumps(root.as_dict())
    # record_stats=True corrected the prior from the observed run
    assert plan_stats.GLOBAL.rows_for(tree) != 2000


def test_explain_analyze_renders(tpcds, prof_on):
    tables, _ = tpcds
    text = profile.explain_analyze(tpcds_plans.PLANS["q55"](),
                                   tpcds_plans.TABLE_SCHEMAS, tables)
    assert "EXPLAIN ANALYZE" in text
    assert "rows est=" in text and "obs=" in text
    assert "time=" in text and "self=" in text
    assert "node(s)" in text


def test_profile_artifact_export(tpcds, prof_on, tmp_path, monkeypatch):
    tables, _ = tpcds
    monkeypatch.setenv("SRJT_PROFILE_DIR", str(tmp_path))
    tree = tpcds_plans.optimized("q55").tree
    with profile.query("q55", ir.fingerprint(tree)):
        lower.execute(tree,
                      lower.TableCatalog(tables,
                                         tpcds_plans.TABLE_SCHEMAS),
                      record_stats=False)
    arts = list(tmp_path.glob("profile-*.json"))
    assert len(arts) == 1
    doc = json.loads(arts[0].read_text())
    assert doc["name"] == "q55" and doc["finished"]
    assert doc["nodes"] and doc["nodes"][0]["out_rows"] is not None


def test_flight_probe_embeds_partial_profile(prof_on):
    n = 100
    tables = {"t": Table([_col(np.arange(n, dtype=np.int64))])}
    schemas = {"t": ["a"]}
    seen = {}

    class Catalog(lower.TableCatalog):
        def scan(self, node):
            # mid-execution: the profile stack has the Scan node open
            seen.update(flight.sample_probes())
            return super().scan(node)

    with profile.query("stuck"):
        lower.execute(ir.Scan("t"), Catalog(tables, schemas),
                      record_stats=False)
    probe = seen.get("plan.active_profile")
    assert probe, seen.keys()
    (prof_dict,) = probe.values()
    assert prof_dict["name"] == "stuck"
    assert prof_dict["open"]               # the in-flight node stack


def test_compile_ledger_attributes_per_fingerprint(tpcds):
    tables, _ = tpcds
    from spark_rapids_jni_tpu.models.compiled import compile_query
    metrics.set_enabled(True)
    metrics.reset()
    try:
        qfn = lower.compile_plan(tpcds_plans.optimized("q55").tree,
                                 tpcds_plans.TABLE_SCHEMAS)
        cq = compile_query(qfn, tables)
        cq.run(tables)
        cq.run(tables)
        led = metrics.ledger_snapshot()
        ent = led[qfn.plan_fingerprint]
        assert ent["captures"] == 1 and ent["capture_ms"] > 0
        assert ent["traces"] >= 1 and ent["trace_ms"] > 0
        assert ent["first_dispatches"] == 1
        assert ent["runs"] == 2
        # visible in the snapshot + prometheus surfaces
        assert qfn.plan_fingerprint in metrics.snapshot()["ledger"]
        prom = metrics.to_prometheus()
        assert "srjt_compile_ledger" in prom
        assert f'plan="{qfn.plan_fingerprint}"' in prom
        assert qfn.plan_fingerprint in metrics.chrome_trace()["srjtLedger"]
    finally:
        metrics.set_enabled(None)
        metrics.reset()


def test_chrome_trace_nests_node_spans(tpcds, prof_on, tmp_path):
    tables, _ = tpcds
    metrics.set_enabled(True)
    metrics.reset()
    try:
        tree = tpcds_plans.optimized("q55").tree
        with metrics.query_span("q55"):
            with profile.query("q55"):
                lower.execute(
                    tree, lower.TableCatalog(tables,
                                             tpcds_plans.TABLE_SCHEMAS),
                    record_stats=False)
        doc = metrics.chrome_trace()
        node_evs = [e for e in doc["traceEvents"]
                    if str(e.get("name", "")).startswith("plan.node:")]
        assert node_evs
        assert all("node_id" in (e.get("args") or {}) for e in node_evs)
        roots = [e for e in doc["traceEvents"]
                 if e.get("name") == "query:q55"]
        assert roots
        # node spans sit INSIDE the query span's interval
        r = roots[0]
        for e in node_evs:
            assert e["ts"] >= r["ts"]
            assert e["ts"] + e["dur"] <= r["ts"] + r["dur"] + 1.0
    finally:
        metrics.set_enabled(None)
        metrics.reset()


# --- tool-layer regressions --------------------------------------------------


def test_trace_report_no_nested_double_count(tmp_path):
    """A parent span containing a child must report parent self-time =
    inclusive - child (the flatten-by-name double-count bug)."""
    import tools.trace_report as tr
    events = [
        {"ph": "X", "name": "stage", "ts": 0, "dur": 100_000,
         "pid": 1, "tid": 1},
        {"ph": "X", "name": "join", "ts": 10_000, "dur": 60_000,
         "pid": 1, "tid": 1},
        {"ph": "X", "name": "stage", "ts": 200_000, "dur": 50_000,
         "pid": 1, "tid": 1},
        # same name on another thread: independent lane
        {"ph": "X", "name": "join", "ts": 0, "dur": 30_000,
         "pid": 1, "tid": 2},
    ]
    agg = tr.summarize(events)
    assert agg["stage"]["total_ms"] == 150.0
    assert agg["stage"]["self_ms"] == 90.0       # 100-60 + 50
    assert agg["join"]["self_ms"] == 90.0        # 60 + 30, no parent leak
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": events}))
    assert tr.main(["tr", str(p)]) == 0


def test_trace_report_by_node_mode():
    import tools.trace_report as tr
    events = [
        {"ph": "X", "name": "plan.node:Join", "ts": 0, "dur": 10_000,
         "pid": 1, "tid": 1, "args": {"node_id": "plan:aaa", "line": "J1"}},
        {"ph": "X", "name": "plan.node:Join", "ts": 20_000, "dur": 5_000,
         "pid": 1, "tid": 1, "args": {"node_id": "plan:bbb", "line": "J2"}},
        {"ph": "X", "name": "other", "ts": 0, "dur": 1_000,
         "pid": 1, "tid": 1},
    ]
    agg = tr.summarize(events, by_node=True)
    assert len(agg) == 2                   # grouped by node id, not name
    assert "other" not in " ".join(agg)


def test_profile_report_flatten_and_regress(tmp_path):
    import tools.profile_report as pr
    node = {"op": "Join", "line": "Join x", "node_id": "plan:a",
            "out_rows": 10, "out_bytes": 80, "wall_ms": 10.0,
            "self_ms": 8.0, "children": [
                {"op": "Scan", "line": "Scan t", "node_id": "plan:b",
                 "out_rows": 100, "out_bytes": 800, "wall_ms": 2.0,
                 "self_ms": 2.0}]}
    prof = {"name": "q", "fingerprint": "plan:a", "wall_ms": 10.0,
            "finished": True, "nodes": [node]}
    old = dict(prof)
    new = json.loads(json.dumps(prof))
    new["nodes"][0]["self_ms"] = 80.0      # 10× regression on the join
    (tmp_path / "old").mkdir()
    (tmp_path / "new").mkdir()
    (tmp_path / "old" / "profile-q-1-1.json").write_text(json.dumps(old))
    (tmp_path / "new" / "profile-q-1-1.json").write_text(json.dumps(new))
    agg = pr.flatten([prof])
    assert agg["plan:a"]["self_ms"] == 8.0
    assert agg["plan:b"]["out_rows"] == 100
    regs = pr.regressions(pr.flatten([new]), pr.flatten([old]), 1.5)
    assert len(regs) == 1 and regs[0][0] == "Join x"
    # CI contract: exit 3 on regression, 0 when clean
    assert pr.main(["pr", str(tmp_path / "new"), "--regress",
                    str(tmp_path / "old")]) == 3
    assert pr.main(["pr", str(tmp_path / "old"), "--regress",
                    str(tmp_path / "old")]) == 0
