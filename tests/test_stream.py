"""Streaming ingest + incremental maintenance (stream/) tests.

The subsystem's contract is that incremental refresh changes COST, never
results: a registered view refreshed over appended row groups must be
bit-identical to a from-scratch recompute of the same plan — including
through the concurrent scheduler — and anything the delta algebra cannot
maintain must fall back to full recompute (visibly, via counters), never
silently drift.  Held here:

* delta-scan boundaries — empty delta, delta spanning a file boundary,
  watermark persistence, the ``until`` snapshot bound, extend-file
  prefix validation, pruning composition.
* merge-state equivalence vs full recompute for every supported agg,
  null-heavy partitions included; unmerged states finalize bit-identical
  for ALL aggs (incl. var/std and f64 sums).
* view classification — maintainable shapes refresh incrementally and
  bit-exactly; window shapes, grand totals, and non-exact aggregates
  fall back (``stream.view.fallback``); ``allow_approx`` opts var views
  back in at allclose fidelity.
* build-index append-extend — field-identical to rebuild when appended
  keys stay in the window; None (rebuild signal) otherwise.
* refresh-while-serving differential through ``exec/``.
"""

import io
import threading

import numpy as np
import pytest

import jax.numpy as jnp

pa = pytest.importorskip("pyarrow")
pq = pytest.importorskip("pyarrow.parquet")

from benchmarks import tpcds_data
from spark_rapids_jni_tpu import exec as xc
from spark_rapids_jni_tpu import types as T
from spark_rapids_jni_tpu.column import Column, Table, force_column
from spark_rapids_jni_tpu.models import tpcds, tpcds_plans
from spark_rapids_jni_tpu.ops import apply_boolean_mask
from spark_rapids_jni_tpu.ops import groupby as G
from spark_rapids_jni_tpu.ops import join_plan as JP
from spark_rapids_jni_tpu.ops.copying import concat_tables
from spark_rapids_jni_tpu.plan import ir, lower
from spark_rapids_jni_tpu.plan import stats as plan_stats
from spark_rapids_jni_tpu.stream import DeltaTable, ViewRegistry
from spark_rapids_jni_tpu.utils import metrics


@pytest.fixture(autouse=True)
def _metrics_on():
    metrics.set_enabled(True)
    metrics.reset()
    yield
    metrics.reset()
    metrics.set_enabled(None)


def _blob(n, start=0, row_group_size=4):
    tab = pa.table({
        "k": pa.array(np.arange(start, start + n, dtype=np.int32)),
        "v": pa.array((np.arange(start, start + n) * 3).astype(np.int64)),
    })
    buf = io.BytesIO()
    pq.write_table(tab, buf, compression="SNAPPY", use_dictionary=False,
                   row_group_size=row_group_size)
    return buf.getvalue()


def _bitcmp(a: Table, b: Table, tag=""):
    assert a.num_rows == b.num_rows, (tag, a.num_rows, b.num_rows)
    assert len(a.columns) == len(b.columns)
    for i in range(len(a.columns)):
        x, y = force_column(a[i]), force_column(b[i])
        assert x.dtype.id == y.dtype.id, (tag, i)
        np.testing.assert_array_equal(np.asarray(x.data),
                                      np.asarray(y.data),
                                      err_msg=f"{tag} col {i} data")
        if x.offsets is not None:
            np.testing.assert_array_equal(np.asarray(x.offsets),
                                          np.asarray(y.offsets),
                                          err_msg=f"{tag} col {i} offsets")
        xv = None if x.validity is None else np.asarray(x.validity)
        yv = None if y.validity is None else np.asarray(y.validity)
        nn = np.ones(x.data.shape[0], bool)
        np.testing.assert_array_equal(nn if xv is None else xv,
                                      nn if yv is None else yv,
                                      err_msg=f"{tag} col {i} validity")


# --- delta scans -------------------------------------------------------------


class TestDeltaScan:
    def test_empty_delta_keeps_schema(self):
        d = DeltaTable("t", files=[_blob(10)])
        wm = d.watermark()
        t = d.scan(since=wm)
        assert t.num_rows == 0 and t.num_columns == 2
        assert metrics.counter_value("stream.delta.rowgroups") == 0
        assert d.schema() == ["k", "v"]

    def test_delta_spans_file_boundary(self):
        d = DeltaTable("t", files=[_blob(10)])          # groups [4, 4, 2]
        wm = (1,)                                       # consumed 4 rows
        d.append_file(_blob(6, start=100))              # groups [4, 2]
        t = d.scan(since=wm)
        # rest of file 0 (6 rows) + all of file 1 (6 rows)
        assert t.num_rows == 12
        np.testing.assert_array_equal(
            np.asarray(force_column(t[0]).data)[:6], np.arange(4, 10))
        assert metrics.counter_value("stream.delta.rowgroups") == 4

    def test_watermark_persistence_and_epoch(self):
        d = DeltaTable("t", files=[_blob(10)])
        assert d.epoch == 1
        wm = d.watermark()
        assert wm == (3,)
        a = d.scan(since=wm)
        b = d.scan(since=wm)
        assert a.num_rows == b.num_rows == 0      # watermark is stable
        d.append_file(_blob(4, start=50))
        assert d.epoch == 2 and d.watermark() == (3, 1)
        assert d.scan(since=wm).num_rows == 4
        assert d.total_rows(wm) == 4 and d.total_rows() == 14
        assert d.delta_bytes(wm) > 0
        assert d.delta_bytes(d.watermark()) == 0

    def test_until_bounds_the_snapshot(self):
        d = DeltaTable("t", files=[_blob(10)])
        assert d.scan(until=(1,)).num_rows == 4
        d.append_file(_blob(6, start=100))
        # a snapshot taken before the append sees none of it
        assert d.scan(since=(1,), until=(3,)).num_rows == 6

    def test_extend_file_prefix_validation(self):
        d = DeltaTable("t", files=[_blob(8)])           # groups [4, 4]
        d.extend_file(0, _blob(12))                     # groups [4, 4, 4]
        assert d.watermark() == (3,)
        with pytest.raises(ValueError):
            d.extend_file(0, _blob(12, row_group_size=5))
        base = tpcds_data.append_rows(8, seed=3, row_group_size=4)
        d2 = DeltaTable("f", files=[base])
        wm = d2.watermark()
        ext = tpcds_data.append_rows(4, seed=4, row_group_size=4, base=base)
        d2.extend_file(0, ext)
        assert d2.scan(since=wm).num_rows == 4

    def test_pruning_composes_with_delta_scan(self):
        d = DeltaTable("t", files=[_blob(16)])          # k sorted per group
        wm = d.watermark()
        d.append_file(_blob(16, start=100))
        t = d.scan(columns=["v"], since=wm,
                   rowgroup_predicate=[("k", "ge", 108)])
        assert t.num_columns == 1
        assert t.num_rows == 8
        assert metrics.counter_value("plan.scan.rowgroups_pruned") == 2


# --- mergeable aggregate states ---------------------------------------------


def _state_tab(n, seed, null_frac=0.3):
    r = np.random.default_rng(seed)
    valid = r.random(n) > null_frac
    return Table([
        Column(T.int32, jnp.asarray(r.integers(0, 7, n).astype(np.int32))),
        Column(T.int64, jnp.asarray(r.integers(-50, 50, n).astype(np.int64)),
               validity=jnp.asarray(valid)),
        Column.from_values(T.float64, jnp.asarray(r.normal(0, 10, n)),
                           validity=jnp.asarray(valid)),
    ])


_ALL_AGGS = [(1, "sum"), (1, "count"), (1, "min"), (1, "max"), (1, "mean"),
             (1, "var"), (1, "std"), (2, "sum"), (2, "mean"), (2, "min"),
             (2, "max"), (2, "var"), (2, "std")]


class TestMergeStates:
    def _spec(self, tab):
        return G.plan_aggregate_states(
            _ALL_AGGS, {i: c.dtype for i, c in enumerate(tab.columns)}, 1)

    def test_merge_equivalence_all_aggs(self):
        # partition B is null-heavy (90%) so all-null groups and
        # validity-merging actually exercise
        a, b = _state_tab(400, 1), _state_tab(250, 2, null_frac=0.9)
        spec = self._spec(a)
        merged = G.finalize_aggregate_states(
            spec, G.merge_aggregate_states(
                spec,
                G.partial_aggregate_states(a, [0], _ALL_AGGS, spec=spec),
                G.partial_aggregate_states(b, [0], _ALL_AGGS, spec=spec)))
        expect = G.groupby_aggregate(concat_tables([a, b]), [0], _ALL_AGGS)
        assert merged.num_rows == expect.num_rows
        for i, o in enumerate(spec.outs):
            x = force_column(expect[1 + i])
            y = force_column(merged[1 + i])
            if o.exact:
                np.testing.assert_array_equal(
                    np.asarray(x.data), np.asarray(y.data),
                    err_msg=f"{o.agg} exact")
            else:
                np.testing.assert_allclose(
                    np.asarray(x.values()), np.asarray(y.values()),
                    rtol=1e-9, atol=1e-9, err_msg=o.agg)
            xv = None if x.validity is None else np.asarray(x.validity)
            yv = None if y.validity is None else np.asarray(y.validity)
            nn = np.ones(expect.num_rows, bool)
            np.testing.assert_array_equal(nn if xv is None else xv,
                                          nn if yv is None else yv,
                                          err_msg=f"{o.agg} validity")

    def test_unmerged_finalize_bit_identical(self):
        # an UNMERGED state must reproduce groupby_aggregate exactly for
        # EVERY agg — float sums, var, std included
        tab = _state_tab(500, 5)
        spec = self._spec(tab)
        got = G.finalize_aggregate_states(
            spec, G.partial_aggregate_states(tab, [0], _ALL_AGGS, spec=spec))
        _bitcmp(got, G.groupby_aggregate(tab, [0], _ALL_AGGS), "unmerged")

    def test_empty_partition_merge_is_identity(self):
        a = _state_tab(300, 7)
        spec = self._spec(a)
        sa = G.partial_aggregate_states(a, [0], _ALL_AGGS, spec=spec)
        se = G.partial_aggregate_states(_state_tab(0, 8), [0], _ALL_AGGS,
                                        spec=spec)
        assert se.num_rows == 0
        _bitcmp(G.finalize_aggregate_states(
                    spec, G.merge_aggregate_states(spec, sa, se)),
                G.finalize_aggregate_states(spec, sa), "empty-merge")
        assert G.merge_aggregate_states(spec, None, sa) is sa

    def test_string_keys_and_exactness_plan(self):
        r = np.random.default_rng(9)
        keys = Column.strings_from_list([f"g{i % 5}" for i in range(200)])
        vals = Column(T.int64,
                      jnp.asarray(r.integers(0, 99, 200).astype(np.int64)))
        tab = Table([keys, vals])
        aggs = [(1, "sum"), (1, "mean"), (1, "count")]
        spec = G.plan_aggregate_states(
            aggs, {i: c.dtype for i, c in enumerate(tab.columns)}, 1)
        assert spec.exact     # int sum/mean/count are all merge-exact
        # merge across two halves built by row masks
        lo = apply_boolean_mask(tab, jnp.arange(200) < 120)
        hi = apply_boolean_mask(tab, jnp.arange(200) >= 120)
        got = G.finalize_aggregate_states(
            spec, G.merge_aggregate_states(
                spec, G.partial_aggregate_states(lo, [0], aggs, spec=spec),
                G.partial_aggregate_states(hi, [0], aggs, spec=spec)))
        _bitcmp(got, G.groupby_aggregate(tab, [0], aggs), "strkeys")
        assert not G.merge_exact("sum", T.float64)
        assert not G.merge_exact("var", T.int64)
        assert G.merge_exact("min", T.float64)

    def test_rejects_unsupported(self):
        tab = _state_tab(10, 1)
        with pytest.raises(ValueError):
            G.plan_aggregate_states([(1, "first")],
                                    {1: tab[1].dtype}, 1)
        with pytest.raises(ValueError):
            G.partial_aggregate_states(tab, [], [(1, "sum")])


# --- build-index append-extend ----------------------------------------------


class TestBuildIndexExtend:
    @pytest.mark.parametrize("with_valid", [False, True])
    def test_extend_identity_vs_rebuild(self, with_valid):
        r = np.random.default_rng(3)
        base = np.r_[100, 159, r.integers(100, 160, 300)].astype(np.int32)
        base = jnp.asarray(base)        # pins the dense window to [100,159]
        delta = jnp.asarray(r.integers(100, 160, 80).astype(np.int32))
        bv = jnp.asarray(np.r_[True, True, r.random(300) > 0.15]) \
            if with_valid else None
        dv = jnp.asarray(r.random(80) > 0.15) if with_valid else None
        ix = JP._build_index(base, bv, True, False)
        assert ix.kind == "dense"
        ext = JP.extend_build_index(ix, delta, dv, 302)
        ref = JP._build_index(
            jnp.concatenate([base, delta]),
            None if bv is None else jnp.concatenate([bv, dv]), True, False)
        assert ext is not None
        assert (ext.kind, ext.n_valid, ext.kmin, ext.span, ext.unique) == \
               (ref.kind, ref.n_valid, ref.kmin, ref.span, ref.unique)
        for mine, theirs in ((ext.row_ids, ref.row_ids),
                             (ext.lut_lo, ref.lut_lo),
                             (ext.lut_cnt, ref.lut_cnt)):
            np.testing.assert_array_equal(np.asarray(mine),
                                          np.asarray(theirs))

    def test_extend_edges(self):
        base = jnp.asarray(np.arange(100, 130, dtype=np.int32))
        ix = JP._build_index(base, None, True, False)
        # out-of-window key → rebuild signal
        assert JP.extend_build_index(
            ix, jnp.asarray(np.array([500], np.int32)), None, 30) is None
        # out-of-window but NULL key → extend still applies
        got = JP.extend_build_index(
            ix, jnp.asarray(np.array([500, 110], np.int32)),
            jnp.asarray(np.array([False, True])), 30)
        assert got is not None and got.n_valid == 31
        # empty delta → same index
        assert JP.extend_build_index(ix, jnp.zeros(0, jnp.int32),
                                     None, 30) is ix
        # sorted engine → rebuild signal
        six = JP._build_index(base, None, False, False)
        assert JP.extend_build_index(six, base, None, 30) is None


# --- view registry -----------------------------------------------------------


def _mini_files():
    return tpcds_data.generate(n_sales=12_000, n_items=400, seed=11,
                               row_group_size=1024)


def _cents_view_plan():
    j = ir.Join(ir.Join(ir.Scan("store_sales"), ir.Scan("item"),
                        ("ss_item_sk",), ("i_item_sk",)),
                ir.Scan("date_dim"), ("ss_sold_date_sk",), ("d_date_sk",))
    f = ir.Filter(j, ir.And((
        ir.Cmp("==", ir.Col("i_manufact_id"), ir.Lit(436)),
        ir.Cmp("==", ir.Col("d_moy"), ir.Lit(11)))))
    keys = ("d_year", "i_brand_id", "i_brand")
    return ir.Sort(ir.Aggregate(f, keys, (
        ("ss_sales_price_cents", "sum", "sum_cents"),
        ("ss_quantity", "mean", "avg_qty"),
        ("ss_quantity", "count", "n"))), keys)


def _registry(files, **kw):
    tables = tpcds.load_tables(files)
    delta = DeltaTable("store_sales", files=[files["store_sales"]])
    statics = {k: tables[k] for k in ("item", "date_dim", "store")}
    schemas = {k: tpcds_plans.TABLE_SCHEMAS[k] for k in statics}
    return delta, ViewRegistry(delta, statics, schemas, **kw), statics, \
        schemas


def _oracle(reg, v):
    cat = lower.TableCatalog(
        {**reg.statics, reg.delta.name: reg.delta.scan()},
        reg.schemas)
    return lower.execute(v.tree, cat, record_stats=False)


class TestViewRegistry:
    def test_incremental_refresh_bit_identical(self):
        files = _mini_files()
        delta, reg, _, _ = _registry(files)
        v = reg.register_view(_cents_view_plan(), name="q3c")
        assert v.kind == "incremental" and v.exact, v.reason
        _bitcmp(reg.refresh(v), _oracle(reg, v), "epoch0")
        for e in (1, 2):
            delta.append_file(tpcds_data.append_rows(
                12_000 // 64, seed=100 + e, n_items=400,
                row_group_size=1024))
            c0 = metrics.counter_value("stream.delta.rowgroups")
            got = reg.refresh(v)
            assert metrics.counter_value("stream.delta.rowgroups") - c0 == 1
            _bitcmp(got, _oracle(reg, v), f"epoch{e}")
        assert metrics.counter_value("stream.refresh.incremental") == 2
        assert metrics.counter_value("stream.view.fallback") == 0
        # re-registering the same plan returns the same view
        assert reg.register_view(_cents_view_plan()) is v
        assert reg.stats()["incremental"] == 1
        reg.close()

    def test_fallbacks_window_grand_total_approx(self):
        files = _mini_files()
        _, reg, _, _ = _registry(files)
        w = reg.register_view(ir.Aggregate(
            ir.Window(ir.Scan("store_sales"), "row_number",
                      ("ss_store_sk",), ("ss_sold_date_sk",), "rn"),
            ("ss_store_sk",), (("rn", "max", "max_rn"),)), name="win")
        assert w.kind == "full" and "Window" in w.reason
        g = reg.register_view(ir.Aggregate(
            ir.Scan("store_sales"), (),
            (("ss_quantity", "sum", "s"),)), name="total")
        assert g.kind == "full" and g.reason == "grand_total"
        a = reg.register_view(ir.Aggregate(
            ir.Scan("store_sales"), ("ss_store_sk",),
            (("ss_ext_sales_price", "var", "v"),)), name="varv")
        assert a.kind == "full" and a.reason.startswith("approx")
        assert metrics.counter_value("stream.view.fallback") == 3
        # full views still serve correct results
        _bitcmp(reg.refresh(w), _oracle(reg, w), "window")
        assert metrics.counter_value("stream.refresh.full") >= 1
        reg.close()

    def test_allow_approx_var_view(self):
        files = _mini_files()
        delta, reg, _, _ = _registry(files, allow_approx=True)
        v = reg.register_view(ir.Aggregate(
            ir.Scan("store_sales"), ("ss_store_sk",),
            (("ss_ext_sales_price", "var", "v"),
             ("ss_ext_sales_price", "mean", "m"))), name="varv")
        assert v.kind == "incremental" and not v.exact
        delta.append_file(tpcds_data.append_rows(
            200, seed=77, n_items=400, row_group_size=1024))
        got, expect = reg.refresh(v), _oracle(reg, v)
        assert got.num_rows == expect.num_rows
        for i in (1, 2):
            np.testing.assert_allclose(
                np.asarray(force_column(got[i]).values()),
                np.asarray(force_column(expect[i]).values()),
                rtol=1e-9, atol=1e-9)
        reg.close()


# --- serving integration -----------------------------------------------------


class TestServing:
    def test_concurrent_refresh_while_serving(self):
        files = _mini_files()
        delta, reg, statics, schemas = _registry(files)
        v = reg.register_view(_cents_view_plan(), name="q3c")
        assert v.kind == "incremental"
        qfn = lower.compile_plan(v.tree,
                                 {**reg.schemas})
        base_tables = {**statics, "store_sales": delta.scan()}
        stop = threading.Event()
        errs: list = []

        def _querier():
            # keep ordinary traffic in flight while refreshes run
            while not stop.is_set():
                try:
                    sched.run("q3c", qfn, base_tables)
                except Exception as e:     # noqa: BLE001
                    errs.append(e)
                    return
        with xc.QueryScheduler(workers=2) as sched:
            th = threading.Thread(target=_querier)
            th.start()
            try:
                for e in (1, 2, 3):
                    delta.append_file(tpcds_data.append_rows(
                        12_000 // 64, seed=200 + e, n_items=400,
                        row_group_size=1024))
                    tk = sched.submit_refresh(reg, v)
                    got = tk.result()
                    _bitcmp(got, _oracle(reg, v), f"epoch{e}")
            finally:
                stop.set()
                th.join()
        assert not errs
        assert metrics.counter_value("stream.refresh.submitted") == 3
        assert metrics.counter_value("stream.refresh.incremental") == 3
        reg.close()


# --- cardinality-stats LRU (bugfix regression) -------------------------------


class TestCardinalityStatsLRU:
    def test_cap_with_read_refresh(self):
        s = plan_stats.CardinalityStats(max_entries=4)
        nodes = [ir.Scan(f"t{i}") for i in range(6)]
        for n in nodes[:4]:
            s.observe(ir.fingerprint(n), 10)
        assert len(s) == 4
        assert s.rows_for(nodes[0]) == 10.0     # read refreshes recency
        s.observe(ir.fingerprint(nodes[4]), 10)
        s.observe(ir.fingerprint(nodes[5]), 10)
        assert len(s) == 4 and s.evictions == 2
        assert s.rows_for(nodes[0]) == 10.0     # survivor: it was read
        assert s.rows_for(nodes[1]) is None     # evicted: it was not

    def test_env_cap(self, monkeypatch):
        monkeypatch.setenv("SRJT_PLAN_STATS_CAP", "2")
        s = plan_stats.CardinalityStats()
        for i in range(5):
            s.observe(ir.fingerprint(ir.Scan(f"e{i}")), i)
        assert len(s) == 2 and s.evictions == 3
