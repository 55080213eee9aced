"""TPC-DS subset differential tests (BASELINE config #3): every query's
result is compared against pandas executing the same plan over the same
Snappy parquet bytes — join + groupby + string keys + decimals end-to-end
through decode → ops → output."""

import io

import numpy as np
import pandas as pd
import pytest

from benchmarks import tpcds_data
from spark_rapids_jni_tpu.models import tpcds


@pytest.fixture(scope="module")
def files():
    return tpcds_data.generate(n_sales=40_000, n_items=500, seed=7)


@pytest.fixture(scope="module")
def dfs(files):
    return {name: pd.read_parquet(io.BytesIO(raw))
            for name, raw in files.items()}


@pytest.fixture(scope="module")
def tables(files):
    return tpcds.load_tables(files)


def _assert_result(out, expect_df, key_cols, val_specs):
    """out: framework Table (keys..., aggs...); expect_df: pandas frame with
    the same columns, unsorted."""
    expect = expect_df.sort_values(key_cols).reset_index(drop=True)
    assert out.num_rows == len(expect), (out.num_rows, len(expect))
    for i, k in enumerate(key_cols):
        got = (out[i].to_pylist() if out[i].dtype.id.name == "STRING"
               else out[i].to_numpy().tolist())
        assert got == expect[k].tolist(), k
    for j, (name, kind) in enumerate(val_specs):
        got = np.asarray(out[len(key_cols) + j].to_numpy(), dtype=np.float64)
        if kind == "decimal2":
            got = got / 100.0
        np.testing.assert_allclose(got, expect[name].to_numpy(), rtol=1e-9)


def test_q3(tables, dfs):
    mid = int(dfs["item"].i_manufact_id.mode()[0])   # guaranteed present
    out = tpcds.q3(tables, manufact_id=mid, moy=11)
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(item[item.i_manufact_id == mid], left_on="ss_item_sk",
                  right_on="i_item_sk")
         .merge(dd[dd.d_moy == 11], left_on="ss_sold_date_sk",
                right_on="d_date_sk"))
    exp = (j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
           ["ss_ext_sales_price"].sum())
    _assert_result(out, exp, ["d_year", "i_brand_id", "i_brand"],
                   [("ss_ext_sales_price", "float")])


def test_q42(tables, dfs):
    mid = int(dfs["item"].i_manager_id.mode()[0])
    out = tpcds.q42(tables, manager_id=mid, year=2000, moy=11)
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(item[item.i_manager_id == mid], left_on="ss_item_sk",
                  right_on="i_item_sk")
         .merge(dd[(dd.d_moy == 11) & (dd.d_year == 2000)],
                left_on="ss_sold_date_sk", right_on="d_date_sk"))
    exp = (j.groupby(["d_year", "i_category_id", "i_category"],
                     as_index=False)["ss_ext_sales_price"].sum())
    _assert_result(out, exp, ["d_year", "i_category_id", "i_category"],
                   [("ss_ext_sales_price", "float")])


def test_q52(tables, dfs):
    out = tpcds.q52(tables, moy=12, year=2001)
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(dd[(dd.d_moy == 12) & (dd.d_year == 2001)],
                  left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(item, left_on="ss_item_sk", right_on="i_item_sk"))
    exp = (j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
           ["ss_ext_sales_price"].sum())
    _assert_result(out, exp, ["d_year", "i_brand_id", "i_brand"],
                   [("ss_ext_sales_price", "float")])


def test_q55(tables, dfs):
    mid = int(dfs["item"].i_manager_id.mode()[0])
    out = tpcds.q55(tables, manager_id=mid)
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item[item.i_manager_id == mid], left_on="ss_item_sk",
                 right_on="i_item_sk")
    exp = (j.groupby(["i_brand_id", "i_brand"], as_index=False)
           ["ss_ext_sales_price"].sum())
    _assert_result(out, exp, ["i_brand_id", "i_brand"],
                   [("ss_ext_sales_price", "float")])


def test_q_state_rollup(tables, dfs):
    out = tpcds.q_state_rollup(tables, state="TN")
    ss, store = dfs["store_sales"], dfs["store"]
    j = ss.merge(store[store.s_state == "TN"], left_on="ss_store_sk",
                 right_on="s_store_sk")
    exp = (j.groupby(["s_state"], as_index=False)
           .agg(price=("ss_sales_price_cents", "sum"),
                qmean=("ss_quantity", "mean"),
                qcount=("ss_quantity", "count")))
    exp["price"] = exp["price"] / 100.0   # decimal(…,2) dollars
    _assert_result(out, exp, ["s_state"],
                   [("price", "decimal2"), ("qmean", "float"),
                    ("qcount", "float")])


def test_q7(tables, dfs):
    out = tpcds.q7(tables, year=2000)
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(dd[dd.d_year == 2000], left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
         .merge(item, left_on="ss_item_sk", right_on="i_item_sk"))
    exp = (j.groupby(["i_item_id"], as_index=False)
           .agg(q=("ss_quantity", "mean"),
                lp=("ss_list_price_cents", "mean"),
                sp=("ss_sales_price_cents", "mean")))
    _assert_result(out, exp, ["i_item_id"],
                   [("q", "float"), ("lp", "float"), ("sp", "float")])


def test_q19(tables, dfs):
    out = tpcds.q19(tables, year=1999, moy=11, manager_lo=1, manager_hi=50)
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(item[(item.i_manager_id >= 1) & (item.i_manager_id <= 50)],
                  left_on="ss_item_sk", right_on="i_item_sk")
         .merge(dd[(dd.d_moy == 11) & (dd.d_year == 1999)],
                left_on="ss_sold_date_sk", right_on="d_date_sk"))
    exp = (j.groupby(["i_brand_id", "i_brand", "i_manufact_id"],
                     as_index=False)["ss_ext_sales_price"].sum())
    _assert_result(out, exp, ["i_brand_id", "i_brand", "i_manufact_id"],
                   [("ss_ext_sales_price", "float")])


def test_q62(tables, dfs):
    out = tpcds.q62(tables, year=2000, qty_lo=10, qty_hi=60)
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = (ss[(ss.ss_quantity >= 10) & (ss.ss_quantity <= 60)]
         .merge(dd[dd.d_year == 2000], left_on="ss_sold_date_sk",
                right_on="d_date_sk"))
    exp = (j.groupby(["d_moy"], as_index=False)
           .agg(cnt=("ss_quantity", "count")))
    _assert_result(out, exp, ["d_moy"], [("cnt", "float")])


def test_q52_topn(tables, dfs):
    out = tpcds.q52_topn(tables, moy=12, year=2001, n=5)
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(dd[(dd.d_moy == 12) & (dd.d_year == 2001)],
                  left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(item, left_on="ss_item_sk", right_on="i_item_sk"))
    exp = (j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
           ["ss_ext_sales_price"].sum()
           .sort_values(["ss_ext_sales_price", "i_brand_id"],
                        ascending=[False, True]).head(5)
           .reset_index(drop=True))
    assert out.num_rows == len(exp)
    assert out[1].to_numpy().tolist() == exp["i_brand_id"].tolist()
    np.testing.assert_allclose(np.asarray(out[3].to_numpy()),
                               exp["ss_ext_sales_price"].to_numpy(),
                               rtol=1e-9)


def test_q65(tables, dfs):
    out = tpcds.q65(tables, frac=0.9)
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    rev = (j.groupby(["i_brand_id"], as_index=False)
           ["ss_ext_sales_price"].sum())
    thr = rev["ss_ext_sales_price"].mean() * 0.9
    exp = (rev[rev.ss_ext_sales_price < thr]
           .sort_values("i_brand_id").reset_index(drop=True))
    _assert_result(out, exp, ["i_brand_id"],
                   [("ss_ext_sales_price", "float")])


def test_q_store_counts(tables, dfs):
    out = tpcds.q_store_counts(tables)
    ss, store = dfs["store_sales"], dfs["store"]
    j = store.merge(ss, left_on="s_store_sk", right_on="ss_store_sk",
                    how="left")
    exp = (j.groupby(["s_store_sk", "s_state"], as_index=False)
           .agg(cnt=("ss_item_sk", "count"))
           .sort_values("s_store_sk").reset_index(drop=True))
    assert out.num_rows == len(exp)
    assert out[0].to_numpy().tolist() == exp["s_store_sk"].tolist()
    assert out[2].to_numpy().tolist() == exp["cnt"].tolist()
    # the never-selling store must appear with count 0
    assert 0 in out[2].to_numpy().tolist()


@pytest.mark.slow
def test_run_all_smoke(files):
    # spec-default parameters may select nothing at this mini scale — an
    # empty result is a valid result (Spark returns empty, not an error)
    results = tpcds.run_all(files)
    assert set(results) == set(tpcds.QUERIES)
    for name, t in results.items():
        # set-operation queries (INTERSECT/EXCEPT) legitimately return a
        # single key column; everything else carries keys + measures
        min_cols = 1 if name in ("q8_intersect", "q87_except") else 2
        assert t.num_columns >= min_cols, name
        assert t.num_rows >= 0, name


# ---- round-3 additions: window / LIKE / union / distinct-count family ----

def test_q67_rank(tables, dfs):
    out = tpcds.q67_rank(tables, top_n=3)
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    rev = (j.groupby(["i_category", "i_brand_id"], as_index=False)
           ["ss_ext_sales_price"].sum())
    rev["rk"] = (rev.sort_values(["ss_ext_sales_price", "i_brand_id"],
                                 ascending=[False, True])
                 .groupby("i_category").cumcount() + 1)
    # pandas rank with our tie semantics: RANK over (sum desc, brand asc)
    # has no ties because brand_id is unique within the sort
    exp = (rev[rev.rk <= 3]
           .sort_values(["i_category", "rk", "i_brand_id"])
           .reset_index(drop=True))
    assert out.num_rows == len(exp)
    assert out[0].to_pylist() == exp["i_category"].tolist()
    assert out[1].to_numpy().tolist() == exp["i_brand_id"].tolist()
    np.testing.assert_allclose(out[2].to_numpy(),
                               exp["ss_ext_sales_price"].to_numpy(),
                               rtol=1e-9)
    assert out[3].to_numpy().tolist() == exp["rk"].tolist()


def test_q_like_brands(tables, dfs):
    out = tpcds.q_like_brands(tables, pat="#1", cat_prefix="S")
    ss, item = dfs["store_sales"], dfs["item"]
    item_f = item[item.i_brand.str.contains("#1", regex=False)
                  & item.i_category.str.startswith("S")]
    j = ss.merge(item_f, left_on="ss_item_sk", right_on="i_item_sk")
    exp = (j.groupby(["i_category"], as_index=False)
           ["ss_ext_sales_price"].sum())
    _assert_result(out, exp, ["i_category"],
                   [("ss_ext_sales_price", "float")])


def test_q_union_channels(tables, dfs):
    out = tpcds.q_union_channels(tables)
    ss, ws, item = dfs["store_sales"], dfs["web_sales"], dfs["item"]
    both = pd.concat([
        ss[["ss_item_sk", "ss_ext_sales_price"]]
        .rename(columns={"ss_item_sk": "sk", "ss_ext_sales_price": "price"}),
        ws[["ws_item_sk", "ws_ext_sales_price"]]
        .rename(columns={"ws_item_sk": "sk", "ws_ext_sales_price": "price"}),
    ])
    j = both.merge(item, left_on="sk", right_on="i_item_sk")
    exp = j.groupby(["i_category"], as_index=False)["price"].sum()
    _assert_result(out, exp, ["i_category"], [("price", "float")])


def test_q_lag_growth(tables, dfs):
    out = tpcds.q_lag_growth(tables)
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    rev = (j.groupby(["ss_store_sk", "d_year", "d_moy"], as_index=False)
           ["ss_ext_sales_price"].sum()
           .sort_values(["ss_store_sk", "d_year", "d_moy"])
           .reset_index(drop=True))
    prev = rev.groupby("ss_store_sk")["ss_ext_sales_price"].shift(1)
    delta = rev["ss_ext_sales_price"] - prev.fillna(0.0)
    assert out.num_rows == len(rev)
    np.testing.assert_array_equal(out[0].to_numpy(),
                                  rev["ss_store_sk"].to_numpy())
    got_delta = np.asarray(
        [v if v is not None else np.nan for v in out[4].to_pylist()])
    want = np.where(prev.isna().to_numpy(), np.nan, delta.to_numpy())
    np.testing.assert_allclose(got_delta, want, rtol=1e-9)


def test_q_running_share(tables, dfs):
    out = tpcds.q_running_share(tables, year=2000)
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = ss.merge(dd[dd.d_year == 2000], left_on="ss_sold_date_sk",
                 right_on="d_date_sk")
    rev = (j.groupby(["ss_store_sk", "d_moy"], as_index=False)
           ["ss_ext_sales_price"].sum()
           .sort_values(["ss_store_sk", "d_moy"]).reset_index(drop=True))
    rev["cum"] = rev.groupby("ss_store_sk")["ss_ext_sales_price"].cumsum()
    assert out.num_rows == len(rev)
    np.testing.assert_allclose(out[3].to_numpy(), rev["cum"].to_numpy(),
                               rtol=1e-9)


def test_q_nunique_items(tables, dfs):
    out = tpcds.q_nunique_items(tables)
    ss = dfs["store_sales"]
    exp = (ss.groupby("ss_store_sk")["ss_item_sk"].nunique()
           .reset_index().sort_values("ss_store_sk"))
    assert out[0].to_numpy().tolist() == exp["ss_store_sk"].tolist()
    assert out[1].to_numpy().tolist() == exp["ss_item_sk"].tolist()


def test_q_having(tables, dfs):
    out = tpcds.q_having(tables, min_total=1000.0)
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    rev = (j.groupby("i_brand_id", as_index=False)
           ["ss_ext_sales_price"].sum())
    exp = rev[rev.ss_ext_sales_price > 1000.0].sort_values("i_brand_id")
    assert out[0].to_numpy().tolist() == exp["i_brand_id"].tolist()
    np.testing.assert_allclose(out[1].to_numpy(),
                               exp["ss_ext_sales_price"].to_numpy(),
                               rtol=1e-9)


def test_q_case_when(tables, dfs):
    out = tpcds.q_case_when(tables, qty_cut=50)
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    j = j.assign(bulk=np.where(j.ss_quantity > 50, j.ss_ext_sales_price, 0.0),
                 retail=np.where(j.ss_quantity > 50, 0.0,
                                 j.ss_ext_sales_price))
    exp = j.groupby("i_category", as_index=False)[["bulk", "retail"]].sum()
    _assert_result(out, exp, ["i_category"],
                   [("bulk", "float"), ("retail", "float")])


def test_q_distinct_pairs(tables, dfs):
    out = tpcds.q_distinct_pairs(tables)
    item = dfs["item"]
    exp = (item[["i_brand_id", "i_category_id"]].drop_duplicates()
           .sort_values(["i_brand_id", "i_category_id"]))
    assert out.num_rows == len(exp)
    assert out[0].to_numpy().tolist() == exp["i_brand_id"].tolist()
    assert out[1].to_numpy().tolist() == exp["i_category_id"].tolist()


def test_q_isin_states(tables, dfs):
    out = tpcds.q_isin_states(tables, states=("TN", "CA"))
    ss, store = dfs["store_sales"], dfs["store"]
    store_f = store[store.s_state.isin(["TN", "CA"])]
    j = ss.merge(store_f, left_on="ss_store_sk", right_on="s_store_sk")
    exp = j.groupby(["s_state"], as_index=False)["ss_ext_sales_price"].sum()
    _assert_result(out, exp, ["s_state"], [("ss_ext_sales_price", "float")])


@pytest.mark.slow      # whole-corpus sweep; every query has its own test
def test_run_all_executes_every_query(files):
    outs = tpcds.run_all(files)
    assert len(outs) == len(tpcds.QUERIES) >= 21
    for name, t in outs.items():
        assert t.num_rows >= 0, name


# ---- round-6 additions: composite multi-key joins + left-outer fusion ----

def test_q_channel_day(tables, dfs):
    from spark_rapids_jni_tpu.utils import metrics
    metrics.set_enabled(True)
    metrics.reset()
    try:
        out = tpcds.q_channel_day(tables)
        counters = metrics.snapshot()["counters"]
    finally:
        metrics.set_enabled(False)
    # the (item_sk, sold_date_sk) tuple took the packed composite path
    assert counters.get("join.pack.composite", 0) >= 1, counters
    ss, ws, item = dfs["store_sales"], dfs["web_sales"], dfs["item"]
    s_rev = (ss.groupby(["ss_item_sk", "ss_sold_date_sk"], as_index=False)
             ["ss_ext_sales_price"].sum())
    w_rev = (ws.groupby(["ws_item_sk", "ws_sold_date_sk"], as_index=False)
             ["ws_ext_sales_price"].sum())
    j = (s_rev.merge(w_rev, left_on=["ss_item_sk", "ss_sold_date_sk"],
                     right_on=["ws_item_sk", "ws_sold_date_sk"])
         .merge(item, left_on="ss_item_sk", right_on="i_item_sk"))
    exp = (j.groupby("i_category", as_index=False)
           .agg(s=("ss_ext_sales_price", "sum"),
                w=("ws_ext_sales_price", "sum")))
    _assert_result(out, exp, ["i_category"], [("s", "float"), ("w", "float")])


def test_q_web_also_qty(tables, dfs):
    out = tpcds.q_web_also_qty(tables)
    ss, ws = dfs["store_sales"], dfs["web_sales"]
    pairs = ws[["ws_item_sk", "ws_sold_date_sk"]].drop_duplicates()
    j = ss.merge(pairs, left_on=["ss_item_sk", "ss_sold_date_sk"],
                 right_on=["ws_item_sk", "ws_sold_date_sk"])
    exp = (j.groupby("ss_store_sk", as_index=False)["ss_quantity"].sum())
    _assert_result(out, exp, ["ss_store_sk"], [("ss_quantity", "float")])


def test_q_brand_rev_left(tables, dfs):
    out = tpcds.q_brand_rev_left(tables, manager_id=28)
    ss, item = dfs["store_sales"], dfs["item"]
    item_f = item[item.i_manager_id == 28]
    j = ss.merge(item_f, left_on="ss_item_sk", right_on="i_item_sk",
                 how="left")
    exp = (j.groupby("i_brand_id", dropna=False, as_index=False)
           .agg(s=("ss_ext_sales_price", "sum"), c=("ss_item_sk", "count"))
           .sort_values("i_brand_id", na_position="last",
                        ignore_index=True))
    assert out.num_rows == len(exp)
    # brand ids incl. the null group for every non-selected item's sales
    got_b = out[0].to_pylist()
    exp_b = [None if pd.isna(b) else int(b) for b in exp["i_brand_id"]]
    # our sort may place the null key first or last — align on key value
    if got_b[0] is None:
        got_b = got_b[1:] + [None]
        perm = list(range(1, len(exp))) + [0]
    else:
        perm = list(range(len(exp)))
    assert got_b == exp_b
    got_s = np.asarray(out[1].to_numpy(), dtype=np.float64)[perm]
    got_c = np.asarray(out[2].to_numpy())[perm]
    np.testing.assert_allclose(got_s, exp["s"].to_numpy(), rtol=1e-9)
    assert got_c.tolist() == exp["c"].tolist()


# --- plan-tree differential sweep --------------------------------------------
# Every ported query runs three ways over the same data: plan-tree
# (optimized + lowered), hand-fused (the oracle-checked kernels above),
# and — transitively through the tests above — the pandas oracle.  The
# plan path must be BIT-identical to the hand-fused path: same dtypes,
# same device buffers, same offsets, same validity.


from spark_rapids_jni_tpu import plan as P                    # noqa: E402
from spark_rapids_jni_tpu.column import force_column          # noqa: E402
from spark_rapids_jni_tpu.models import tpcds_plans           # noqa: E402
from spark_rapids_jni_tpu.plan import ir as pir               # noqa: E402

PLAN_QUERIES = sorted(tpcds_plans.PLANS)


def _plan_params(name, dfs):
    """Pick filter values guaranteed to select rows in this dataset."""
    if name == "q3":
        return {"manufact_id": int(dfs["item"].i_manufact_id.mode()[0])}
    if name in ("q42", "q55"):
        return {"manager_id": int(dfs["item"].i_manager_id.mode()[0])}
    return {}


def _assert_bitwise(got, exp):
    assert got.num_rows == exp.num_rows
    assert got.num_columns == exp.num_columns
    for i in range(got.num_columns):
        a, b = force_column(got[i]), force_column(exp[i])
        assert a.dtype.id == b.dtype.id, f"col {i} dtype"
        np.testing.assert_array_equal(np.asarray(a.data),
                                      np.asarray(b.data), err_msg=f"col {i}")
        assert (a.offsets is None) == (b.offsets is None), f"col {i} offsets"
        if a.offsets is not None:
            np.testing.assert_array_equal(np.asarray(a.offsets),
                                          np.asarray(b.offsets))
        assert (a.validity is None) == (b.validity is None), \
            f"col {i} validity"
        if a.validity is not None:
            np.testing.assert_array_equal(np.asarray(a.validity),
                                          np.asarray(b.validity))


@pytest.mark.parametrize("name", PLAN_QUERIES)
def test_plan_tree_matches_hand_fused(tables, dfs, name):
    params = _plan_params(name, dfs)
    qfn, tree = tpcds_plans.plan_fn(name, **params)
    got = qfn(tables)
    exp = getattr(tpcds, name)(tables, **params)
    assert got.num_rows > 0            # params chosen so rows survive
    _assert_bitwise(got, exp)
    # and again straight from the UN-optimized tree: the rewrites are
    # result-invariant, not just "usually equivalent"
    cat = P.TableCatalog(tables, tpcds_plans.TABLE_SCHEMAS)
    _assert_bitwise(P.execute(tree, cat, record_stats=False), exp)


@pytest.mark.parametrize("name", PLAN_QUERIES)
def test_plan_fusion_is_rule_detected(name):
    # raw plan definitions contain NO hand-wired fused node ...
    raw = tpcds_plans.PLANS[name]()
    assert not any(isinstance(n, pir.FusedJoinAggregate)
                   for n in pir.walk(raw))
    # ... the optimizer introduces it
    res = tpcds_plans.optimized(name)
    assert any(ev.rule == "fuse_join_aggregate" for ev in res.events)
    assert any(isinstance(n, pir.FusedJoinAggregate)
               for n in pir.walk(res.tree))
    # and every query gets at least one pushdown rewrite too
    assert any(ev.rule in ("projection_pushdown", "filter_pushdown")
               for ev in res.events)


@pytest.mark.parametrize("row_group_size", [None, 256])
def test_plan_file_catalog_matches_hand_fused(files, tables, dfs,
                                              row_group_size):
    """Lowered Scan nodes read parquet bytes directly (pruned decode);
    results must still be bit-identical to hand kernels over the fully
    decoded tables.  The counters show the planner's pushdown reaching the
    decoder: columns dropped before decode and, where the dimension files
    have more than one row group, groups dropped by their footer stats."""
    from spark_rapids_jni_tpu.utils import metrics
    if row_group_size is not None:
        files = tpcds_data.generate(n_sales=20_000, n_items=2_000, seed=5,
                                    row_group_size=row_group_size)
        tables = tpcds.load_tables(files)
        dfs = {"item": pd.read_parquet(io.BytesIO(files["item"]))}
    params = _plan_params("q3", dfs)
    res = tpcds_plans.optimized("q3", **params)
    metrics.set_enabled(True)
    metrics.reset()
    try:
        out = P.execute(res.tree, P.FileCatalog(dict(files)),
                        record_stats=False)
        counters = metrics.snapshot()["counters"]
    finally:
        metrics.set_enabled(False)
    assert counters.get("plan.scan.columns_pruned", 0) >= 1, counters
    if row_group_size is not None:
        assert counters.get("plan.scan.rowgroups_pruned", 0) >= 1, counters
    _assert_bitwise(out, tpcds.q3(tables, **params))


def test_plan_capture_replay_matches_hand_fused(tables, dfs):
    from spark_rapids_jni_tpu.models import compiled
    params = _plan_params("q42", dfs)
    qfn, _ = tpcds_plans.plan_fn("q42", **params)
    cq = compiled.compile_query(qfn, tables)
    exp = tpcds.q42(tables, **params)
    _assert_bitwise(cq.run(tables), exp)
    assert qfn.plan_fingerprint.startswith("plan:")
