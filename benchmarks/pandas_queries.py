"""Pandas implementations of the full TPC-DS query subset.

The host counterpart of ``models/tpcds.py:QUERIES`` — every plan
re-expressed over pandas DataFrames (single-process pandas is what the
image provides).  Each function takes ``dfs`` (table name → DataFrame)
and returns a DataFrame/Series; result row counts are cross-checked
against the program's results in ``tests/test_pandas_queries.py``.

These are plan translations, not golden oracles — the per-query pandas
differentials in ``tests/test_tpcds*.py`` remain the correctness
authority for the framework.
"""

from __future__ import annotations

import numpy as np
import pandas as pd


def q3(dfs, manufact_id=436, moy=11):
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(item[item.i_manufact_id == manufact_id],
                  left_on="ss_item_sk", right_on="i_item_sk")
         .merge(dd[dd.d_moy == moy], left_on="ss_sold_date_sk",
                right_on="d_date_sk"))
    return (j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
            ["ss_ext_sales_price"].sum())


def q42(dfs, manager_id=1, year=2000, moy=11):
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(item[item.i_manager_id == manager_id],
                  left_on="ss_item_sk", right_on="i_item_sk")
         .merge(dd[(dd.d_moy == moy) & (dd.d_year == year)],
                left_on="ss_sold_date_sk", right_on="d_date_sk"))
    return (j.groupby(["d_year", "i_category_id", "i_category"],
                      as_index=False)["ss_ext_sales_price"].sum())


def q52(dfs, moy=12, year=2001):
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(dd[(dd.d_moy == moy) & (dd.d_year == year)],
                  left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(item, left_on="ss_item_sk", right_on="i_item_sk"))
    return (j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False)
            ["ss_ext_sales_price"].sum())


def q55(dfs, manager_id=28):
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item[item.i_manager_id == manager_id],
                 left_on="ss_item_sk", right_on="i_item_sk")
    return (j.groupby(["i_brand_id", "i_brand"], as_index=False)
            ["ss_ext_sales_price"].sum())


def q_state_rollup(dfs, state="TN"):
    ss, store = dfs["store_sales"], dfs["store"]
    j = ss.merge(store[store.s_state == state], left_on="ss_store_sk",
                 right_on="s_store_sk")
    return (j.groupby("s_state", as_index=False)
            .agg(s=("ss_sales_price_cents", "sum"),
                 m=("ss_quantity", "mean"),
                 c=("ss_quantity", "count")))


def q7(dfs, year=2000):
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(dd[dd.d_year == year], left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
         .merge(item, left_on="ss_item_sk", right_on="i_item_sk"))
    return (j.groupby("i_item_id", as_index=False)
            .agg(q=("ss_quantity", "mean"),
                 lp=("ss_list_price_cents", "mean"),
                 sp=("ss_sales_price_cents", "mean")))


def q19(dfs, year=1999, moy=11, manager_lo=1, manager_hi=50):
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    itf = item[(item.i_manager_id >= manager_lo)
               & (item.i_manager_id <= manager_hi)]
    j = (ss.merge(itf, left_on="ss_item_sk", right_on="i_item_sk")
         .merge(dd[(dd.d_moy == moy) & (dd.d_year == year)],
                left_on="ss_sold_date_sk", right_on="d_date_sk"))
    return (j.groupby(["i_brand_id", "i_brand", "i_manufact_id"],
                      as_index=False)["ss_ext_sales_price"].sum())


def q62(dfs, year=2000, qty_lo=10, qty_hi=60):
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    ssf = ss[(ss.ss_quantity >= qty_lo) & (ss.ss_quantity <= qty_hi)]
    j = ssf.merge(dd[dd.d_year == year], left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
    return j.groupby("d_moy", as_index=False)["ss_quantity"].count()


def q52_topn(dfs, moy=12, year=2001, n=10):
    out = q52(dfs, moy=moy, year=year)
    return out.sort_values(["ss_ext_sales_price", "i_brand_id"],
                           ascending=[False, True]).head(n)


def q65(dfs, frac=0.9):
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    rev = j.groupby("i_brand_id", as_index=False)["ss_ext_sales_price"].sum()
    thr = rev.ss_ext_sales_price.mean() * frac
    return rev[rev.ss_ext_sales_price < thr]


def q_store_counts(dfs):
    ss, store = dfs["store_sales"], dfs["store"]
    j = store.merge(ss, left_on="s_store_sk", right_on="ss_store_sk",
                    how="left")
    return (j.groupby(["s_store_sk", "s_state"], as_index=False)
            ["ss_item_sk"].count())


def q67_rank(dfs, top_n=3):
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    rev = (j.groupby(["i_category", "i_brand_id"], as_index=False)
           ["ss_ext_sales_price"].sum())
    rev = rev.sort_values(["i_category", "ss_ext_sales_price", "i_brand_id"],
                          ascending=[True, False, True])
    rev["rk"] = (rev.groupby("i_category")["ss_ext_sales_price"]
                 .rank(method="min", ascending=False).astype(int))
    return rev[rev.rk <= top_n]


def q_like_brands(dfs, pat="#1", cat_prefix="S"):
    ss, item = dfs["store_sales"], dfs["item"]
    itf = item[item.i_brand.str.contains(pat, regex=False)
               & item.i_category.str.startswith(cat_prefix)]
    j = ss.merge(itf, left_on="ss_item_sk", right_on="i_item_sk")
    return (j.groupby("i_category", as_index=False)
            ["ss_ext_sales_price"].sum())


def q_union_channels(dfs):
    ss, ws, item = dfs["store_sales"], dfs["web_sales"], dfs["item"]
    both = pd.concat([
        ss[["ss_item_sk", "ss_ext_sales_price"]]
        .rename(columns={"ss_item_sk": "item_sk",
                         "ss_ext_sales_price": "price"}),
        ws[["ws_item_sk", "ws_ext_sales_price"]]
        .rename(columns={"ws_item_sk": "item_sk",
                         "ws_ext_sales_price": "price"})])
    j = both.merge(item, left_on="item_sk", right_on="i_item_sk")
    return j.groupby("i_category", as_index=False)["price"].sum()


def q_lag_growth(dfs):
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    rev = (j.groupby(["ss_store_sk", "d_year", "d_moy"], as_index=False)
           ["ss_ext_sales_price"].sum()
           .sort_values(["ss_store_sk", "d_year", "d_moy"]))
    prev = rev.groupby("ss_store_sk")["ss_ext_sales_price"].shift(1)
    rev["delta"] = rev.ss_ext_sales_price - prev.fillna(0.0)
    return rev


def q_running_share(dfs, year=2000):
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = ss.merge(dd[dd.d_year == year], left_on="ss_sold_date_sk",
                 right_on="d_date_sk")
    rev = (j.groupby(["ss_store_sk", "d_moy"], as_index=False)
           ["ss_ext_sales_price"].sum()
           .sort_values(["ss_store_sk", "d_moy"]))
    rev["cum"] = rev.groupby("ss_store_sk")["ss_ext_sales_price"].cumsum()
    return rev


def q_nunique_items(dfs):
    ss = dfs["store_sales"]
    return (ss.groupby("ss_store_sk", as_index=False)
            ["ss_item_sk"].nunique())


def q_having(dfs, min_total=1000.0):
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    rev = j.groupby("i_brand_id", as_index=False)["ss_ext_sales_price"].sum()
    return rev[rev.ss_ext_sales_price > min_total]


def q_case_when(dfs, qty_cut=50):
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    price = j.ss_ext_sales_price.fillna(0.0)
    bulk = j.ss_quantity.gt(qty_cut).fillna(False)
    j = j.assign(bulk_rev=np.where(bulk, price, 0.0),
                 retail_rev=np.where(bulk, 0.0, price))
    return (j.groupby("i_category", as_index=False)
            [["bulk_rev", "retail_rev"]].sum())


def q_distinct_pairs(dfs):
    item = dfs["item"]
    return item[["i_brand_id", "i_category_id"]].drop_duplicates()


def q_isin_states(dfs, states=("TN", "CA")):
    ss, store = dfs["store_sales"], dfs["store"]
    j = ss.merge(store[store.s_state.isin(list(states))],
                 left_on="ss_store_sk", right_on="s_store_sk")
    return (j.groupby("s_state", as_index=False)
            ["ss_ext_sales_price"].sum())


def _rollup(j, keys, aggs):
    """Pandas grouping-sets union with a Spark-style grouping_id."""
    frames = []
    for lvl in range(len(keys), -1, -1):
        sub = keys[:lvl]
        gid = sum(1 << (len(keys) - 1 - i) for i in range(lvl, len(keys)))
        if sub:
            g = j.groupby(sub, as_index=False).agg(**aggs)
        else:
            g = pd.DataFrame([{n: j[c].agg(f)
                               for n, (c, f) in aggs.items()}])
        for k in keys[lvl:]:
            g[k] = None
        g["grouping_id"] = gid
        frames.append(g)
    return pd.concat(frames, ignore_index=True)


def q36_rollup(dfs):
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    return _rollup(j, ["i_category", "i_brand"],
                   {"rev": ("ss_ext_sales_price", "sum")})


def q86_rollup(dfs):
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    return _rollup(j, ["d_year", "d_moy"],
                   {"rev": ("ss_ext_sales_price", "sum")})


def q27_cube(dfs):
    ss, item, store = dfs["store_sales"], dfs["item"], dfs["store"]
    j = (ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
         .merge(store, left_on="ss_store_sk", right_on="s_store_sk"))
    frames = []
    for gid, sub in [(0, ["i_category", "s_state"]), (1, ["i_category"]),
                     (2, ["s_state"]), (3, [])]:
        if sub:
            g = j.groupby(sub, as_index=False).agg(
                mq=("ss_quantity", "mean"), rev=("ss_ext_sales_price", "sum"))
        else:
            g = pd.DataFrame([{"mq": j.ss_quantity.mean(),
                               "rev": j.ss_ext_sales_price.sum()}])
        g["grouping_id"] = gid
        frames.append(g)
    return pd.concat(frames, ignore_index=True)


def q5_grouping_sets(dfs):
    ss, ws, item = dfs["store_sales"], dfs["web_sales"], dfs["item"]
    both = pd.concat([
        ss[["ss_item_sk", "ss_ext_sales_price"]].assign(channel=0)
        .rename(columns={"ss_item_sk": "item_sk",
                         "ss_ext_sales_price": "price"}),
        ws[["ws_item_sk", "ws_ext_sales_price"]].assign(channel=1)
        .rename(columns={"ws_item_sk": "item_sk",
                         "ws_ext_sales_price": "price"})])
    j = both.merge(item, left_on="item_sk", right_on="i_item_sk")
    frames = []
    for sub in [["channel", "i_category"], ["channel"], []]:
        if sub:
            g = j.groupby(sub, as_index=False).agg(rev=("price", "sum"))
        else:
            g = pd.DataFrame([{"rev": j.price.sum()}])
        frames.append(g)
    return pd.concat(frames, ignore_index=True)


def q78_outer(dfs):
    ss, ws = dfs["store_sales"], dfs["web_sales"]
    s = (ss.groupby("ss_item_sk", as_index=False)
         ["ss_ext_sales_price"].sum())
    w = (ws.groupby("ws_item_sk", as_index=False)
         ["ws_ext_sales_price"].sum())
    j = s.merge(w, left_on="ss_item_sk", right_on="ws_item_sk",
                how="outer")
    j["key"] = j.ss_item_sk.fillna(j.ws_item_sk)
    j["s_rev"] = j.ss_ext_sales_price.fillna(0.0)
    j["w_rev"] = j.ws_ext_sales_price.fillna(0.0)
    return j[["key", "s_rev", "w_rev"]]


def q25_two_fact(dfs, year=2000):
    ss, ws, dd = dfs["store_sales"], dfs["web_sales"], dfs["date_dim"]
    ddf = dd[dd.d_year == year]
    js = ss.merge(ddf, left_on="ss_sold_date_sk", right_on="d_date_sk")
    jw = ws.merge(ddf, left_on="ws_sold_date_sk", right_on="d_date_sk")
    s = js.groupby("ss_item_sk", as_index=False)["ss_ext_sales_price"].sum()
    w = jw.groupby("ws_item_sk", as_index=False)["ws_ext_sales_price"].sum()
    return s.merge(w, left_on="ss_item_sk", right_on="ws_item_sk")


def q88_counts(dfs):
    ss = dfs["store_sales"]
    q = ss.ss_quantity
    return pd.DataFrame([{
        f"b{i}": int(((q >= lo) & (q <= hi)).sum())
        for i, (lo, hi) in enumerate([(1, 25), (26, 50), (51, 75),
                                      (76, 100)])}])


def q90_ratio(dfs):
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    am = int((j.d_moy <= 6).sum())
    pm = int((j.d_moy > 6).sum())
    return pd.DataFrame([{"am": am, "pm": pm, "ratio": am / max(pm, 1)}])


def q29_minmax(dfs):
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    return (j.groupby("i_brand_id", as_index=False)
            .agg(mn=("ss_quantity", "min"), mx=("ss_quantity", "max"),
                 mean=("ss_quantity", "mean")))


def q48_bands(dfs):
    ss, store = dfs["store_sales"], dfs["store"]
    q, p = ss.ss_quantity, ss.ss_sales_price_cents
    m = (((q >= 1) & (q <= 20) & (p < 50_00))
         | ((q >= 41) & (q <= 60) & (p > 150_00)))
    j = ss[m].merge(store, left_on="ss_store_sk", right_on="s_store_sk")
    return j.groupby("s_state", as_index=False)["ss_quantity"].sum()


def q13_avg_bands(dfs):
    ss = dfs["store_sales"]
    q, p = ss.ss_quantity, ss.ss_sales_price_cents
    out = {}
    for i, (lo, hi) in enumerate([(1, 33), (34, 66), (67, 100)]):
        m = (q >= lo) & (q <= hi) & p.notna()
        out[f"b{i}"] = float(p[m].sum() / max(int(m.sum()), 1) / 100.0)
    return pd.DataFrame([out])


def q96_count(dfs, year=2000, qty_min=80):
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = ss[ss.ss_quantity >= qty_min].merge(
        dd[dd.d_year == year], left_on="ss_sold_date_sk",
        right_on="d_date_sk")
    return pd.DataFrame([{"rows": len(j),
                          "qty": int(j.ss_quantity.sum())}])


def q23_semi(dfs, min_sales=30):
    ss = dfs["store_sales"]
    freq = ss.groupby("ss_item_sk").size()
    keep = freq[freq > min_sales].index
    hits = ss[ss.ss_item_sk.isin(keep)]
    return pd.DataFrame([{"total": float(hits.ss_ext_sales_price.sum()),
                          "rows": len(hits)}])


def q16_anti(dfs):
    ss, item = dfs["store_sales"], dfs["item"]
    unsold = item[~item.i_item_sk.isin(ss.ss_item_sk.unique())]
    return unsold[["i_item_sk", "i_manufact_id"]]


def q_minmax_price(dfs):
    item = dfs["item"]
    return (item.groupby("i_category", as_index=False)
            .agg(mn=("i_current_price", "min"),
                 mx=("i_current_price", "max")))


def q_multi_measure(dfs):
    ss = dfs["store_sales"]
    return (ss.groupby("ss_store_sk", as_index=False)
            .agg(q=("ss_quantity", "sum"),
                 s=("ss_sales_price_cents", "sum"),
                 lp=("ss_list_price_cents", "mean")))


def q_rollup3(dfs):
    ss, dd, store = dfs["store_sales"], dfs["date_dim"], dfs["store"]
    j = (ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(store, left_on="ss_store_sk", right_on="s_store_sk"))
    return _rollup(j, ["d_year", "d_moy", "s_state"],
                   {"rev": ("ss_ext_sales_price", "sum")})


def q_first_last(dfs):
    ss = dfs["store_sales"]
    srt = ss.sort_values("ss_sold_date_sk", kind="stable")
    return (srt.groupby("ss_item_sk", as_index=False)
            .agg(first=("ss_sales_price_cents", "first"),
                 last=("ss_sales_price_cents", "last")))


def q_rownum_dedup(dfs, keep=2):
    ss, dd = dfs["store_sales"], dfs["date_dim"]
    j = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    rev = (j.groupby(["ss_store_sk", "d_moy"], as_index=False)
           ["ss_ext_sales_price"].sum()
           .sort_values(["ss_store_sk", "ss_ext_sales_price", "d_moy"],
                        ascending=[True, False, True]))
    rev["rn"] = rev.groupby("ss_store_sk").cumcount() + 1
    return rev[rev.rn <= keep]


def q_cross_ratio(dfs):
    ss, ws, item = dfs["store_sales"], dfs["web_sales"], dfs["item"]
    js = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    jw = ws.merge(item, left_on="ws_item_sk", right_on="i_item_sk")
    s = js.groupby("i_category", as_index=False)["ss_ext_sales_price"].sum()
    w = jw.groupby("i_category", as_index=False)["ws_ext_sales_price"].sum()
    j = s.merge(w, on="i_category")
    j["ratio"] = j.ws_ext_sales_price / j.ss_ext_sales_price
    return j


def q_null_share(dfs):
    ws, item = dfs["web_sales"], dfs["item"]
    j = ws.merge(item, left_on="ws_item_sk", right_on="i_item_sk")
    return (j.groupby("i_category", as_index=False)
            .agg(rows=("ws_item_sk", "count"),
                 nn=("ws_ext_sales_price", "count"),
                 s=("ws_ext_sales_price", "sum")))


def q17_stats(dfs):
    ss, store = dfs["store_sales"], dfs["store"]
    j = ss.merge(store, left_on="ss_store_sk", right_on="s_store_sk")
    return (j.groupby("s_state", as_index=False)
            .agg(m=("ss_quantity", "mean"), sd=("ss_quantity", "std"),
                 c=("ss_quantity", "count")))


def q8_intersect(dfs):
    ss, ws, item = dfs["store_sales"], dfs["web_sales"], dfs["item"]
    js = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    jw = ws.merge(item, left_on="ws_item_sk", right_on="i_item_sk")
    both = np.intersect1d(js.i_category_id.unique(),
                          jw.i_category_id.unique())
    return pd.DataFrame({"i_category_id": np.sort(both)})


def q87_except(dfs):
    ss, ws, item = dfs["store_sales"], dfs["web_sales"], dfs["item"]
    js = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    jw = ws.merge(item, left_on="ws_item_sk", right_on="i_item_sk")
    only = np.setdiff1d(js.i_brand_id.unique(), jw.i_brand_id.unique())
    return pd.DataFrame({"i_brand_id": np.sort(only)})


def q_dense_rank_cat(dfs, top_n=2):
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    j = (ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
         .merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk"))
    rev = (j.groupby(["i_category", "d_moy"], as_index=False)
           ["ss_ext_sales_price"].sum())
    rev["dr"] = (rev.groupby("i_category")["ss_ext_sales_price"]
                 .rank(method="dense", ascending=False).astype(int))
    return rev[rev.dr <= top_n]


def q34_baskets(dfs, qty_min=60):
    ss = dfs["store_sales"]
    per_item = (ss.groupby(["ss_store_sk", "ss_item_sk"], as_index=False)
                ["ss_quantity"].sum())
    big = per_item[per_item.ss_quantity >= qty_min]
    return (big.groupby("ss_store_sk", as_index=False)
            ["ss_item_sk"].count())


def q_channel_day(dfs):
    ss, ws, item = dfs["store_sales"], dfs["web_sales"], dfs["item"]
    s_rev = (ss.groupby(["ss_item_sk", "ss_sold_date_sk"], as_index=False)
             ["ss_ext_sales_price"].sum())
    w_rev = (ws.groupby(["ws_item_sk", "ws_sold_date_sk"], as_index=False)
             ["ws_ext_sales_price"].sum())
    j = s_rev.merge(w_rev, left_on=["ss_item_sk", "ss_sold_date_sk"],
                    right_on=["ws_item_sk", "ws_sold_date_sk"])
    j = j.merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    return (j.groupby("i_category", as_index=False)
            .agg(s=("ss_ext_sales_price", "sum"),
                 w=("ws_ext_sales_price", "sum")))


def q_web_also_qty(dfs):
    ss, ws = dfs["store_sales"], dfs["web_sales"]
    pairs = ws[["ws_item_sk", "ws_sold_date_sk"]].drop_duplicates()
    j = ss.merge(pairs, left_on=["ss_item_sk", "ss_sold_date_sk"],
                 right_on=["ws_item_sk", "ws_sold_date_sk"])
    return (j.groupby("ss_store_sk", as_index=False)["ss_quantity"].sum())


def q_brand_rev_left(dfs, manager_id=28):
    ss, item = dfs["store_sales"], dfs["item"]
    j = ss.merge(item[item.i_manager_id == manager_id],
                 left_on="ss_item_sk", right_on="i_item_sk", how="left")
    return (j.groupby("i_brand_id", dropna=False, as_index=False)
            .agg(s=("ss_ext_sales_price", "sum"),
                 c=("ss_item_sk", "count")))


QUERIES = {
    "q3": q3, "q42": q42, "q52": q52, "q55": q55,
    "q_state_rollup": q_state_rollup, "q7": q7, "q19": q19, "q62": q62,
    "q52_topn": q52_topn, "q65": q65, "q_store_counts": q_store_counts,
    "q67_rank": q67_rank, "q_like_brands": q_like_brands,
    "q_union_channels": q_union_channels, "q_lag_growth": q_lag_growth,
    "q_running_share": q_running_share, "q_nunique_items": q_nunique_items,
    "q_having": q_having, "q_case_when": q_case_when,
    "q_distinct_pairs": q_distinct_pairs, "q_isin_states": q_isin_states,
    "q36_rollup": q36_rollup, "q86_rollup": q86_rollup,
    "q27_cube": q27_cube, "q5_grouping_sets": q5_grouping_sets,
    "q78_outer": q78_outer, "q25_two_fact": q25_two_fact,
    "q88_counts": q88_counts, "q90_ratio": q90_ratio,
    "q29_minmax": q29_minmax, "q48_bands": q48_bands,
    "q13_avg_bands": q13_avg_bands, "q96_count": q96_count,
    "q23_semi": q23_semi, "q16_anti": q16_anti,
    "q_minmax_price": q_minmax_price, "q_multi_measure": q_multi_measure,
    "q_rollup3": q_rollup3, "q_first_last": q_first_last,
    "q_rownum_dedup": q_rownum_dedup, "q_cross_ratio": q_cross_ratio,
    "q_null_share": q_null_share,
    "q17_stats": q17_stats, "q8_intersect": q8_intersect,
    "q87_except": q87_except, "q_dense_rank_cat": q_dense_rank_cat,
    "q34_baskets": q34_baskets, "q_channel_day": q_channel_day,
    "q_web_also_qty": q_web_also_qty, "q_brand_rev_left": q_brand_rev_left,
}
