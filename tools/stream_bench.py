#!/usr/bin/env python
"""Streaming incremental-maintenance bench → STREAM_BENCH.json.

Measures the claim the ``stream/`` subsystem makes: refreshing a
materialized view after an append touches O(delta) work, not O(table).
Three TPC-DS-shaped views (all on the merge-EXACT tier — int64 cents
sums, counts, min/max, integer means — so refreshed results must be
bit-identical, not just close) are registered over the ``store_sales``
fact, then N epochs each append 1/64 of the base table
(``benchmarks/tpcds_data.append_rows``) and measure, per view per epoch:

  refresh_s   — ``ViewRegistry.refresh``: delta row groups decoded,
                partial states merged into the running state, post tail
                re-applied.
  full_s      — from-scratch recompute of the same optimized plan over a
                full ``DeltaTable.scan()`` (min of two runs, so the
                number is warm-compile: the honest steady-state cost of
                NOT maintaining the view).

plus the decoded-work assertion: the ``stream.delta.rowgroups`` counter
must advance by EXACTLY the appended file's row-group count (full
recomputes land on ``stream.scan.rowgroups``, so the two cannot blur),
and every epoch's refresh result must be bit-identical to the full
recompute.

Pass gates (recorded in the JSON): per-view median warm speedup >= 10x,
delta row-group accounting exact everywhere, all epochs bit-identical.

Usage: python tools/stream_bench.py [n_sales] [epochs] [out.json]
"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax

from spark_rapids_jni_tpu.utils import compile_cache

compile_cache.configure()


def canon(table):
    from spark_rapids_jni_tpu.column import force_column
    out = []
    for c in table.columns:
        c = force_column(c)
        out.append(np.asarray(c.data))
        if c.offsets is not None:
            out.append(np.asarray(c.offsets))
        if c.validity is not None:
            out.append(np.asarray(c.validity))
    return out


def identical(a, b) -> bool:
    return len(a) == len(b) and all(
        x.shape == y.shape and np.array_equal(x, y) for x, y in zip(a, b))


def view_plans():
    """Three maintainable TPC-DS-shaped views, exact tier throughout."""
    from spark_rapids_jni_tpu.plan import ir

    def q3_cents():
        # q3's join-filter-aggregate shape with the decimal measure kept
        # as int64 cents (the merge-exact spelling of its revenue sum)
        j = ir.Join(ir.Join(ir.Scan("store_sales"), ir.Scan("item"),
                            ("ss_item_sk",), ("i_item_sk",)),
                    ir.Scan("date_dim"), ("ss_sold_date_sk",), ("d_date_sk",))
        f = ir.Filter(j, ir.And((
            ir.Cmp("==", ir.Col("i_manufact_id"), ir.Lit(436)),
            ir.Cmp("==", ir.Col("d_moy"), ir.Lit(11)))))
        keys = ("d_year", "i_brand_id", "i_brand")
        return ir.Sort(ir.Aggregate(f, keys, (
            ("ss_sales_price_cents", "sum", "sum_cents"),
            ("ss_quantity", "count", "n"))), keys)

    def store_daily():
        # wide-key rollup feed: per store per day revenue + volume
        f = ir.Filter(ir.Scan("store_sales"),
                      ir.Cmp("<=", ir.Col("ss_store_sk"), ir.Lit(8)))
        keys = ("ss_store_sk", "ss_sold_date_sk")
        return ir.Aggregate(f, keys, (
            ("ss_sales_price_cents", "sum", "rev_cents"),
            ("ss_list_price_cents", "sum", "list_cents"),
            ("ss_quantity", "sum", "units"),
            ("ss_quantity", "count", "n")))

    def price_profile():
        # selection + integer-mean family over a small key domain
        keys = ("ss_store_sk",)
        return ir.Sort(ir.Aggregate(ir.Scan("store_sales"), keys, (
            ("ss_sales_price_cents", "min", "min_cents"),
            ("ss_sales_price_cents", "max", "max_cents"),
            ("ss_quantity", "mean", "avg_qty"),
            ("ss_quantity", "count", "n"))), keys)

    return {"q3_cents": q3_cents(), "store_daily": store_daily(),
            "price_profile": price_profile()}


def main():
    n_sales = int(sys.argv[1]) if len(sys.argv) > 1 else 1_600_000
    epochs = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    out_path = sys.argv[3] if len(sys.argv) > 3 else "STREAM_BENCH.json"
    # one year of dates: every (store, day) cell of the widest view is
    # populated by the base load, so appends extend existing groups and
    # the running state keeps a STABLE shape across epochs — the steady
    # state a streaming view lives in (a changing group count retraces,
    # which is an honest first-sighting cost but not the regime measured)
    n_items, n_dates, rgs = 2000, 366, 4096
    n_append = max(n_sales // 64, 1)

    from benchmarks import tpcds_data
    from spark_rapids_jni_tpu.models import tpcds, tpcds_plans
    from spark_rapids_jni_tpu.plan import lower
    from spark_rapids_jni_tpu.stream import DeltaTable, ViewRegistry
    from spark_rapids_jni_tpu.stream.delta import _file_meta
    from spark_rapids_jni_tpu.utils import metrics

    metrics.set_enabled(True)   # the row-group counters ARE the assertion

    print(f"backend: {jax.default_backend()}  n_sales: {n_sales}  "
          f"append: {n_append} rows x {epochs} epochs  "
          f"row_group_size: {rgs}", flush=True)
    files = tpcds_data.generate(n_sales=n_sales, n_items=n_items,
                                n_dates=n_dates, seed=5, row_group_size=rgs)
    tables = tpcds.load_tables(files)
    statics = {k: tables[k] for k in ("item", "date_dim", "store")}
    schemas = {k: tpcds_plans.TABLE_SCHEMAS[k] for k in statics}

    blobs = [tpcds_data.append_rows(n_append, seed=9000 + e,
                                    n_items=n_items, n_dates=n_dates,
                                    row_group_size=rgs)
             for e in range(1, epochs + 1)]

    # warm pass: run the IDENTICAL append/refresh sequence through a
    # shadow registry first.  Filter and join outputs have data-dependent
    # row counts, so each epoch's delta relation is a shape the jit cache
    # has never seen — the warm pass pays that one-time compile for every
    # (epoch, view) so the measured pass times steady-state refresh work,
    # the same out-of-band warming discipline serve_bench applies to its
    # plan cache.
    wdelta = DeltaTable("store_sales", files=[files["store_sales"]])
    wreg = ViewRegistry(wdelta, statics, schemas)
    wviews = [wreg.register_view(p, name=f"warm:{n}")
              for n, p in view_plans().items()]
    print("warming shape variants (shadow pass)...", flush=True)
    for blob in blobs:
        wdelta.append_file(blob)
        for v in wviews:
            wreg.refresh(v)
    wreg.close()

    delta = DeltaTable("store_sales", files=[files["store_sales"]])
    reg = ViewRegistry(delta, statics, schemas)
    views = {}
    for name, plan in view_plans().items():
        v = reg.register_view(plan, name=name)
        assert v.kind == "incremental", (name, v.reason)
        assert v.exact, name
        views[name] = v

    def full(v):
        cat = lower.TableCatalog(
            {**statics, "store_sales": delta.scan()}, reg.schemas)
        return lower.execute(v.tree, cat, record_stats=False)

    results = {"n_sales": n_sales, "epochs": epochs,
               "append_rows": n_append, "row_group_size": rgs,
               "views": {n: {"kind": v.kind, "exact": v.exact,
                             "epochs": []}
                         for n, v in views.items()}}

    for e in range(1, epochs + 1):
        blob = blobs[e - 1]
        ngroups, _ = _file_meta(blob)
        delta.append_file(blob)
        for name, v in views.items():
            c0 = metrics.counter_value("stream.delta.rowgroups")
            t0 = time.perf_counter()
            got = canon(reg.refresh(v))
            refresh_s = time.perf_counter() - t0
            dgroups = metrics.counter_value("stream.delta.rowgroups") - c0

            t0 = time.perf_counter()
            expect = canon(full(v))
            full1 = time.perf_counter() - t0
            t0 = time.perf_counter()
            expect2 = canon(full(v))
            full_s = min(full1, time.perf_counter() - t0)

            ok = identical(got, expect) and identical(expect, expect2)
            rg_ok = dgroups == len(ngroups)
            results["views"][name]["epochs"].append({
                "epoch": e, "refresh_s": round(refresh_s, 5),
                "full_s": round(full_s, 5),
                "speedup": round(full_s / refresh_s, 2),
                "delta_rowgroups": int(dgroups),
                "appended_rowgroups": len(ngroups),
                "rowgroups_exact": rg_ok, "identical": ok})
            assert ok, f"{name} epoch {e}: refresh diverged from recompute"
            assert rg_ok, (f"{name} epoch {e}: decoded {dgroups} delta row "
                           f"groups, appended {len(ngroups)}")
            print(f"epoch {e} {name:14s}: refresh {refresh_s * 1e3:8.2f} ms"
                  f"  full {full_s * 1e3:8.2f} ms"
                  f"  ({full_s / refresh_s:6.1f}x)  "
                  f"groups {int(dgroups)}/{len(ngroups)}  bit-identical",
                  flush=True)

    all_pass = True
    for name, rec in results["views"].items():
        sp = sorted(ep["speedup"] for ep in rec["epochs"])
        med = sp[len(sp) // 2]
        rec["median_speedup"] = med
        rec["pass_10x"] = med >= 10.0
        rec["rowgroups_exact"] = all(ep["rowgroups_exact"]
                                     for ep in rec["epochs"])
        rec["all_identical"] = all(ep["identical"] for ep in rec["epochs"])
        all_pass &= (rec["pass_10x"] and rec["rowgroups_exact"]
                     and rec["all_identical"])
        print(f"{name:14s}: median {med:6.1f}x  "
              f"{'PASS' if rec['pass_10x'] else 'FAIL'}", flush=True)
    results["counters"] = {
        k: v for k, v in sorted(metrics.snapshot()["counters"].items())
        if k.startswith("stream.")}
    results["pass"] = all_pass
    reg.close()
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {out_path}  overall: {'PASS' if all_pass else 'FAIL'}",
          flush=True)
    if not all_pass:
        sys.exit(1)


if __name__ == "__main__":
    main()
