#!/usr/bin/env python
"""On-chip smoke: the transcode, scan and served-SQL path on one TPU chip.

Drives the system's main path once, through the entry points a user calls,
in ONE process on ONE chip, at sizes fixed here:

* A  — ``convert_to_rows`` / ``convert_from_rows`` on the reference's
  nvbench axes: 12 and 212 fixed columns x 1M rows, and the 12-column
  mixed schema with 4 string columns x 1M rows.  Round trip equals the
  input bit for bit; row bytes equal a plain reference (the vectorised
  NumPy host engine at full size for fixed schemas, the scalar oracle on
  the first 10k rows for strings).
* A2 — one 1M x 12 table through the C ABI's ``srjt_to_rows_device`` /
  ``srjt_from_rows_device`` (the JVM hand-off), failing on a null handle.
* B  — TPC-H q6 over 6M rows from Parquet: ``models.q6.run`` (footer
  parse, row-group walk, device decode, fused predicate+aggregate) equals
  NumPy on the generator arrays.
* C  — TPC-DS served SQL: a 500k-row fact resident on the chip (cut from
  10M by the time limit, see ``SALES_ROWS``),
  ``QueryScheduler(workers=4).submit_sql`` of q3/q42/q52/q55, each three
  times (capture+compile, then plan-cache hits), each answer compared
  with its pandas twin.

It fails (non-zero exit, ``"ok": false``) when JAX finds no TPU, when a
phase raises, when a comparison differs, or when a phase was served by a
fallback (xpack rejecting a geometry, a Pallas kernel degrading, the
native library missing, the C ABI returning a null handle).

``--chips 4`` runs ONLY the cross-chip paths: the row-shuffle step on a
4-device mesh against a host checksum, and ``QueryScheduler(devices=4)``
answering q3 sixteen times with every replica serving.

The last stdout line is the one JSON object the driver reads; everything
else is on earlier lines.  Times printed here are observations of one
cold run, not benchmark results.
"""

from __future__ import annotations

import argparse
import ctypes as C
import io
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# sizes of the one-chip run (no size options: the smoke is one fixed drive)
TRANSCODE_ROWS = 1_000_000
ORACLE_ROWS = 10_000          # rowconv/reference.py is a scalar Python loop
SCAN_ROWS = 6_000_000
# Cut from the 10M-row fact of the round-5 records: the smoke must end, cold,
# inside the driver's 1200 s, and at 10M rows phase C alone took 1146 s on
# the chip (PERF.md, PR 23) — the TPU compiler spends ~30 s per sort once an
# operand passes 16384 elements, three times per query (capture, sizes
# program, replay program), and q52/q55 sort their join matches.  At 500k
# rows q52's ~14k matches stay under that cliff.
SALES_ROWS = 500_000
N_ITEMS, N_STORES = 20_000, 50
SQL_QUERIES = ("q3", "q42", "q52", "q55")
SQL_REPEATS = 3
# q6 revenue is an f64 sum over ~230k products; the chip's f64 is emulated
# and its reduction order differs from NumPy's
Q6_RTOL = 1e-6
SQL_RTOL = 1e-9               # tests/test_tpcds.py holds the queries to this


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class SmokeFailure(AssertionError):
    """A comparison differed or a fallback served the phase."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _block(tree) -> None:
    import jax
    jax.block_until_ready(jax.tree_util.tree_leaves(tree))


# --- A: transcode ------------------------------------------------------------

def build_table(n_rows: int, n_cols: int, string_every: int = 0,
                seed: int = 7):
    """The reference's nvbench type cycle (row_conversion.cpp:30-38, f64
    included), ~10% nulls on every third column."""
    import spark_rapids_jni_tpu as sr
    from spark_rapids_jni_tpu import Column, Table
    cycle = [sr.int8, sr.int16, sr.int32, sr.int64, sr.float32, sr.float64,
             sr.bool8]
    words = np.array(["", "tpu", "spark-rapids", "columnar row transcode",
                      "x" * 24, "payload"], dtype=object)
    rng = np.random.default_rng(seed)
    cols = []
    for i in range(n_cols):
        if string_every and i % string_every == string_every - 1:
            strs = words[rng.integers(0, len(words), n_rows)].tolist()
            cols.append(Column.strings_from_list(strs))
            continue
        dt = cycle[i % len(cycle)]
        if dt == sr.bool8:
            arr = rng.integers(0, 2, n_rows).astype(np.uint8)
        elif dt.storage.kind == "f":
            arr = rng.standard_normal(n_rows).astype(dt.storage)
        else:
            info = np.iinfo(dt.storage)
            arr = rng.integers(info.min // 2, info.max // 2, n_rows,
                               dtype=dt.storage)
        validity = rng.random(n_rows) < 0.9 if i % 3 == 0 else None
        cols.append(Column.from_numpy(arr, dt, validity))
    return Table(cols)


def _columns_identical(a, b) -> bool:
    """Bit-for-bit: payload, offsets and validity of every column."""
    if a.num_columns != b.num_columns or a.num_rows != b.num_rows:
        return False
    for ca, cb in zip(a.columns, b.columns):
        if ca.dtype != cb.dtype:
            return False
        va = np.asarray(ca.validity_or_true())
        vb = np.asarray(cb.validity_or_true())
        if not np.array_equal(va, vb):
            return False
        if ca.offsets is not None:
            if not (np.array_equal(np.asarray(ca.offsets),
                                   np.asarray(cb.offsets))
                    and np.array_equal(np.asarray(ca.data),
                                       np.asarray(cb.data))):
                return False
            continue
        # null slots included: the transcode moves every payload bit
        if not np.array_equal(np.asarray(ca.data), np.asarray(cb.data)):
            return False
    return True


def _head(table, k: int):
    from spark_rapids_jni_tpu import Table
    from spark_rapids_jni_tpu.rowconv.convert import _slice_column
    return Table([_slice_column(c, 0, k) for c in table.columns])


def phase_transcode(n_rows: int = TRANSCODE_ROWS,
                    oracle_rows: int = ORACLE_ROWS, seed: int = 7) -> dict:
    from spark_rapids_jni_tpu import convert_from_rows, convert_to_rows
    from spark_rapids_jni_tpu.rowconv import (host as host_engine, ragged,
                                              reference, xpack)
    out = {}
    for name, n_cols, string_every in (("fixed12", 12, 0),
                                       ("fixed212", 212, 0),
                                       ("strings_mixed12", 12, 3)):
        table = build_table(n_rows, n_cols, string_every, seed)
        _block([c.data for c in table.columns])
        fb0 = dict(xpack.fallback_counts)
        t0 = time.perf_counter()
        batches = convert_to_rows(table)
        _block([b.data for b in batches])
        t_to = time.perf_counter() - t0
        check(len(batches) == 1, f"{name}: expected one batch")
        t0 = time.perf_counter()
        back = convert_from_rows(batches[0], table.schema)
        _block([c.data for c in back.columns])
        t_from = time.perf_counter() - t0
        check(_columns_identical(table, back),
              f"{name}: to_rows -> from_rows differs from the input")
        got = batches[0].host_bytes()
        if string_every:
            k = min(oracle_rows, n_rows)
            want, _ = reference.to_rows_np(_head(table, k))
            end = int(np.asarray(batches[0].offsets)[k])
            check(end == want.shape[0]
                  and np.array_equal(got[:end], want),
                  f"{name}: row bytes differ from the scalar oracle")
            fb = {r: c - fb0.get(r, 0)
                  for r, c in xpack.fallback_counts.items()
                  if c != fb0.get(r, 0)}
            engine = ("xpack" if not fb else
                      "ragged-dma" if ragged.dma_supported() else "xla-gather")
            say("A.strings_engine", engine=engine, xpack_fallbacks=fb,
                ragged_dma_supported=ragged.dma_supported())
            check(not fb, f"{name}: xpack fell back: {fb}")
        else:
            want = host_engine.to_rows_fixed_np(table)
            check(np.array_equal(got, np.asarray(want).reshape(-1)),
                  f"{name}: row bytes differ from the NumPy host engine")
        out[name] = {"rows": n_rows, "row_bytes": int(got.shape[0]),
                     "to_rows_cold_s": round(t_to, 3),
                     "from_rows_cold_s": round(t_from, 3)}
        say("A." + name, **out[name])
        del table, batches, back, got, want
    return out


# --- A2: the C ABI ------------------------------------------------------------

def phase_c_abi(n_rows: int = TRANSCODE_ROWS, seed: int = 7) -> dict:
    """Host buffers -> libsrjt table handle -> srjt_to_rows_device ->
    srjt_from_rows_device -> host buffers, compared with the C++ host
    engine's bytes and the input.  The ``_device`` symbols return null on
    ANY failure (the JVM caller then takes the host engine); here null is
    an error, and bridge.py has logged the Python exception.

    A smoke at 12 columns, one thread.  The path's guard at a public shape
    is the benchmark's cell ``fixed155_cabi_t4`` (PR 36): four task threads,
    the reference's 155-column table, every caller's last round trip
    compared byte for byte (``python3 -m chipbench --workload
    fixed155_cabi_t4``; on the CPU ``chipbench/tests/test_cabi_cell.py``)."""
    from spark_rapids_jni_tpu import native
    lib = native.load()
    check(lib is not None, f"libsrjt.so unavailable: {native.build_error}")
    check(lib.srjt_device_available() == 1, "srjt_device_available() == 0")
    table = build_table(n_rows, 12, 0, seed)
    keep, handles, tids = [], [], []
    for col in table.columns:
        data = np.ascontiguousarray(np.asarray(col.data))
        valid = (None if col.validity is None else
                 np.ascontiguousarray(np.asarray(col.validity), np.uint8))
        keep += [data, valid]
        tid = int(col.dtype.id)        # TypeId IS the C ABI's type id
        tids.append(tid)
        handles.append(lib.srjt_column_fixed(
            tid, 0, n_rows, data.ctypes.data_as(C.c_void_p),
            None if valid is None else valid.ctypes.data_as(C.c_void_p)))
    check(all(handles), "srjt_column_fixed returned null")
    t = lib.srjt_table((C.c_void_p * len(handles))(*handles), len(handles))
    for h in handles:
        lib.srjt_column_free(h)
    check(t, "srjt_table returned null")
    t0 = time.perf_counter()
    dev = lib.srjt_to_rows_device(t)
    t_to = time.perf_counter() - t0
    check(dev, "srjt_to_rows_device returned null: the device engine "
               "failed (see the bridge's logged exception above)")
    host = lib.srjt_to_rows(t)
    check(host, "srjt_to_rows (host engine) returned null")

    def batch_bytes(rows):
        size = lib.srjt_rows_batch_size(rows, 0)
        return np.ctypeslib.as_array(lib.srjt_rows_batch_data(rows, 0),
                                     shape=(size,))
    check(lib.srjt_rows_num_batches(dev) == lib.srjt_rows_num_batches(host)
          == 1, "C ABI: expected one batch from both engines")
    check(np.array_equal(batch_bytes(dev), batch_bytes(host)),
          "C ABI: device rows differ from the C++ host engine's")
    tid_arr = np.asarray(tids, np.int32)
    scales = np.zeros(len(tids), np.int32)
    t0 = time.perf_counter()
    back = lib.srjt_from_rows_device(
        dev, tid_arr.ctypes.data_as(C.c_void_p),
        scales.ctypes.data_as(C.c_void_p), len(tids))
    t_from = time.perf_counter() - t0
    check(back, "srjt_from_rows_device returned null: the device engine "
                "failed (see the bridge's logged exception above)")
    check(lib.srjt_table_rows(back) == n_rows
          and lib.srjt_table_cols(back) == len(tids),
          "C ABI: round-trip table has the wrong shape")
    for i, col in enumerate(table.columns):
        h = C.c_void_p(lib.srjt_table_column(back, i))
        raw = np.ctypeslib.as_array(
            lib.srjt_column_data(h), shape=(lib.srjt_column_data_size(h),))
        vptr = lib.srjt_column_valid(h)
        valid = (np.ones(n_rows, bool) if not vptr else
                 np.ctypeslib.as_array(vptr, shape=(n_rows,)).astype(bool))
        want_valid = np.asarray(col.validity_or_true())
        src = np.ascontiguousarray(np.asarray(col.data))
        width = src.dtype.itemsize * (src.shape[1] if src.ndim == 2 else 1)
        same = (np.array_equal(valid, want_valid) and np.array_equal(
            raw.reshape(n_rows, width)[want_valid],
            src.view(np.uint8).reshape(n_rows, width)[want_valid]))
        lib.srjt_column_free(h)
        check(same, f"C ABI: column {i} differs after the round trip")
    for free, h in ((lib.srjt_rows_free, dev), (lib.srjt_rows_free, host),
                    (lib.srjt_table_free, back), (lib.srjt_table_free, t)):
        free(h)
    out = {"rows": n_rows, "to_rows_device_cold_s": round(t_to, 3),
           "from_rows_device_cold_s": round(t_from, 3)}
    say("A2.c_abi", **out)
    return out


# --- B: scan ------------------------------------------------------------------

def phase_scan(n_rows: int = SCAN_ROWS, seed: int = 3) -> dict:
    from benchmarks import tpch_data
    from spark_rapids_jni_tpu.models import q6
    from spark_rapids_jni_tpu.utils import metrics
    raw, arrays = tpch_data.generate_q6(n_rows, seed)
    lo, hi = 8766, 8766 + 365          # 1994-01-01 .. 1995-01-01
    want_rev, want_n = tpch_data.q6_reference(arrays, lo, hi)
    was = metrics.enabled()
    metrics.set_enabled(True)
    try:
        f0 = metrics.counter_value("parquet.host_fallback_cols")
        walls = []
        for _ in range(2):             # cold (compiles), then warm
            t0 = time.perf_counter()
            rev, matched = q6.run(raw, lo, hi)
            walls.append(round(time.perf_counter() - t0, 3))
        fell_back = metrics.counter_value("parquet.host_fallback_cols") - f0
    finally:
        metrics.set_enabled(was)
    out = {"rows": n_rows, "parquet_bytes": len(raw), "matched": matched,
           "revenue": rev, "numpy_revenue": want_rev,
           "cold_s": walls[0], "warm_s": walls[1],
           "host_fallback_cols": int(fell_back)}
    say("B.q6_scan", **out)
    check(fell_back == 0,
          f"scan: {fell_back} column decodes fell back to the host")
    check(matched == want_n,
          f"q6 matched {matched} rows, NumPy matched {want_n}")
    check(abs(rev - want_rev) <= Q6_RTOL * max(abs(want_rev), 1.0),
          f"q6 revenue {rev!r} differs from NumPy's {want_rev!r}")
    return out


# --- C: served SQL -------------------------------------------------------------

def _compare_with_pandas(name: str, out, expect) -> int:
    """Result Table (keys..., SUM) vs the pandas twin's frame."""
    keys = [c for c in expect.columns if c != "ss_ext_sales_price"]
    expect = expect.sort_values(keys).reset_index(drop=True)
    check(out.num_rows == len(expect),
          f"{name}: {out.num_rows} rows, pandas has {len(expect)}")
    for i, k in enumerate(keys):
        got = (out[i].to_pylist() if out[i].dtype.id.name == "STRING"
               else out[i].to_numpy().tolist())
        check(got == expect[k].tolist(), f"{name}: key column {k} differs")
    got = np.asarray(out[len(keys)].to_numpy(), np.float64)
    want = expect["ss_ext_sales_price"].to_numpy()
    check(np.all(np.isfinite(got)), f"{name}: non-finite sums")
    if not np.allclose(got, want, rtol=SQL_RTOL, atol=0.0):
        err = np.max(np.abs(got - want) / np.abs(want))
        raise SmokeFailure(f"{name}: SUM(ss_ext_sales_price) differs from "
                           f"pandas (max rel err {err:.3g})")
    return len(expect)


def _load_tpcds(n_sales: int, n_items: int, n_stores: int, seed: int):
    import pandas as pd
    from benchmarks import tpcds_data
    from spark_rapids_jni_tpu.models import tpcds
    files = tpcds_data.generate(n_sales=n_sales, n_items=n_items,
                                n_stores=n_stores, seed=seed)
    dfs = {k: pd.read_parquet(io.BytesIO(v)) for k, v in files.items()
           if k in ("store_sales", "item", "date_dim")}
    tables = tpcds.load_tables(files)
    _block([c.data for t in tables.values() for c in t.columns])
    return tables, dfs


def phase_sql(n_sales: int = SALES_ROWS, n_items: int = N_ITEMS,
              n_stores: int = N_STORES, seed: int = 5,
              queries=SQL_QUERIES) -> dict:
    from benchmarks import pandas_queries
    from spark_rapids_jni_tpu import exec as xc
    from spark_rapids_jni_tpu.models import tpcds_sql as TS
    from spark_rapids_jni_tpu.utils import metrics
    t0 = time.perf_counter()
    tables, dfs = _load_tpcds(n_sales, n_items, n_stores, seed)
    say("C.load", n_sales=n_sales, n_items=n_items, n_stores=n_stores,
        seconds=round(time.perf_counter() - t0, 1))
    was = metrics.enabled()
    metrics.set_enabled(True)
    out = {}
    try:
        with xc.QueryScheduler(workers=4) as sched:
            for q in queries:
                params = TS.PARAMS.get(q, {})
                expect = getattr(pandas_queries, q)(dfs, **params)
                c0 = {k: metrics.counter_value(k) for k in
                      ("compiled.capture", "exec.plan_cache.hit",
                       "exec.plan_cache.miss")}
                walls, rows = [], None
                for _ in range(SQL_REPEATS):
                    t0 = time.perf_counter()
                    res = sched.submit_sql(TS.SQL[q], tables,
                                           schemas=TS.TABLE_SCHEMAS,
                                           params=params).result()
                    _block(res)
                    walls.append(round(time.perf_counter() - t0, 3))
                    rows = _compare_with_pandas(q, res, expect)
                delta = {k: int(metrics.counter_value(k) - v)
                         for k, v in c0.items()}
                out[q] = {"rows": rows, "cold_s": walls[0],
                          "warm_s": walls[1:], **delta}
                say("C." + q, **out[q])
                check(delta["exec.plan_cache.hit"] >= SQL_REPEATS - 1,
                      f"{q}: repeats did not hit the plan cache: {delta}")
    finally:
        metrics.set_enabled(was)
    return out


# --- four chips (behind --chips 4) ---------------------------------------------

def phase_mesh_shuffle(n_dev: int = 4) -> dict:
    """The shuffle step ``__graft_entry__`` validates on virtual devices
    (64K rows/device), on the real mesh: zero drops and the global
    checksum the host computes from the same inputs."""
    import __graft_entry__ as G
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(n_dev)
    check(len({d.id for d in mesh.devices.flat}) == n_dev,
          f"mesh holds {mesh.devices.size} devices, wanted {n_dev}")
    t0 = time.perf_counter()
    out = G.run_shuffle(mesh)
    out["cold_s"] = round(time.perf_counter() - t0, 3)
    say("M.shuffle", **out)
    check(out["dropped"] == 0, f"shuffle dropped {out['dropped']} rows")
    check(out["received"] == out["rows"],
          f"shuffle received {out['received']} of {out['rows']} rows")
    check(out["checksum"] == out["host_checksum"],
          f"shuffle checksum {out['checksum']} differs from the host's "
          f"{out['host_checksum']}")
    return out


def phase_replicas(n_dev: int = 4, requests: int = 16,
                   n_sales: int = SALES_ROWS, n_items: int = N_ITEMS,
                   n_stores: int = N_STORES, seed: int = 5) -> dict:
    """``QueryScheduler(devices=N)``: q3 answered ``requests`` times, every
    answer equal to pandas', every replica serving at least one."""
    from benchmarks import pandas_queries
    from spark_rapids_jni_tpu import exec as xc
    from spark_rapids_jni_tpu.models import tpcds_sql as TS
    from spark_rapids_jni_tpu.utils import metrics
    tables, dfs = _load_tpcds(n_sales, n_items, n_stores, seed)
    params = TS.PARAMS["q3"]
    expect = pandas_queries.q3(dfs, **params)
    was = metrics.enabled()
    metrics.set_enabled(True)
    try:
        t0 = time.perf_counter()
        # max_batch=1: identical requests otherwise coalesce into ONE
        # launch on whichever replica dequeues first, and the other
        # replicas would rightly serve nothing
        with xc.QueryScheduler(workers=2 * n_dev, devices=n_dev,
                               max_batch=1) as sched:
            # exec.device.<platform><id>.completed, one per replica
            names = ["exec.device." + rep.name.replace(":", "")
                     + ".completed" for rep in sched.replicas]
            check(len(set(names)) == n_dev,
                  f"replicas share devices: {names}")
            c0 = [metrics.counter_value(k) for k in names]
            tickets = [sched.submit_sql(TS.SQL["q3"], tables,
                                        schemas=TS.TABLE_SCHEMAS,
                                        params=params)
                       for _ in range(requests)]
            for t in tickets:
                _compare_with_pandas("q3", t.result(), expect)
        wall = round(time.perf_counter() - t0, 3)
        served = [int(metrics.counter_value(k) - v)
                  for k, v in zip(names, c0)]
    finally:
        metrics.set_enabled(was)
    out = {"devices": n_dev, "requests": requests,
           "served": dict(zip(names, served)), "wall_s": wall}
    say("M.replicas", **out)
    check(all(s > 0 for s in served),
          f"a replica served nothing: completed per device = {served}")
    return out


# --- driver ---------------------------------------------------------------------

def _native_stamp() -> dict:
    """Whether libsrjt.so loaded, and what was built: the package's build
    stamp (ci/build_info.py) plus the library file's own identity."""
    import hashlib
    import runpy
    from spark_rapids_jni_tpu import native
    lib = native.load()
    stamp = {"loaded": lib is not None, "build_error": native.build_error}
    if lib is not None:
        with open(native._LIB_PATH, "rb") as f:
            blob = f.read()
        stamp.update(bytes=len(blob),
                     sha256=hashlib.sha256(blob).hexdigest()[:16],
                     mtime=time.strftime(
                         "%Y-%m-%dT%H:%M:%SZ",
                         time.gmtime(os.path.getmtime(native._LIB_PATH))))
    try:
        runpy.run_path(os.path.join(ROOT, "ci", "build_info.py"),
                       run_name="__main__")
        vi = runpy.run_path(os.path.join(ROOT, "spark_rapids_jni_tpu",
                                         "version_info.py"))
        stamp.update(version=vi["version"], revision=vi["revision"],
                     built=vi["date"])
    except Exception as e:  # noqa: BLE001 — the stamp is informative only
        stamp["stamp_error"] = repr(e)
    return stamp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the cross-chip paths (mesh shuffle + "
                         "replica serving) on four chips")
    ap.add_argument("--seed", type=int, default=7,
                    help="seed of every generated dataset")
    args = ap.parse_args(argv)

    device = {"platform": None, "kind": None, "count": 0}
    ok, error = False, None
    try:
        import jax
        from spark_rapids_jni_tpu.utils import compile_cache
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        if device["platform"] != "tpu":
            raise SmokeFailure(
                f"no TPU: jax.devices()[0].platform == "
                f"{device['platform']!r}")
        if device["count"] != args.chips:
            raise SmokeFailure(f"--chips {args.chips} but JAX reports "
                               f"{device['count']} devices")
        say("start", device=device, jax=jax.__version__,
            compile_cache=compile_cache.configure(), seed=args.seed)
        t_all = time.perf_counter()
        if args.chips == 4:
            phase_mesh_shuffle(4)
            phase_replicas(4, seed=args.seed)
        else:
            from spark_rapids_jni_tpu.rowconv import xpallas
            stamp = _native_stamp()
            say("native", **stamp)
            check(stamp["loaded"], "libsrjt.so did not build or load")
            phase_transcode(seed=args.seed)
            phase_c_abi(seed=args.seed)
            phase_scan(seed=args.seed)
            phase_sql(seed=args.seed)
            say("fallbacks", xpallas=dict(xpallas._counts))
            check(xpallas._counts["fallbacks"] == 0,
                  f"Pallas kernels degraded: {xpallas._counts}")
        say("done", seconds=round(time.perf_counter() - t_all, 1))
        ok = True
    except Exception as e:  # noqa: BLE001 — the boundary: reported on the last line and in the exit code
        error = f"{type(e).__name__}: {e}"
        traceback.print_exc()
        sys.stderr.flush()
    result = {"ok": ok, "device": device}
    if error is not None:
        result["error"] = error[:500]
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
