#!/usr/bin/env python
"""On-TPU validation sweep → PALLAS_TPU_CHECK.json.

Interpret-mode tests (the CPU pytest suite) cannot catch Mosaic compile or
miscompile issues, so once per round this script byte-compares, on the real
chip:

1. the ragged DMA engine (pack / unpack / segmented_copy) vs NumPy;
2. the full string JCUDF transcode (DMA path) vs the scalar NumPy oracle
   (``rowconv/reference.py``) across schema shapes;
3. the fixed-width u32-words transcode (round-3 permute/transpose
   formulations) vs the oracle across the schema matrix, including FLOAT64
   bit-pair columns and decimal128 — byte movement must be exact on chip;
4. the arithmetic f64 bits<->values path (``utils.f64bits``) round-trips
   normals/inf/nan exactly on the emulated-f64 backend.

Exits non-zero when the backend is not a TPU or any check fails.

Usage: python tools/tpu_check.py [out.json]
"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

import spark_rapids_jni_tpu as sr
from spark_rapids_jni_tpu import Table, Column, convert_to_rows, convert_from_rows
from spark_rapids_jni_tpu.rowconv import ragged, reference
from spark_rapids_jni_tpu.rowconv.layout import compute_row_layout
from spark_rapids_jni_tpu.utils import compile_cache, f64bits

compile_cache.configure()

RESULTS = {"backend": None, "checks": [], "ok": True}


def record(name, ok, note=""):
    RESULTS["checks"].append({"name": name, "ok": bool(ok), "note": note})
    RESULTS["ok"] = RESULTS["ok"] and bool(ok)
    print(f"  {'PASS' if ok else 'FAIL'} {name} {note}", flush=True)


def check_ragged():
    from benchmarks.ragged_data import random_ragged
    rng = np.random.default_rng(0)
    for n, M, aligned in [(301, 64, False), (1000, 256, False),
                          (777, 33, False), (4097, 300, True)]:
        dense, offs, flat = random_ragged(rng, n, M, aligned)
        got = np.asarray(ragged.pack_rows(jnp.asarray(dense), offs))
        record(f"ragged.pack n={n} M={M}", np.array_equal(got, flat))
        got2 = np.asarray(ragged.unpack_rows(jnp.asarray(flat), offs, M))
        record(f"ragged.unpack n={n} M={M}", np.array_equal(got2, dense))

    # gappy segmented copy
    S, n = 500000, 400
    src = rng.integers(1, 256, S).astype(np.uint8)
    sizes = rng.integers(0, 256, n)
    gaps = rng.integers(0, 700, n)
    src_offs = np.cumsum(sizes + gaps) - (sizes + gaps)
    dst_offs = np.concatenate([[0], np.cumsum(sizes)])[:-1]
    total = int(sizes.sum())
    expect = np.zeros(total, np.uint8)
    for k in range(n):
        expect[dst_offs[k]:dst_offs[k] + sizes[k]] = \
            src[src_offs[k]:src_offs[k] + sizes[k]]
    got = np.asarray(ragged.segmented_copy(jnp.asarray(src), src_offs,
                                           dst_offs, sizes, total))
    record("ragged.segmented_copy gappy", np.array_equal(got, expect))


def check_strings_transcode():
    rng = np.random.default_rng(1)
    words = ["", "a", "spark", "tpu-native kernels", "xy",
             "longer string payload!", "ab\x00cd"]
    for n, nulls in [(1000, None), (503, 7)]:
        strs = [words[i] for i in rng.integers(0, len(words), n)]
        if nulls:
            strs = [None if i % nulls == 0 else s
                    for i, s in enumerate(strs)]
        t = Table([
            Column.from_numpy(rng.integers(-100, 100, n).astype(np.int32)),
            Column.strings_from_list(strs),
            Column.from_numpy(rng.integers(0, 2**40, n).astype(np.int64)),
            Column.strings_from_list(
                [words[i] for i in rng.integers(0, len(words), n)]),
        ])
        b = convert_to_rows(t)
        ob, _ = reference.to_rows_np(t)
        record(f"strings to_rows oracle n={n} nulls={nulls}",
               np.array_equal(b[0].host_bytes(), ob))
        back = convert_from_rows(b[0], t.schema)
        ok = (back[1].to_pylist() == t[1].to_pylist()
              and back[3].to_pylist() == t[3].to_pylist()
              and np.array_equal(back[0].to_numpy(), t[0].to_numpy()))
        record(f"strings roundtrip n={n} nulls={nulls}", ok)


SCHEMAS = {
    "int32_only": [sr.int32] * 3,
    "mixed_words": [sr.int32, sr.int16, sr.int8],
    "wide_mixed": [sr.int64, sr.int32, sr.int16, sr.int8, sr.float32,
                   sr.bool8, sr.float64] * 2,
    "bytes_only": [sr.int8] * 5,
    "timestamps_decimals": [sr.timestamp_ms, sr.decimal32(-2),
                            sr.decimal64(-4), sr.bool8, sr.types.decimal128(-4)],
    # wide enough to route through the 2-D-transpose interleave (W > 40)
    # while staying under the 1KB JCUDF row limit (~920B rows, W=230)
    "wide_135col": [sr.int32, sr.float64, sr.float32] * 45,
}


def _random_table(rng, schema, n):
    cols = []
    for i, dt in enumerate(schema):
        v = (rng.random(n) < 0.8) if i % 2 == 0 else None
        if dt.id == sr.TypeId.DECIMAL128:
            lanes = rng.integers(-2**62, 2**62, (n, 2), dtype=np.int64)
            cols.append(Column(dt, jnp.asarray(lanes),
                               validity=None if v is None else jnp.asarray(v)))
        elif dt == sr.bool8:
            cols.append(Column.from_numpy(
                rng.integers(0, 2, n).astype(np.uint8), dt, v))
        elif dt.storage.kind == "f":
            cols.append(Column.from_numpy(
                rng.standard_normal(n).astype(dt.storage), dt, v))
        else:
            info = np.iinfo(dt.storage)
            cols.append(Column.from_numpy(
                rng.integers(info.min // 2, info.max // 2, n,
                             dtype=dt.storage), dt, v))
    return Table(cols)


def check_strings_large_n():
    """from_rows' large-n branch (device-side slots, no host metadata) must
    agree byte-for-byte with the small-n slots+segmented-copy branch."""
    from spark_rapids_jni_tpu.rowconv import convert as cv
    rng = np.random.default_rng(5)
    n = 70000   # > _DMA_FROM_ROWS_MAX_N (65536)
    words = ["", "a", "tpu", "larger payload string", "x" * 30]
    t = Table([
        Column.from_numpy(rng.integers(-1000, 1000, n).astype(np.int32)),
        Column.strings_from_list(
            [words[i] for i in rng.integers(0, len(words), n)]),
        Column.strings_from_list(
            [words[i] for i in rng.integers(0, len(words), n)]),
    ])
    b = convert_to_rows(t)[0]
    big = convert_from_rows(b, t.schema)          # large-n branch
    old = cv._DMA_FROM_ROWS_MAX_N
    cv._DMA_FROM_ROWS_MAX_N = 1 << 40
    try:
        small = convert_from_rows(b, t.schema)    # slots + segmented copy
    finally:
        cv._DMA_FROM_ROWS_MAX_N = old
    ok = True
    for ca, cb in zip(big.columns, small.columns):
        ok = ok and np.array_equal(np.asarray(ca.data), np.asarray(cb.data))
        if ca.offsets is not None:
            ok = ok and np.array_equal(np.asarray(ca.offsets),
                                       np.asarray(cb.offsets))
        ok = ok and np.array_equal(np.asarray(ca.validity_or_true()),
                                   np.asarray(cb.validity_or_true()))
    record("strings from_rows large-n == small-n", ok)


def check_xpack_engines():
    """Round-5 engines on the real chip: the fused to_rows/from_rows xpack
    programs (prove they ENGAGE, then byte-compare vs the non-xpack path),
    segmented_gather, and cap-boundary geometries incl. empty strings and
    an Lw outlier."""
    import os
    from spark_rapids_jni_tpu.rowconv import xpack
    rng = np.random.default_rng(7)
    cases = [
        ("bench_shape", 4000, lambda i: ["", "tpu", "spark-rapids",
                                         "columnar row transcode",
                                         "x" * 24, "payload"][i % 6]),
        ("empty_heavy", 2000, lambda i: "" if i % 3 else "ab"),
        ("outlier", 1500, lambda i: "z" * 300 if i == 700 else "s" * (i % 9)),
    ]
    for name, n, gen in cases:
        strs = [gen(i) for i in range(n)]
        t = Table([
            Column.from_numpy(rng.integers(-99, 99, n).astype(np.int64),
                              sr.int64, rng.random(n) < 0.9),
            Column.strings_from_list(strs),
            Column.strings_from_list([s[::-1] for s in strs]),
        ])
        layout = compute_row_layout(t.schema)
        b = convert_to_rows(t)[0]
        res = xpack.from_rows_var_x(layout, b)
        record(f"xpack from_rows engages [{name}]", res is not None)
        got = convert_from_rows(b, t.schema)
        # save/restore around the A/B write below, not a config read
        saved = os.environ.get("SRJT_XPACK")  # srjt-lint: disable=knob-env
        os.environ["SRJT_XPACK"] = "0"
        try:
            want_b = convert_to_rows(t)[0]
            want = convert_from_rows(want_b, t.schema)
        finally:
            if saved is None:
                del os.environ["SRJT_XPACK"]
            else:
                os.environ["SRJT_XPACK"] = saved
        record(f"xpack to_rows bytes [{name}]",
               np.array_equal(b.host_bytes(), want_b.host_bytes()))
        ok = True
        for ca, cb in zip(got.columns, want.columns):
            ok = ok and np.array_equal(np.asarray(ca.data),
                                       np.asarray(cb.data))
            if ca.offsets is not None:
                ok = ok and np.array_equal(np.asarray(ca.offsets),
                                           np.asarray(cb.offsets))
        record(f"xpack from_rows columns [{name}]", ok)

    # segmented_gather: ordered segments with gaps, vs numpy
    S = 200_000
    src_b = rng.integers(0, 256, S).astype(np.uint8)
    nseg = 3000
    lens = rng.integers(0, 90, nseg).astype(np.int32)
    gaps = rng.integers(0, 8, nseg)
    starts = np.zeros(nseg, np.int64)
    p = 0
    for i in range(nseg):
        starts[i] = p
        p += lens[i] + gaps[i]
    dst = np.zeros(nseg + 1, np.int64)
    np.cumsum(lens, out=dst[1:])
    geom = xpack.plan_segmented_gather(starts, lens, dst)
    record("segmented_gather plans", geom is not None)
    if geom is not None:
        got = np.asarray(xpack.segmented_gather(
            geom, jnp.asarray(src_b), jnp.asarray(starts.astype(np.int32)),
            jnp.asarray(lens), jnp.asarray(dst.astype(np.int32))))
        want = np.concatenate(
            [src_b[s:s + l] for s, l in zip(starts, lens)])             if lens.sum() else np.zeros(0, np.uint8)
        record("segmented_gather bytes", np.array_equal(got, want))


def check_dict_strings():
    """Dictionary-string device decode (round 5) byte-exact on chip vs the
    host decoder, nulls included."""
    import io
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_jni_tpu.parquet import decode, device_scan
    rng = np.random.default_rng(9)
    n = 30_000
    words = ["", "tpu", "dictionary-entry-payload", "x" * 60, "ünïcodé"]
    vals = [None if rng.random() < 0.1 else words[i]
            for i in rng.integers(0, len(words), n)]
    t = pa.table({"s": pa.array(vals, pa.string())})
    buf = io.BytesIO()
    pq.write_table(t, buf, compression="SNAPPY", use_dictionary=True,
                   row_group_size=12_000)
    raw = buf.getvalue()
    dev = device_scan.scan_table(raw).columns[0]
    host = decode.read_table(raw).columns[0]
    ok = (np.array_equal(np.asarray(dev.data), np.asarray(host.data))
          and np.array_equal(np.asarray(dev.offsets),
                             np.asarray(host.offsets))
          and np.array_equal(np.asarray(dev.validity_or_true()),
                             np.asarray(host.validity_or_true())))
    record("dict strings device decode", ok)


def check_dict_fast_path():
    """Dictionary fast path on chip: the scan keeps codes (no byte
    materialization), dictionary-aware predicates (evaluate once per
    entry, gather the boolean by code) match a per-row byte-matrix
    oracle, and code gathers match reference row selection."""
    import io
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_jni_tpu.column import DictColumn, Table
    from spark_rapids_jni_tpu.ops import filter as F
    from spark_rapids_jni_tpu.ops import strings as S
    from spark_rapids_jni_tpu.parquet import device_scan
    rng = np.random.default_rng(11)
    n = 20_000
    words = ["alpha", "alpaca", "beta", "betamax", "", "gamma-ray",
             "alphabet"]
    picks = rng.integers(0, len(words), n)
    vals = [None if rng.random() < 0.1 else words[i] for i in picks]
    t = pa.table({"s": pa.array(vals, pa.string())})
    buf = io.BytesIO()
    pq.write_table(t, buf, use_dictionary=True, row_group_size=8_000)
    col = device_scan.scan_table(buf.getvalue()).columns[0]
    record("dict fast path scan produces codes", isinstance(col, DictColumn))
    if not isinstance(col, DictColumn):
        return

    # dictionary-aware predicate vs byte-matrix oracle (per-row evaluate)
    def oracle(pred):
        return np.array([bool(v is not None and pred(v)) for v in vals])

    checks = [
        ("equal", S.equal_to_scalar(col, "alpha"), oracle(lambda v: v == "alpha")),
        ("starts_with", S.starts_with(col, "alp"), oracle(lambda v: v.startswith("alp"))),
        ("like", S.like(col, "%eta%"), oracle(lambda v: "eta" in v)),
    ]
    for name, got, want in checks:
        bits = np.asarray(got.data) != 0
        if got.validity is not None:
            bits = bits & np.asarray(got.validity)
        record(f"dict predicate {name} vs oracle", np.array_equal(bits, want))
    m = F.isin(col, ["beta", "gamma-ray", "absent"])
    record("dict isin vs oracle",
           np.array_equal(np.asarray(m), oracle(lambda v: v in ("beta", "gamma-ray"))))

    # code gather: row selection without touching string bytes
    idx = jnp.asarray(rng.integers(0, n, 4_000).astype(np.int32))
    g = F.gather(Table([col]), idx).columns[0]
    record("dict gather stays codes", isinstance(g, DictColumn))
    want = [vals[i] for i in np.asarray(idx)]
    record("dict gather rows", g.to_pylist() == want)


def check_fixed_words():
    rng = np.random.default_rng(2)
    for name, schema in SCHEMAS.items():
        n = 4097
        t = _random_table(rng, schema, n)
        b = convert_to_rows(t)
        want, _ = reference.to_rows_np(t)
        record(f"fixed words to_rows {name}",
               np.array_equal(b[0].host_bytes(), want))
        back = convert_from_rows(b[0], t.schema)
        ok = True
        for ca, cb in zip(back.columns, t.columns):
            va = np.asarray(ca.validity_or_true())
            ok = ok and np.array_equal(va, np.asarray(cb.validity_or_true()))
            da, db = np.asarray(ca.data), np.asarray(cb.data)
            ok = ok and np.array_equal(da[va], db[va])
        record(f"fixed words roundtrip {name}", ok)


def check_f64bits():
    """The arithmetic bits<->values path, within the backend's contract:
    the TPU's emulated f64 carries only ~47-49 effective mantissa bits, so
    the promise is ulp-bounded closeness for normals, exactness for specials
    (powers of two, zeros, infinities), and self-consistent round-trips —
    bit-exactness exists only on native-bitcast backends (CPU suite)."""
    rng = np.random.default_rng(3)
    # Full ~48-bit precision exists only in the middle of the emulation's
    # f32-like exponent window: near its bottom the value's LOW f32
    # component denormal-flushes (precision shrinks gradually, like
    # denormals do), so the ulp assertion samples |x| in ~[2^-60, 2^60].
    vals = np.concatenate([
        rng.standard_normal(4000),
        rng.standard_normal(4000) * 10.0 ** rng.integers(-18, 18, 4000),
        np.array([0.0, -0.0, 1.0, -1.0, np.inf, -np.inf, np.nan,
                  2.0 ** -60, 2.0 ** 60, 0.5, 2.0 ** 100]),
    ]).astype(np.float64)
    bits = vals.view(np.uint32).reshape(-1, 2)
    dec = np.asarray(jax.jit(f64bits.from_bits)(jnp.asarray(bits)))
    finite = np.isfinite(vals)
    # ulp distance via ordered-int mapping of the bit patterns
    a = vals.view(np.int64).copy()
    b = dec.view(np.int64).copy()
    a = np.where(a < 0, np.int64(-2**63) - a, a)
    b = np.where(b < 0, np.int64(-2**63) - b, b)
    ulps = np.abs(a - b)[finite].max() if finite.any() else 0
    record("f64bits.from_bits ulp-bounded", ulps <= 64, f"max ulps={ulps}")
    specials = np.isin(vals, [0.0, 1.0, -1.0, 0.5, 2.0 ** 100]) | ~np.isfinite(vals)
    nan = np.isnan(vals)
    ok_special = np.array_equal(
        dec[specials & ~nan].view(np.uint64),
        vals[specials & ~nan].view(np.uint64)) and np.isnan(dec[nan]).all()
    record("f64bits.from_bits exact on specials", ok_special)
    # encode(decode(bits)) must be self-consistent: decoding again on the
    # same backend reproduces the same emulated value
    enc = np.asarray(jax.jit(
        lambda x: f64bits.to_bits(f64bits.from_bits(x)))(jnp.asarray(bits)))
    dec2 = np.asarray(jax.jit(f64bits.from_bits)(jnp.asarray(enc)))
    ok_rt = np.array_equal(dec2[finite], dec[finite]) and np.isnan(dec2[nan]).all()
    record("f64bits encode(decode) self-consistent", ok_rt)
    # outside the window, decode degrades monotonically to 0 / +-inf
    big = np.array([1e300, -1e300, 1e-300, -1e-300], np.float64)
    dbig = np.asarray(jax.jit(f64bits.from_bits)(
        jnp.asarray(big.view(np.uint32).reshape(-1, 2))))
    record("f64bits out-of-window degrades to 0/inf",
           dbig[0] == np.inf and dbig[1] == -np.inf
           and dbig[2] == 0.0 and abs(dbig[3]) == 0.0,
           f"decoded={dbig.tolist()}")


def check_query_ops():
    """Minimal on-chip repros for the op family behind the 13 TPU-crashing
    queries (VERDICT weak #1: rollup/grouping-sets/cube, rank/window,
    string-compare) — each probe is one op over ~1-2k rows, differentially
    checked against a host oracle, so a worker crash here pinpoints the
    culprit op without running the query suite."""
    from spark_rapids_jni_tpu import ops
    from spark_rapids_jni_tpu.ops import strings as S
    from spark_rapids_jni_tpu.ops import window as W

    rng = np.random.default_rng(13)
    n = 1500
    a = rng.integers(0, 7, n).astype(np.int64)
    b = rng.integers(0, 5, n).astype(np.int64)
    v = rng.integers(-100, 100, n).astype(np.int64)
    av = rng.random(n) < 0.9       # null keys ride along (Spark groups them)
    t = Table([Column.from_numpy(a, validity=av), Column.from_numpy(b),
               Column.from_numpy(v)])

    def host_sets(sets):
        # oracle: one dict pass per grouping set, Spark grouping_id bits
        # (MSB = first key, set when the key is aggregated away)
        rows = set()
        for s in sets:
            gid = sum(1 << (1 - k) for k in range(2) if k not in s)
            acc = {}
            for i in range(n):
                ka = (int(a[i]) if av[i] else None) if 0 in s else None
                kb = int(b[i]) if 1 in s else None
                acc[(ka, kb)] = acc.get((ka, kb), 0) + int(v[i])
            rows |= {(ka, kb, sv, gid) for (ka, kb), sv in acc.items()}
        return rows

    def got_rows(out):
        return set(zip(out[0].to_pylist(), out[1].to_pylist(),
                       out[2].to_pylist(), out[3].to_pylist()))

    out = ops.groupby_rollup(t, [0, 1], [(2, "sum")])
    record("query-ops rollup(sum)",
           got_rows(out) == host_sets([[0, 1], [0], []]))
    out = ops.groupby_cube(t, [0, 1], [(2, "sum")])
    record("query-ops cube(sum)",
           got_rows(out) == host_sets([[0, 1], [0], [1], []]))
    out = ops.groupby_grouping_sets(t, [0, 1], [[0], [1]], [(2, "sum")])
    record("query-ops grouping-sets(sum)",
           got_rows(out) == host_sets([[0], [1]]))

    # rank / dense_rank / row_number / lag vs a host scan
    part = rng.integers(0, 40, n).astype(np.int64)
    key = rng.integers(0, 25, n).astype(np.int64)
    wt = Table([Column.from_numpy(part), Column.from_numpy(key),
                Column.from_numpy(v)])
    spec = W.WindowSpec(wt, partition_by=[0], order_by_keys=[1])
    order = sorted(range(n), key=lambda i: (part[i], key[i], i))
    exp_rn = np.zeros(n, np.int64)
    exp_rk = np.zeros(n, np.int64)
    exp_dr = np.zeros(n, np.int64)
    exp_lag = [None] * n
    pos = rk = dr = 0
    for j, i in enumerate(order):
        prev = order[j - 1] if j else None
        if prev is None or part[prev] != part[i]:
            pos, rk, dr = 1, 1, 1
        else:
            pos += 1
            if key[prev] != key[i]:
                rk, dr = pos, dr + 1
            exp_lag[i] = int(v[prev])
        exp_rn[i], exp_rk[i], exp_dr[i] = pos, rk, dr
    record("query-ops row_number",
           np.array_equal(np.asarray(W.row_number(spec).to_numpy()), exp_rn))
    record("query-ops rank",
           np.array_equal(np.asarray(W.rank(spec, [1]).to_numpy()), exp_rk))
    record("query-ops dense_rank",
           np.array_equal(np.asarray(W.dense_rank(spec, [1]).to_numpy()),
                          exp_dr))
    record("query-ops lag", W.lag(spec, 2, 1).to_pylist() == exp_lag)

    # string compares (contains / starts_with / equal_to_scalar)
    words = ["", "brand#1", "BRAND#12", "spark", "s", "importers #1",
             "xx#1yy", None]
    strs = [words[i] for i in rng.integers(0, len(words), n)]
    sc = Column.strings_from_list(strs)
    want = [None if s is None else ("#1" in s) for s in strs]
    record("query-ops strings.contains",
           S.contains(sc, "#1").to_pylist() == want)
    want = [None if s is None else s.startswith("s") for s in strs]
    record("query-ops strings.starts_with",
           S.starts_with(sc, "s").to_pylist() == want)
    want = [None if s is None else (s == "spark") for s in strs]
    record("query-ops strings.equal_to_scalar",
           S.equal_to_scalar(sc, "spark").to_pylist() == want)


def check_composite_pack():
    """Composite-key pack/unpack lowering (join engine v2 multi-key): the
    mixed-radix int64 mul/add pack chain and its floordiv/mod inverse,
    jitted on chip, vs a NumPy oracle — then one end-to-end 2-key join
    planned through ``join_plan.plan_keys`` whose pairs must reproduce the
    host tuple join.  A miscompile in the int64 chains shows up here as a
    single failing probe, not a wrong TPC-DS aggregate."""
    from spark_rapids_jni_tpu.ops import join_plan
    from spark_rapids_jni_tpu.ops.join import join_indices

    rng = np.random.default_rng(17)
    n = 4096
    for name, spans, kmins in [
        ("3key_small", (19, 64, 256), (-7, 0, 1000)),
        ("2key_wide", (1 << 20, 1 << 21), (123_456, -998_877)),
        ("4key_mixed", (11, 13, 17, 1 << 30), (0, -5, 2, -(1 << 29))),
    ]:
        lanes = [rng.integers(k, k + s, n, dtype=np.int64)
                 for s, k in zip(spans, kmins)]
        comp = np.zeros(n, np.int64)
        stride = 1
        for s, k, l in zip(spans[::-1], kmins[::-1], lanes[::-1]):
            comp += (l - k) * stride
            stride *= s

        @jax.jit
        def pack(ls, spans=spans, kmins=kmins):
            c = jnp.zeros(n, jnp.int64)
            st = 1
            for s, k, l in zip(spans[::-1], kmins[::-1], ls[::-1]):
                d = l.astype(jnp.int64) - k
                c = c + jnp.clip(d, 0, s - 1) * st
                st *= s
            return c

        got = np.asarray(pack([jnp.asarray(l) for l in lanes]))
        record(f"composite pack {name}", np.array_equal(got, comp))

        @jax.jit
        def unpack(c, spans=spans, kmins=kmins):
            outs = []
            for s, k in zip(spans[::-1], kmins[::-1]):
                outs.append(c % s + k)
                c = c // s
            return outs[::-1]

        back = [np.asarray(x) for x in unpack(jnp.asarray(comp))]
        record(f"composite unpack {name}",
               all(np.array_equal(b, l) for b, l in zip(back, lanes)))

    # end-to-end: planner packs, engines probe, pairs match host tuples
    import collections
    nb, npr = 3000, 8000
    ra = rng.integers(-50, 50, nb, dtype=np.int64)
    rb = rng.integers(0, 9, nb, dtype=np.int64)
    sel = rng.integers(0, nb, npr)
    la = ra[sel]
    lb = np.where(rng.random(npr) < 0.8, rb[sel], rb[sel] + 10)
    lt = [Column.from_numpy(la), Column.from_numpy(lb)]
    rt = [Column.from_numpy(ra), Column.from_numpy(rb)]
    plan = join_plan.plan_keys(lt, rt)
    record("composite plan_keys mode", plan.mode == "composite", plan.mode)
    li, ri = join_indices(lt, rt, "inner")
    li, ri = np.asarray(li), np.asarray(ri)
    keys_eq = (np.array_equal(la[li], ra[ri])
               and np.array_equal(lb[li], rb[ri]))
    cnt = collections.Counter(zip(ra.tolist(), rb.tolist()))
    want = sum(cnt[(x, y)] for x, y in zip(la.tolist(), lb.tolist()))
    record("composite 2-key join pairs", keys_eq and li.shape[0] == want,
           f"pairs={li.shape[0]}")


GROUPS = [
    ("ragged engine", check_ragged),
    ("strings transcode", check_strings_transcode),
    ("strings large-n branch", check_strings_large_n),
    ("xpack engines (round 5)", check_xpack_engines),
    ("dict strings", check_dict_strings),
    ("dict fast path (codes + predicates)", check_dict_fast_path),
    ("fixed-width u32-words transcode", check_fixed_words),
    ("f64 bits<->values", check_f64bits),
    ("chip-killer query ops (rollup/window/string-compare)",
     check_query_ops),
    ("composite-key pack/unpack lowering", check_composite_pack),
]


def main() -> int:
    t0 = time.time()
    dev = jax.devices()[0]
    RESULTS["backend"] = dev.platform
    RESULTS["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())}
    if dev.platform != "tpu":
        RESULTS["ok"] = False
        RESULTS["error"] = "not running on a TPU backend"
    else:
        for title, fn in GROUPS:
            print(f"{title}:", flush=True)
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — a group that raises is a FAIL, and the sweep goes on
                record(f"{fn.__name__} raised", False, repr(e)[:400])
    RESULTS["seconds"] = round(time.time() - t0, 1)
    out = sys.argv[1] if len(sys.argv) > 1 else "PALLAS_TPU_CHECK.json"
    with open(out, "w") as f:
        json.dump(RESULTS, f, indent=1)
    failed = [c["name"] for c in RESULTS["checks"] if not c["ok"]]
    print(json.dumps({"ok": RESULTS["ok"], "device": RESULTS["device"],
                      "checks": len(RESULTS["checks"]), "failed": failed,
                      "seconds": RESULTS["seconds"]}), flush=True)
    return 0 if RESULTS["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
