"""Dictionary-string fast path differentials.

The scan keeps fully dictionary-encoded string columns as
:class:`DictColumn` (int32 codes + dictionary) and every relational op
consumes the codes; bytes materialize only at the output boundary
(rowconv / host extraction).  These tests hold the contract three ways:

* **differential** — every op (filter, join, groupby, sort, rowconv) on
  the dict path is bit-identical to the forced-materialized path
  (``SRJT_DICT_STRINGS=0``) and agrees with a pandas oracle;
* **laziness** — the dict path never bumps ``strings.dict.materialize``
  before the output boundary (counter-asserted);
* **runtime parity** — results survive capture/replay compilation and
  the concurrent exec scheduler unchanged.
"""

import io

import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

from spark_rapids_jni_tpu import types as T
from spark_rapids_jni_tpu.column import Column, DictColumn, Table, as_dict_column
from spark_rapids_jni_tpu.ops import filter as F
from spark_rapids_jni_tpu.ops import groupby as G
from spark_rapids_jni_tpu.ops import join_plan as J
from spark_rapids_jni_tpu.ops import sort as SORT
from spark_rapids_jni_tpu.ops import strings as S
from spark_rapids_jni_tpu.parquet import decode, device_scan
from spark_rapids_jni_tpu.rowconv import convert as RC
from spark_rapids_jni_tpu.utils import metrics


def _write(cols: dict, row_group_size=2_000, use_dictionary=True) -> bytes:
    import pyarrow as pa
    import pyarrow.parquet as pq
    t = pa.table(cols)
    buf = io.BytesIO()
    pq.write_table(t, buf, use_dictionary=use_dictionary,
                   row_group_size=row_group_size)
    return buf.getvalue()


def _strings(n, card, null_p, seed, prefix="brand"):
    rng = np.random.default_rng(seed)
    return [None if rng.random() < null_p
            else f"{prefix}_{rng.integers(0, card):03d}" for _ in range(n)]


@pytest.fixture(scope="module")
def raw():
    import pyarrow as pa
    n = 6_000
    rng = np.random.default_rng(5)
    return _write({
        "s": pa.array(_strings(n, 24, 0.12, 5), pa.string()),
        "k": rng.integers(0, 40, n).astype(np.int64),
        "x": rng.integers(-100, 100, n).astype(np.int64),
    })


def _scan_dict(raw_bytes) -> Table:
    t = device_scan.scan_table(raw_bytes)
    assert isinstance(t[0], DictColumn), "scan did not keep dict codes"
    return t


def _scan_mat(raw_bytes, monkeypatch) -> Table:
    monkeypatch.setenv("SRJT_DICT_STRINGS", "0")
    try:
        t = device_scan.scan_table(raw_bytes)
    finally:
        monkeypatch.delenv("SRJT_DICT_STRINGS", raising=False)
    assert as_dict_column(t[0]) is None
    return t


def _df(raw_bytes) -> pd.DataFrame:
    import pyarrow.parquet as pq
    return pq.read_table(io.BytesIO(raw_bytes)).to_pandas()


def _mask_arr(m: Column) -> jnp.ndarray:
    bits = m.data != 0
    return bits if m.validity is None else bits & m.validity


def _cols_equal(a: Column, b: Column):
    assert a.to_pylist() == b.to_pylist()


def _tables_equal(a: Table, b: Table):
    assert a.num_columns == b.num_columns
    for ca, cb in zip(a.columns, b.columns):
        _cols_equal(ca, cb)


# --- scan + laziness --------------------------------------------------------


def test_scan_matches_host_decode(raw):
    t = _scan_dict(raw)
    ref = decode.read_table(raw)
    _tables_equal(t, ref)


def test_dict_path_is_lazy_until_output(raw):
    metrics.set_enabled(True)
    try:
        base = metrics.snapshot()["counters"]

        def delta(name):
            snap = metrics.snapshot()["counters"]
            return snap.get(name, 0) - base.get(name, 0)

        t = _scan_dict(raw)
        assert delta("plan.scan.dict_cols") >= 1
        assert delta("parquet.pages.dict") >= 1
        col = t[0]
        mask = S.like(col, "%_00%")
        ft = F.mask_table(t, _mask_arr(mask))
        gt = G.groupby_aggregate(ft, [0], [(2, "sum")])
        perm = SORT.order_by(t, [0, 1], [True, True])
        F.gather(t, perm)
        assert delta("strings.dict.predicate") >= 1
        assert delta("strings.dict.gather") >= 1
        # nothing above may touch string bytes
        assert delta("strings.dict.materialize") == 0
        del gt
        # ...until the output boundary does
        _ = col.data
        assert delta("strings.dict.materialize") == 1
    finally:
        metrics.set_enabled(None)


def test_knob_forces_materialized_scan(raw, monkeypatch):
    t = _scan_mat(raw, monkeypatch)
    _tables_equal(t, decode.read_table(raw))


# --- filter -----------------------------------------------------------------


@pytest.mark.parametrize("pred", ["eq", "starts", "like", "isin"])
def test_filter_differential(raw, monkeypatch, pred):
    td, tm, df = _scan_dict(raw), _scan_mat(raw, monkeypatch), _df(raw)
    sd, sm = td[0], tm[0]
    if pred == "eq":
        md, mm = S.equal_to_scalar(sd, "brand_003"), S.equal_to_scalar(sm, "brand_003")
        want = df["s"] == "brand_003"
    elif pred == "starts":
        md, mm = S.starts_with(sd, "brand_01"), S.starts_with(sm, "brand_01")
        want = df["s"].str.startswith("brand_01")
    elif pred == "like":
        md, mm = S.like(sd, "%d_02%"), S.like(sm, "%d_02%")
        want = df["s"].str.contains("d_02", regex=False)
    else:
        vals = ["brand_001", "brand_017", "missing"]
        md = Column(T.bool8, F.isin(sd, vals))
        mm = Column(T.bool8, F.isin(sm, vals))
        want = df["s"].isin(vals)
    want = (want == True).to_numpy(dtype=bool)   # noqa: E712 (NaN → False)

    bd, bm = np.asarray(_mask_arr(md)), np.asarray(_mask_arr(mm))
    np.testing.assert_array_equal(bd, want)
    np.testing.assert_array_equal(bd, bm)
    fd = F.apply_boolean_mask(td, jnp.asarray(bd))
    fm = F.apply_boolean_mask(tm, jnp.asarray(bm))
    assert isinstance(fd[0], DictColumn)   # filtered rows stay codes
    _tables_equal(fd, fm)
    assert fd[0].to_pylist() == df["s"][want].tolist()
    md2 = F.mask_table(td, jnp.asarray(bd))   # non-compacting variant
    mm2 = F.mask_table(tm, jnp.asarray(bm))
    assert isinstance(md2[0], DictColumn)
    _tables_equal(md2, mm2)


# --- sort -------------------------------------------------------------------


def test_sort_permutation_bit_identical(raw, monkeypatch):
    td, tm = _scan_dict(raw), _scan_mat(raw, monkeypatch)
    for asc in (True, False):
        pd_ = np.asarray(SORT.order_by(td, [0, 2], [asc, True]))
        pm = np.asarray(SORT.order_by(tm, [0, 2], [asc, True]))
        np.testing.assert_array_equal(pd_, pm)
    perm = SORT.order_by(td, [0], [True])
    got = F.gather(td, perm)[0].to_pylist()
    nn = sorted(_df(raw)["s"].dropna().tolist())
    assert [v for v in got if v is not None] == nn


# --- groupby ----------------------------------------------------------------


def test_groupby_differential(raw, monkeypatch):
    td, tm, df = _scan_dict(raw), _scan_mat(raw, monkeypatch), _df(raw)
    gd = G.groupby_aggregate(td, [0], [(2, "sum")])
    gm = G.groupby_aggregate(tm, [0], [(2, "sum")])
    _tables_equal(gd, gm)
    want = df[df["s"].notna()].groupby("s")["x"].sum().to_dict()
    got = dict(zip(gd[0].to_pylist(), gd[1].to_pylist()))
    for k, v in want.items():
        assert got[k] == v


# --- join (multi-file, incompatible per-file dictionaries) ------------------


@pytest.mark.slow      # heaviest dict-path JIT in the module (~37 s)
def test_join_across_incompatible_dictionaries(raw, monkeypatch):
    import pyarrow as pa
    # second file: overlapping-but-different dictionary (other card/order)
    n2 = 3_000
    rng = np.random.default_rng(9)
    raw2 = _write({
        "s": pa.array(_strings(n2, 30, 0.1, 9), pa.string()),
        "y": rng.integers(0, 10, n2).astype(np.int64),
    }, row_group_size=1_100)
    ld, rd = _scan_dict(raw), _scan_dict(raw2)
    lm, rm = _scan_mat(raw, monkeypatch), _scan_mat(raw2, monkeypatch)
    # per-file dictionaries differ: shared encode must reconcile them
    jd = J.join_aggregate(ld, rd, [0], [0], group_keys=[0], aggs=[(2, "sum")])
    jm = J.join_aggregate(lm, rm, [0], [0], group_keys=[0], aggs=[(2, "sum")])
    _tables_equal(jd, jm)
    dfl, dfr = _df(raw), _df(raw2)
    merged = dfl.merge(dfr, on="s")
    want = merged.groupby("s")["x"].sum().to_dict()
    got = dict(zip(jd[0].to_pylist(), jd[1].to_pylist()))
    assert {k: v for k, v in got.items() if k is not None} == want


def test_encode_shared_consistency(raw, monkeypatch):
    import pyarrow as pa
    raw2 = _write({"s": pa.array(_strings(2_000, 8, 0.2, 3), pa.string())})
    a, b = _scan_dict(raw)[0], _scan_dict(raw2)[0]
    ea, eb = S.encode_shared([a, b])
    strs = a.to_pylist() + b.to_pylist()
    codes = np.asarray(ea.data).tolist() + np.asarray(eb.data).tolist()
    seen = {}
    for c, v in zip(codes, strs):
        if v is None:
            continue
        assert seen.setdefault(c, v) == v        # one code ↔ one string
    assert len(set(seen.values())) == len(seen)  # one string ↔ one code


# --- rowconv ----------------------------------------------------------------


def test_rowconv_boundary_bit_identical(raw, monkeypatch):
    td, tm = _scan_dict(raw), _scan_mat(raw, monkeypatch)
    bd, bm = RC.convert_to_rows(td), RC.convert_to_rows(tm)
    assert len(bd) == len(bm)
    for x, y in zip(bd, bm):
        np.testing.assert_array_equal(np.asarray(x.data), np.asarray(y.data))


def test_rowconv_dict_passthrough(raw):
    td = _scan_dict(raw)
    enc, dicts = RC.dict_encode_for_rows(td)
    assert list(dicts) == [0]
    assert enc[0].dtype.id == T.int32.id      # codes ride the fixed path
    batches = RC.convert_to_rows(enc)
    parts = [RC.convert_from_rows(b, [c.dtype for c in enc.columns])
             for b in batches]
    assert len(parts) == 1
    back = RC.restore_dict_columns(parts[0], dicts)
    assert isinstance(back[0], DictColumn)
    _tables_equal(back, decode.read_table(raw))


# --- edges: null codes, empty dictionary ------------------------------------


def test_heavy_nulls(monkeypatch):
    import pyarrow as pa
    rawn = _write({"s": pa.array(_strings(3_000, 5, 0.85, 7), pa.string()),
                   "x": np.arange(3_000, dtype=np.int64)})
    tn = device_scan.scan_table(rawn)
    _tables_equal(tn, decode.read_table(rawn))
    d = as_dict_column(tn[0])
    if d is not None:
        m = S.equal_to_scalar(tn[0], "brand_002")
        bits = (np.asarray(m.data) != 0) & np.asarray(m.validity)
        want = np.array([v == "brand_002" if v is not None else False
                         for v in _df(rawn)["s"]])
        np.testing.assert_array_equal(bits, want)


def test_all_null_column():
    import pyarrow as pa
    rawn = _write({"s": pa.array([None] * 500, pa.string()),
                   "x": np.arange(500, dtype=np.int64)})
    tn = device_scan.scan_table(rawn)
    _tables_equal(tn, decode.read_table(rawn))


def test_empty_dictionary_unit():
    # a DictColumn over a zero-entry dictionary (every row null)
    empty = Column(T.string, jnp.zeros(0, jnp.uint8), jnp.zeros(1, jnp.int32))
    d = DictColumn(jnp.zeros(7, jnp.int32), empty,
                   jnp.zeros(7, bool))
    assert d.to_pylist() == [None] * 7
    m = S.equal_to_scalar(d, "anything")
    assert not (np.asarray(m.data) != 0).any()
    mat = d.materialize()
    assert np.asarray(mat.offsets).tolist() == [0] * 8


# --- a whole query on dictionary codes ---------------------------------------


def test_tpcds_string_query_on_codes_bit_identical(monkeypatch):
    """q_like_brands (scan -> LIKE/prefix predicate -> join -> groupby) over
    a dictionary-encoded item file: no string byte is touched while the
    query runs, and the answer is the materialized path's, bit for bit."""
    import pyarrow.parquet as pq
    from benchmarks import tpcds_data
    from spark_rapids_jni_tpu.models import tpcds

    def redict(raw_bytes):         # the generator writes plain pages
        t = pq.read_table(io.BytesIO(raw_bytes))
        return _write(dict(zip(t.column_names, t.columns)))

    files = tpcds_data.generate(n_sales=8_000, n_items=300, seed=5)
    item_raw = redict(files["item"])
    sales = decode.read_table(files["store_sales"], columns=tpcds.SS_COLS)

    def load():                    # the two tables the query reads
        return {"store_sales": sales,
                "item": device_scan.scan_table(item_raw,
                                               columns=tpcds.ITEM_COLS)}

    brand = tpcds.ITEM_COLS.index("i_brand")
    on_codes = load()
    assert as_dict_column(on_codes["item"][brand]) is not None
    metrics.set_enabled(True)
    try:
        before = metrics.snapshot()["counters"]
        got = tpcds.QUERIES["q_like_brands"](on_codes)
        after = metrics.snapshot()["counters"]
    finally:
        metrics.set_enabled(None)
    assert after.get("strings.dict.predicate", 0) \
        > before.get("strings.dict.predicate", 0)
    assert after.get("strings.dict.materialize", 0) \
        == before.get("strings.dict.materialize", 0)
    monkeypatch.setenv("SRJT_DICT_STRINGS", "0")
    materialized = load()
    assert as_dict_column(materialized["item"][brand]) is None
    want = tpcds.QUERIES["q_like_brands"](materialized)
    assert got.num_rows == want.num_rows > 0
    _tables_equal(got, want)
    for a, b in zip(got.columns, want.columns):
        assert a.dtype.id == b.dtype.id


# --- runtime parity: capture/replay + concurrent scheduler ------------------


def _qfn(tables):
    t = tables["t"]
    m = S.starts_with(t[0], "brand_0")
    ft = F.mask_table(t, _mask_arr(m))
    g = G.groupby_aggregate(ft, [0], [(2, "sum")])
    perm = SORT.order_by(g, [0], [True])
    return F.gather(g, perm)


def test_capture_replay_bit_identity(raw):
    from spark_rapids_jni_tpu.models.compiled import compile_query
    tables = {"t": _scan_dict(raw)}
    cq = compile_query(_qfn, tables)
    out = cq.run(tables)
    _tables_equal(out, cq.expected)
    out2 = cq.run_unchecked(tables)
    _tables_equal(out2, cq.expected)


def test_scheduler_bit_identity(raw):
    from spark_rapids_jni_tpu import exec as xc
    tables = {"t": _scan_dict(raw)}
    want = _qfn(tables)
    with xc.QueryScheduler(workers=2) as sched:
        tickets = [sched.submit(f"dictq{i}", _qfn, tables) for i in range(4)]
        for tk in tickets:
            _tables_equal(tk.result(timeout=300), want)
