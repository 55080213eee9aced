"""Whole-query compilation (models/compiled.py): every TPC-DS subset query
must trace into ONE jitted program under syncs capture/replay and produce
exactly the eager result — the per-query single-dispatch contract behind
the SF1 wall-time work (VERDICT r3 next-step #3)."""

import numpy as np
import pytest

from benchmarks import tpcds_data
from spark_rapids_jni_tpu.models import tpcds
from spark_rapids_jni_tpu.models.compiled import compile_query
from spark_rapids_jni_tpu.utils import syncs


@pytest.fixture(scope="module")
def tables():
    files = tpcds_data.generate(n_sales=20_000, n_items=300, seed=11)
    return tpcds.load_tables(files)


def _tables_equal(a, b):
    assert a.num_columns == b.num_columns
    assert a.num_rows == b.num_rows
    for i in range(a.num_columns):
        ca, cb = a[i], b[i]
        assert ca.dtype.id == cb.dtype.id
        if ca.dtype.id.name == "STRING":
            assert ca.to_pylist() == cb.to_pylist()
        elif ca.dtype.id.name in ("FLOAT32", "FLOAT64"):
            # integer/key/count results must match EXACTLY; float
            # aggregates may differ by reassociation ulps — fusing the
            # whole query lets XLA reshape reduction trees (observed: one
            # grand-total mean off by 1 ulp on the CPU backend).  The
            # tolerance is a few ulps of the dtype, so it actually absorbs
            # what the comment claims (1e-12 would not cover a single
            # float32 ulp at ~1.2e-7 relative).
            rtol = 1e-12 if ca.dtype.id.name == "FLOAT64" else 1e-6
            np.testing.assert_allclose(np.asarray(ca.to_numpy()),
                                       np.asarray(cb.to_numpy()),
                                       rtol=rtol, atol=0)
        else:
            np.testing.assert_array_equal(np.asarray(ca.to_numpy()),
                                          np.asarray(cb.to_numpy()))


# the three heaviest JIT compiles ride the slow lane; the other ~20
# cases keep capture/replay bit-identity inside the tier-1 time budget
_SLOW_COMPILE = {"q27_cube", "q19", "q36_rollup"}


@pytest.mark.parametrize(
    "qname", [pytest.param(q, marks=pytest.mark.slow)
              if q in _SLOW_COMPILE else q
              for q in sorted(tpcds.QUERIES)])
def test_compiled_matches_eager(tables, qname):
    qfn = tpcds.QUERIES[qname]
    cq = compile_query(qfn, tables)
    out = cq.run(tables)        # checked: validates the tape, then runs
    _tables_equal(out, cq.expected)
    # steady state: re-execution is ONE dispatch, ZERO host syncs
    before = syncs.sync_count()
    out2 = cq.run_unchecked(tables)
    assert syncs.sync_count() == before
    _tables_equal(out2, cq.expected)


@pytest.mark.slow
def test_stale_tape_raises(tables):
    """VERDICT r4 weak #6: re-running a compiled plan against refreshed
    data whose true resolved sizes differ (same shapes, different join
    cardinalities) must raise, not silently return wrong rows.  The
    reference re-measures its sizes every call (row_conversion.cu:
    2205-2215); run() re-measures on device with one stacked sync."""
    from spark_rapids_jni_tpu.models.compiled import StaleTapeError
    cq = compile_query(tpcds.QUERIES["q3"], tables)
    assert len(cq.tape) > 0
    # refreshed data: identical shapes, different content → different
    # join/filter cardinalities
    files2 = tpcds_data.generate(n_sales=20_000, n_items=300, seed=77)
    tables2 = tpcds.load_tables(files2)
    with pytest.raises(StaleTapeError):
        cq.run(tables2)
    # the same refreshed tables recompile cleanly
    cq2 = compile_query(tpcds.QUERIES["q3"], tables2)
    out = cq2.run(tables2)
    _tables_equal(out, cq2.expected)


@pytest.mark.slow
def test_replay_detects_divergence(tables):
    cq = compile_query(tpcds.QUERIES["q3"], tables)
    # a tape for a different plan must not silently misresolve
    with pytest.raises(Exception):
        with syncs.replay(list(cq.tape[:1])):
            tpcds.QUERIES["q3"](tables)


# --- the star cell's two queries, as it serves them (SQL text -> plan) -----

# The tapes a capture records at this data set, written down at the parent
# of PR 35 (the pair expansion by binary search): the expansion adds no
# sync, so both tapes keep their length and every scalar.  Position 3 is
# the pair count the expansion is sized with.
_STAR_TAPES = {
    "q3": (18, 396, 17476, 60, 90, 301, 1050, 1, 7, 142, 474, 55, 8, 5, 47,
           6, 55, 8, 55),
    "q42": (212, 4, 19955, 649, 30, 661, 690, 1, 17, 1283, 3900, 87, 11, 6,
            39, 12, 66, 11, 66),
}
_STAR_PARAMS = {"q3": {"manufact_id": 436, "moy": 11},
                "q42": {"manager_id": 1, "moy": 11, "year": 2000}}


@pytest.fixture(scope="module")
def star_tables():
    from chipbench import datagen
    files = datagen.tpcds_star_parquet(60_000, 20_000, 12, 2001, 1098,
                                       order_seed=35)
    return tpcds.load_tables(files)


@pytest.mark.parametrize("cut", ["block", "chunked", "chunked_narrow_rows"])
@pytest.mark.parametrize("qname", ["q3", "q42"])
def test_star_queries_keep_their_tapes(star_tables, monkeypatch, qname, cut):
    """A sorted 18- / 212-key build probed by counting compares, then the
    pair expansion: however ``ops.select`` cuts it up (one chunk of pairs
    as in the star cell, chunks of 17, rows of 16 starts in three levels),
    capture syncs what it synced and the checked replay answers what the
    eager run answered."""
    from spark_rapids_jni_tpu import sql as sql_fe
    from spark_rapids_jni_tpu.models import tpcds_sql as TS
    from spark_rapids_jni_tpu.ops import select
    from spark_rapids_jni_tpu.utils import metrics
    if cut != "block":
        monkeypatch.setattr(select, "CHUNK_PAIRS", 17)
    if cut == "chunked_narrow_rows":
        monkeypatch.setattr(select, "ROW_WORDS", 16)
    form = cut.split("_")[0]
    qfn = sql_fe.compile_sql(TS.SQL[qname], TS.TABLE_SCHEMAS,
                             _STAR_PARAMS[qname])
    was = metrics.enabled()
    metrics.set_enabled(True)
    try:
        before = metrics.counter_value(f"join.expand.select.{form}")
        cq = compile_query(qfn, star_tables)
        assert metrics.counter_value(
            f"join.expand.select.{form}") == before + 1
    finally:
        metrics.set_enabled(was)
    assert cq.tape == _STAR_TAPES[qname]
    _tables_equal(cq.run(star_tables), cq.expected)
