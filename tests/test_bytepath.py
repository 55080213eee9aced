"""Byte-path differential suite (round 6).

Every raw-speed path the staging campaign added must be BIT-IDENTICAL to
the eager path it replaced, across the encodings the scan tier handles:

* slab-coalesced (and pipelined) staging vs eager per-buffer uploads,
  including dictionary-encoded and null-heavy columns;
* the Pallas kernels vs their lax fallbacks (interpret mode — CPU CI
  gates parity; chip wins are measured, not assumed);
* the fused scan→filter vs scan-then-``apply_boolean_mask``, at both the
  scanner and the planner tier;
* buffer donation forced on, under ``SRJT_SANITIZE=strict``.
"""

import io

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_jni_tpu.parquet import device_scan
from spark_rapids_jni_tpu.utils import flight

RNG = np.random.default_rng(29)
N = 6000


def _write(t: pa.Table, **kw) -> bytes:
    buf = io.BytesIO()
    pq.write_table(t, buf, **kw)
    return buf.getvalue()


@pytest.fixture(scope="module")
def raw() -> bytes:
    nn = RNG.integers(0, 1000, N).astype(np.int64)
    t = pa.table({
        "a": pa.array(RNG.integers(0, 1000, N).astype(np.int32)),
        "f": pa.array(RNG.standard_normal(N)),
        "low": pa.array(RNG.integers(0, 50, N).astype(np.int64)),
        "d": pa.array([f"val{v}" for v in RNG.integers(0, 30, N)]),
        "s": pa.array([f"s{v}" for v in RNG.integers(0, 2000, N)]),
        "nn": pa.array([None if m else int(v) for v, m in
                        zip(nn, RNG.random(N) < 0.4)], pa.int64()),
    })
    return _write(t, compression="NONE", row_group_size=1500)


def _assert_tables_identical(a, b):
    assert a.num_columns == b.num_columns
    for ca, cb in zip(a.columns, b.columns):
        # paths may differ in wrapper class (Lazy/Dict) but never in
        # dtype or bytes
        assert ca.dtype == cb.dtype
        np.testing.assert_array_equal(np.asarray(ca.data),
                                      np.asarray(cb.data))
        if ca.offsets is not None:
            np.testing.assert_array_equal(np.asarray(ca.offsets),
                                          np.asarray(cb.offsets))
        np.testing.assert_array_equal(np.asarray(ca.validity_or_true()),
                                      np.asarray(cb.validity_or_true()))


def _scan(raw_bytes, monkeypatch, env, **kw):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    try:
        return device_scan.scan_table(raw_bytes, **kw)
    finally:
        for k in env:
            monkeypatch.delenv(k, raising=False)


@pytest.fixture(scope="module")
def eager(raw):
    """The eager-path reference scan, shared across comparisons (these
    knobs are host-side — no jit cache interaction, safe to reuse)."""
    import os
    os.environ["SRJT_STAGE_SLABS"] = "0"
    os.environ["SRJT_FUSED_FILTER"] = "0"
    try:
        return device_scan.scan_table(raw)
    finally:
        del os.environ["SRJT_STAGE_SLABS"], os.environ["SRJT_FUSED_FILTER"]


# --- staged vs eager ---------------------------------------------------------


@pytest.mark.parametrize("pipeline", ["0", "1"])
def test_staged_scan_bit_identical(raw, eager, monkeypatch, pipeline):
    staged = _scan(raw, monkeypatch, {"SRJT_STAGE_SLABS": "1",
                                      "SRJT_STAGE_PIPELINE": pipeline})
    _assert_tables_identical(eager, staged)


def _scan_with_flight(raw_bytes, monkeypatch, env):
    """(table, flight events of the scan)."""
    was = flight.enabled()
    flight.set_enabled(True)
    flight.reset()
    try:
        return _scan(raw_bytes, monkeypatch, env), flight.events()
    finally:
        flight.set_enabled(was)


def test_staged_scan_coalesces_and_overlaps(raw, monkeypatch):
    _, evs = _scan_with_flight(raw, monkeypatch, {"SRJT_STAGE_SLABS": "1",
                                                  "SRJT_STAGE_PIPELINE": "1"})
    flushes = [e for e in evs if e["kind"] == "parquet.stage.flush"]
    assert flushes and sum(e["slabs"] for e in flushes) >= 1
    overlap = [e for e in evs if e["kind"] == "parquet.stage.overlap"]
    assert overlap and overlap[-1]["columns"] > 1


def test_staged_tiny_slab_cap_still_identical(raw, eager, monkeypatch):
    # a 4 KiB cap forces many waves/slabs — split boundaries must not
    # change a single byte
    staged = _scan(raw, monkeypatch, {"SRJT_STAGE_SLABS": "1",
                                      "SRJT_STAGE_SLAB_BYTES": "4096"})
    _assert_tables_identical(eager, staged)


# --- pallas kernels (interpret) ---------------------------------------------


def test_pallas_u8_to_u32_parity(monkeypatch):
    from spark_rapids_jni_tpu.rowconv import xpallas
    monkeypatch.setenv("SRJT_PALLAS_TRANSPOSE", "interpret")
    flat = jnp.asarray(RNG.integers(0, 256, 4 * 512 * 3, dtype=np.int64)
                       .astype(np.uint8))
    out = xpallas.try_u8_to_u32(flat)
    assert out is not None
    ref = np.frombuffer(np.asarray(flat).tobytes(), np.uint32)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_pallas_gather_rows_parity(monkeypatch):
    from spark_rapids_jni_tpu.rowconv import xpallas
    monkeypatch.setenv("SRJT_PALLAS_DICT_GATHER", "interpret")
    mat = jnp.asarray(RNG.integers(0, 2**32, (77, 19), dtype=np.int64)
                      .astype(np.uint32))
    idx = jnp.asarray(RNG.integers(0, 77, 999).astype(np.int32))
    out = xpallas.try_gather_rows(mat, idx)
    assert out is not None
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(mat)[np.asarray(idx)])


def test_pallas_extract_rows_parity(monkeypatch):
    from spark_rapids_jni_tpu.rowconv import xpallas
    monkeypatch.setenv("SRJT_PALLAS_EXTRACT", "interpret")
    rows, M = 50, 48
    lens = RNG.integers(1, 40, rows)
    offs = np.zeros(rows + 1, np.int64)
    offs[1:] = np.cumsum(lens)
    payload = RNG.integers(0, 256, int(offs[-1]), dtype=np.int64) \
        .astype(np.uint8)
    out = xpallas.try_extract_rows(jnp.asarray(payload), offs, M)
    assert out is not None
    got = np.asarray(out)
    for j in range(rows):
        ln = min(int(lens[j]), M)
        np.testing.assert_array_equal(got[j, :ln],
                                      payload[offs[j]:offs[j] + ln])


def test_pallas_pack_windows_parity(monkeypatch):
    from spark_rapids_jni_tpu.rowconv import xpack, xpallas
    n, Mw = 512, 40
    dense = jnp.asarray(RNG.integers(0, 2**32, (n, Mw), dtype=np.int64)
                        .astype(np.uint32))
    # rows are 8-byte aligned (the layout contract): even word sizes;
    # P must cover every row starting inside one 128-word window
    rs = 2 * RNG.integers(8, Mw // 2 + 1, n)
    dst = np.concatenate([[0], np.cumsum(rs)]).astype(np.int32)
    dst_w = jnp.asarray(dst)
    total_w = int(dst[-1])
    nwin = -(-total_w // xpack.WIN_W)
    P = int(np.bincount(dst[:-1] // xpack.WIN_W,
                        minlength=nwin).max()) + 1
    lax_out = np.asarray(xpack.pack_windows(dense, dst_w, total_w, P, nwin))
    monkeypatch.setenv("SRJT_PALLAS_PACKWIN", "interpret")
    out = xpallas.try_pack_windows(dense, dst_w, total_w, P, nwin)
    assert out is not None
    np.testing.assert_array_equal(np.asarray(out), lax_out)


@pytest.mark.slow
def test_pallas_interpret_scan_bit_identical(raw, monkeypatch):
    """The whole scan with every kernel knob in interpret mode — the
    in-trace dispatch sites (dict gather, u8→u32) against the lax scan."""
    from spark_rapids_jni_tpu.rowconv import xpallas
    base = _scan(raw, monkeypatch, {"SRJT_DICT_STRINGS": "0"})
    jax.clear_caches()
    before = dict(xpallas._counts)
    knobs_env = {"SRJT_PALLAS_TRANSPOSE": "interpret",
                 "SRJT_PALLAS_DICT_GATHER": "interpret",
                 "SRJT_PALLAS_EXTRACT": "interpret",
                 "SRJT_PALLAS_PACKWIN": "interpret",
                 "SRJT_DICT_STRINGS": "0"}
    try:
        pall = _scan(raw, monkeypatch, knobs_env)
    finally:
        jax.clear_caches()     # drop kernel-mode traces for later tests
    assert xpallas._counts["hits"] > before.get("hits", 0)
    _assert_tables_identical(base, pall)


# --- fused scan→filter -------------------------------------------------------


@pytest.fixture(scope="module")
def pdf(raw):
    return pq.read_table(io.BytesIO(raw)).to_pandas()


def _ref_filtered(t, df, conds):
    """Reference: the shared unfiltered scan + the planner's own mask
    semantics (nulls fail every conjunct)."""
    from spark_rapids_jni_tpu.ops.filter import apply_boolean_mask
    keep = np.ones(len(df), bool)
    for cname, op, val in conds:
        col = df[cname]
        v = val.decode() if isinstance(val, bytes) else val
        m = {"eq": col == v, "lt": col < v, "le": col <= v,
             "gt": col > v, "ge": col >= v}[op]
        keep &= np.asarray(m.fillna(False)) & ~np.asarray(col.isna())
    return apply_boolean_mask(t, jnp.asarray(keep)), int(keep.sum())


# each distinct kept-row count retraces the decode program, so the
# per-case cost is real compile time: keep one case per predicate
# category in the tier-1 gate, push the rest to -m slow
@pytest.mark.parametrize("conds", [
    [("a", "lt", 500)],
    pytest.param([("a", "ge", 250), ("low", "lt", 40)],
                 marks=pytest.mark.slow),
    [("d", "eq", b"val7")],
    pytest.param([("s", "eq", b"s42")], marks=pytest.mark.slow),
    [("nn", "ge", 100)],                    # null-heavy: nulls must fail
    pytest.param([("a", "lt", 800), ("d", "eq", b"val3"),
                  ("nn", "lt", 900)], marks=pytest.mark.slow),
])
def test_fused_filter_differential(raw, eager, pdf, monkeypatch, conds):
    fused = _scan(raw, monkeypatch, {"SRJT_FUSED_FILTER": "1"},
                  row_predicate=conds)
    ref, n_kept = _ref_filtered(eager, pdf, conds)
    assert getattr(fused, "fused_filter_complete", False)
    assert fused.num_rows == n_kept
    _assert_tables_identical(ref, fused)


def test_fused_filter_off_knob(raw, monkeypatch):
    t = _scan(raw, monkeypatch, {"SRJT_FUSED_FILTER": "0"},
              row_predicate=[("a", "lt", 500)])
    assert not getattr(t, "fused_filter_complete", False)
    assert t.num_rows == N          # predicate ignored: planner reapplies


def test_fused_filter_unsupported_cond_incomplete(raw, eager, pdf,
                                                  monkeypatch):
    # a float conjunct is not host-evaluable → handled subset prunes,
    # ``complete`` stays False so the planner re-applies its mask
    t = _scan(raw, monkeypatch, {"SRJT_FUSED_FILTER": "1"},
              row_predicate=[("a", "lt", 500), ("f", "lt", 0.0)])
    assert not getattr(t, "fused_filter_complete", False)
    ref, _ = _ref_filtered(eager, pdf, [("a", "lt", 500)])
    _assert_tables_identical(ref, t)


def test_planner_skips_reapply_on_full_pushdown(raw, monkeypatch):
    from spark_rapids_jni_tpu import plan as P
    from spark_rapids_jni_tpu.plan import ir
    from spark_rapids_jni_tpu.utils import metrics
    cat = P.FileCatalog({"t": raw})
    tree = ir.Scan("t", columns=("a", "low"),
                   predicate=ir.Cmp("<", ir.Col("a"), ir.Lit(500)))
    metrics.set_enabled(True)
    metrics.reset()
    try:
        monkeypatch.setenv("SRJT_FUSED_FILTER", "1")
        out = P.execute(tree, cat)
        fused_hits = metrics.counter_value("plan.scan.filter_fused")
        monkeypatch.setenv("SRJT_FUSED_FILTER", "0")
        ref = P.execute(tree, cat)
    finally:
        metrics.set_enabled(False)
        monkeypatch.delenv("SRJT_FUSED_FILTER", raising=False)
    assert fused_hits >= 1
    _assert_tables_identical(ref, out)


# --- prefetch ingest attribution ---------------------------------------------


def test_prefetch_ingest_attribution(raw, monkeypatch):
    from spark_rapids_jni_tpu.exec.prefetch import Prefetcher
    from spark_rapids_jni_tpu.utils import metrics
    monkeypatch.setenv("SRJT_STAGE_SLABS", "1")
    metrics.set_enabled(True)
    metrics.reset()
    was = flight.enabled()
    flight.set_enabled(True)
    flight.reset()
    p = Prefetcher(depth=1)
    try:
        assert p.stage("k", lambda: device_scan.scan_table(raw))
        # wait for the STAGING THREAD to finish the load — taking earlier
        # would race it and run the loader inline (a miss, unattributed)
        p._slots["k"]["done"].wait(timeout=60)
        t = p.take("k")
        assert t.num_rows == N
    finally:
        p.close()
        metrics.set_enabled(False)
        flight.set_enabled(was)
    evs = [e for e in flight.events()
           if e["kind"] == "exec.prefetch.ingest"]
    assert evs, "prefetch load did not attribute its staging work"
    assert evs[-1]["slab_bytes"] > 0 and evs[-1]["transfers"] >= 1


# --- donation under the strict sanitizer -------------------------------------


def test_forced_donation_strict_sanitizer(raw, eager, monkeypatch):
    from spark_rapids_jni_tpu.analysis import sanitize
    sanitize.reset()
    try:
        donated, evs = _scan_with_flight(
            raw, monkeypatch, {"SRJT_SCAN_DONATE": "1",
                               "SRJT_SANITIZE": "strict"})
    finally:
        sanitize.reset()
    _assert_tables_identical(eager, donated)
    # the donation engaged, it was not only asked for
    donates = [e for e in evs if e["kind"] == "parquet.scan.donate"]
    assert donates and donates[-1]["bytes"] > 0 and donates[-1]["buffers"] >= 1


@pytest.mark.slow
def test_forced_donation_with_staging_and_filter(raw, eager, pdf,
                                                 monkeypatch):
    conds = [("a", "lt", 500), ("nn", "ge", 100)]
    ref, n_kept = _ref_filtered(eager, pdf, conds)
    t = _scan(raw, monkeypatch,
              {"SRJT_SCAN_DONATE": "1", "SRJT_STAGE_SLABS": "1",
               "SRJT_FUSED_FILTER": "1"}, row_predicate=conds)
    assert t.num_rows == n_kept
    _assert_tables_identical(ref, t)
