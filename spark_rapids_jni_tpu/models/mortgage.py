"""Mortgage ETL pipeline (BASELINE config #5): the string/decimal-cast-heavy
feature-engineering stage of the RAPIDS Spark Mortgage demo, on this
framework's op library.

The reference accelerates this workload through libcudf's string-cast +
join + groupby kernels (SURVEY §2.9; config #5 "string/decimal cast
heavy").  Pipeline, all device-side after decode:

  1. scan raw perf/acq parquet (STRING-typed raw columns)
  2. parse: dates (``strings.to_date``), decimals (``to_decimal``),
     integers (``to_int64``), delinquency codes with unparseable "X" → -1
  3. dictionary-encode the categorical dimensions (seller/state)
  4. per-loan aggregation over performance records: max delinquency, mean
     UPB, record count, first reporting period
  5. join the loan features onto the parsed acquisition table → one
     all-numeric feature row per loan (the XGBoost input shape)
"""

from __future__ import annotations

from .. import types as T
from ..column import Column, Table
from ..ops import (cast, fill_null, groupby_aggregate, inner_join,
                   sort_table)
from ..ops import strings as S
from ..parquet import device_scan as decode  # device fast path, host fallback

PERF_COLS = ["loan_id", "monthly_reporting_period", "current_actual_upb",
             "current_loan_delinquency_status", "servicer_name"]
ACQ_COLS = ["loan_id", "orig_interest_rate", "orig_upb", "orig_date",
            "state", "seller_name"]

# feature-table column order produced by etl()
FEATURE_COLS = ["loan_id", "orig_rate_e4", "orig_upb", "orig_date_days",
                "state_code", "seller_code", "max_delinquency", "mean_upb",
                "num_records", "first_period_days"]


def load_tables(files: dict[str, bytes]) -> dict[str, Table]:
    return {"perf": decode.read_table(files["perf"], columns=PERF_COLS),
            "acq": decode.read_table(files["acq"], columns=ACQ_COLS)}


def _parse_perf(perf: Table) -> Table:
    """Raw performance strings → typed columns (loan_id, period_days,
    upb_cents, delinq)."""
    loan = perf[PERF_COLS.index("loan_id")]
    period = S.to_date(perf[PERF_COLS.index("monthly_reporting_period")],
                       "%m/%d/%Y")
    upb = S.to_decimal(perf[PERF_COLS.index("current_actual_upb")], -2)
    # "X" (unknown) parses to null; the demo maps it to -1 before the max
    delinq = fill_null(
        S.to_int64(perf[PERF_COLS.index("current_loan_delinquency_status")]),
        -1)
    return Table([loan, period, upb, delinq])


def _parse_acq(acq: Table) -> Table:
    """Raw acquisition strings → typed columns + categorical codes."""
    loan = acq[ACQ_COLS.index("loan_id")]
    rate = S.to_decimal(acq[ACQ_COLS.index("orig_interest_rate")], -4)
    upb = S.to_int64(acq[ACQ_COLS.index("orig_upb")])
    odate = S.to_date(acq[ACQ_COLS.index("orig_date")], "%Y-%m-%d")
    state_codes, _ = S.dictionary_encode(acq[ACQ_COLS.index("state")])
    seller = acq[ACQ_COLS.index("seller_name")]
    seller_codes, _ = S.dictionary_encode(seller)
    # null seller → code -1 (the demo's "OTHER/unknown" bucket)
    seller_codes = fill_null(
        Column(seller_codes.dtype, seller_codes.data,
               validity=seller.validity), -1)
    return Table([loan, rate, upb, odate, state_codes, seller_codes])


def etl(files: dict[str, bytes]) -> Table:
    """Full pipeline → feature table (FEATURE_COLS order, sorted by loan)."""
    return etl_tables(load_tables(files))


def etl_tables(tables: dict[str, Table]) -> Table:
    """The decode-free plan over loaded tables — separable so the whole
    string-parse/aggregate/join pipeline compiles to ONE program through
    ``models.compiled.compile_query`` (the per-loan parse syncs that made
    the eager pipeline ~300 s at toy scale collapse into the capture
    tape)."""
    perf = _parse_perf(tables["perf"])
    acq = _parse_acq(tables["acq"])

    # per-loan aggregates over the performance records
    agg = groupby_aggregate(
        perf, [0],
        [(3, "max"),     # max delinquency
         (2, "mean"),    # mean UPB (decimal64(-2) → float64 mean of cents)
         (0, "count"),   # record count
         (1, "min")])    # first reporting period
    # columns: loan_id, max_delinq, mean_upb_cents, count, min_period

    joined = inner_join(acq, agg, 0, 0)
    # acq(6) ++ agg(5): drop the duplicate right-side loan_id.  The mean
    # over the decimal64(-2) UPB column is already value-domain dollars
    # (groupby applies the decimal scale to mean/var/std).
    feats = [joined[i] for i in range(6)] + [joined[i] for i in range(7, 11)]
    out = sort_table(Table(feats), [0])
    return out


def feature_spec():
    """The demo's ETL→ML handoff: every numeric ETL output except the loan
    id feeds the model; the label is "severely delinquent"
    (max_delinquency > 2 — the synthetic generator emits delinquency
    grades 2/3, so >2 is the class split that actually separates).
    The returned spec packs ``etl_tables`` output straight into the
    on-device feature matrix."""
    from ..ml.features import Feature, FeatureSpec
    feats = [c for c in FEATURE_COLS
             if c not in ("loan_id", "max_delinquency")]
    return FeatureSpec.of([Feature(c, impute="mean") for c in feats],
                          label="max_delinquency",
                          label_transform=("gt", 2.0))


def feature_matrix(files: dict[str, bytes]):
    """Feature table → dense float32 [n_loans, n_features-1] + loan ids —
    the XGBoost handoff (everything numeric, nulls already absorbed)."""
    import jax.numpy as jnp
    t = etl(files)
    lanes = []
    for c in t.columns[1:]:
        data = cast(c, T.float64).values() if c.dtype.is_decimal else c.values()
        lanes.append(data.astype(jnp.float32))
    return t[0].data, jnp.stack(lanes, axis=1)
