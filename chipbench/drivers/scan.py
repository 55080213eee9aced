"""Closed-loop scans: ``models.q6.run(parquet_bytes, lo, hi)`` from the
same host bytes every call — footer parse, page walk, snappy, upload,
device decode, the f64 boundary and the fused predicate + aggregate.  Work
is counted in Parquet rows taken from host bytes to the answer on the
host."""

from __future__ import annotations

import types

from .. import datagen, references


def setup(config: dict, traffic: dict, seed: int, rec):
    raw, arrays = datagen.tpch_q6_parquet(config["rows"], seed,
                                          config["row_group_rows"])
    state = types.SimpleNamespace(
        raw=raw, arrays=arrays, rows=config["rows"],
        lo=config["date_lo_days"], hi=config["date_hi_days"],
        answers=[], facts={"parquet_bytes": len(raw)})
    for i in range(int(traffic.get("warmup_calls", 2))):
        call(state, 0, i, rec)
    state.answers.clear()
    return state


def call(state, caller: int, i: int, rec) -> float:
    from spark_rapids_jni_tpu.models import q6
    with rec.span("scan"):
        state.answers.append(q6.run(state.raw, state.lo, state.hi))
    return float(state.rows)


def answers(state):
    return list(state.answers)


def control_answers(state, got):
    """q6 in float32 — predicate, product and sum — in the program's place."""
    import numpy as np
    low = references.q6_numpy(state.arrays, state.lo, state.hi,
                              dtype=np.float32)
    return [low] * len(got)


def compare(state, got) -> dict:
    """Every answer of the window against NumPy on the generator arrays: the
    matched-row count exactly, the revenue by its relative gap."""
    want_rev, want_n = references.q6_numpy(state.arrays, state.lo, state.hi)
    count_gap = max((abs(n - want_n) for _, n in got), default=0)
    rev_gap = max((abs(r - want_rev) / abs(want_rev) for r, _ in got),
                  default=0.0)
    return {"matched_rows_gap": {"value": count_gap, "limit": 0},
            "revenue_rel_gap": {"value": rev_gap, "limit": REVENUE_RTOL}}


# set from readings on the chip (PERF.md §2 "limits of correct")
REVENUE_RTOL = 1e-10
