"""Closed-loop SQL streams through ``QueryScheduler.submit_sql`` over tables
resident on the chip: front end, planner, plan cache, checked replay, ops.
Each stream alternates the configuration's queries; half of the streams
start on the first query and half on the second, which ones from the seed.
Work is counted in queries answered."""

from __future__ import annotations

import io
import sys
import threading
import time
import types

import numpy as np

from .. import datagen, references

SUM_COLUMN = "ss_ext_sales_price"


def _block(tree):
    import jax
    jax.block_until_ready(jax.tree_util.tree_leaves(tree))


def _note(what: str, t0: float):
    print(f"sql setup: {what} {time.perf_counter() - t0:.1f} s",
          file=sys.stderr, flush=True)


def setup(config: dict, traffic: dict, seed: int, rec):
    from spark_rapids_jni_tpu import exec as xc
    from spark_rapids_jni_tpu.models import tpcds
    from spark_rapids_jni_tpu.utils import metrics
    t0 = time.perf_counter()
    # the data set is the configuration's (as dbgen's is at a scale factor):
    # join and group sizes, and with them every compiled shape, are the same
    # in every run; the seed orders the fact's rows and places the streams
    files = datagen.tpcds_star_parquet(
        config["sales_rows"], config["items"], config["stores"],
        config["data_seed"], config["dates"], order_seed=seed)
    _note("generated", t0)
    tables = tpcds.load_tables(files)
    _block(tables)
    _note("tables on the chip", t0)
    queries = list(config["queries"])
    callers = int(traffic["callers"])
    first = np.zeros(callers, int)
    first[np.random.default_rng(seed).permutation(callers)[:callers // 2]] = 1
    # max_batch 1: every request is a launch of its own.  Streams of the
    # spec's throughput test differ in their substitution parameters, so no
    # two of their requests are one request; four streams sending the same
    # two texts would otherwise coalesce, and lock into phase (PERF.md).
    sched = xc.QueryScheduler(workers=int(traffic["workers"]),
                              max_batch=int(traffic["max_batch"]))
    sched.__enter__()
    state = types.SimpleNamespace(
        files=files, tables=tables, sched=sched, queries=queries,
        params=config["params"], first=first.tolist(), answers=[],
        lock=threading.Lock(), frames=None, facts={})
    for qi, q in enumerate(queries):
        for _ in range(int(traffic["warmup_max_submits"])):
            hits = metrics.counter_value("exec.plan_cache.hit")
            _submit(state, q, rec)
            _note(f"{q} warm-up call", t0)
            if metrics.counter_value("exec.plan_cache.hit") > hits:
                break
        else:
            raise RuntimeError(f"{q}: the plan cache never hit in warm-up")
    state.answers.clear()
    return state


def _submit(state, q: str, rec):
    from spark_rapids_jni_tpu.models import tpcds_sql as TS
    with rec.span("query:" + q):
        out = state.sched.submit_sql(
            TS.SQL[q], state.tables, schemas=TS.TABLE_SCHEMAS,
            params=state.params[q]).result()
        _block(out)
    with state.lock:
        state.answers.append((q, out))


def call(state, caller: int, i: int, rec) -> float:
    _submit(state, state.queries[(state.first[caller] + i)
                                 % len(state.queries)], rec)
    return 1.0


def _host_answer(out, n_keys: int):
    keys = [(out[i].to_pylist() if out[i].dtype.id.name == "STRING"
             else out[i].to_numpy().tolist()) for i in range(n_keys)]
    return keys, np.asarray(out[n_keys].to_numpy(), np.float64)


def answers(state):
    """Every answer of the window, on the host.  Stops the scheduler and
    frees the tables."""
    got = [(q, _host_answer(out, 3)) for q, out in state.answers]
    state.sched.__exit__(None, None, None)
    state.tables = state.answers = None
    return got


def _twins(state, dtype):
    import pandas as pd
    if state.frames is None:
        state.frames = {k: pd.read_parquet(io.BytesIO(v))
                        for k, v in state.files.items() if k != "store"}
    return {q: references.SQL_TWINS[q](state.frames, dtype=dtype,
                                       **state.params[q])
            for q in state.queries}


def control_answers(state, got):
    """The pandas twins summing in float32, in the program's place."""
    low = _twins(state, np.float32)
    return [(q, ([low[q][k].tolist() for k in low[q].columns[:3]],
                 low[q][SUM_COLUMN].to_numpy(np.float64)))
            for q, _ in got]


def compare(state, got) -> dict:
    """Against the pandas twins: answers whose rows or keys differ, and the
    widest relative gap of a sum."""
    twin = _twins(state, np.float64)
    key_gap, sum_gap = 0, 0.0
    for q, (keys, sums) in got:
        want = twin[q]
        want_keys = [want[k].tolist() for k in want.columns[:3]]
        if keys != want_keys or len(sums) != len(want):
            key_gap += 1
            continue
        ref = want[SUM_COLUMN].to_numpy()
        if len(ref):
            gap = np.abs(sums - ref) / np.abs(ref)
            sum_gap = max(sum_gap, float(np.max(np.where(
                np.isfinite(gap), gap, np.inf))))
    return {"answers_with_wrong_keys": {"value": key_gap, "limit": 0},
            "sum_rel_gap": {"value": sum_gap, "limit": SUM_RTOL}}


# set from readings on the chip (PERF.md §2 "limits of correct")
SUM_RTOL = 1e-10
