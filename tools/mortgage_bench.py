#!/usr/bin/env python
"""Mortgage ETL timing → MORTGAGE_BENCH.json.

Round-3 state: the eager pipeline spent ~300 s producing a (300, 9)
feature matrix — per-loan string-parse syncs and eager dispatches.
Round 4 compiles the whole decode-free plan
(``models.mortgage.etl_tables``) into ONE program via the capture/replay
machinery (``models/compiled.py``), so the steady state is a single
dispatch.  Reported:

  decode_s   — parquet → device tables (host staging + upload)
  cold_s     — eager capture run (records the sync tape) + fused compile
  warm_s     — one-dispatch re-execution, wall incl. result pull
  steady_ms  — trip-count-differenced in-jit time per execution

The tail extends the demo end-to-end into a trained model (the ``ml/``
handoff): the ETL output packs into an on-device feature matrix
(``models.mortgage.feature_spec``), a logistic "ever delinquent" model
trains through the fused-epoch harness (``train_rows_per_s``), and the
final loss is checked against a sklearn logistic-regression reference on
the identical standardized features (``sklearn_parity``).

Usage: python tools/mortgage_bench.py [n_loans] [out.json]
"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax

from spark_rapids_jni_tpu.utils import compile_cache

compile_cache.configure(min_compile_secs=0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main():
    n_loans = int(sys.argv[1]) if len(sys.argv) > 1 else 300
    out_path = sys.argv[2] if len(sys.argv) > 2 else "MORTGAGE_BENCH.json"
    print(f"backend: {jax.default_backend()}  n_loans: {n_loans}",
          flush=True)

    from benchmarks import mortgage_data
    from spark_rapids_jni_tpu.models import mortgage
    from spark_rapids_jni_tpu.models.compiled import compile_query
    from spark_rapids_jni_tpu.utils import syncs
    from tools.query_bench import steady_per_iter

    files = mortgage_data.generate(n_loans=n_loans, seed=11)
    res = {"n_loans": n_loans}

    t0 = time.perf_counter()
    tables = mortgage.load_tables(files)
    for t in tables.values():
        for c in t.columns:
            np.asarray(c.data[:1])
    res["decode_s"] = round(time.perf_counter() - t0, 2)
    print(f"decode: {res['decode_s']}s", flush=True)

    syncs.reset_sync_count()
    t0 = time.perf_counter()
    cq = compile_query(mortgage.etl_tables, tables)
    jax.block_until_ready([c.data for c in cq.expected.columns])
    np.asarray(cq.expected[0].data[:1])
    res["cold_s"] = round(time.perf_counter() - t0, 2)
    res["cold_syncs"] = syncs.reset_sync_count()
    print(f"cold: {res['cold_s']}s  syncs={res['cold_syncs']}", flush=True)

    out = cq.run(tables)           # compile the fused + size programs
    np.asarray(out[0].data[:1])
    syncs.reset_sync_count()
    t0 = time.perf_counter()
    out = cq.run(tables)           # checked: staleness guard sync included
    jax.block_until_ready([c.data for c in out.columns])
    np.asarray(out[0].data[:1])
    res["warm_s"] = round(time.perf_counter() - t0, 3)
    res["warm_syncs"] = syncs.reset_sync_count()
    t0 = time.perf_counter()
    out = cq.run_unchecked(tables)  # the one-dispatch steady form
    jax.block_until_ready([c.data for c in out.columns])
    np.asarray(out[0].data[:1])
    res["warm_unchecked_s"] = round(time.perf_counter() - t0, 3)
    res["rows_out"] = out.num_rows
    print(f"warm: {res['warm_s']}s  syncs={res['warm_syncs']}  "
          f"rows={res['rows_out']}", flush=True)

    per = steady_per_iter(cq._prog, tables)
    res["steady_ms"] = round(per * 1e3, 1) if per is not None else None
    print(f"steady: {res['steady_ms']} ms", flush=True)

    # --- ETL → trained model: the ml/ handoff on the ETL output ------------
    import jax.numpy as jnp
    from spark_rapids_jni_tpu import ml

    spec = mortgage.feature_spec()
    t0 = time.perf_counter()
    fb = spec.pack(out, mortgage.FEATURE_COLS)
    fb.X.block_until_ready()
    res["pack_s"] = round(time.perf_counter() - t0, 3)

    # standardize on-device (dollar/day-scale lanes would swamp the logits);
    # sklearn sees the identical standardized matrix
    mean = jnp.mean(fb.X, axis=0)
    std = jnp.maximum(jnp.std(fb.X, axis=0), jnp.float32(1e-6))
    fb = ml.FeatureBatch((fb.X - mean) / std, fb.y, fb.feature_names)

    epochs = 300
    pipe = ml.BatchPipeline(fb, batch_size=32, seed=11)
    tr = ml.Trainer(ml.logistic_regression(), ml.sgd(lr=0.5, momentum=0.9))
    fit = tr.fit(pipe, 2)          # warm the shuffle + fused-epoch programs
    syncs.reset_sync_count()
    t0 = time.perf_counter()
    fit = tr.fit(pipe, epochs)
    train_s = time.perf_counter() - t0
    res["train_s"] = round(train_s, 3)
    res["train_epochs"] = epochs
    res["train_syncs"] = syncs.reset_sync_count()
    res["train_rows_per_s"] = round(pipe.rows_per_epoch * epochs / train_s)
    res["final_loss"] = round(fit.final_loss, 5)
    print(f"train: {res['train_s']}s  {res['train_rows_per_s']} rows/s  "
          f"loss={res['final_loss']}  syncs={res['train_syncs']}", flush=True)

    try:
        from sklearn.linear_model import LogisticRegression
        from sklearn.metrics import log_loss
        hX, hy = np.asarray(fb.X), np.asarray(fb.y)
        ref = LogisticRegression(penalty=None, max_iter=2000).fit(hX, hy)
        res["sklearn_loss"] = round(
            float(log_loss(hy, ref.predict_proba(hX))), 5)
        res["sklearn_parity"] = bool(
            res["final_loss"] <= res["sklearn_loss"] * 1.1 + 0.02)
        print(f"sklearn: loss={res['sklearn_loss']}  "
              f"parity={res['sklearn_parity']}", flush=True)
    except ImportError:            # sklearn is optional on minimal images
        res["sklearn_loss"] = None
        res["sklearn_parity"] = None

    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
    print("wrote", out_path, flush=True)


if __name__ == "__main__":
    main()
