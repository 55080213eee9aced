#!/usr/bin/env python
"""Parquet → gradient-step throughput for the ml/ handoff → ML_BENCH.json.

Two pipelines over the same synthetic parquet file, same model, same batch
schedule:

  device — ``parquet/`` device scan → ``FeatureSpec.pack`` (JCUDF row
           stream reinterpretation, dict-string categoricals stay codes)
           → ``BatchPipeline`` device shuffle → fused-``lax.scan`` epochs
           (ONE dispatch per epoch, zero steady-state host syncs);
  host   — pyarrow decode → pandas/numpy feature pack (the differential
           oracle) → python minibatch loop over numpy SGD steps (the
           classic "pull the query result to the host and train there").

The features must be BIT-IDENTICAL across the two pipelines (the oracle is
the same contract ``tests/test_ml.py`` pins); throughput is end-to-end
rows/s from parquet bytes to the last gradient step.  The premerge gate
expects ``speedup_vs_host ≥ 3`` on CPU CI.

Usage: python tools/ml_bench.py [n_rows] [out.json]
"""

import io
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax

from spark_rapids_jni_tpu.utils import compile_cache

compile_cache.configure(min_compile_secs=0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

EPOCHS = 12
BATCH = 32
N_CATS = 8                       # dict-encoded string features (the usual
MOMENTUM = 0.9                   # fraud/ads feature-table shape)
SEED = 17


def gen_parquet(n: int, seed: int = SEED) -> bytes:
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(seed)
    cols = {}
    for i in range(6):
        cols[f"num{i}"] = rng.normal(size=n)
    for i in range(4):
        cols[f"int{i}"] = rng.integers(-500, 500, n)
    nullable = rng.integers(0, 100, n)
    mask = rng.random(n) < 0.15
    cols["amount"] = pa.array(np.where(mask, 0, nullable),
                              mask=mask, type=pa.int64())
    for i in range(N_CATS):
        vocab = [f"c{i}_{v:03d}" for v in range(16 + 8 * i)]
        cols[f"cat{i}"] = pa.array([vocab[j] for j in rng.integers(
            0, len(vocab), n)]).dictionary_encode()
    z = cols["num0"] - 0.5 * cols["num1"] + 0.01 * cols["int0"]
    cols["label"] = (z + rng.normal(size=n) * 0.3 > 0).astype(np.int64)
    buf = io.BytesIO()
    pq.write_table(pa.table(cols), buf, compression="SNAPPY")
    return buf.getvalue()


NUMERIC = [f"num{i}" for i in range(6)] + [f"int{i}" for i in range(4)]
CATEGORICAL = [f"cat{i}" for i in range(N_CATS)]
FEATURES = NUMERIC + ["amount"] + CATEGORICAL


def host_features(blob: bytes):
    """The numpy oracle: same lane contract as FeatureSpec.pack."""
    import pyarrow.parquet as pq
    tab = pq.read_table(io.BytesIO(blob))
    lanes = []
    for name in NUMERIC:
        lanes.append(np.asarray(tab[name]).astype(np.float32))
    amt = tab["amount"].to_pandas()
    vals = amt.to_numpy(dtype=np.float64, na_value=np.nan)
    valid = ~np.isnan(vals)
    mean = np.float32(vals[valid].sum() / valid.sum())
    lanes.append(np.where(valid, vals.astype(np.float32), mean))
    for name in CATEGORICAL:
        strs = [str(v) for v in tab[name].to_pylist()]
        rank = {v: i for i, v in enumerate(sorted(set(strs)))}
        lanes.append(np.array([rank[v] for v in strs], np.float32))
    X = np.stack(lanes, axis=1)
    y = np.asarray(tab["label"]).astype(np.float32)
    return X, y


def host_train(X, y, epochs: int, batch: int, lr=1e-4, momentum=MOMENTUM):
    """The host-loop baseline: per-epoch numpy shuffle + momentum-SGD
    minibatches — the same math the device trainer runs."""
    rng = np.random.default_rng(SEED)
    n, k = X.shape
    nb = n // batch
    w = np.zeros(k, np.float32)
    b = np.float32(0.0)
    vw = np.zeros(k, np.float32)
    vb = np.float32(0.0)
    lr, mu = np.float32(lr), np.float32(momentum)
    for _ in range(epochs):
        perm = rng.permutation(n)[:nb * batch]
        Xs = X[perm].reshape(nb, batch, k)
        ys = y[perm].reshape(nb, batch)
        for i in range(nb):
            xb, yb = Xs[i], ys[i]
            z = xb @ w + b
            with np.errstate(over="ignore"):        # exp(-z) → inf ⇒ p = 0
                p = np.float32(1.0) / (np.float32(1.0) + np.exp(-z))
            g = (p - yb) / np.float32(batch)
            vw = mu * vw + xb.T @ g
            vb = mu * vb + g.sum(dtype=np.float32)
            w = w - lr * vw
            b = b - lr * vb
    return w, b


def main():
    n_rows = int(sys.argv[1]) if len(sys.argv) > 1 else 80000
    out_path = sys.argv[2] if len(sys.argv) > 2 else "ML_BENCH.json"
    print(f"backend: {jax.default_backend()}  n_rows: {n_rows}", flush=True)

    from spark_rapids_jni_tpu import ml
    from spark_rapids_jni_tpu.ml import features as F
    from spark_rapids_jni_tpu.parquet import device_scan as decode
    from spark_rapids_jni_tpu.utils import syncs

    blob = gen_parquet(n_rows)
    res = {"n_rows": n_rows, "epochs": EPOCHS, "batch": BATCH,
           "parquet_bytes": len(blob)}

    spec = F.FeatureSpec.of(
        [F.Feature(c) for c in NUMERIC]
        + [F.Feature("amount", impute="mean")]
        + [F.Feature(c) for c in CATEGORICAL],
        label="label", label_transform=("gt", 0.0))
    names = FEATURES + ["label"]

    # --- device pipeline: parquet → pack → fused epochs --------------------
    # cold pass: parquet decode + pack + warm epoch all compile here (the
    # persistent .jax_cache amortizes this across runs, mirroring how the
    # mortgage bench reports cold vs steady)
    t0 = time.perf_counter()
    tbl = decode.read_table(blob, columns=names)
    fb = spec.pack(tbl, names)
    fb.X.block_until_ready()
    res["decode_pack_cold_s"] = round(time.perf_counter() - t0, 3)
    pipe = ml.BatchPipeline(fb, batch_size=BATCH, seed=SEED)
    tr = ml.Trainer(ml.logistic_regression(),
                    ml.sgd(lr=1e-4, momentum=MOMENTUM))
    params, ostate = tr.init(pipe.k)
    t0 = time.perf_counter()
    Xb, yb = pipe.epoch_arrays(0)               # warm epoch: compiles
    params, ostate, loss = tr.run_epoch(params, ostate, Xb, yb)
    loss.block_until_ready()
    res["train_cold_s"] = round(time.perf_counter() - t0, 3)

    # steady end-to-end pass: fresh decode → pack → EPOCHS fused epochs,
    # exactly the recurring-training-job path
    t0 = time.perf_counter()
    tbl = decode.read_table(blob, columns=names)
    fb = spec.pack(tbl, names)
    fb.X.block_until_ready()
    decode_pack_s = time.perf_counter() - t0
    res["decode_pack_s"] = round(decode_pack_s, 3)
    pipe = ml.BatchPipeline(fb, batch_size=BATCH, seed=SEED)
    # warm the fresh pipeline's shuffle program (identical shape → persistent
    # cache hit); the recurring job reuses compiled programs, so compile time
    # belongs in the cold numbers, not the steady pass
    wp, wo = tr.init(pipe.k)
    Xb, yb = pipe.epoch_arrays(0)
    jax.block_until_ready(tr.run_epoch(wp, wo, Xb, yb))
    params, ostate = tr.init(pipe.k)
    syncs.reset_sync_count()
    t0 = time.perf_counter()
    for e in range(EPOCHS):
        Xb, yb = pipe.epoch_arrays(e)
        params, ostate, loss = tr.run_epoch(params, ostate, Xb, yb)
    steady_syncs = syncs.sync_count()
    loss.block_until_ready()
    steady_s = time.perf_counter() - t0
    res["steady_syncs"] = steady_syncs
    res["train_steady_s"] = round(steady_s, 3)
    res["final_loss"] = round(float(loss), 5)
    dev_e2e = decode_pack_s + steady_s
    res["device_rows_per_s"] = round(pipe.rows_per_epoch * EPOCHS / dev_e2e)
    print(f"device: decode+pack {res['decode_pack_s']}s (cold "
          f"{res['decode_pack_cold_s']}s)  steady {res['train_steady_s']}s  "
          f"syncs={steady_syncs}  {res['device_rows_per_s']} rows/s",
          flush=True)

    # --- host baseline ------------------------------------------------------
    t0 = time.perf_counter()
    hX, hy = host_features(blob)
    res["host_decode_pack_s"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    host_train(hX, hy, EPOCHS, BATCH)
    host_train_s = time.perf_counter() - t0
    res["host_train_s"] = round(host_train_s, 3)
    host_e2e = res["host_decode_pack_s"] + host_train_s
    res["host_rows_per_s"] = round(
        (hX.shape[0] // BATCH) * BATCH * EPOCHS / host_e2e)
    res["speedup_vs_host"] = round(
        res["device_rows_per_s"] / res["host_rows_per_s"], 2)
    print(f"host: decode+pack {res['host_decode_pack_s']}s  train "
          f"{res['host_train_s']}s  {res['host_rows_per_s']} rows/s  "
          f"speedup {res['speedup_vs_host']}x", flush=True)

    # --- bit-identity gate --------------------------------------------------
    res["features_bit_identical"] = bool(
        np.array_equal(np.asarray(fb.X), hX)
        and np.array_equal(np.asarray(fb.y),
                           (hy > 0).astype(np.float32)))
    print(f"features bit-identical: {res['features_bit_identical']}",
          flush=True)

    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
    print("wrote", out_path, flush=True)
    if not res["features_bit_identical"]:
        sys.exit(1)
    if res["steady_syncs"] != 0:
        sys.exit(1)


if __name__ == "__main__":
    main()
