#!/usr/bin/env python
"""Per-stage transcode profiling on the real chip → PROFILE_transcode.json.

VERDICT r2 weak #1: 3.77 GB/s driver round-trip vs a 70-110 GB/s elementwise
ceiling, with no per-stage breakdown.  This script answers "where does the
time go" with honest device timing:

* every measurement is a dependency-chained ``fori_loop`` inside ONE jit with
  one tiny D2H at the end;
* the fixed dispatch+sync overhead is removed exactly by differencing two
  trip counts of the SAME jitted
  loop: t(N_HI) - t(N_LO) over (N_HI - N_LO) iterations.

Measured stages:
  1. sync/dispatch floor (empty body)
  2. elementwise u32 ceiling, XLA and Pallas HBM copy
  3. interleave variants  (u32 [W, n] -> flat [n*W], JCUDF word order)
  4. deinterleave variants (flat -> [W, n])
  5. u8<->u32 lane conversion
  6. current full to_rows / from_rows / round trip at the bench schema

Usage: python tools/profile_transcode.py [out.json]
"""

import functools
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
from jax import lax

RESULTS = {"backend": None, "stages": []}
N_LO, N_HI = 5, 45


def _loop(body):
    """jit(data, iters) running ``body(data)`` chained ``iters`` times."""
    @jax.jit
    def run(data, iters):
        def step(_, carry):
            acc, data_ = carry
            d = lax.optimization_barrier((data_, acc))[0]
            out = body(d)
            out = lax.optimization_barrier(out)
            leaf = jax.tree_util.tree_leaves(out)[0]
            probe = lax.convert_element_type(jnp.ravel(leaf)[0], jnp.int32)
            return (acc + probe) % jnp.int32(65521), data_
        acc, _ = lax.fori_loop(0, iters, step, (jnp.int32(0), data))
        return acc
    return run


def measure(name, body, data, nbytes, note=""):
    """Record per-iteration device seconds and GB/s for ``body``."""
    run = _loop(body)
    try:
        np.asarray(run(data, N_LO))          # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(data, N_LO))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(run(data, N_HI))
        t_hi = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        RESULTS["stages"].append({"name": name, "error": repr(e)[:300]})
        print(f"  FAIL {name}: {e!r}"[:200], flush=True)
        return None
    per_iter = max((t_hi - t_lo) / (N_HI - N_LO), 1e-9)
    gbps = nbytes / per_iter / 1e9
    RESULTS["stages"].append({
        "name": name, "per_iter_ms": round(per_iter * 1e3, 3),
        "gbps": round(gbps, 2), "nbytes": nbytes,
        "t_lo_s": round(t_lo, 4), "t_hi_s": round(t_hi, 4), "note": note,
    })
    print(f"  {name}: {per_iter*1e3:.3f} ms/iter  {gbps:.2f} GB/s  {note}",
          flush=True)
    return per_iter


# ---------------------------------------------------------------------------
# interleave / deinterleave variants.  Contract: x is u32 [W, n] (words
# stacked, n multiple of 128); output is the flat JCUDF word stream
# out[r*W + w] = x[w, r], shape [n*W] (or a wide-minor 2-D view of it).
# ---------------------------------------------------------------------------

def il_strided(x):
    W, n = x.shape
    out = jnp.zeros((n // 128, 128 * W), jnp.uint32)
    for w in range(W):
        out = out.at[:, w::W].set(x[w].reshape(n // 128, 128))
    return out


def il_transpose(x):
    return x.T.reshape(-1)


def il_perm3(x):
    W, n = x.shape
    return x.reshape(W, n // 128, 128).transpose(1, 2, 0).reshape(
        n // 128, 128 * W)


def _mk_il_pallas(kind, tr):
    from jax.experimental import pallas as pl

    def f(x):
        W, n = x.shape

        def kernel(x_ref, o_ref):
            xb = x_ref[...]                       # [W, tr]
            if kind == "transpose":
                o_ref[...] = xb.T.reshape(tr // 128, 128 * W)
            else:                                 # strided lane writes
                o = jnp.zeros((tr // 128, 128 * W), jnp.uint32)
                for w in range(W):
                    o = o.at[:, w::W].set(xb[w].reshape(tr // 128, 128))
                o_ref[...] = o

        return pl.pallas_call(
            kernel,
            grid=(n // tr,),
            in_specs=[pl.BlockSpec((W, tr), lambda i: (0, i))],
            out_specs=pl.BlockSpec((tr // 128, 128 * W), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n // 128, 128 * W), jnp.uint32),
        )(x)
    return f


def dl_strided(flat_w):
    def f(x2):
        n128, lanes = x2.shape
        W = lanes // 128
        return jnp.stack([x2[:, w::W].reshape(-1) for w in range(W)])
    return f(flat_w)


def dl_transpose_fn(W):
    def f(flat):
        return flat.reshape(-1, W).T
    return f


def dl_perm3_fn(W):
    def f(x2):
        n128 = x2.shape[0]
        return x2.reshape(n128, 128, W).transpose(2, 0, 1).reshape(W, -1)
    return f


def _mk_dl_pallas(tr, W):
    from jax.experimental import pallas as pl

    def f(x2):
        n128 = x2.shape[0]
        n = n128 * 128

        def kernel(x_ref, o_ref):
            xb = x_ref[...]                       # [tr//128, 128W]
            o_ref[...] = xb.reshape(tr, W).T      # [W, tr]

        return pl.pallas_call(
            kernel,
            grid=(n // tr,),
            in_specs=[pl.BlockSpec((tr // 128, 128 * W), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((W, tr), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((W, n), jnp.uint32),
        )(x2)
    return f


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else "PROFILE_transcode.json"
    RESULTS["backend"] = jax.default_backend()
    print(f"backend: {RESULTS['backend']}", flush=True)
    rng = np.random.default_rng(0)

    # 1. floor
    measure("floor_empty", lambda d: d, jnp.zeros((8, 128), jnp.uint32), 0)

    # 2. ceilings
    n_ew = 1 << 24                                # 64 MiB u32
    big = jnp.asarray(rng.integers(0, 2**32, n_ew, dtype=np.uint32))
    measure("xla_elementwise_u32", lambda x: x * jnp.uint32(3) + jnp.uint32(1),
            big, 2 * 4 * n_ew, "read+write counted")

    from jax.experimental import pallas as pl

    def pallas_copy(x):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        blk = 1 << 16
        return pl.pallas_call(
            kernel, grid=(x.shape[0] // blk,),
            in_specs=[pl.BlockSpec((blk,), lambda i: (i,))],
            out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
    measure("pallas_copy_u32", pallas_copy, big, 2 * 4 * n_ew)

    # 3./4. interleave / deinterleave, bench-like W and wide W
    n = 1 << 20
    for W in (11, 53):
        x = jnp.asarray(rng.integers(0, 2**32, (W, n), dtype=np.uint32))
        flat2 = jnp.asarray(
            rng.integers(0, 2**32, (n // 128, 128 * W), dtype=np.uint32))
        nbytes = 2 * 4 * n * W
        measure(f"il_strided_W{W}", il_strided, x, nbytes)
        measure(f"il_transpose_W{W}", il_transpose, x, nbytes)
        measure(f"il_perm3_W{W}", il_perm3, x, nbytes)
        for tr in (2048, 8192):
            measure(f"il_pallas_T_W{W}_tr{tr}",
                    _mk_il_pallas("transpose", tr), x, nbytes)
        measure(f"il_pallas_S_W{W}_tr2048", _mk_il_pallas("strided", 2048),
                x, nbytes)
        measure(f"dl_strided_W{W}", dl_strided, flat2, nbytes)
        measure(f"dl_transpose_W{W}", dl_transpose_fn(W),
                flat2.reshape(-1), nbytes)
        measure(f"dl_perm3_W{W}", dl_perm3_fn(W), flat2, nbytes)
        measure(f"dl_pallas_W{W}_tr2048", _mk_dl_pallas(2048, W), flat2,
                nbytes)

    # 5. u8<->u32
    from spark_rapids_jni_tpu.rowconv import ragged
    nb8 = 1 << 26
    b8 = jnp.asarray(rng.integers(0, 256, nb8, dtype=np.uint8))
    w32 = jnp.asarray(rng.integers(0, 2**32, nb8 // 4, dtype=np.uint32))
    measure("u8_to_u32", ragged.u8_to_u32, b8, 2 * nb8)
    measure("u32_to_u8", ragged.u32_to_u8, w32, 2 * nb8)

    # 5b. fixed-path compose breakdown (VERDICT r5 task: which stage of
    # _to_rows_fixed_words eats the gap between the 343 GB/s interleave
    # ceiling and the ~30 GB/s public path?)
    import bench as bench_mod_
    from spark_rapids_jni_tpu.rowconv import convert as cv
    from spark_rapids_jni_tpu.rowconv.layout import compute_row_layout
    tbl_c = bench_mod_.build_table(1_000_000, 12)
    lay_c = compute_row_layout(tbl_c.schema)
    Wc = lay_c.fixed_row_size // 4
    nrows = tbl_c.num_rows
    datas_c = tuple(c.data for c in tbl_c.columns)
    valid_c = jnp.stack([c.validity_or_true() for c in tbl_c.columns],
                        axis=1)
    row_bytes_c = nrows * lay_c.fixed_row_size

    def stage_only(a):
        ds = a
        return tuple(cv._stage_column_dt(d, dt)
                     for d, dt in zip(ds, lay_c.schema))
    measure("fx_stage_columns", stage_only, datas_c, row_bytes_c,
            f"per-column bitcast staging, W={Wc}")

    def vbytes_only(a):
        v = a
        outs = []
        for k in range(lay_c.validity_bytes):
            acc = jnp.zeros((nrows,), jnp.uint32)
            for i in range(min(8, lay_c.num_columns - k * 8)):
                acc = acc | (v[:, k * 8 + i].astype(jnp.uint32)
                             << jnp.uint32(i))
            outs.append(acc)
        return tuple(outs)
    measure("fx_validity_bytes", vbytes_only, valid_c, nrows * 2)

    staged_pre = tuple(cv._stage_column_dt(d, dt)
                       for d, dt in zip(datas_c, lay_c.schema))

    def compose_only(a):
        st = a
        plan = cv._word_plan(lay_c)
        words = []
        for w in range(Wc):
            acc = None
            for ii, kind, arg in plan[w]:
                if kind == "vbyte":
                    continue
                x = st[ii]
                v = (x if kind == "full"
                     else x[:, arg] if kind == "pair"
                     else x << jnp.uint32(arg * 8))
                acc = v if acc is None else acc | v
            words.append(acc if acc is not None
                         else jnp.zeros((nrows,), jnp.uint32))
        return tuple(words)
    measure("fx_compose_words", compose_only, staged_pre, row_bytes_c,
            "from pre-staged arrays (no bitcasts)")

    def whole_words(a):
        ds, v = a
        return cv._to_rows_fixed_words(lay_c, ds, v)
    measure("fx_to_rows_words_full", whole_words, (datas_c, valid_c),
            row_bytes_c, "stage+compose+interleave")

    from spark_rapids_jni_tpu import convert_to_rows as _ctr
    b0_c = _ctr(tbl_c)[0]

    def decode_words(a):
        return cv._from_rows_fixed_words(lay_c, a)
    measure("fx_from_rows_words_full", decode_words, b0_c.data,
            row_bytes_c, "deinterleave+decode")

    # 6. current public path at the bench schema (reuse section 5b's table)
    table = tbl_c
    from spark_rapids_jni_tpu import convert_to_rows, convert_from_rows
    from spark_rapids_jni_tpu.column import Column, Table as _Table

    batches0 = convert_to_rows(table)
    row_bytes = sum(b.num_bytes for b in batches0)
    schema = table.schema

    def to_rows_body(tbl):
        return convert_to_rows(tbl)[0].data
    measure("current_to_rows_1M", to_rows_body, table, row_bytes,
            "row bytes counted once")

    def from_rows_body(batch):
        t = convert_from_rows(batch, schema)
        return t.columns[0].data
    measure("current_from_rows_1M", from_rows_body, batches0[0], row_bytes,
            "row bytes counted once")

    def rt_body(tbl):
        b = convert_to_rows(tbl)[0]
        t = convert_from_rows(b, schema)
        return t.columns[0].data
    measure("current_roundtrip_1M", rt_body, table, 2 * row_bytes,
            "row bytes counted per direction (bench metric)")

    with open(out_path, "w") as f:
        json.dump(RESULTS, f, indent=1)
    print(f"wrote {out_path}", flush=True)


if __name__ == "__main__":
    main()
