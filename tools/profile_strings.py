#!/usr/bin/env python
"""String-transcode formulation shootout on the real chip → PROFILE_strings.json.

VERDICT r3 next-step #1: the var-width path is ~2000× off the fixed path
(0.013-0.042 GB/s wall vs 27.9+).  The round-3 design moved bytes with the
ragged DMA engine, whose per-segment cost is O(staged window) — at the bench
geometry (11-byte strings, 125-byte rows) that is ~50× write amplification —
and whose host-side geometry prep uploads MBs of metadata per call.
The round-4 redesign is a single-jit gather/roll
formulation; this script measures every candidate primitive so the chosen
formulation is evidence-based (same methodology as profile_transcode.py:
dependency-chained fori_loop, trip-count differenced).

Stages measured:
  1. per-element 1D gather, u8 and u32, sorted and random indices
  2. row-gather of [*, 128] u32 blocks (512B granularity)
  3. vmap'd dynamic_slice window gather (8/32-word windows per row)
  4. take_along_axis in-row gather [n, 32]
  5. within-row variable roll via log-shift select tree [n, 32]
  6. marker-cumsum segment_of at pack scale
  7. ragged engine at TINY segments (the bench geometry) for comparison
  8. candidate fused pack: out32[q] = dense_flat[q + delta[row_of[q]]]

Usage: python tools/profile_strings.py [out.json]
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
N_LO, N_HI = 3, 13
OUT_PATH = "PROFILE_strings.json"


def _flush():
    with open(OUT_PATH, "w") as f:
        json.dump(RESULTS, f, indent=1)


def _loop(body):
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


def measure(name, body, data, nbytes, note="", n_elems=None):
    run = _loop(body)
    try:
        np.asarray(run(data, N_LO))          # compile + warm
        t0 = time.perf_counter()
        np.asarray(run(data, N_LO))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(run(data, N_HI))
        t_hi = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001
        RESULTS["stages"].append({"name": name, "error": repr(e)[:300]})
        _flush()
        print(f"  FAIL {name}: {e!r}"[:200], flush=True)
        return None
    per_iter = (t_hi - t_lo) / (N_HI - N_LO)
    if per_iter <= 0:
        RESULTS["stages"].append({"name": name, "error": "nonpositive delta",
                                  "t_lo_s": t_lo, "t_hi_s": t_hi})
        _flush()
        print(f"  NOISY {name}: t_lo={t_lo:.3f} t_hi={t_hi:.3f}", flush=True)
        return None
    gbps = nbytes / per_iter / 1e9
    rec = {"name": name, "per_iter_ms": round(per_iter * 1e3, 3),
           "gbps": round(gbps, 2), "nbytes": nbytes, "note": note}
    if n_elems:
        rec["gelems_per_s"] = round(n_elems / per_iter / 1e9, 4)
    RESULTS["stages"].append(rec)
    _flush()
    extra = f"  {rec.get('gelems_per_s','')} Gelem/s" if n_elems else ""
    print(f"  {name}: {per_iter*1e3:.3f} ms/iter  {gbps:.2f} GB/s{extra}  "
          f"{note}", flush=True)
    return per_iter


def main():
    global OUT_PATH
    if len(sys.argv) > 1:
        OUT_PATH = sys.argv[1]   # incremental flushes must hit the same file
    RESULTS["backend"] = jax.default_backend()
    print(f"backend: {RESULTS['backend']}", flush=True)
    rng = np.random.default_rng(0)

    # --- 1. per-element 1D gather -----------------------------------------
    NSRC = 1 << 25                       # 32M
    src32 = jnp.asarray(rng.integers(0, 2**32, NSRC, dtype=np.uint32))
    src8 = jnp.asarray(rng.integers(0, 256, NSRC, dtype=np.uint8))
    NIDX = 1 << 23                       # 8M indices
    idx_sorted = jnp.asarray(np.sort(rng.integers(0, NSRC, NIDX)).astype(np.int32))
    idx_rand = jnp.asarray(rng.integers(0, NSRC, NIDX).astype(np.int32))
    # near-affine sorted indices (the pack pattern: idx = q + small delta)
    q = np.arange(NIDX, dtype=np.int64)
    idx_affine = jnp.asarray((q + np.minimum(q // 37, NSRC - NIDX - 1))
                             .astype(np.int32))

    for nm, idx in [("sorted", idx_sorted), ("rand", idx_rand),
                    ("affine", idx_affine)]:
        measure(f"gather_u32_{nm}", lambda i, s=src32: s[i], idx,
                NIDX * 4 * 2, n_elems=NIDX)
    measure("gather_u8_sorted", lambda i, s=src8: s[i], idx_sorted,
            NIDX * 2, n_elems=NIDX)

    # --- 2. row-gather of [*, 128] blocks ---------------------------------
    src2d = src32.reshape(-1, 128)        # [256K, 128]
    ridx = jnp.asarray(np.sort(rng.integers(0, src2d.shape[0], 1 << 17))
                       .astype(np.int32))
    measure("rowgather_512B", lambda i, s=src2d: s[i], ridx,
            (1 << 17) * 512 * 2, n_elems=1 << 17)
    # [*, 8] rows (32B granularity)
    src2d8 = src32.reshape(-1, 8)
    ridx8 = jnp.asarray(np.sort(rng.integers(0, src2d8.shape[0], 1 << 21))
                        .astype(np.int32))
    measure("rowgather_32B", lambda i, s=src2d8: s[i], ridx8,
            (1 << 21) * 32 * 2, n_elems=1 << 21)

    # --- 3. vmap'd dynamic_slice window gather ----------------------------
    NROW = 1 << 20
    starts = jnp.asarray(np.sort(rng.integers(0, NSRC - 64, NROW))
                         .astype(np.int32))

    def win_gather(W):
        def f(st, s=src32):
            return jax.vmap(
                lambda o: lax.dynamic_slice(s, (o,), (W,)))(st)
        return f
    measure("winslice_8w", win_gather(8), starts, NROW * 32 * 2,
            n_elems=NROW * 8)
    measure("winslice_32w", win_gather(32), starts, NROW * 128 * 2,
            n_elems=NROW * 32)

    # --- 4./5. in-row gather and log-shift roll ---------------------------
    M = 32
    x_nm = jnp.asarray(rng.integers(0, 2**32, (NROW, M), dtype=np.uint32))
    shift = jnp.asarray(rng.integers(0, M, NROW).astype(np.int32))
    ridx_in = jnp.asarray(rng.integers(0, M, (NROW, M)).astype(np.int32))

    def tala(i, x=x_nm):
        return jnp.take_along_axis(x, i, axis=1)
    measure("take_along_axis_32", tala, ridx_in, NROW * M * 4 * 2,
            n_elems=NROW * M)

    def logshift(s, x=x_nm):
        # right-shift each row by s[r] words: out[r, k] = x[r, k - s[r]]
        out = x
        for b in range(5):                     # log2(32)
            sh = 1 << b
            shifted = jnp.pad(out, ((0, 0), (sh, 0)))[:, :M]
            bit = ((s >> b) & 1).astype(bool)[:, None]
            out = jnp.where(bit, shifted, out)
        return out
    measure("logshift_roll_32", logshift, shift, NROW * M * 4 * 2,
            "5 select passes")

    # --- 6. marker-cumsum segment_of --------------------------------------
    TOT = 1 << 25
    seg_starts = np.sort(rng.integers(0, TOT, 1 << 20)).astype(np.int32)
    seg_starts = jnp.asarray(np.concatenate(
        [[0], seg_starts, [TOT]]).astype(np.int32))

    def segof(st):
        markers = jnp.zeros((TOT,), jnp.int32).at[st[1:-1]].add(1)
        return jnp.cumsum(markers)
    measure("segment_of_32M", segof, seg_starts, TOT * 4 * 2,
            "marker scatter + cumsum")

    # --- 7. ragged engine at bench-tiny segments --------------------------
    from spark_rapids_jni_tpu.rowconv import ragged
    if ragged.dma_supported():
        n_seg = 1 << 20
        lens = rng.integers(0, 25, n_seg)     # 0..24B strings (bench mix)
        offs = np.zeros(n_seg + 1, dtype=np.int64)
        np.cumsum(lens, out=offs[1:])
        total = int(offs[-1])
        chars = jnp.asarray(rng.integers(0, 256, total, dtype=np.uint8))
        t0 = time.perf_counter()
        r = ragged.unpack(chars, offs, 32)
        np.asarray(r[:1, :1])
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        r = ragged.unpack(chars, offs, 32)
        np.asarray(r[:1, :1])
        t2 = time.perf_counter() - t0
        RESULTS["stages"].append({
            "name": "ragged_unpack_tiny_wall", "cold_s": round(t1, 3),
            "warm_s": round(t2, 3),
            "gbps_warm": round(total / t2 / 1e9, 3),
            "note": f"{n_seg} segs avg {total/n_seg:.1f}B — wall incl host prep"})
        print(f"  ragged_unpack_tiny: cold {t1:.2f}s warm {t2:.2f}s "
              f"({total/t2/1e9:.3f} GB/s)", flush=True)

        dense = jnp.asarray(rng.integers(0, 256, (n_seg, 32), dtype=np.uint8))
        ro = np.zeros(n_seg + 1, dtype=np.int64)
        np.cumsum(rng.integers(8, 33, n_seg), out=ro[1:])
        t0 = time.perf_counter()
        p = ragged.pack(dense, ro)
        np.asarray(p[:1])
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        p = ragged.pack(dense, ro)
        np.asarray(p[:1])
        t2 = time.perf_counter() - t0
        RESULTS["stages"].append({
            "name": "ragged_pack_tiny_wall", "cold_s": round(t1, 3),
            "warm_s": round(t2, 3),
            "gbps_warm": round(int(ro[-1]) / t2 / 1e9, 3),
            "note": "1M rows avg 20B packed — wall incl host prep"})
        print(f"  ragged_pack_tiny: cold {t1:.2f}s warm {t2:.2f}s", flush=True)

    # --- 8. candidate fused pack ------------------------------------------
    # rows of Mw=32 words packed to ~20 words each: out[q] = flat[q + d[row_of[q]]]
    n_rows = 1 << 20
    Mw = 32
    lens_w = rng.integers(8, 33, n_rows)
    offw = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(lens_w, out=offw[1:])
    TOTW = int(offw[-1])
    delta_np = (np.arange(n_rows, dtype=np.int64) * Mw - offw[:-1]).astype(np.int32)
    dense_flat = jnp.asarray(rng.integers(0, 2**32, n_rows * Mw,
                                          dtype=np.uint32))
    offw_dev = jnp.asarray(offw.astype(np.int32))
    delta_dev = jnp.asarray(delta_np)

    def fused_pack(args):
        flat, offs, delta = args
        markers = jnp.zeros((TOTW,), jnp.int32).at[offs[1:-1]].add(1)
        row_of = jnp.cumsum(markers)
        qq = jnp.arange(TOTW, dtype=jnp.int32)
        return flat[qq + delta[row_of]]
    measure("fused_pack_gather", fused_pack,
            (dense_flat, offw_dev, delta_dev), TOTW * 4 * 2,
            f"{n_rows} rows, segment_of + affine gather", n_elems=TOTW)

    _flush()
    print(f"wrote {OUT_PATH}", flush=True)


if __name__ == "__main__":
    main()
