#!/usr/bin/env python
"""The two rates `ops.join_plan.COMPARE_PROBE_MAX_KEYS` stands on.

On the chip, over a 10M-row int32 probe and a sorted build of n keys drawn
from a span of 20k ids (TPC-DS q42's shape at n = 212; 4n where n is
larger), times the two ways `probe_counts` probes a sorted index:

  bsearch — the two default `jnp.searchsorted` (a scan of ceil(log2(n+1))
            levels, each a probe-length gather from the key table): ns per
            row per gather, `g`
  compare — `join_plan._probe_compare`: ns per build key per row, `c`

and prints one JSON line per (n, dtype), then the n where the two cost the
same at the rates of the largest table measured.  TPU only: a CPU run says
nothing about either rate.

Usage: python tools/probe_rates.py [n_probe] [out.json]
"""

import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, ".")

KEY_COUNTS = (18, 32, 64, 128, 129, 212, 1024, 4096, 16384, 32768, 65536)


def _median_ms(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))          # compile + warm
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out)


def main(argv):
    n_probe = int(argv[1]) if len(argv) > 1 else 10_000_000
    out_path = argv[2] if len(argv) > 2 else "chiprun_out/probe_rates.json"
    import jax
    import jax.numpy as jnp
    import numpy as np
    import spark_rapids_jni_tpu  # noqa: F401  (x64 on, as the program runs)
    from spark_rapids_jni_tpu.ops import join_plan

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"probe_rates: needs a TPU, found {dev.platform}")

    @jax.jit
    def bsearch(keys, q):
        lo = jnp.searchsorted(keys, q, side="left")
        hi = jnp.searchsorted(keys, q, side="right")
        return lo, hi - lo

    rng = np.random.default_rng(27)
    rows = []
    for dt in (np.int32, np.int64):
        for n in KEY_COUNTS if dt == np.int32 else (212,):
            span = max(20_000, 4 * n)
            q = jnp.asarray(rng.integers(0, span, n_probe).astype(dt))
            keys = jnp.asarray(np.sort(rng.choice(span, n, replace=False))
                               .astype(dt))
            same = all(bool(jnp.array_equal(a, b)) for a, b in
                       zip(bsearch(keys, q), join_plan._probe_compare(keys, q)))
            levels = math.ceil(math.log2(n + 1))
            b_ms = _median_ms(bsearch, (keys, q), 3)
            c_ms = _median_ms(join_plan._probe_compare, (keys, q), 5)
            temp = join_plan._probe_compare.lower(keys, q).compile() \
                .memory_analysis().temp_size_in_bytes
            row = {"n_keys": n, "dtype": np.dtype(dt).name, "n_probe": n_probe,
                   "bsearch_ms": b_ms, "compare_ms": c_ms, "levels": levels,
                   "g_ns_per_row_per_gather": b_ms * 1e6 / n_probe / (2 * levels),
                   "c_ns_per_key_per_row": c_ms * 1e6 / n_probe / (2 * n),
                   "compare_temp_bytes": temp, "equal": same}
            rows.append(row)
            print(json.dumps(row), flush=True)
    last = max((r for r in rows if r["dtype"] == "int32"),
               key=lambda r: r["n_keys"])
    g, c = last["g_ns_per_row_per_gather"], last["c_ns_per_key_per_row"]
    n = 1
    while (n + 1) * c <= math.ceil(math.log2(n + 2)) * g:
        n += 1
    res = {"device": dev.device_kind, "g_ns": g, "c_ns": c, "break_even_keys": n,
           "rows": rows}
    print(json.dumps({k: v for k, v in res.items() if k != "rows"}), flush=True)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main(sys.argv)
