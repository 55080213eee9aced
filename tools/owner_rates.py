#!/usr/bin/env python
"""The rates `ops.select`'s constants stand on (PERF.md section 6, PR 35).

On the chip, over n probe rows (10M: TPC-DS q42's fact at the star cell's
size) and `total` outputs, times from a caller's side the ways to invert
the running sum of match counts, "which row owns output j":

  search64      the expansion as it was: `jnp.searchsorted` over int64
                `starts`, ceil(log2(n+1)) levels, two word-gathers a level
  search32      the same with 32-bit words
  nonzero       `jnp.nonzero(mask, size=total)`: what a unique join's
                compaction lowers to under a trace (counts of 0 and 1 only)
  block K/top   `ops.select._owners_block`: compare-count over the block firsts
                (recursing above `top` of them), one row gather of K
                starts, a dense count and max
  dense K       the running sum and its [nb, K] block view alone

Every form is checked against search64 before it is timed.  A call of
under ~0.5 ms reads the launch, not the device.  TPU only.

Usage: python tools/owner_rates.py [n] [out.json]
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, ".")

TOTALS = (9050, 106597, 1_000_000, None)        # None: every row owns one
# (row words, firsts compare-counted in one go, bytes of rows a chunk gathers)
# as the issue drew it; every first in one go; bigger chunks; as shipped; a
# third level; wider and narrower rows
BLOCKS = ((512, 1 << 15, 256 << 20), (128, 1 << 17, 256 << 20),
          (128, 4096, 256 << 20), (128, 4096, 64 << 20),
          (128, 128, 64 << 20),
          (256, 4096, 64 << 20), (64, 4096, 64 << 20))


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
    n = int(argv[1]) if len(argv) > 1 else 10_000_000
    out_path = argv[2] if len(argv) > 2 else "chiprun_out/owner_rates.json"
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    import spark_rapids_jni_tpu  # noqa: F401  (x64 on, as the program runs)
    from spark_rapids_jni_tpu.ops import select

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"owner_rates: needs a TPU, found {dev.platform}")

    @partial(jax.jit, static_argnames=("total", "word"))
    def search(counts, total, word):
        c = counts.astype(word)
        starts = jnp.cumsum(c) - c
        ids = jnp.arange(total, dtype=word)
        left = jnp.searchsorted(starts, ids, side="right") - 1
        return left.astype(jnp.int64), (ids - starts[left]).astype(jnp.int64)

    @partial(jax.jit, static_argnames=("total",))
    def nonzero(counts, total):
        return jnp.nonzero(counts > 0, size=total)[0]

    @partial(jax.jit, static_argnames=("k",))
    def dense(counts, k):
        c = counts.astype(jnp.int32)
        return select._block_views(jnp.cumsum(c) - c, k, 1 << 30)

    rng = np.random.default_rng(35)
    rows = []

    def record(form, total, ms, **kw):
        row = {"form": form, "n": n, "total": total, "ms": ms,
               "ns_per_output": ms * 1e6 / total, **kw}
        rows.append(row)
        print(json.dumps(row), flush=True)

    host = rng.integers(0, 3, n - n % 1024).astype(np.int32)
    counts = jnp.asarray(host)
    for k in (128, 512):
        record("dense", host.size, _median_ms(dense, (counts, k), 7), k=k)

    for want in TOTALS:
        if want is not None and want > n:
            continue
        if want is None:
            host = np.ones(n, np.int32)
        else:
            host = np.zeros(n, np.int32)
            host[rng.choice(n, want, replace=False)] = 1
        total = int(host.sum())
        counts = jnp.asarray(host)
        ref = search(counts, total, jnp.int64)
        slow = total > 2_000_000

        def check(got):
            return all(bool(jnp.array_equal(a, b)) for a, b in zip(got, ref))

        record("search64", total,
               _median_ms(partial(search, total=total, word=jnp.int64),
                          (counts,), 2 if slow else 5))
        same = check(search(counts, total, jnp.int32))
        record("search32", total,
               _median_ms(partial(search, total=total, word=jnp.int32),
                          (counts,), 2 if slow else 5), equal=same)
        same = bool(jnp.array_equal(nonzero(counts, total), ref[0]))
        record("nonzero", total,
               _median_ms(partial(nonzero, total=total), (counts,),
                          2 if slow else 5), equal=same)
        for k, top, rows_bytes in BLOCKS:
            chunk = rows_bytes // (4 * k)
            fn = partial(select._owners_block, total=total, row_words=k, top=top,
                         chunk=chunk)
            same = check(fn(counts))
            temp = select._owners_block.lower(
                counts, total, k, top, chunk).compile() \
                .memory_analysis().temp_size_in_bytes
            record("block", total, _median_ms(fn, (counts,), 5), k=k,
                   top=top, chunk=chunk, chunks=-(-total // chunk),
                   temp_bytes=temp, equal=same)

    # the shapes the records hint at: q42's second join compacts a
    # 106597-row mask to 2974 rows ("fusion.73", 8.8 ms a q42)
    host = np.zeros(106597, np.int32)
    host[rng.choice(106597, 2974, replace=False)] = 1
    small = jnp.asarray(host)
    record("nonzero", 2974, _median_ms(partial(nonzero, total=2974),
                                       (small,), 9), n_mask=106597)
    fn = partial(select._owners_block, total=2974, row_words=select.ROW_WORDS,
                 top=select.COMPARE_TOP,
                 chunk=select.CHUNK_PAIRS)
    same = bool(jnp.array_equal(fn(small)[0], nonzero(small, 2974)))
    record("block", 2974, _median_ms(fn, (small,), 9), n_mask=106597,
           k=select.ROW_WORDS, equal=same)

    # where COMPARE_TOP decides: 3125 block firsts, compare-counted in one
    # go (top 4096) or selected through a second level of 25 (top 1024)
    host = np.zeros(400_000, np.int32)
    host[rng.choice(400_000, 100_000, replace=False)] = 1
    few = jnp.asarray(host)
    for top in (1024, 4096):
        fn = partial(select._owners_block, total=100_000, row_words=128,
                     top=top, chunk=1 << 17)
        record("block", 100_000, _median_ms(fn, (few,), 9), n_rows=400_000,
               k=128, top=top)

    # runs that straddle blocks and chunks, rows that own nothing: the
    # shipped form against the search on a skewed left join's counts
    host = rng.integers(0, 3, n).astype(np.int32)
    host[rng.choice(n, 1000, replace=False)] = 3000
    host = np.maximum(host, 1)
    total = int(host.sum())
    counts = jnp.asarray(host)
    got = select.owners(counts, total)
    ref = search(counts, total, jnp.int32)
    same = all(bool(jnp.array_equal(a, b)) for a, b in zip(got, ref))
    record("block", total,
           _median_ms(partial(select.owners, total=total), (counts,), 3),
           skewed_left_join=True, form_picked=select.form(total),
           equal=same)
    record("search32", total,
           _median_ms(partial(search, total=total, word=jnp.int32),
                      (counts,), 2), skewed_left_join=True)

    res = {"device": dev.device_kind, "n": n, "rows": rows,
           "all_equal": all(r.get("equal", True) for r in rows)}
    print(json.dumps({"device": res["device"], "all_equal": res["all_equal"]}),
          flush=True)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(res, f, indent=1)
    if not res["all_equal"]:
        sys.exit("owner_rates: a form disagrees with the search")


if __name__ == "__main__":
    main(sys.argv)
