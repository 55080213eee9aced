"""TPC-H Q6: the scan → filter → sum revenue pipeline (BASELINE config #2).

    SELECT sum(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= DATE '1994-01-01'
      AND l_shipdate <  DATE '1995-01-01'
      AND l_discount BETWEEN 0.05 AND 0.07
      AND l_quantity < 24

TPU-first shape: the Parquet scan decodes ON DEVICE for the fast-path
column shapes (``parquet.device_scan``: PLAIN bitcast / dictionary gather /
def-level expansion as jitted ops over the raw page bytes; host fallback
otherwise), and the predicate + multiply + masked sum is ONE fused jitted
program — the filter never compacts (``ops.filter.mask_table`` discipline),
so the whole query is a single static-shaped VPU pass over the four columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..column import Table
from ..utils import metrics

COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]


@jax.jit
def q6_kernel(quantity, extendedprice, discount, shipdate,
              date_lo, date_hi):
    """The fused predicate+aggregate; dates as int32 days since epoch."""
    mask = ((shipdate >= date_lo) & (shipdate < date_hi)
            & (discount >= 0.05 - 1e-9) & (discount <= 0.07 + 1e-9)
            & (quantity < 24))
    revenue = jnp.where(mask, extendedprice * discount, 0.0)
    return jnp.sum(revenue, dtype=jnp.float64), jnp.sum(mask, dtype=jnp.int64)


def run(file_bytes: bytes, date_lo_days: int, date_hi_days: int):
    """Scan a lineitem parquet file and compute Q6 revenue on device."""
    from ..parquet import device_scan
    with metrics.span("q6.run", file_bytes=len(file_bytes)) as sp:
        table = device_scan.scan_table(file_bytes, columns=COLUMNS)
        # dispatch, then the host's wait for upload + decode + kernel
        with metrics.span("q6.answer"):
            q, ep, disc, ship = (table[i].values() for i in range(4))
            revenue, matched = q6_kernel(q, ep, disc, ship,
                                         jnp.int32(date_lo_days),
                                         jnp.int32(date_hi_days))
            answer = float(revenue), int(matched)
        if sp is not None:
            sp.annotate(rows=table.num_rows)
    return answer
