"""Mini TPC-H lineitem generators.

``generate`` feeds the Q1 pricing-summary query: decimal measures are
written as parquet DECIMAL (FLBA) so the framework's decimal decode path
feeds the query; flags are low-cardinality strings like the spec's
returnflag/linestatus.  ``generate_q6`` is the SF-scale q6 scan input: the
four q6 columns as PLAIN doubles/ints, built with array ops only so 6M
rows take seconds.
"""

from __future__ import annotations

import decimal
import io

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq


def generate(n: int = 50_000, seed: int = 21) -> tuple[bytes, dict]:
    rng = np.random.default_rng(seed)
    epoch98 = 10561    # days 1970 → 1998-12-01
    qty = rng.integers(1, 51, n).astype(np.int64)
    price_c = rng.integers(90_000, 10_000_000, n)        # cents
    disc_c = rng.integers(0, 11, n)                      # 0.00-0.10
    tax_c = rng.integers(0, 9, n)                        # 0.00-0.08
    ship = rng.integers(epoch98 - 2500, epoch98 + 100, n).astype(np.int32)
    flags = np.where(rng.random(n) < 0.5, "N",
                     np.where(rng.random(n) < 0.5, "A", "R"))
    status = np.where(flags == "N", "O", "F")

    table = pa.table({
        "l_returnflag": pa.array(flags.tolist()),
        "l_linestatus": pa.array(status.tolist()),
        "l_quantity": pa.array(qty),
        "l_extendedprice": pa.array(
            [decimal.Decimal(int(c)) / 100 for c in price_c],
            pa.decimal128(12, 2)),
        "l_discount": pa.array(
            [decimal.Decimal(int(c)) / 100 for c in disc_c],
            pa.decimal128(4, 2)),
        "l_tax": pa.array(
            [decimal.Decimal(int(c)) / 100 for c in tax_c],
            pa.decimal128(4, 2)),
        "l_shipdate": pa.array(ship, pa.date32()),
    })
    buf = io.BytesIO()
    pq.write_table(table, buf, compression="SNAPPY")
    raw = {"flags": flags, "status": status, "qty": qty,
           "price_c": price_c, "disc_c": disc_c, "tax_c": tax_c,
           "ship": ship}
    return buf.getvalue(), raw


def generate_q6(n: int = 6_000_000, seed: int = 3) -> tuple[bytes, tuple]:
    """Snappy parquet of the four TPC-H q6 columns (``models.q6.COLUMNS``)
    in 1M-row groups, plus the raw generator arrays
    ``(qty, price, disc, ship)`` for the NumPy reference."""
    rng = np.random.default_rng(seed)
    epoch94 = 8766     # days 1970 → 1994-01-01
    qty = rng.integers(1, 51, n).astype(np.int64)
    price = (rng.random(n) * 100000).round(2)
    disc = rng.integers(0, 11, n).astype(np.float64) / 100.0
    ship = rng.integers(epoch94 - 400, epoch94 + 800, n).astype(np.int32)
    t = pa.table({
        "l_quantity": pa.array(qty),
        "l_extendedprice": pa.array(price),
        "l_discount": pa.array(disc),
        "l_shipdate": pa.array(ship, pa.int32()),
    })
    buf = io.BytesIO()
    pq.write_table(t, buf, compression="SNAPPY", use_dictionary=False,
                   row_group_size=1 << 20)
    return buf.getvalue(), (qty, price, disc, ship)


def q6_reference(raw: tuple, date_lo: int, date_hi: int) -> tuple[float, int]:
    """NumPy q6 over the generator arrays: (revenue, matched rows)."""
    qty, price, disc, ship = raw
    mask = ((ship >= date_lo) & (ship < date_hi)
            & (disc >= 0.05 - 1e-9) & (disc <= 0.07 + 1e-9) & (qty < 24))
    return float(np.sum(np.where(mask, price * disc, 0.0))), int(mask.sum())
