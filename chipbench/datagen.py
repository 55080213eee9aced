"""Seeded inputs of every cell, as plain NumPy / Parquet bytes.

Nothing here imports the program: the drivers hand these arrays to the
system under test and the same arrays to the plain references.
"""

from __future__ import annotations

import io

import numpy as np

# spark-rapids-jni src/main/cpp/benchmarks/row_conversion.cpp: the cycle of
# both of its axes ("Fixed Width Only", 212 columns; "Fixed or Variable
# Width" without strings, 155 columns)
NVBENCH_CYCLE = ("int8", "int32", "int16", "int64", "int32", "bool8",
                 "uint16", "uint8", "uint64")
_NP = {"int8": np.int8, "int16": np.int16, "int32": np.int32,
       "int64": np.int64, "uint8": np.uint8, "uint16": np.uint16,
       "uint32": np.uint32, "uint64": np.uint64, "float32": np.float32,
       "float64": np.float64, "bool8": np.uint8}


def np_dtype(type_name: str) -> np.dtype:
    return np.dtype(_NP[type_name])


def nvbench_columns(n_rows: int, n_cols: int, seed: int,
                    null_every: int = 3, valid_share: float = 0.9,
                    cycle=NVBENCH_CYCLE):
    """``[(type_name, values, validity | None)]``: a fixed-width table of
    the reference's benchmark, ~10% nulls on every ``null_every``-th
    column."""
    rng = np.random.default_rng(seed)
    cols = []
    for i in range(n_cols):
        name = cycle[i % len(cycle)]
        dt = np_dtype(name)
        if name == "bool8":
            arr = rng.integers(0, 2, n_rows, dtype=np.uint8)
        elif dt.kind == "f":
            arr = rng.standard_normal(n_rows).astype(dt)
        else:
            info = np.iinfo(dt)
            arr = rng.integers(info.min // 2, info.max // 2, n_rows,
                               dtype=dt)
        valid = (rng.random(n_rows) < valid_share
                 if null_every and i % null_every == 0 else None)
        cols.append((name, arr, valid))
    return cols


# --- TPC-H q6 ---------------------------------------------------------------

Q6_DATE_LO = 8766               # days 1970-01-01 -> 1994-01-01
Q6_DATE_HI = 8766 + 365         # 1995-01-01


def tpch_q6_parquet(n_rows: int, seed: int, row_group_rows: int = 1 << 20):
    """Snappy Parquet (PLAIN, no dictionary) of the four q6 columns plus the
    generator arrays ``(qty, price, disc, ship)`` for the reference."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n_rows).astype(np.int64)
    price = (rng.random(n_rows) * 100000).round(2)
    disc = rng.integers(0, 11, n_rows).astype(np.float64) / 100.0
    ship = rng.integers(Q6_DATE_LO - 400, Q6_DATE_LO + 800,
                        n_rows).astype(np.int32)
    t = pa.table({
        "l_quantity": pa.array(qty),
        "l_extendedprice": pa.array(price),
        "l_discount": pa.array(disc),
        "l_shipdate": pa.array(ship, pa.int32()),
    })
    buf = io.BytesIO()
    pq.write_table(t, buf, compression="SNAPPY", use_dictionary=False,
                   row_group_size=row_group_rows)
    return buf.getvalue(), (qty, price, disc, ship)


# --- TPC-DS star ------------------------------------------------------------

CATEGORIES = ["Books", "Home", "Electronics", "Jewelry", "Music",
              "Shoes", "Sports", "Women", "Men", "Children"]
STATES = ["TN", "CA", "TX", "WA", "NY", "GA", "OH", "IL"]


def _parquet(table) -> bytes:
    import pyarrow.parquet as pq
    buf = io.BytesIO()
    pq.write_table(table, buf, compression="SNAPPY", use_dictionary=False)
    return buf.getvalue()


def tpcds_star_parquet(n_sales: int, n_items: int, n_stores: int, seed: int,
                       n_dates: int, order_seed: int) -> dict[str, bytes]:
    """``store_sales`` + ``item`` / ``date_dim`` / ``store`` as Snappy
    Parquet: uniform surrogate keys, low-cardinality string dimensions,
    decimal measures as scaled int64 cents.  ``order_seed`` shuffles the rows
    of ``store_sales``: the same rows, so every join and group has the same
    size, in another order."""
    import decimal
    import pyarrow as pa
    rng = np.random.default_rng(seed)
    item = pa.table({
        "i_item_sk": pa.array(np.arange(1, n_items + 1, dtype=np.int32)),
        "i_item_id": pa.array(
            [f"AAAA{sk:012d}" for sk in range(1, n_items + 1)]),
        "i_current_price": pa.array(
            [decimal.Decimal(int(c)) / 100
             for c in rng.integers(50, 500_00, n_items)],
            pa.decimal128(7, 2)),
        "i_brand_id": pa.array(
            rng.integers(1000, 1100, n_items).astype(np.int32)),
        "i_brand": pa.array(
            [f"brand#{b}" for b in rng.integers(1, 60, n_items)]),
        "i_category_id": pa.array(
            rng.integers(1, len(CATEGORIES) + 1, n_items).astype(np.int32)),
        "i_category": pa.array(
            [CATEGORIES[c] for c in rng.integers(0, len(CATEGORIES),
                                                 n_items)]),
        "i_manufact_id": pa.array(
            rng.integers(1, 1000, n_items).astype(np.int32)),
        "i_manager_id": pa.array(
            rng.integers(1, 100, n_items).astype(np.int32)),
    })
    date_dim = pa.table({
        "d_date_sk": pa.array(np.arange(1, n_dates + 1, dtype=np.int32)),
        "d_year": pa.array(
            (1999 + (np.arange(n_dates) // 366)).astype(np.int32)),
        "d_moy": pa.array(
            (1 + (np.arange(n_dates) // 30) % 12).astype(np.int32)),
    })
    store = pa.table({
        "s_store_sk": pa.array(np.arange(1, n_stores + 1, dtype=np.int32)),
        "s_state": pa.array(
            [STATES[s] for s in rng.integers(0, len(STATES), n_stores)]),
    })
    price_cents = rng.integers(100, 300_00, n_sales).astype(np.int64)
    list_cents = price_cents + rng.integers(0, 50_00, n_sales)
    qty = rng.integers(1, 100, n_sales).astype(np.int32)
    order = np.random.default_rng(order_seed).permutation(n_sales)
    store_sales = pa.table({name: pa.array(values[order]) for name, values in {
        "ss_sold_date_sk":
            rng.integers(1, n_dates + 1, n_sales).astype(np.int32),
        "ss_item_sk":
            rng.integers(1, n_items + 1, n_sales).astype(np.int32),
        "ss_store_sk":
            rng.integers(1, max(n_stores, 2), n_sales).astype(np.int32),
        "ss_quantity": qty,
        "ss_sales_price_cents": price_cents,
        "ss_list_price_cents": list_cents,
        "ss_ext_sales_price": (price_cents * qty).astype(np.float64) / 100.0,
    }.items()})
    return {"store_sales": _parquet(store_sales), "item": _parquet(item),
            "date_dim": _parquet(date_dim), "store": _parquet(store)}
