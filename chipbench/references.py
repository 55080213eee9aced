"""Plain references and their controls.  NumPy / pandas only.

Each reference is a straightforward implementation of the semantics the
configuration states, fed the same seeded data as the program; nothing here
imports the program or takes anything the program made.  A *control* is the
reference with one guarantee of the configuration broken (or computed one
precision below the stated one); the comparison that decides ``correct`` has
to fail it (``python3 -m chipbench.control``, tests/test_chipbench.py).
"""

from __future__ import annotations

import numpy as np

from .datagen import np_dtype

JCUDF_ROW_ALIGNMENT = 8


def jcudf_fixed_layout(type_names):
    """JCUDF layout of a fixed-width schema: ``(starts, sizes,
    validity_offset, validity_bytes, row_size)``.  C-struct rows, each
    column aligned to its own size, one validity bit per column appended
    byte-aligned after the data, the row padded to 8 bytes
    (spark-rapids-jni row_conversion.cu compute_column_information)."""
    starts, sizes, off = [], [], 0
    for name in type_names:
        size = np_dtype(name).itemsize
        off = -(-off // size) * size
        starts.append(off)
        sizes.append(size)
        off += size
    vbytes = -(-len(type_names) // 8)
    row = -(-(off + vbytes) // JCUDF_ROW_ALIGNMENT) * JCUDF_ROW_ALIGNMENT
    return starts, sizes, off, vbytes, row


def pack_rows_fixed(columns, ignore_nulls: bool = False) -> np.ndarray:
    """``[(type_name, values, validity | None)]`` -> uint8 ``[n, row_size]``
    JCUDF rows.  ``ignore_nulls=True`` is the control: every validity bit
    written as valid (the guarantee broken: rows carry the nulls)."""
    starts, sizes, voff, vbytes, row = jcudf_fixed_layout(
        [c[0] for c in columns])
    n = columns[0][1].shape[0]
    out = np.zeros((n, row), dtype=np.uint8)
    for (name, values, _), start, size in zip(columns, starts, sizes):
        raw = np.ascontiguousarray(values, dtype=np_dtype(name))
        out[:, start:start + size] = raw.view(np.uint8).reshape(n, size)
    for ci, (_, _, valid) in enumerate(columns):
        bits = (np.ones(n, np.uint8) if valid is None or ignore_nulls
                else valid.astype(np.uint8))
        out[:, voff + ci // 8] |= bits << np.uint8(ci % 8)
    return out


def q6_numpy(arrays, date_lo: int, date_hi: int, dtype=np.float64):
    """TPC-H q6 over the generator arrays: ``(revenue, matched rows)``.
    ``dtype=np.float32`` is the control: the predicate, the product and the
    sum one precision below the float64 the configuration states."""
    qty, price, disc, ship = arrays
    price, disc = price.astype(dtype), disc.astype(dtype)
    eps = dtype(1e-9)
    mask = ((ship >= date_lo) & (ship < date_hi)
            & (disc >= dtype(0.05) - eps) & (disc <= dtype(0.07) + eps)
            & (qty < 24))
    revenue = np.sum(np.where(mask, price * disc, dtype(0)), dtype=dtype)
    return float(revenue), int(mask.sum())


def _star(dfs, item_mask, date_mask, keys, dtype):
    ss, item, dd = dfs["store_sales"], dfs["item"], dfs["date_dim"]
    ss = ss.assign(ss_ext_sales_price=ss.ss_ext_sales_price.astype(dtype))
    j = (ss.merge(item[item_mask], left_on="ss_item_sk",
                  right_on="i_item_sk")
         .merge(dd[date_mask], left_on="ss_sold_date_sk",
                right_on="d_date_sk"))
    out = j.groupby(keys, as_index=False)["ss_ext_sales_price"].sum()
    return out.sort_values(keys).reset_index(drop=True)


def q3_pandas(dfs, manufact_id, moy, dtype=np.float64):
    """TPC-DS q3: brand revenue of one manufacturer in one month of every
    year.  ``dtype=np.float32`` is the control."""
    item, dd = dfs["item"], dfs["date_dim"]
    return _star(dfs, item.i_manufact_id == manufact_id, dd.d_moy == moy,
                 ["d_year", "i_brand_id", "i_brand"], dtype)


def q42_pandas(dfs, manager_id, year, moy, dtype=np.float64):
    """TPC-DS q42: category revenue of one manager in one month."""
    item, dd = dfs["item"], dfs["date_dim"]
    return _star(dfs, item.i_manager_id == manager_id,
                 (dd.d_moy == moy) & (dd.d_year == year),
                 ["d_year", "i_category_id", "i_category"], dtype)


SQL_TWINS = {"q3": q3_pandas, "q42": q42_pandas}
