"""Vectorized host (NumPy) JCUDF engine.

Two roles:
* the full-size plain reference of ``chip_smoke.py``, and
* a production host fallback for row conversion when no accelerator is
  attached (the reference has no such fallback — its only engine is CUDA —
  so this is strictly additive capability).

Unlike ``reference.py`` (the deliberately scalar oracle), this module is the
fastest reasonable pure-NumPy implementation: strided views + packbits, no
Python per-row loops on the fixed-width path.
"""

from __future__ import annotations

import numpy as np

from .. import types as T
from ..column import Table
from .layout import compute_row_layout
from .reference import _col_valid


def _valid_matrix(table: Table) -> np.ndarray:
    return np.stack([_col_valid(c) for c in table.columns], axis=1)


def to_rows_fixed_np(table: Table) -> np.ndarray:
    """Fixed-width table → uint8 [n, fixed_row_size] (vectorized)."""
    layout = compute_row_layout(table.schema)
    assert layout.fixed_width_only
    n = table.num_rows
    out = np.zeros((n, layout.fixed_row_size), dtype=np.uint8)
    for ci, col in enumerate(table.columns):
        start = layout.column_starts[ci]
        sz = layout.column_sizes[ci]
        # Column payloads are already in storage form (f64 = u32 bit pairs,
        # decimal128 = int64 lane pairs), so a raw byte view is exact.
        data = np.ascontiguousarray(np.asarray(col.data))
        out[:, start:start + sz] = data.view(np.uint8).reshape(n, sz)
    valid = _valid_matrix(table)
    vbytes = np.packbits(valid, axis=1, bitorder="little")
    out[:, layout.validity_offset:
        layout.validity_offset + layout.validity_bytes] = vbytes
    return out


def from_rows_fixed_np(rows: np.ndarray, schema) -> tuple[list, np.ndarray]:
    """uint8 [n, row_size] → (list of value arrays, valid bool [n, ncols])."""
    layout = compute_row_layout(list(schema))
    assert layout.fixed_width_only
    n = rows.shape[0]
    datas = []
    for ci, dt in enumerate(layout.schema):
        start = layout.column_starts[ci]
        sz = layout.column_sizes[ci]
        b = np.ascontiguousarray(rows[:, start:start + sz])
        if dt.id == T.TypeId.FLOAT64:    # storage form: u32 [n, 2] bit pairs
            datas.append(b.view(np.uint32).reshape(n, 2))
        else:
            datas.append(b.view(dt.storage).reshape(n))
    vb = rows[:, layout.validity_offset:
              layout.validity_offset + layout.validity_bytes]
    valid = np.unpackbits(np.ascontiguousarray(vb), axis=1,
                          bitorder="little")[:, :layout.num_columns].astype(bool)
    return datas, valid
