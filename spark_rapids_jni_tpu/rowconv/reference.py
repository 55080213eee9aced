"""Host (NumPy) oracle for the JCUDF row format.

The reference validates its tiled CUDA path differentially against the legacy
``*_fixed_width_optimized`` path (``tests/row_conversion.cpp:49-58,575-584``).
Here the slow-but-obvious NumPy implementation plays the oracle role for the
JAX/Pallas device path: both must produce byte-identical JCUDF rows.

This module is deliberately scalar and readable — it is the specification.
"""

from __future__ import annotations

import numpy as np

from .. import types as T
from ..column import Column, Table
from .layout import (JCUDF_ROW_ALIGNMENT, RowLayout, compute_row_layout,
                     row_sizes_with_strings)


def _col_valid(col: Column) -> np.ndarray:
    if col.validity is None:
        return np.ones(col.num_rows, dtype=bool)
    return np.asarray(col.validity)


def to_rows_np(table: Table) -> tuple[np.ndarray, np.ndarray]:
    """Table → (row_bytes: uint8 [total], row_offsets: int32 [n+1])."""
    layout = compute_row_layout(table.schema)
    n = table.num_rows

    if layout.fixed_width_only:
        row_sizes = np.full(n, layout.fixed_row_size, dtype=np.int64)
    else:
        total_lens = np.zeros(n, dtype=np.int64)
        for ci in layout.variable_column_indices:
            offs = np.asarray(table[ci].offsets, dtype=np.int64)
            total_lens += offs[1:] - offs[:-1]
        row_sizes = row_sizes_with_strings(layout, total_lens)

    row_offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(row_sizes, out=row_offsets[1:])
    out = np.zeros(int(row_offsets[-1]), dtype=np.uint8)

    # Hoist every device payload to host ONCE: a per-row ``np.asarray`` on
    # a device array is a host sync per row — n*cols of them turn this
    # oracle from seconds into hours.
    host_data = [np.asarray(c.data) for c in table.columns]
    host_offs = [None if c.offsets is None else np.asarray(c.offsets)
                 for c in table.columns]
    host_valid = [_col_valid(c) for c in table.columns]

    for r in range(n):
        base = int(row_offsets[r])
        # fixed-width slots + string (offset, len) slots
        var_cursor = layout.fixed_plus_validity
        for ci, col in enumerate(table.columns):
            start = base + layout.column_starts[ci]
            if col.dtype.is_variable_width:
                offs = host_offs[ci]
                length = int(offs[r + 1] - offs[r])
                slot = np.asarray([var_cursor, length], dtype=np.uint32)
                out[start:start + 8] = slot.view(np.uint8)
                chars = host_data[ci][offs[r]:offs[r + 1]]
                out[base + var_cursor:base + var_cursor + length] = chars
                var_cursor += length
            elif col.dtype.id.name == "DECIMAL128":
                lanes = np.ascontiguousarray(host_data[ci][r], dtype=np.int64)  # (lo, hi)
                out[start:start + 16] = lanes.view(np.uint8)
            elif col.dtype.id == T.TypeId.FLOAT64:
                # storage is the u32 [n, 2] bit pattern (column.py invariant)
                halves = np.ascontiguousarray(host_data[ci][r], dtype=np.uint32)
                out[start:start + 8] = halves.view(np.uint8)
            else:
                val = np.ascontiguousarray(host_data[ci][r:r + 1],
                                       dtype=col.dtype.storage)
                sz = layout.column_sizes[ci]
                out[start:start + sz] = val.view(np.uint8)
        # validity bytes, bit i of byte b = column b*8+i (RowConversion.java:56-58)
        vbase = base + layout.validity_offset
        for b in range(layout.validity_bytes):
            byte = 0
            for i in range(min(8, table.num_columns - b * 8)):
                if host_valid[b * 8 + i][r]:
                    byte |= 1 << i
            out[vbase + b] = byte

    return out, row_offsets.astype(np.int32)


def from_rows_np(row_bytes: np.ndarray, row_offsets: np.ndarray,
                 schema: list[T.DType]) -> Table:
    """(row_bytes, row_offsets) + schema → Table (inverse of to_rows_np)."""
    layout = compute_row_layout(schema)
    row_bytes = np.asarray(row_bytes, dtype=np.uint8)
    row_offsets = np.asarray(row_offsets, dtype=np.int64)
    n = row_offsets.shape[0] - 1

    datas = []
    validities = np.zeros((n, len(schema)), dtype=bool)
    for ci, dt in enumerate(schema):
        if dt.is_variable_width:
            datas.append([])  # list of per-row bytes
        elif dt.id == T.TypeId.DECIMAL128:
            datas.append(np.zeros((n, 2), dtype=np.int64))
        elif dt.id == T.TypeId.FLOAT64:
            datas.append(np.zeros((n, 2), dtype=np.uint32))  # bit pairs
        else:
            datas.append(np.zeros(n, dtype=dt.storage))

    for r in range(n):
        base = int(row_offsets[r])
        vbase = base + layout.validity_offset
        for ci, dt in enumerate(schema):
            validities[r, ci] = bool(
                (row_bytes[vbase + ci // 8] >> (ci % 8)) & 1)
            start = base + layout.column_starts[ci]
            if dt.is_variable_width:
                slot = row_bytes[start:start + 8].view(np.uint32)
                off, length = int(slot[0]), int(slot[1])
                datas[ci].append(row_bytes[base + off:base + off + length])
            elif dt.id == T.TypeId.DECIMAL128:
                datas[ci][r] = row_bytes[start:start + 16].view(np.int64)
            elif dt.id == T.TypeId.FLOAT64:
                datas[ci][r] = row_bytes[start:start + 8].view(np.uint32)
            else:
                sz = layout.column_sizes[ci]
                datas[ci][r] = row_bytes[start:start + sz].view(dt.storage)[0]

    cols = []
    for ci, dt in enumerate(schema):
        valid = validities[:, ci]
        v = None if valid.all() else valid
        if dt.is_variable_width:
            lengths = np.asarray([len(b) for b in datas[ci]], dtype=np.int32)
            offs = np.zeros(n + 1, dtype=np.int32)
            np.cumsum(lengths, out=offs[1:])
            chars = (np.concatenate(datas[ci]) if n and offs[-1] else
                     np.zeros(0, dtype=np.uint8))
            import jax.numpy as jnp
            cols.append(Column(dt, jnp.asarray(chars), jnp.asarray(offs),
                               None if v is None else jnp.asarray(v)))
        elif dt.id in (T.TypeId.DECIMAL128, T.TypeId.FLOAT64):
            import jax.numpy as jnp
            cols.append(Column(dt, jnp.asarray(datas[ci]),
                               validity=None if v is None
                               else jnp.asarray(v)))
        else:
            cols.append(Column.from_numpy(datas[ci], dt, v))
    return Table(cols)
