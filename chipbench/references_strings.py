"""The plain reference of the strings cell and its control.  NumPy only;
imports nothing of the program and takes nothing the program made.

JCUDF rows with strings (spark-rapids-jni row_conversion.cu:216-261,
827-875, 1331-1370): every column a slot in a C-struct row, a fixed column
aligned to its own size, a string an 8-byte ``(offset from the row's
start: u32, length: u32)`` slot aligned to 4; one validity bit a column,
byte-aligned after the slots; then the chars of the string columns in
column order from byte ``fixed_plus_validity`` (unaligned); the row padded
to 8.  A string's bytes travel by its offsets whatever its validity bit
says.
"""

from __future__ import annotations

import numpy as np

from .datagen import np_dtype

JCUDF_ROW_ALIGNMENT = 8
CHUNK_ROWS = 1 << 16


def jcudf_layout(type_names):
    """``(starts, sizes, validity_offset, validity_bytes,
    fixed_plus_validity)``; ``"string"`` is the 8-byte slot aligned to
    4."""
    starts, sizes, off = [], [], 0
    for name in type_names:
        size, align = ((8, 4) if name == "string"
                       else (np_dtype(name).itemsize,) * 2)
        off = -(-off // align) * align
        starts.append(off)
        sizes.append(size)
        off += size
    vbytes = -(-len(type_names) // 8)
    return starts, sizes, off, vbytes, off + vbytes


def row_offsets(columns) -> np.ndarray:
    """int64 ``[n + 1]`` byte offsets of the rows of one batch."""
    fpv = jcudf_layout([c[0] for c in columns])[4]
    n = _rows(columns)
    size = np.full(n, fpv, np.int64)
    for name, values, _ in columns:
        if name == "string":
            size += np.diff(values[0].astype(np.int64))
    size = -(-size // JCUDF_ROW_ALIGNMENT) * JCUDF_ROW_ALIGNMENT
    out = np.zeros(n + 1, np.int64)
    np.cumsum(size, out=out[1:])
    return out


def _rows(columns) -> int:
    name, values, _ = columns[0]
    return (values[0].shape[0] - 1) if name == "string" else values.shape[0]


def pack_rows_strings(columns, slots_from_chars: bool = False):
    """``[(type_name, values, validity | None)]`` -> ``(uint8 [total] JCUDF
    row bytes, int64 [n + 1] row offsets)``.  Vectorised: a chunk of rows is
    laid out as a matrix as wide as its widest row (the slots by column
    slices, each string column's chars by one ``np.repeat`` scatter) and a
    mask of every row's own size flattens it, row after row.

    ``slots_from_chars=True`` is the control: a string slot's offset
    counted from the start of the chars region, not from the row's start
    (the guarantee broken: a reader of the row finds its strings)."""
    names = [c[0] for c in columns]
    starts, sizes, voff, vbytes, fpv = jcudf_layout(names)
    n = _rows(columns)
    offsets = row_offsets(columns)
    out = np.empty(int(offsets[-1]), np.uint8)
    for lo in range(0, n, CHUNK_ROWS):
        hi = min(lo + CHUNK_ROWS, n)
        k = hi - lo
        size = np.diff(offsets[lo:hi + 1])
        dense = np.zeros((k, int(size.max(initial=0))), np.uint8)
        tail = np.full(k, fpv, np.int64)      # where the next chars go
        for ci, (name, values, valid) in enumerate(columns):
            start = starts[ci]
            if name == "string":
                offs = values[0][lo:hi + 1].astype(np.int64)
                lens = np.diff(offs)
                slot = np.stack([tail - (fpv if slots_from_chars else 0),
                                 lens], axis=1).astype(np.uint32)
                dense[:, start:start + 8] = slot.view(np.uint8)
                total = int(offs[-1] - offs[0])
                row = np.repeat(np.arange(k), lens)
                col = (np.repeat(tail - (offs[:-1] - offs[0]), lens)
                       + np.arange(total))
                dense[row, col] = values[1][offs[0]:offs[-1]]
                tail = tail + lens
            else:
                raw = np.ascontiguousarray(values[lo:hi],
                                           dtype=np_dtype(name))
                dense[:, start:start + sizes[ci]] = raw.view(
                    np.uint8).reshape(k, sizes[ci])
            bits = (np.ones(k, np.uint8) if valid is None
                    else valid[lo:hi].astype(np.uint8))
            dense[:, voff + ci // 8] |= bits << np.uint8(ci % 8)
        keep = np.arange(dense.shape[1])[None, :] < size[:, None]
        out[offsets[lo]:offsets[hi]] = dense[keep]
    return out, offsets
