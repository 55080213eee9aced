"""The plain reference of a table that takes more than one row batch, and
its controls.  NumPy only; imports nothing of the program.

The bytes are ``references.pack_rows_fixed``'s over the whole table; what
this file adds is where the reference cuts them.  Its rule, from the
description of spark-rapids-jni's ``build_batches`` (``row_conversion.cu``)
and not from the program's copy of it: a batch's byte offsets are int32, so
a batch holds at most ``cap = 2**31 - 1`` bytes; while the rows that are
left take more than that, the next batch gets as many whole rows as fit
under the cap, rounded **down to a multiple of 32 rows** (so that a
batch's validity words start on a word of the table's); the last batch
takes what is left and is never rounded.
"""

from __future__ import annotations

import numpy as np

ROW_MULTIPLE = 32
INT32_BYTES = 2 ** 31


def plain_batch_boundaries(row_size: int, n: int, cap: int,
                           round_to_32: bool = True) -> list[int]:
    """Row boundaries ``[0, b1, ..., n]`` of the batches of ``n`` rows of
    ``row_size`` bytes under ``cap`` bytes a batch.  ``round_to_32=False``
    is the control: every batch filled to the last row that fits (the
    guarantee broken: splits fall on multiples of 32 rows)."""
    if row_size > cap:
        raise ValueError("a row is larger than a batch may be")
    bounds, left = [0], n
    while left * row_size > cap:
        take = cap // row_size
        if round_to_32 and take > ROW_MULTIPLE:
            take -= take % ROW_MULTIPLE
        bounds.append(bounds[-1] + take)
        left -= take
    bounds.append(n)
    return bounds


def boundary_mismatches(got_rows: list[int], row_size: int, n: int,
                        cap: int) -> int:
    """``got_rows``: the rows of each batch, in order.  Boundaries that are
    not the plain rule's, batches too many or too few, and batches that an
    int32 offset cannot address."""
    want = plain_batch_boundaries(row_size, n, cap)
    got = [0]
    for rows in got_rows:
        got.append(got[-1] + int(rows))
    wrong = sum(a != b for a, b in zip(got, want)) + abs(len(got) - len(want))
    return wrong + sum(int(rows) * row_size >= INT32_BYTES
                       for rows in got_rows)


def offset_mismatches(offsets: np.ndarray, rows: int, row_size: int) -> int:
    """A batch's row offsets against ``arange(rows + 1) * row_size`` as
    int32: entries that differ (all of them where the dtype or the length
    does)."""
    offsets = np.asarray(offsets)
    if offsets.dtype != np.int32 or offsets.shape != (rows + 1,):
        return max(offsets.size, rows + 1)
    want = np.arange(rows + 1, dtype=np.int64) * row_size
    return int(np.count_nonzero(offsets.astype(np.int64) != want))
