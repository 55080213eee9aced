"""Seeded inputs of the strings cell, as plain NumPy: the reference
benchmark's "Fixed or Variable Width" table with its STRING column kept.

Nothing here imports the program.  A fixed column is drawn by
``datagen.nvbench_columns``' rule (integers uniform over half their range,
bool8 0/1, ~10% nulls on every ``null_every``-th column); a string column
is ``(offsets int32[n + 1], chars uint8)`` with lengths from the
configuration's ``string_len`` and chars uniform over printable ASCII.  A
null string keeps a length drawn like any other: its bytes travel by its
offsets whatever its validity bit says.
"""

from __future__ import annotations

import numpy as np

from .datagen import NVBENCH_CYCLE, np_dtype

STRINGS_CYCLE = NVBENCH_CYCLE + ("string",)
PRINTABLE = (32, 127)           # [lo, hi) of a char


def string_lengths(rng, n_rows: int, string_len: dict) -> np.ndarray:
    """``dist: normal`` is cudf's default STRING profile as the builder
    knows it: normal over [lo, hi], mean the middle, sd a sixth of the
    span, rounded and clipped; ``dist: constant`` is ``hi`` on every
    row."""
    lo, hi = int(string_len["lo"]), int(string_len["hi"])
    if string_len["dist"] == "constant":
        return np.full(n_rows, hi, np.int64)
    if string_len["dist"] != "normal":
        raise ValueError(f"no string length rule {string_len['dist']!r}")
    drawn = rng.normal((lo + hi) / 2.0, (hi - lo) / 6.0, n_rows)
    return np.clip(np.rint(drawn), lo, hi).astype(np.int64)


def _chars(rng, size: int) -> np.ndarray:
    """Uniform printable chars, in bulk: a float32 draw scaled to the range
    (bounded uint8 draws reject-sample at 0.8 s a column of 16.7 MB)."""
    lo, hi = PRINTABLE
    return (rng.random(size, dtype=np.float32) * (hi - lo)).astype(
        np.uint8) + np.uint8(lo)


def strings_columns(n_rows: int, n_cols: int, seed: int, null_every: int,
                    valid_share: float, string_len: dict,
                    cycle=STRINGS_CYCLE):
    """``[(type_name, values, validity | None)]``; ``values`` of a
    ``"string"`` column is ``(offsets int32[n + 1], chars uint8)``."""
    rng = np.random.default_rng(seed)
    cols = []
    for i in range(n_cols):
        name = cycle[i % len(cycle)]
        if name == "string":
            offsets = np.zeros(n_rows + 1, np.int32)
            np.cumsum(string_lengths(rng, n_rows, string_len),
                      out=offsets[1:])
            arr = (offsets, _chars(rng, int(offsets[-1])))
        elif name == "bool8":
            arr = rng.integers(0, 2, n_rows, dtype=np.uint8)
        else:
            info = np.iinfo(np_dtype(name))
            arr = rng.integers(info.min // 2, info.max // 2, n_rows,
                               dtype=np_dtype(name))
        valid = (rng.random(n_rows) < valid_share
                 if null_every and i % null_every == 0 else None)
        cols.append((name, arr, valid))
    return cols
