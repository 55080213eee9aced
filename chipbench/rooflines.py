"""Least bytes a call has to move through HBM, from the configuration's
shapes alone.  Each is a lower bound (whatever implements the call moves at
least this much), so a share of the roofline computed from it can only read
too low, never above 100%.
"""

from __future__ import annotations

from .datagen import np_dtype
from .references import jcudf_fixed_layout


def schema(config: dict) -> list[str]:
    cycle = config["type_cycle"]
    return [cycle[i % len(cycle)] for i in range(config["columns"])]


def transcode_roundtrip(config: dict, facts: dict | None = None) -> int:
    """One ``convert_to_rows`` + ``convert_from_rows``: each direction reads
    its input once and writes its output once — 2 x (column payload + one
    validity byte a row for every nullable column + JCUDF row bytes)."""
    n, n_cols = config["rows"], config["columns"]
    names = schema(config)
    payload = sum(np_dtype(t).itemsize for t in names)
    every = config["null_every"]
    nullable = len(range(0, n_cols, every)) if every else 0
    row = jcudf_fixed_layout(names)[4]
    return 2 * n * (payload + nullable + row)


def row_bytes(config: dict) -> int:
    """JCUDF row bytes of one batch of the configuration's table."""
    return config["rows"] * jcudf_fixed_layout(schema(config))[4]


def q6_scan(config: dict, facts: dict) -> int:
    """One q6 scan: the file's bytes land in HBM once, the four decoded
    columns (i64, f64, f64, i32) are written once and read once."""
    return facts["parquet_bytes"] + 2 * config["rows"] * (8 + 8 + 8 + 4)


BYTES = {"transcode_roundtrip": transcode_roundtrip, "q6_scan": q6_scan}
