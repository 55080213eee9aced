"""Least bytes a round trip of the strings cell has to move through HBM:
shape-fixed and blind to what implements it, so a share computed from it
can only read too low."""

from __future__ import annotations

from .datagen import np_dtype


def schema(config: dict) -> list[str]:
    cycle = config["type_cycle"]
    return [cycle[i % len(cycle)] for i in range(config["columns"])]


def strings_roundtrip(config: dict, facts: dict) -> int:
    """One ``convert_to_rows`` + ``convert_from_rows``: each direction reads
    its input once and writes its output once — 2 x (fixed payload + one
    validity byte a row for every nullable column + 4 B x (rows + 1) of
    offsets a string column + chars + row bytes + 4 B x (rows + 1) of row
    offsets).  ``facts``: ``row_bytes``, ``char_bytes`` of the run's
    table."""
    n, names = config["rows"], schema(config)
    payload = sum(np_dtype(t).itemsize for t in names if t != "string")
    strings = sum(t == "string" for t in names)
    every = config["null_every"]
    nullable = len(range(0, len(names), every)) if every else 0
    return 2 * (n * (payload + nullable) + 4 * (n + 1) * (strings + 1)
                + facts["char_bytes"] + facts["row_bytes"])


BYTES = {"strings_roundtrip": strings_roundtrip}
