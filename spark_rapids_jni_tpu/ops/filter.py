"""Row filtering (libcudf apply_boolean_mask / copy_if analog).

Two-phase shape discipline, same as the string path (SURVEY §7 step 4):
dynamic result sizes don't exist under XLA, so filtering is

  phase 1 (device): predicate → bool mask → count (one scalar sync)
  phase 2 (device): statically-shaped gather of the surviving rows

For fully-jitted pipelines that must avoid the sync, ``mask_table`` keeps
the static shape and marks filtered-out rows invalid instead — aggregations
honor validity, so scan→filter→agg plans (TPC-H q6 shape) never compact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..column import Column, DictColumn, Table, as_dict_column
from .select import owners


def _segment_gather(offs: jnp.ndarray, idx: jnp.ndarray):
    """Element indices + new offsets for gathering variable-width segments."""
    lens = (offs[1:] - offs[:-1])[idx]
    new_offs = jnp.concatenate([jnp.zeros(1, lens.dtype), jnp.cumsum(lens)])
    from ..utils import syncs
    total = syncs.scalar(new_offs[-1])   # size resolution (capture/replay)
    starts = offs[:-1][idx]
    # marker-cumsum segment lookup, not a per-char binary search — same
    # cliff fix as DictColumn.materialize (string gathers walk every char)
    from ..rowconv.convert import _segment_of
    elem_ids = jnp.arange(total, dtype=jnp.int64)
    row_of = _segment_of(new_offs.astype(jnp.int32), int(total))
    src = starts.astype(jnp.int64)[row_of] + (
        elem_ids - new_offs.astype(jnp.int64)[row_of])
    return src, new_offs.astype(jnp.int32)


def _gather_column(col: Column, idx: jnp.ndarray) -> Column:
    d = as_dict_column(col)
    if d is not None:
        # codes gather only — the dictionary is shared, bytes stay unread,
        # and (unlike the plain STRING branch) there is no size sync
        from ..utils import metrics
        metrics.count("strings.dict.gather")
        dv = None if d.validity is None else d.validity[idx]
        return DictColumn(d.codes[idx], d.dictionary, dv,
                          sorted_dict=d.sorted_dict)
    v = None if col.validity is None else col.validity[idx]
    if col.dtype.id == T.TypeId.STRUCT:
        return Column(col.dtype, col.data, None, v,
                      [_gather_column(ch, idx) for ch in col.children])
    if col.dtype.id == T.TypeId.LIST:
        src, new_offs = _segment_gather(col.offsets, idx)
        return Column(col.dtype, col.data, new_offs, v,
                      [_gather_column(col.children[0], src)])
    if col.dtype.is_variable_width:   # STRING: chars live in .data
        src, new_offs = _segment_gather(col.offsets, idx)
        return Column(col.dtype, col.data[src], new_offs, v)
    return Column(col.dtype, col.data[idx], validity=v)


def gather(table: Table, idx: jnp.ndarray) -> Table:
    """Gather rows by index (libcudf gather analog).

    Columns come back LAZY (:class:`~..column.LazyColumn`): each
    materializes on first payload access, so plan tails that only read a
    few columns never pay the others' gathers — or, for string columns,
    their size-resolution syncs.  This is the structural projection pass
    that keeps wide joins from materializing (and OOMing on) columns the
    query never references.
    """
    from ..column import LazyColumn
    n_out = int(idx.shape[0])
    # DictColumns gather EAGERLY: a codes gather is one cheap fixed-width
    # take with no size sync, and staying a concrete DictColumn (not a lazy
    # wrapper) keeps the dictionary visible across jit boundaries
    return Table([
        _gather_column(c, idx) if isinstance(c, DictColumn) else
        LazyColumn(c.dtype, n_out,
                   (lambda c=c: _gather_column(c, idx)))
        for c in table.columns])


def sized_nonzero(mask: jnp.ndarray, n_keep: int) -> jnp.ndarray:
    """Ascending indices of the True rows, shaped ``[n_keep]``.

    Every dynamic-size site is two-phase (count sync, then sized
    selection), so by the time this runs the mask is usually concrete —
    and then a host ``np.flatnonzero`` is a single linear pass.  Under a
    trace (capture/replay) the mask is a tracer and the selection has to
    be jittable: "which row owns output j" with the mask as the counts
    (``select.owners``: dense work), where ``jnp.nonzero(mask, size=)``
    lowers to a scatter-add of one update a mask row (0.9 s over a
    10M-row mask on a v5e: PERF.md section 6, PR 35).  Parity is preserved —
    same ascending order, same zero padding when the clamped size exceeds
    the population count.
    """
    if isinstance(mask, jax.core.Tracer):
        idx, _ = owners(mask, n_keep)
        return jnp.where(jnp.arange(n_keep) < jnp.sum(mask), idx, 0)
    idx = np.flatnonzero(np.asarray(mask))
    if idx.shape[0] >= n_keep:
        idx = idx[:n_keep]
    else:
        idx = np.pad(idx, (0, n_keep - idx.shape[0]))
    # same int64 index dtype the sized device lowering produces (x64 on)
    return jnp.asarray(idx)


def apply_boolean_mask(table: Table, mask: jnp.ndarray) -> Table:
    """Keep rows where mask is True (compacting; one host sync for the count)."""
    from ..utils import metrics, syncs
    n_keep = syncs.scalar(jnp.sum(mask))   # counted host sync (dynamic size)
    metrics.profile_op("filter", rows_in=table.num_rows, rows_kept=n_keep)
    idx = sized_nonzero(mask, n_keep)
    return gather(table, idx)


def mask_table(table: Table, mask: jnp.ndarray) -> Table:
    """Filter without compaction: failing rows become invalid (null).

    Static-shaped, fully jittable; downstream reductions/groupbys honor
    validity so results match the compacting filter.  Deferred per column
    (see ``gather``) so masking a wide table doesn't force unread columns.
    """
    from ..column import LazyColumn, force_column

    def mk(c):
        if isinstance(c, DictColumn):   # eager: validity AND only, no bytes
            v = mask if c.validity is None else (c.validity & mask)
            return DictColumn(c.codes, c.dictionary, v,
                              sorted_dict=c.sorted_dict)

        def thunk(c=c):
            g = force_column(c)
            if isinstance(g, DictColumn):
                v = mask if g.validity is None else (g.validity & mask)
                return DictColumn(g.codes, g.dictionary, v,
                                  sorted_dict=g.sorted_dict)
            v = mask if g.validity is None else (g.validity & mask)
            return Column(g.dtype, g.data, g.offsets, v, g.children)
        return LazyColumn(c.dtype, c.num_rows, thunk)

    return Table([mk(c) for c in table.columns])


def fill_null(col: Column, value) -> Column:
    """Replace nulls with a scalar (Spark ``coalesce(col, lit)`` / cudf
    ``replace_nulls``).  Fixed-width columns only."""
    if (col.dtype.is_variable_width or col.dtype.is_nested
            or col.dtype.id == T.TypeId.DECIMAL128):
        raise TypeError(f"fill_null not supported on {col.dtype.id.name}")
    if col.validity is None:
        return col
    if col.dtype.id == T.TypeId.FLOAT64:   # bit-pair storage: fill with bits
        from ..utils import f64bits
        fill = jnp.asarray(f64bits.np_to_bits(
            np.asarray([value], np.float64))[0])
        data = jnp.where(col.validity[:, None], col.data, fill[None, :])
    else:
        data = jnp.where(col.validity, col.data,
                         jnp.asarray(value, col.data.dtype))
    return Column(col.dtype, data, validity=None)


def isin(col: Column, values) -> jnp.ndarray:
    """Null-safe SQL ``col IN (v1, v2, …)`` mask (Spark semantics: null
    rows yield False).  Fixed-width columns probe a sorted value list with
    one searchsorted; string columns OR a few vectorized equality passes
    (IN-lists are short in practice)."""
    if col.dtype.id == T.TypeId.STRING:
        from . import strings
        d = as_dict_column(col)
        if d is not None:
            # membership once per dictionary entry, then gather by code
            from ..utils import metrics
            metrics.count("strings.dict.predicate")
            nd = d.dictionary.num_rows
            if nd == 0:
                m = jnp.zeros(d.codes.shape, bool)
            else:
                dm = isin(d.dictionary, values)
                m = dm[jnp.clip(d.codes, 0, nd - 1)]
            metrics.count("strings.dict.gather")
            if d.validity is not None:
                m = m & d.validity
            return m
        payloads = [v.encode() if isinstance(v, str) else bytes(v)
                    for v in values if v is not None]
        m = jnp.zeros(col.num_rows, bool)
        if payloads:
            # one shared byte matrix; per-value compare is a masked row-AND
            mat, lens = strings._search_matrix(
                col, max(len(p) for p in payloads))
            for p in payloads:
                eq = jnp.asarray(lens == len(p))
                for k, b in enumerate(p):
                    eq = eq & (mat[:, k] == b)
                m = m | eq
    elif col.dtype.is_nested or col.dtype.id == T.TypeId.DECIMAL128:
        raise NotImplementedError(f"isin on {col.dtype.id.name}")
    elif col.dtype.id == T.TypeId.FLOAT64:
        # Membership on the canonicalized bit lanes, not decoded values: on
        # TPU ``from_bits`` carries ~48 mantissa bits, so two distinct
        # doubles can decode equal and match spuriously.  Probes are
        # bit-converted on host (exact) with the same canonicalization as
        # ``group_key_lanes`` (-0.0 == 0.0, all NaNs one value — Spark
        # equality, under which NaN IN (NaN) is true).
        from ..utils.f64bits import equality_key_u64, np_equality_key_u64
        probes = []
        for v in values:
            if v is None:
                continue
            try:
                fv = np.float64(v)
            except (OverflowError, ValueError, TypeError):
                continue
            if np.isnan(fv) or fv == v or isinstance(v, float):
                probes.append(fv)
        if not probes:
            m = jnp.zeros(col.num_rows, bool)
        else:
            pb = np_equality_key_u64(np.asarray(probes, np.float64))
            key = equality_key_u64(col.data)
            vals = jnp.sort(jnp.asarray(np.unique(pb)))
            pos = jnp.clip(jnp.searchsorted(vals, key), 0, vals.shape[0] - 1)
            m = vals[pos] == key
    else:
        # keep only probes that survive an EXACT round trip into the
        # column's storage dtype — a lossy cast (3.5 → 3 into int32, or an
        # out-of-range literal) must match nothing, not its truncation;
        # None (SQL NULL) literals never match non-null rows
        storage = col.dtype.storage
        kept = []
        for v in values:
            if v is None:
                continue
            try:
                cast_v = storage.type(v)
            except (OverflowError, ValueError, TypeError):
                continue
            if cast_v == v:
                kept.append(cast_v)
        if not kept:
            return jnp.zeros(col.num_rows, bool)
        vals = jnp.sort(jnp.asarray(np.asarray(kept, storage)))
        cdata = col.values()   # FLOAT64 bit pairs decode to f64 values
        pos = jnp.clip(jnp.searchsorted(vals, cdata), 0,
                       vals.shape[0] - 1)
        m = vals[pos] == cdata
    if col.validity is not None:
        m = m & col.validity
    return m
