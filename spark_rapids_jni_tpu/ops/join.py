"""Equi-joins (libcudf hash-join analog, join engine v2).

TPU-first design choice: libcudf joins via GPU hash tables (open addressing,
random scatter) — a poor fit for the VPU/MXU.  Engine v2 probes through a
planner-selected build-side index (``ops.join_plan``):

* **dense direct lookup** — for dense integer key ranges (TPC-DS surrogate
  keys) a ``(span,)`` CSR lookup table turns each probe into one gather;
  with unique build keys the pair-expansion step is skipped entirely.
* **sort-probe** — the fallback for sparse/float/string keys: sort the
  build side once, then find each probe key's run of equal build keys.

``join_plan.probe_counts`` probes in one of three ways, picked from the
index it is handed: ``dense`` — two gathers from the CSR window (a dense
index); ``compare`` — count the build keys below / equal to each probe
key, a fused compare-and-sum with no gather (a sorted index of at most
``join_plan.COMPARE_PROBE_MAX_KEYS`` keys); ``bsearch`` — two
``jnp.searchsorted``, each a scan of ``ceil(log2(n+1))`` levels whose
body is a probe-length gather from the key table (a larger sorted index).

Both index kinds return identical (lo, counts, row_ids) probe results, so
this module's match-expansion tail — the only dynamically-sized step, its
total resolved with one scalar sync per the two-phase discipline — is
shared, and the engines produce bit-identical join indices.  Build-side
indexes are cached on column-buffer identity (``join_plan.build_index``).

The expansion turns per-probe-row match counts into one output row per
pair, probe-row-major and in build order within a row.  Which probe row
owns pair ``j``, and ``j``'s place in that row's run, is the inverse of the
counts' running sum: ``select.owners``, a block select made of dense passes,
a fused compare-count over block firsts and one gather of whole rows (no
binary search over the probe side, no element gathered from it).  It takes
the pairs in one chunk or several by the pair count alone and syncs
nothing; ``join.expand.select.<block|chunked>`` counts which.

Join keys: any fixed-width column, or a LIST of key columns (multi-column
equi-join — tuple equality, a null in ANY key column never matches).
Multi-column keys are planned by ``join_plan.plan_keys``: dense-eligible
tuples range-compress into one int64 composite riding the single-key
engines unchanged; everything else probes on a 64-bit fingerprint and this
module verifies true lane equality on the candidate pairs.
"""

from __future__ import annotations

from typing import Literal, Sequence, Union

import jax.numpy as jnp
import numpy as np

from ..column import Column, Table
from ..memory import arena
from ..memory.budget import PAIR_EXPANSION_BYTES
from ..utils import metrics, syncs
from . import join_plan, select
from .filter import gather, sized_nonzero

JoinKey = Union[Column, Sequence[Column]]
OnKey = Union[int, Sequence[int]]


def _key_with_nulls_last(col: Column):
    """Key lane where null rows are moved past any real key (never match)."""
    if col.dtype.id.name == "FLOAT64":
        # Compare the stored bit pattern, not decoded values: on TPU
        # ``from_bits`` carries ~48 mantissa bits, so two distinct doubles
        # can decode equal.  The canonicalized (-0.0 == 0.0, all NaNs one
        # value — Spark join equality) monotone bits→uint map keeps both
        # order and equality exact with zero f64 arithmetic.
        from ..utils.f64bits import ordered_key_u64
        return ordered_key_u64(col.data), col.validity
    data = col.values()
    if col.validity is None:
        return data, None
    return data, col.validity


def _as_key_cols(key) -> list:
    return list(key) if isinstance(key, (list, tuple)) else [key]


def join_indices(left: JoinKey, right: JoinKey,
                 how: Literal["inner", "left", "semi", "anti"] = "inner"):
    """Compute (left_idx, right_idx) gather maps for an equi-join.

    Each side takes one key Column or an equal-length list of key columns
    (multi-column equi-join).  ``semi``/``anti`` return only left_idx.
    ``left`` outer marks unmatched rows with right_idx == -1 (callers
    null-fill on gather).
    """
    with metrics.span("join.indices", how=how):
        return _join_indices(_as_key_cols(left), _as_key_cols(right), how)


def _join_indices(lcols: list, rcols: list, how: str):
    # plan the probe lanes (string encode / composite pack / fingerprint),
    # then index the build (right) side — planner-selected layout, memoized
    # on the key buffers' identity; null build keys are dropped outright
    plan = join_plan.plan_keys(lcols, rcols)
    ix = join_plan.build_index(plan.rdata, plan.rvalid, plan.dense_ok)
    if metrics.recording() and ix.max_run > 0:
        # hottest build key's row count — the AQE skew signal (free: the
        # dense uniqueness test already synced it)
        metrics.observe("join.build_index.max_run", ix.max_run)
    lo, counts = join_plan.probe_counts(ix, plan.ldata, plan.lvalid)
    nr = ix.row_ids.shape[0]

    if plan.verify:
        # hashed probe lane: counts are CANDIDATE counts — every output
        # below must reject fingerprint collisions first
        return _verified_join(plan, ix, lo, counts, how)
    ldata, lvalid = plan.ldata, plan.lvalid

    if how in ("semi", "anti"):
        # two-phase like every dynamic size (count sync → sized nonzero) so
        # the whole plan stays traceable under capture/replay
        m = (counts > 0) if how == "semi" else (counts == 0)
        k = syncs.scalar(jnp.sum(m))
        return sized_nonzero(m, k)

    if ix.unique and nr > 0:
        # unique build keys: each probe row matches ≤ 1 build row — no pair
        # expansion, the match mask IS the output
        pos = jnp.minimum(lo, nr - 1)
        if how == "inner":
            total = syncs.scalar(jnp.sum(counts))   # scalar sync (pair count)
            if metrics.recording():
                metrics.observe("join.match_rows", total)
            metrics.profile_op("join", engine=ix.kind, how=how,
                               probe=join_plan.probe_kind(ix),
                               match_rows=total, unique_build=True)
            left_idx = sized_nonzero(counts > 0, total)
            right_idx = ix.row_ids[pos[left_idx]]
            return left_idx, right_idx
        left_idx = jnp.arange(ldata.shape[0], dtype=jnp.int64)
        right_idx = jnp.where(counts > 0, ix.row_ids[pos], -1)
        return left_idx, right_idx

    if how == "left":
        # match count needs its own sync here (total below includes the
        # unmatched keep-one rows); unconditional so capture/replay tapes
        # never depend on metrics state
        matched_rows = syncs.scalar(jnp.sum(counts))
        out_counts = jnp.maximum(counts, 1)   # unmatched keep one row
    else:
        matched_rows = None
        out_counts = counts

    total = syncs.scalar(jnp.sum(out_counts))     # scalar sync (pair count)
    if metrics.recording():
        # the ephemeral pair-expansion buffer (~10× input on skewed keys)
        # is the HBM-arena pressure point — ROADMAP open item
        metrics.count("join.expand.calls")
        metrics.count(f"join.expand.select.{select.form(total)}")
        metrics.observe("join.expand.pair_elements", total)
        metrics.observe("join.match_rows",
                        total if matched_rows is None else matched_rows)
        metrics.annotate(expand_pairs=total)
    metrics.profile_op(
        "join", engine=ix.kind, how=how, probe=join_plan.probe_kind(ix),
        expand_pairs=total,
        match_rows=total if matched_rows is None else matched_rows)
    with _expansion(ldata.shape[0], total):
        left_idx, within = select.owners(out_counts, total)
        if nr == 0:
            right_idx = jnp.full(left_idx.shape, -1, dtype=jnp.int64)
        elif how == "left":
            matched = within < counts[left_idx]
            r_pos = lo[left_idx] + jnp.where(matched, within, 0)
            right_idx = jnp.where(
                matched, ix.row_ids[jnp.minimum(r_pos, nr - 1)], -1)
        else:
            # inner: every pair is a match, so the gather of its row's
            # count (an element a pair from the probe side) has no reader;
            # 2% of star_streams4's sql_qps (PERF.md section 6, PR 35)
            r_pos = lo[left_idx] + within
            right_idx = ix.row_ids[jnp.minimum(r_pos, nr - 1)]
        return left_idx, right_idx


def _expansion(n: int, total: int):
    """Admission-control the ephemeral working set of expanding ``total``
    pairs over n probe rows (the int64 lanes and mask of the tail, and what
    ``select.owners`` holds) before XLA materializes it; under pressure this
    spills LRU arena residents first (soft: an admitted query completes)."""
    return arena.reserve(
        total * PAIR_EXPANSION_BYTES + select.temp_bytes(n, total),
        tag="join.expand")


def _pair_candidates(ix, lo, counts):
    """Aligned (probe_row, build_row) candidate pairs from probe results —
    the shared inner-pair enumeration: unique-build rows come straight off
    the scatter LUT, everything else runs the arena-admitted expansion."""
    nr = ix.row_ids.shape[0]
    total = syncs.scalar(jnp.sum(counts))         # scalar sync (pair count)
    if nr == 0 or total == 0:
        z = jnp.zeros(0, jnp.int64)
        return z, z
    if ix.unique:
        left_idx = sized_nonzero(counts > 0, total)
        right_idx = ix.row_ids[jnp.minimum(lo, nr - 1)[left_idx]]
        return left_idx, right_idx
    if metrics.recording():
        metrics.count("join.expand.calls")
        metrics.count(f"join.expand.select.{select.form(total)}")
        metrics.observe("join.expand.pair_elements", total)
    with _expansion(counts.shape[0], total):
        left_idx, within = select.owners(counts, total)
        r_pos = lo[left_idx].astype(jnp.int64) + within
        right_idx = ix.row_ids[jnp.minimum(r_pos, nr - 1)]
        return left_idx, right_idx


def _verified_join(plan, ix, lo, counts, how: str):
    """Fingerprint/fallback tail: enumerate candidate pairs on the hashed
    probe lane, then keep only pairs where EVERY true key lane matches —
    fingerprint collisions are rejected before any output is built."""
    li, ri = _pair_candidates(ix, lo, counts)
    eq = jnp.ones(li.shape[0], jnp.bool_)
    for ll, rl in plan.verify:
        eq = eq & (ll[li] == rl[ri])
    kept = syncs.scalar(jnp.sum(eq))         # scalar sync (verified pairs)
    if metrics.recording():
        metrics.count("join.verify.candidates", int(li.shape[0]))
        metrics.count("join.verify.collisions", int(li.shape[0]) - kept)
        if how in ("inner", "left"):
            metrics.observe("join.match_rows", kept)
    metrics.profile_op("join", engine=ix.kind, how=how,
                       probe=join_plan.probe_kind(ix),
                       candidates=int(li.shape[0]), match_rows=kept)
    sel = sized_nonzero(eq, kept)
    li, ri = li[sel], ri[sel]
    if how == "inner":
        return li, ri
    n = plan.ldata.shape[0]
    has = jnp.zeros(n, jnp.bool_).at[li].set(True)
    if how in ("semi", "anti"):
        m = has if how == "semi" else ~has
        k = syncs.scalar(jnp.sum(m))
        return sized_nonzero(m, k)
    # left outer: verified pairs plus one null-extended row per unmatched
    # probe row, restored to probe-row-major order (the expansion tail's
    # output order) by a stable sort on the left index
    miss = ~has
    nm = syncs.scalar(jnp.sum(miss))
    mi = sized_nonzero(miss, nm)
    left_idx = jnp.concatenate([li, mi])
    right_idx = jnp.concatenate([ri, jnp.full(nm, -1, jnp.int64)])
    order = jnp.argsort(left_idx, stable=True)
    return left_idx[order], right_idx[order]


def _key_of(t: Table, on: OnKey):
    return [t[i] for i in on] if isinstance(on, (list, tuple)) else t[on]


def inner_join(left: Table, right: Table, left_on: OnKey,
               right_on: OnKey) -> Table:
    """Inner equi-join; result columns = left columns ++ right columns.
    ``left_on``/``right_on``: one column index or equal-length lists."""
    li, ri = join_indices(_key_of(left, left_on), _key_of(right, right_on),
                          "inner")
    lt = gather(left, li)
    rt = gather(right, ri)
    return Table(list(lt.columns) + list(rt.columns))


def _empty_column(dt) -> Column:
    from .. import types as T
    if dt.id == T.TypeId.LIST:
        return Column(dt, arena.zeros(0, jnp.uint8), arena.zeros(1, jnp.int32),
                      None, [_empty_column(dt.children[0])])
    if dt.id == T.TypeId.STRUCT:
        return Column(dt, arena.zeros(0, jnp.uint8), None, None,
                      [_empty_column(f) for f in dt.children])
    if dt.is_variable_width:
        return Column(dt, arena.zeros(0, jnp.uint8), arena.zeros(1, jnp.int32))
    if dt.id == T.TypeId.DECIMAL128:
        return Column(dt, arena.zeros((0, 2), jnp.int64))
    if dt.id == T.TypeId.FLOAT64:     # bit-pair storage invariant
        return Column(dt, arena.zeros((0, 2), jnp.uint32))
    return Column(dt, arena.zeros(0, dt.storage))


def _null_column(dt, n: int) -> Column:
    from .. import types as T
    nulls = arena.zeros(n, jnp.bool_)
    if dt.id == T.TypeId.LIST:
        return Column(dt, arena.zeros(0, jnp.uint8),
                      arena.zeros(n + 1, jnp.int32), nulls,
                      [_empty_column(dt.children[0])])
    if dt.id == T.TypeId.STRUCT:
        return Column(dt, arena.zeros(0, jnp.uint8), None, nulls,
                      [_null_column(f, n) for f in dt.children])
    if dt.is_variable_width:
        return Column(dt, arena.zeros(0, jnp.uint8),
                      arena.zeros(n + 1, jnp.int32), nulls)
    if dt.id == T.TypeId.DECIMAL128:
        return Column(dt, arena.zeros((n, 2), jnp.int64), validity=nulls)
    if dt.id == T.TypeId.FLOAT64:     # bit-pair storage invariant
        return Column(dt, arena.zeros((n, 2), jnp.uint32), validity=nulls)
    return Column(dt, arena.zeros(n, dt.storage), validity=nulls)


def left_join(left: Table, right: Table, left_on: OnKey,
              right_on: OnKey) -> Table:
    """Left outer equi-join; unmatched right columns become null."""
    li, ri = join_indices(_key_of(left, left_on), _key_of(right, right_on),
                          "left")
    lt = gather(left, li)
    if right.num_rows == 0:   # nothing to gather — all-null right columns
        right_cols = [_null_column(c.dtype, int(li.shape[0]))
                      for c in right.columns]
        return Table(list(lt.columns) + right_cols)
    matched = ri >= 0
    rt = gather(right, jnp.maximum(ri, 0))

    def _with_matched(c):
        # deferred like the gather itself: the validity AND must not force
        # columns the plan never reads
        from ..column import LazyColumn, force_column

        def thunk(c=c):
            g = force_column(c)
            v = matched if g.validity is None else (g.validity & matched)
            return Column(g.dtype, g.data, g.offsets, v, g.children)
        return LazyColumn(c.dtype, c.num_rows, thunk)

    return Table(list(lt.columns) + [_with_matched(c) for c in rt.columns])


def right_join(left: Table, right: Table, left_on: OnKey,
               right_on: OnKey) -> Table:
    """Right outer equi-join; result columns = left ++ right, with null
    left columns on unmatched right rows."""
    mirrored = left_join(right, left, right_on, left_on)
    cols = list(mirrored.columns)            # right ++ left
    return Table(cols[right.num_columns:] + cols[:right.num_columns])


def full_outer_join(left: Table, right: Table, left_on: OnKey,
                    right_on: OnKey) -> Table:
    """Full outer equi-join: left-join pairs plus unmatched right rows with
    null left columns (Spark FULL OUTER)."""
    from .copying import concat_tables
    lj = left_join(left, right, left_on, right_on)
    extra = anti_join(right, left, right_on, left_on)
    if extra.num_rows == 0:
        return lj
    null_left = [_null_column(c.dtype, extra.num_rows) for c in left.columns]
    return concat_tables([lj, Table(null_left + list(extra.columns))])


def semi_join(left: Table, right: Table, left_on: OnKey,
              right_on: OnKey) -> Table:
    return gather(left, join_indices(_key_of(left, left_on),
                                     _key_of(right, right_on), "semi"))


def anti_join(left: Table, right: Table, left_on: OnKey,
              right_on: OnKey) -> Table:
    return gather(left, join_indices(_key_of(left, left_on),
                                     _key_of(right, right_on), "anti"))
