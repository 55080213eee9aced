"""Join planning: build-side indexes, dense-key direct lookup, index
caching, and join→aggregate fusion (join engine v2).

Join-strategy heuristic (the planner)
-------------------------------------
:func:`build_index` inspects the build (right) side once and picks between
two physical index layouts.  Both expose the same probe interface —
``(lo, counts)`` positions into a key-sorted ``row_ids`` array — so the
match-expansion tail in ``ops.join`` is shared and the engines produce
bit-identical indices:

* **dense** — eligible when both key columns are fixed-width integer-kind
  (ints, dates/timestamps, decimal32/64 raw payloads, dictionary codes
  from string keys; NOT float bit-keys, decimal128 limbs, or uint64) and
  the observed build key span ``kmax - kmin + 1`` satisfies
  ``span <= max(DENSE_SPAN_FACTOR * n_valid, DENSE_SPAN_FLOOR)`` and
  ``span <= DENSE_SPAN_CAP``.  A ``(span,)`` CSR lookup table
  (slot → start offset + run length into key-sorted ``row_ids``) is
  materialized once; probing is one subtract + clip + two gathers
  (``join.probe.dense``).  TPC-DS surrogate keys are contiguous
  integers, so unfiltered star joins take this path.  When every slot
  holds at most one build row (``unique``) the index is built by direct
  scatter (no sort at all) and ``ops.join`` skips pair expansion entirely.
* **sorted** — the fallback for sparse/float/string/128-bit keys, and
  for a filtered dimension whose few keys span many ids: a stable key
  sort, probed one of two ways by the build's size, which the index
  carries (:func:`probe_kind`).  At most ``COMPARE_PROBE_MAX_KEYS`` keys:
  count the keys below / not above each probe key, a compare fused into a
  sum over the key axis, no gather (``join.probe.compare``).  More: two
  ``jnp.searchsorted``, each a scan of ``ceil(log2(n+1))`` levels whose
  body gathers once per probe row from the key table
  (``join.probe.bsearch``) — on the chip a gather costs the same 8.6 ns a
  row from a 212-entry table as from a large one.

``join.engine.<kind>`` and ``join.probe.<kind>`` tick where the Python
runs: once per join in eager execution, and under whole-query replay
once per plan, at its capture run (a replay re-trace records nothing,
``utils/metrics.py``), not once per request.

The span bounds (``kmin``/``kmax``), the valid-row count, and the
uniqueness bit all resolve through ``syncs.scalar``, so the planner's
branch decisions replay identically under ``models/compiled.py``
capture/replay, and the compiled-plan staleness guard re-derives them
against refreshed data (a key-range drift raises ``StaleTapeError``
instead of silently probing the wrong window).

Build-side index cache
----------------------
Indexes are memoized on the key buffers' device-array identity
(weakref'd, entries drop with the arrays, and the cache is automatically
disabled under capture/replay so tapes stay aligned).  A dimension table
is therefore sorted/indexed ONCE per process and reused across every
join of every query in a suite run.

Since the HBM-arena PR the cache is capacity-bounded and evictable: each
entry's device footprint is LRU-tracked against ``SRJT_INDEX_CACHE_CAP``
(cap overflow drops the LRU entry — ``join.build_index.evictions``), and
when the arena is enabled entries register with ``memory.spill`` so
budget pressure moves their lanes to host RAM; a later cache hit faults
them back bit-exactly (``join.build_index.faultback``).
"""

from __future__ import annotations

import contextlib
import os
import threading
import weakref
from collections import OrderedDict
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..analysis import sanitize
from ..column import Column, Table, as_dict_column, force_column
from ..utils import knobs, metrics, syncs
from .filter import sized_nonzero

DENSE_SPAN_FACTOR = 2
DENSE_SPAN_FLOOR = 4096
DENSE_SPAN_CAP = 1 << 23
# A sorted index of at most this many build keys is probed by counting
# compares, a larger one by binary search (probe_kind).  Per probe row a
# binary search costs 2 * ceil(log2(n+1)) * g and the count 2 * n * c, with
# g the ns a row of one gather from the key table and c the ns a key a row
# of one fused compare-and-add.  On a v5e over a 10M-row int32 probe
# (tools/probe_rates.py, PERF.md section 6, PR 27): g = 8.6 at 212 keys,
# 7.54 from 4096 up; c = 0.0011 at 212, 0.00224 from 4096 up.  They meet
# where n / ceil(log2(n+1)) = 7.54 / 0.00224, n = 53866 at 16 levels; this
# is the power of two under it (measured there: the count 1470 ms, the
# search 2414; at 65536 the count loses, 2937 against 2565).
COMPARE_PROBE_MAX_KEYS = 1 << 15
# [n_valid, block] cells a backend that does not fuse the compare into the
# reduction holds at a time in _probe_compare: 64 MiB of int32 a side.
_UNFUSED_COMPARE_CELLS = 1 << 24

# THREAD-LOCAL: the exec runtime's degraded-admission path pins one
# request's joins to the low-footprint sorted engine from its worker
# thread; a process-global flag would leak the degradation into queries
# running concurrently on other workers.
_forced_tls = threading.local()    # .kind: None | "dense" | "sorted"


def forced_engine() -> Optional[str]:
    f = getattr(_forced_tls, "kind", None) \
        or knobs.get("SRJT_JOIN_ENGINE")
    return f if f in ("dense", "sorted") else None


@contextlib.contextmanager
def force_engine(kind: Optional[str]):
    """Pin the physical join engine ("dense" / "sorted"; None restores the
    planner heuristic) for the CURRENT THREAD — benchmark/test hook plus
    the exec runtime's degraded-admission routing (both engines produce
    bit-identical indices, so this only trades footprint for speed)."""
    old = getattr(_forced_tls, "kind", None)
    _forced_tls.kind = kind
    try:
        yield
    finally:
        _forced_tls.kind = old


class BuildIndex(NamedTuple):
    """Physical index over the build side's valid (non-null-key) rows."""
    kind: str                            # "dense" | "sorted"
    n_valid: int                         # valid build rows (static)
    row_ids: jnp.ndarray                 # [n_valid] key-sorted, stable
    sorted_keys: Optional[jnp.ndarray]   # [n_valid] (sorted kind only)
    kmin: int                            # dense: lookup-window base key
    span: int                            # dense: lut length (0 if sorted)
    lut_lo: Optional[jnp.ndarray]        # [span] slot → start into row_ids
    lut_cnt: Optional[jnp.ndarray]       # [span] slot → run length
    unique: bool                         # dense: every slot holds ≤ 1 row
    max_run: int = 0                     # dense: hottest key's row count
    #   (free: the uniqueness test already syncs max(lut_cnt); 0 = unknown
    #   on the sorted path).  The adaptive executor reads this as its skew
    #   signal — see skew_stats().


def _index_nbytes(ix: "BuildIndex") -> int:
    return sum(int(a.nbytes) for a in
               (ix.row_ids, ix.sorted_keys, ix.lut_lo, ix.lut_cnt)
               if a is not None)


class _IndexCache:
    """LRU build-index cache keyed on key-buffer identity, capacity-bound
    and arena-evictable (the fix for the PR 1 unbounded memo).

    * plain LRU over device bytes: inserting past ``SRJT_INDEX_CACHE_CAP``
      drops the least-recently-used entry (``join.build_index.evictions``).
    * arena tier (``SRJT_HBM_ARENA``/``SRJT_HBM_BUDGET`` set): entries
      register as ``memory.spill`` residents; budget pressure spills their
      lanes to host RAM, and the next cache hit faults them back.
    * entries die with their key arrays (weakref callbacks) and the cache
      is bypassed under syncs capture/replay, exactly like the old memo.

    Thread-safety: all map/byte-accounting mutation happens under the
    arena's ``budget._LOCK`` (an RLock).  That lock is deliberately SHARED
    with ``memory.spill``: the spiller closures below run inside
    ``spill.reclaim`` — which ``budget.charge`` invokes while holding the
    lock — so a private cache lock would deadlock ABBA against the
    register/unregister path.  Weakref death callbacks re-enter safely.
    """

    def __init__(self):
        self._d: "OrderedDict[tuple, dict]" = OrderedDict()
        self._device_bytes = 0

    @staticmethod
    def _lock():
        from ..memory import budget as mbudget
        return mbudget._LOCK

    @staticmethod
    def _cap() -> Optional[int]:
        from ..memory import budget as mbudget
        return mbudget.parse_bytes(knobs.get("SRJT_INDEX_CACHE_CAP"))

    def _drop(self, key, *, count_eviction: bool) -> None:
        from ..memory import spill as mspill
        with self._lock():
            e = self._d.pop(key, None)
            if e is None:
                return
            if not e["payload"].spilled:
                self._device_bytes -= e["nbytes"]
                mspill.unregister(("join_index",) + key)
        if count_eviction and metrics.recording():
            metrics.count("join.build_index.evictions")

    def get(self, tag: str, arrays) -> Optional["BuildIndex"]:
        if syncs.mode() != "normal":
            return None
        key = (tag,) + tuple(id(a) for a in arrays)
        from ..memory import spill as mspill
        with self._lock():
            e = self._d.get(key)
            if e is None:
                return None
            for r, a in zip(e["refs"], arrays):
                if r() is not a:
                    return None
            self._d.move_to_end(key)
            if not e["payload"].spilled:
                mspill.touch(("join_index",) + key)
                return e["value"]
            lanes = e["payload"].get()          # fault back (bit-exact)
            kind, n_valid, kmin, span, unique, max_run = e["meta"]
            e["value"] = BuildIndex(kind, n_valid, lanes["row_ids"],
                                    lanes["sorted_keys"], kmin, span,
                                    lanes["lut_lo"], lanes["lut_cnt"],
                                    unique, max_run)
            self._device_bytes += e["nbytes"]
            mspill.register(("join_index",) + key, e["nbytes"],
                            "join.build_index", e["payload"].spill)
            if metrics.recording():
                metrics.count("join.build_index.faultback")
            self._evict_over_cap(keep=key)
            return e["value"]

    def _evict_over_cap(self, keep=None) -> None:
        # caller holds the lock
        cap = self._cap()
        if cap is None:
            return
        while self._device_bytes > cap and len(self._d) > 1:
            lru = next(k for k in self._d if k != keep) \
                if keep is not None else next(iter(self._d))
            self._drop(lru, count_eviction=True)
            if lru == keep:
                break

    def put(self, tag: str, arrays, ix: "BuildIndex") -> None:
        if syncs.mode() != "normal":
            return
        key = (tag,) + tuple(id(a) for a in arrays)
        try:
            refs = tuple(
                weakref.ref(a, lambda _, k=key: self._drop(
                    k, count_eviction=False))
                for a in arrays)
        except TypeError:
            return
        from ..memory import spill as mspill
        payload = mspill.SpillableArrays(
            "join.build_index",
            {"row_ids": ix.row_ids, "sorted_keys": ix.sorted_keys,
             "lut_lo": ix.lut_lo, "lut_cnt": ix.lut_cnt})
        entry = {"refs": refs, "value": ix, "payload": payload,
                 "nbytes": payload.nbytes,
                 "meta": (ix.kind, ix.n_valid, ix.kmin, ix.span,
                          ix.unique, ix.max_run)}

        def _spiller(e=entry):
            with self._lock():                  # reentrant under reclaim
                freed = e["payload"].spill()
                if freed:
                    e["value"] = None           # drop the device refs
                    self._device_bytes -= e["nbytes"]
                return freed

        with self._lock():
            # two threads can miss-then-build the same key concurrently;
            # dropping the loser's entry first keeps the byte ledger exact
            self._drop(key, count_eviction=False)
            self._d[key] = entry
            self._device_bytes += entry["nbytes"]
            mspill.register(("join_index",) + key, entry["nbytes"],
                            "join.build_index", _spiller)
            self._evict_over_cap(keep=key)

    def clear(self) -> None:
        from ..memory import spill as mspill
        with self._lock():
            for key, e in list(self._d.items()):
                if not e["payload"].spilled:
                    mspill.unregister(("join_index",) + key)
            self._d.clear()
            self._device_bytes = 0

    def device_bytes(self) -> int:
        return self._device_bytes


_INDEX_CACHE = _IndexCache()


def dense_eligible(col: Column) -> bool:
    """Key dtypes the direct-lookup window arithmetic is exact for."""
    dt = col.dtype
    if dt.is_variable_width or dt.is_nested:
        return False
    if dt.id in (T.TypeId.FLOAT32, T.TypeId.FLOAT64, T.TypeId.DECIMAL128):
        return False
    sd = np.dtype(dt.storage)
    if sd.kind not in "iu":
        return False
    return not (sd.kind == "u" and sd.itemsize == 8)


def build_index(data: jnp.ndarray, valid, dense_ok: bool) -> BuildIndex:
    """Index the build side, memoized on the key buffers' identity
    (capacity-bound LRU; arena-evictable — see :class:`_IndexCache`)."""
    forced = forced_engine()
    tag = f"join_build_index:{forced or 'auto'}"
    key_arrays = (data,) if valid is None else (data, valid)
    hit = _INDEX_CACHE.get(tag, key_arrays)
    if hit is not None:
        if metrics.recording():
            metrics.count("join.build_index.cache_hit")
            metrics.count(f"join.engine.{hit.kind}")
        return hit
    with metrics.span("join.build_index"):
        ix = _build_index(data, valid, dense_ok and forced != "sorted",
                          forced == "dense")
        if metrics.recording():
            metrics.count("join.build_index.cache_miss")
            metrics.count(f"join.engine.{ix.kind}")
            metrics.annotate(engine=ix.kind, n_valid=ix.n_valid,
                             key_span=ix.span)
    _INDEX_CACHE.put(tag, key_arrays, ix)
    return ix


def _key_sorted_order(data, valid, n_valid: int):
    """Valid build rows in stable key-sorted order (ties keep original row
    order — the exact ``r_order`` the sort-probe engine produces)."""
    order = jnp.argsort(data, stable=True)
    if valid is None:
        return order, data[order]
    skeys = data[order]
    rank = jnp.where(valid, 0, 1)[order]
    rr = jnp.lexsort((skeys, rank))       # valid first, then key, stable
    return order[rr][:n_valid], skeys[rr][:n_valid]


def _build_index(data, valid, try_dense: bool, must_dense: bool):
    n = int(data.shape[0])
    n_valid = n if valid is None else syncs.scalar(jnp.sum(valid))
    kmin = span = 0
    dense = False
    if try_dense and n_valid > 0:
        info = np.iinfo(np.dtype(data.dtype))
        dmin = data if valid is None else jnp.where(valid, data, info.max)
        dmax = data if valid is None else jnp.where(valid, data, info.min)
        kmin = syncs.scalar(jnp.min(dmin))
        span = syncs.scalar(jnp.max(dmax)) - kmin + 1
        limit = DENSE_SPAN_CAP if must_dense else min(
            max(DENSE_SPAN_FACTOR * n_valid, DENSE_SPAN_FLOOR),
            DENSE_SPAN_CAP)
        dense = span <= limit
    if not dense:
        order, skeys = _key_sorted_order(data, valid, n_valid)
        return BuildIndex("sorted", n_valid, order, skeys, 0, 0, None, None,
                          False)
    slot64 = data.astype(jnp.int64) - kmin
    ok = jnp.ones(n, jnp.bool_) if valid is None else valid
    slot = jnp.clip(slot64, 0, span - 1).astype(jnp.int32)
    lut_cnt = jnp.zeros(span, jnp.int32).at[slot].add(ok.astype(jnp.int32))
    lut_lo = (jnp.cumsum(lut_cnt) - lut_cnt).astype(jnp.int32)
    max_run = syncs.scalar(jnp.max(lut_cnt))
    unique = max_run <= 1
    if unique:
        # no sort anywhere: each valid row scatters straight to its slot
        tgt = jnp.where(ok, lut_lo[slot].astype(jnp.int64),
                        jnp.int64(n_valid))
        row_ids = jnp.zeros(n_valid, jnp.int64).at[tgt].set(
            jnp.arange(n, dtype=jnp.int64), mode="drop")
    else:
        row_ids, _ = _key_sorted_order(data, valid, n_valid)
    return BuildIndex("dense", n_valid, row_ids, None, int(kmin), int(span),
                      lut_lo, lut_cnt, bool(unique), int(max_run))


def extend_build_index(ix: BuildIndex, delta_data, delta_valid,
                       base_n: int) -> Optional[BuildIndex]:
    """Append build rows ``[base_n, base_n + len(delta_data))`` to a dense
    index, reusing the existing CSR window instead of invalidate-and-
    rebuild: counts scatter-add into the same ``span`` slots, existing
    ``row_ids`` remap positionally, delta rows append after each slot's
    run.  The result is field-identical to ``_build_index`` over the
    concatenated keys whenever every valid appended key lands inside
    ``[kmin, kmin + span)`` — within a slot, old rows precede delta rows
    in original order, exactly the stable key-sorted order a rebuild
    produces.  Returns None when not applicable (sorted index, or an
    appended key escapes the window): the caller rebuilds."""
    if ix.kind != "dense":
        return None
    m = int(delta_data.shape[0])
    if m == 0:
        return ix
    d = delta_data.astype(jnp.int64) - ix.kmin
    ok = jnp.ones(m, jnp.bool_) if delta_valid is None else delta_valid
    in_win = (d >= 0) & (d < ix.span)
    if syncs.scalar(jnp.sum((~in_win) & ok)) > 0:
        if metrics.recording():
            metrics.count("join.build_index.extend_window_miss")
        return None
    m_valid = m if delta_valid is None else syncs.scalar(jnp.sum(ok))
    if m_valid == 0:
        return ix
    with metrics.span("join.build_index.extend", rows=m):
        slot = jnp.clip(d, 0, ix.span - 1).astype(jnp.int32)
        new_cnt = ix.lut_cnt.at[slot].add(ok.astype(jnp.int32))
        new_lo = (jnp.cumsum(new_cnt) - new_cnt).astype(jnp.int32)
        # remap existing key-sorted positions: position → slot via the old
        # cumulative counts, then shift by the slot's new start
        pos = jnp.arange(ix.n_valid, dtype=jnp.int32)
        cum_old = jnp.cumsum(ix.lut_cnt)
        old_slot = jnp.searchsorted(cum_old, pos, side="right") \
            .astype(jnp.int32)
        old_pos = new_lo[old_slot] + (pos - ix.lut_lo[old_slot])
        # delta rows stable-sorted by slot (invalid rows sort last via the
        # span sentinel and are sliced off), ranked within their run
        sort_key = jnp.where(ok, slot, jnp.int32(ix.span))
        dorder = jnp.argsort(sort_key, stable=True)[:m_valid]
        ds = sort_key[dorder]
        idxs = jnp.arange(m_valid, dtype=jnp.int32)
        head = jnp.concatenate([jnp.ones(1, jnp.bool_), ds[1:] != ds[:-1]])
        run_start = jax.lax.cummax(jnp.where(head, idxs, 0))
        delta_pos = new_lo[ds] + ix.lut_cnt[ds] + (idxs - run_start)
        n_total = ix.n_valid + m_valid
        row_ids = jnp.zeros(n_total, jnp.int64) \
            .at[old_pos].set(ix.row_ids) \
            .at[delta_pos].set(jnp.int64(base_n) + dorder.astype(jnp.int64))
        max_run = syncs.scalar(jnp.max(new_cnt))
        unique = bool(max_run <= 1)
        if metrics.recording():
            metrics.count("join.build_index.extended")
        return BuildIndex("dense", n_total, row_ids, None, ix.kmin, ix.span,
                          new_lo, new_cnt, unique, int(max_run))


def _compare_counts(sorted_keys, ldata):
    """``(lo, hi - lo)`` of the two binary searches, by counting the build
    keys below / not above each probe key.  ``compare_all`` uses the
    comparators of the default ``scan`` method, so both results are equal
    to its everywhere, for every dtype."""
    lo = jnp.searchsorted(sorted_keys, ldata, side="left",
                          method="compare_all")
    hi = jnp.searchsorted(sorted_keys, ldata, side="right",
                          method="compare_all")
    return lo, hi - lo


@jax.jit
def _probe_compare(sorted_keys, ldata):
    """:func:`_compare_counts` in one jit, also when the caller runs
    eagerly (a plan's capture run).  XLA:TPU fuses the compare into the
    sum over the key axis, so no ``[n_valid, n_probe]`` array exists
    (0 temp bytes at 212 keys x 10M rows, tools/probe_rates.py).  XLA:CPU
    does not fuse into a reduction and would hold two such arrays, so off
    the TPU the probe axis goes by in blocks of bounded size."""
    n, m = sorted_keys.shape[0], ldata.shape[0]
    block = m if jax.default_backend() == "tpu" \
        else max(_UNFUSED_COMPARE_CELLS // max(n, 1), 1)
    if m <= block:
        return _compare_counts(sorted_keys, ldata)
    nb = -(-m // block)
    q = jnp.pad(ldata, (0, nb * block - m)).reshape(nb, block)
    lo, counts = jax.lax.map(
        lambda qb: _compare_counts(sorted_keys, qb), q)
    return lo.reshape(-1)[:m], counts.reshape(-1)[:m]


def probe_kind(ix: BuildIndex) -> str:
    """How :func:`probe_counts` probes ``ix``: ``dense`` (two gathers from
    the CSR window), ``compare`` (a sorted index of at most
    ``COMPARE_PROBE_MAX_KEYS`` keys) or ``bsearch`` (a larger one)."""
    if ix.kind == "dense":
        return "dense"
    return "compare" if ix.n_valid <= COMPARE_PROBE_MAX_KEYS else "bsearch"


def probe_counts(ix: BuildIndex, ldata, lvalid):
    """Per probe row: (first match position into ``ix.row_ids``, match
    count).  On a dense index ``lo`` is unspecified where ``counts == 0``
    (callers guard); on a sorted one it is the insertion point, whichever
    of the two ways computes it."""
    kind = probe_kind(ix)
    if metrics.recording():
        metrics.count(f"join.probe.{kind}")
    if kind == "dense":
        d = ldata.astype(jnp.int64) - ix.kmin
        in_r = (d >= 0) & (d < ix.span)
        if lvalid is not None:
            in_r = in_r & lvalid
        slot = jnp.clip(d, 0, max(ix.span - 1, 0)).astype(jnp.int32)
        counts = jnp.where(in_r, ix.lut_cnt[slot], 0)
        return ix.lut_lo[slot], counts
    if kind == "compare":
        lo, counts = _probe_compare(ix.sorted_keys, ldata)
    else:
        lo = jnp.searchsorted(ix.sorted_keys, ldata, side="left")
        hi = jnp.searchsorted(ix.sorted_keys, ldata, side="right")
        counts = hi - lo
    if lvalid is not None:
        counts = jnp.where(lvalid, counts, 0)
    return lo, counts


def skew_stats(ix: BuildIndex) -> Optional[dict]:
    """Hot-key summary from the dense CSR histogram, or None when the
    index carries no histogram (sorted engine, or empty build side).

    ``skew`` is the hottest key's run length over the mean run length —
    the factor by which that key's pair expansion exceeds a uniform
    key's.  Derived entirely from values the build already synced
    (``n_valid`` and ``max_run``), so reading it costs nothing and is
    capture/replay consistent."""
    if ix.kind != "dense" or ix.max_run <= 0 or ix.n_valid <= 0:
        return None
    n_keys = max(1, ix.span)
    mean_run = ix.n_valid / n_keys
    return {"max_run": ix.max_run,
            "n_valid": ix.n_valid,
            "span": ix.span,
            "skew": ix.max_run / max(mean_run, 1.0)}


# --- multi-column key packing ------------------------------------------------


COMPOSITE_BITS = 63     # packed tuples must index as a non-negative int64


class KeyPlan(NamedTuple):
    """Physical probe plan for one (possibly multi-column) equi-join key.

    ``ldata``/``rdata`` are the single fixed-width lanes the engines
    consume; ``verify`` carries ``(left_lane, right_lane)`` pairs that
    candidate matches must additionally satisfy — empty when the probe
    lane alone encodes tuple equality exactly (single keys, composites)."""
    mode: str            # "single" | "composite" | "fingerprint" | "fallback"
    ldata: jnp.ndarray
    lvalid: Optional[jnp.ndarray]
    rdata: jnp.ndarray
    rvalid: Optional[jnp.ndarray]
    verify: tuple
    dense_ok: bool


def _and_valid(a, b):
    if a is None:
        return b
    return a if b is None else (a & b)


def _key_lanes(col: Column):
    """Fixed-width equality lanes for one (already string-encoded) key
    column: one int-kind lane for everything the single-key path probes,
    two int64 limb lanes for decimal128."""
    from .join import _key_with_nulls_last
    c = force_column(col)
    if c.dtype.id == T.TypeId.DECIMAL128:
        return [c.data[:, 0], c.data[:, 1]], c.validity
    data, valid = _key_with_nulls_last(c)
    return [data], valid


class _PlanCache:
    """Tiny LRU memo for multi-key pack plans, keyed on the key columns'
    device-buffer identity.  Without it every repeated multi-key probe
    would re-pack into FRESH composite arrays and the build-index cache
    (also identity-keyed) could never hit; with it the second probe of the
    same key buffers returns the same ``KeyPlan`` object and the index
    cache sees the same ``rdata`` buffer.  Bypassed under capture/replay
    for the same reason the index cache is: a memo hit would skip the
    window ``syncs.scalar`` calls and misalign the tape.

    Mutation is guarded by an RLock (reentrant on purpose: a weakref
    death callback can fire from a GC point inside ``put`` on the same
    thread that already holds the lock)."""

    def __init__(self, cap: int = 8):
        self._d: "OrderedDict[tuple, dict]" = OrderedDict()
        self._cap = cap
        self._mu = sanitize.tracked_rlock("ops.join_plan.index_cache")

    def _evict(self, key) -> None:
        with self._mu:
            self._d.pop(key, None)

    def get(self, key, arrays) -> Optional["KeyPlan"]:
        if syncs.mode() != "normal":
            return None
        with self._mu:
            e = self._d.get(key)
            if e is None:
                return None
            for r, a in zip(e["refs"], arrays):
                if r() is not a:
                    return None
            self._d.move_to_end(key)
            return e["plan"]

    def put(self, key, arrays, plan: "KeyPlan") -> None:
        if syncs.mode() != "normal":
            return
        try:
            refs = tuple(
                weakref.ref(a, lambda _, k=key: self._evict(k))
                for a in arrays)
        except TypeError:
            return
        with self._mu:
            self._d[key] = {"refs": refs, "plan": plan}
            while len(self._d) > self._cap:
                self._d.popitem(last=False)

    def clear(self) -> None:
        with self._mu:
            self._d.clear()


_PLAN_CACHE = _PlanCache()


def plan_keys(left_cols: Sequence[Column],
              right_cols: Sequence[Column]) -> KeyPlan:
    """Plan the physical probe lanes for a k-column equi-join key.

    Single keys pass through untouched (join engine v2 behavior).  String
    columns are dictionary-encoded first against one shared dictionary
    (``strings.encode_shared`` — code equality == string equality), then
    multi-column tuples pack one of three ways:

    * **composite** — every column is :func:`dense_eligible` and the
      product of the build-side windows ``[kmin_i, kmin_i + span_i)`` fits
      in 63 bits: the tuple packs into one non-negative int64
      (mixed-radix over the windows), probe rows falling outside any build
      window are invalidated, and composite equality == tuple equality —
      so the dense LUT, build-index cache, arena admission and
      capture/replay machinery all apply to multi-key joins unchanged.
    * **fingerprint** — the windows overflow 63 bits: probe on a 64-bit
      murmur3 fingerprint of the tuple (``ops.hashing.fingerprint64``) and
      let ``ops.join`` verify true lane equality on the candidate pairs.
    * **fallback** — some column can never pack exactly (f64 bit-keys,
      decimal128 limbs, uint64): same hashed probe + verification, counted
      separately so traces show the tuple never qualified for packing.
    """
    from . import strings
    k = len(left_cols)
    if k != len(right_cols):
        raise ValueError("join keys: left/right lists differ in length")
    if k == 0:
        raise ValueError("join keys: at least one key column required")
    enc_l, enc_r = [], []
    for lc, rc in zip(left_cols, right_cols):
        if lc.dtype.is_variable_width or rc.dtype.is_variable_width:
            # DictColumn sides ride the dictionary-level shared encode —
            # codes out, row bytes never read, and the int32 result keeps
            # the key on the dense lane (see strings.encode_shared)
            if (as_dict_column(lc) is not None
                    or as_dict_column(rc) is not None):
                metrics.count("join.dict_keys")
            lc, rc = strings.encode_shared([lc, rc])
        enc_l.append(lc)
        enc_r.append(rc)
    if k == 1 and not any(force_column(c).dtype.id == T.TypeId.DECIMAL128
                          for c in (enc_l[0], enc_r[0])):
        # decimal128 is excluded: its (n, 2) limb storage has no single
        # probe lane, so it packs below like a 2-lane tuple — hashed
        # fingerprint probe + exact limb verification — instead of
        # handing the sort-probe engine a 2-D array
        from .join import _key_with_nulls_last
        lc, rc = enc_l[0], enc_r[0]
        ldata, lvalid = _key_with_nulls_last(force_column(lc))
        rdata, rvalid = _key_with_nulls_last(force_column(rc))
        return KeyPlan("single", ldata, lvalid, rdata, rvalid, (),
                       dense_eligible(rc) and dense_eligible(lc))
    with metrics.span("join.pack", n_keys=k):
        enc_l = [force_column(c) for c in enc_l]
        enc_r = [force_column(c) for c in enc_r]
        arrays = [a for c in enc_l + enc_r
                  for a in (c.data, c.validity) if a is not None]
        ck = tuple(id(a) for a in arrays)
        hit = _PLAN_CACHE.get(ck, arrays)
        if hit is not None:
            metrics.count("join.pack.cache_hit")
            if metrics.recording():
                metrics.annotate(mode=hit.mode, cached=True)
            return hit
        plan = _pack_keys(enc_l, enc_r)
        _PLAN_CACHE.put(ck, arrays, plan)
        return plan


def _pack_keys(lcols, rcols) -> KeyPlan:
    from .hashing import fingerprint64

    llanes, rlanes = [], []
    lvalid = rvalid = None
    packable = True
    for lc, rc in zip(lcols, rcols):
        ll, lv = _key_lanes(lc)
        rl, rv = _key_lanes(rc)
        llanes += ll
        rlanes += rl
        lvalid = _and_valid(lvalid, lv)
        rvalid = _and_valid(rvalid, rv)
        packable = packable and dense_eligible(lc) and dense_eligible(rc)
    if packable:
        # build-side window per column — unconditional scalar syncs (the
        # capture/replay tape must not depend on metrics state); an
        # all-null build column degenerates to a span-1 window nothing on
        # the probe side can enter, which is exactly "null never matches"
        windows = []
        prod = 1
        for rl in rlanes:
            if rl.shape[0] == 0:
                windows.append((0, 1))
                continue
            info = np.iinfo(np.dtype(rl.dtype))
            vmin = rl if rvalid is None else jnp.where(rvalid, rl, info.max)
            vmax = rl if rvalid is None else jnp.where(rvalid, rl, info.min)
            kmin = syncs.scalar(jnp.min(vmin))
            span = max(syncs.scalar(jnp.max(vmax)) - kmin + 1, 1)
            windows.append((kmin, span))
            prod *= span
        if prod < (1 << COMPOSITE_BITS):
            # mixed-radix pack, last key fastest; per-lane clip keeps the
            # accumulator in [0, prod) so int64 arithmetic never wraps
            comp_l = jnp.zeros(llanes[0].shape[0], jnp.int64)
            comp_r = jnp.zeros(rlanes[0].shape[0], jnp.int64)
            in_win = None
            stride = 1
            for (kmin, span), ll, rl in zip(windows[::-1], llanes[::-1],
                                            rlanes[::-1]):
                dl = ll.astype(jnp.int64) - kmin
                ok = (dl >= 0) & (dl < span)
                in_win = ok if in_win is None else (in_win & ok)
                comp_l = comp_l + jnp.clip(dl, 0, span - 1) * stride
                dr = jnp.clip(rl.astype(jnp.int64) - kmin, 0, span - 1)
                comp_r = comp_r + dr * stride
                stride *= span
            # probe tuples outside any build window cannot match — fold
            # the window test into key validity (the engines' null mask)
            lvalid = _and_valid(lvalid, in_win)
            metrics.count("join.pack.composite")
            if metrics.recording():
                metrics.annotate(mode="composite", span_product=prod)
            return KeyPlan("composite", comp_l, lvalid, comp_r, rvalid,
                           (), True)
        mode = "fingerprint"
    else:
        mode = "fallback"
    metrics.count(f"join.pack.{mode}")
    if metrics.recording():
        metrics.annotate(mode=mode)
    verify = tuple(zip(llanes, rlanes))
    return KeyPlan(mode, fingerprint64(llanes), lvalid,
                   fingerprint64(rlanes), rvalid, verify, False)


# --- join→aggregate fusion ---------------------------------------------------


def _take_col(col: Column, idx) -> Column:
    from .filter import _gather_column
    return _gather_column(force_column(col), idx)


def _null_where(col: Column, keep) -> Column:
    """Gathered build column with validity additionally masked by ``keep``
    — the eager twin of ``ops.join.left_join``'s deferred ``_with_matched``
    (bit-identical null pattern)."""
    g = force_column(col)
    v = keep if g.validity is None else (g.validity & keep)
    return Column(g.dtype, g.data, g.offsets, v, g.children)


def join_aggregate(left: Table, right: Table, left_on, right_on,
                   group_keys: Sequence[int],
                   aggs: Sequence[tuple[int, str]],
                   how: str = "inner") -> Table:
    """``groupby_aggregate(join(left, right, left_on, right_on), group_keys,
    aggs)`` without materializing the join pairs, for ``how`` in
    ``("inner", "left")``.

    ``left_on``/``right_on`` take a single column index or equal-length
    index lists (multi-column keys route through :func:`plan_keys` like
    ``ops.join``).  ``group_keys`` and the agg value indices address the
    joined (left ++ right) schema.  Fused shapes:

    * **unique build side** (the TPC-DS star shape — fact ⋈ dimension on a
      surrogate PK): matched probe rows ARE the joined rows, so only the
      group-key/value columns are gathered (one compaction sync) and fed
      straight into ``ops.groupby``'s segment reductions — no pair
      expansion, no wide joined table.  LEFT OUTER skips even the
      compaction: every probe row is a joined row, left columns pass
      through untouched and build columns null out where unmatched.
    * **probe-side-only columns** over a duplicated build side: each probe
      row's match count becomes a weight (sum/count/mean weight their
      contributions; min/max ignore multiplicity), so the pairs still
      never materialize.  LEFT OUTER keeps unmatched rows at weight 1 —
      their single null-extended joined row.

    Anything else — including fingerprint-probed multi-key tuples, whose
    candidate counts are not true match counts — falls back to the
    materialized join + groupby (identical result either way —
    differentially tested in tests/test_join_v2.py).
    """
    from .groupby import groupby_aggregate
    from .join import inner_join, left_join

    if how not in ("inner", "left"):
        raise ValueError(f"join_aggregate: unsupported how={how!r}")
    nl = left.num_columns
    lon = list(left_on) if isinstance(left_on, (list, tuple)) else [left_on]
    ron = list(right_on) if isinstance(right_on, (list, tuple)) \
        else [right_on]
    plan = plan_keys([left[i] for i in lon], [right[i] for i in ron])
    needed = list(group_keys) + [vi for vi, _ in aggs]

    def _unfused():
        j = (inner_join if how == "inner" else left_join)(
            left, right, left_on, right_on)
        return groupby_aggregate(j, list(group_keys), list(aggs))

    if plan.verify:
        metrics.count("join.fused.fallback_join")
        with metrics.span("join.aggregate", path="fallback_join"):
            return _unfused()

    ix = build_index(plan.rdata, plan.rvalid, plan.dense_ok)
    if ix.unique:
        metrics.count("join.fused.unique_gather")
        with metrics.span("join.aggregate", path="unique_gather"):
            lo, counts = probe_counts(ix, plan.ldata, plan.lvalid)
            pos = jnp.minimum(lo, max(ix.n_valid - 1, 0))
            if how == "inner":
                m = counts > 0
                k = syncs.scalar(jnp.sum(m))
                li = sized_nonzero(m, k)
                ri = ix.row_ids[pos[li]]
                cols = [_take_col(left[ci], li) if ci < nl
                        else _take_col(right[ci - nl], ri) for ci in needed]
            else:
                matched = counts > 0
                ri = jnp.where(matched, ix.row_ids[pos], 0)
                cols = [force_column(left[ci]) if ci < nl
                        else _null_where(_take_col(right[ci - nl], ri),
                                         matched)
                        for ci in needed]
            nk = len(group_keys)
            return groupby_aggregate(
                Table(cols), list(range(nk)),
                [(nk + i, agg) for i, (_, agg) in enumerate(aggs)])

    if (group_keys and all(ci < nl for ci in needed)
            and _weighted_ok([left[ci] for ci in group_keys],
                             [(left[vi], agg) for vi, agg in aggs])):
        metrics.count("join.fused.weighted_groupby")
        with metrics.span("join.aggregate", path="weighted_groupby"):
            lo, counts = probe_counts(ix, plan.ldata, plan.lvalid)
            if how == "inner":
                m = counts > 0
                k = syncs.scalar(jnp.sum(m))
                li = sized_nonzero(m, k)
                w = counts.astype(jnp.int64)[li]
                return _weighted_groupby(
                    [_take_col(left[ci], li) for ci in group_keys],
                    [(_take_col(left[vi], li), agg) for vi, agg in aggs], w)
            w = jnp.maximum(counts, 1).astype(jnp.int64)
            return _weighted_groupby(
                [force_column(left[ci]) for ci in group_keys],
                [(force_column(left[vi]), agg) for vi, agg in aggs], w)

    metrics.count("join.fused.fallback_join")
    with metrics.span("join.aggregate", path="fallback_join"):
        return _unfused()


def _weighted_ok(key_cols, val_aggs) -> bool:
    for c in key_cols:
        dt = c.dtype
        if (dt.is_variable_width or dt.is_nested
                or dt.id in (T.TypeId.FLOAT64, T.TypeId.DECIMAL128)):
            return False
    for c, agg in val_aggs:
        dt = c.dtype
        if dt.is_variable_width or dt.is_nested or dt.id == T.TypeId.DECIMAL128:
            return False
        if agg not in ("sum", "count", "mean", "min", "max"):
            return False
        if dt.id == T.TypeId.FLOAT64 and agg in ("min", "max"):
            return False          # bit-exact selection needs the full path
    return True


def _weighted_groupby(key_cols, val_aggs, w) -> Table:
    """Groupby over matched probe rows where row ``i`` stands for ``w[i]``
    identical joined pairs — mirrors ``ops.groupby`` semantics/dtypes for
    the shapes :func:`_weighted_ok` admits."""
    from .groupby import (_agg_out_dtype, _agg_segment, _cast_res,
                          _empty_result, _segment_ids, _take_rows)
    from .sort import order_by

    nk = len(key_cols)
    sub = Table(key_cols + [c for c, _ in val_aggs])
    if sub.num_rows == 0:
        return _empty_result(sub, list(range(nk)),
                             [(nk + i, a) for i, (_, a) in
                              enumerate(val_aggs)])
    order = order_by(Table(key_cols), list(range(nk)))
    skeys = [_take_rows(c, order) for c in key_cols]
    seg_ids = _segment_ids([c.data for c in skeys],
                           [c.validity for c in skeys])
    ns = syncs.scalar(seg_ids[-1]) + 1
    n = order.shape[0]
    head_pos = jax.ops.segment_min(jnp.arange(n, dtype=jnp.int32), seg_ids,
                                   ns)
    out_cols = [_take_rows(c, head_pos) for c in skeys]
    ws = w[order]
    for col, agg in val_aggs:
        valid = None if col.validity is None else col.validity[order]
        if agg == "count":
            ones = ws if valid is None else jnp.where(valid, ws, 0)
            res = jax.ops.segment_sum(ones, seg_ids, ns)
            dt = _agg_out_dtype(col.dtype, agg)
            out_cols.append(Column(dt, res.astype(dt.storage)))
            continue
        vals = col.values()[order]
        if agg in ("sum", "mean"):
            kind = col.dtype.storage.kind
            acc = vals.astype(jnp.float64 if kind == "f" else jnp.int64)
            acc = acc if valid is None else jnp.where(valid, acc, 0)
            s = jax.ops.segment_sum(acc * ws.astype(acc.dtype), seg_ids, ns)
            if agg == "sum":
                dt = _agg_out_dtype(col.dtype, agg)
                out_cols.append(Column.from_values(dt, _cast_res(s, dt)))
                continue
            cnt = jax.ops.segment_sum(
                ws if valid is None else jnp.where(valid, ws, 0),
                seg_ids, ns)
            res = s.astype(jnp.float64) / jnp.maximum(cnt, 1).astype(
                jnp.float64)
            dt = _agg_out_dtype(col.dtype, agg)
            out_cols.append(Column.from_values(dt, _cast_res(res, dt)))
            continue
        # min/max: pair multiplicity is irrelevant — plain segment select
        res = _agg_segment(vals, valid, seg_ids, agg, ns,
                           col.dtype.storage.kind)
        if valid is not None:
            cnt = _agg_segment(vals, valid, seg_ids, "count", ns,
                               col.dtype.storage.kind)
            out_cols.append(Column.from_values(
                col.dtype, _cast_res(res, col.dtype), validity=cnt > 0))
        else:
            out_cols.append(Column.from_values(col.dtype,
                                               _cast_res(res, col.dtype)))
    return Table(out_cols)
