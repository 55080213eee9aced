"""LRU registry of evictable device residents with host-spill/fault-back.

The spark-rapids analog is ``RapidsBufferCatalog`` + the device→host→disk
spill tiers: long-lived device residents (cached build-side join indexes,
promoted host-cache columns, parquet scan slabs) register here with their
byte footprint; when ``memory.budget`` sees pressure it walks this
registry in LRU order and asks residents to spill.

Spilling at this layer moves a resident's device arrays to pinned-enough
host RAM (``np.asarray`` — a D2H transfer on the chip, a view-copy on
CPU) and drops the device references so XLA's
BFC arena can actually reuse the HBM.  Faulting back is ``jnp.asarray``
on next touch.  All payloads in this engine are integer/bit-pattern
arrays (FLOAT64 is stored as u32 bit pairs — the Column invariant), so a
spill→fault-back round trip is bit-exact on every backend.

Residents must be *re-derivable or self-contained*: the registry never
spills buffers a running plan holds references to — only caches that can
fault back (or rebuild) on their next touch.
"""

from __future__ import annotations

import weakref
from collections import OrderedDict
from typing import Callable, Optional

import numpy as np

from ..analysis import sanitize
from ..utils import flight, metrics
from . import budget

_reg: "OrderedDict[object, Resident]" = OrderedDict()


class Resident:
    """One evictable device-resident entry.

    ``spiller()`` must free the resident's device references and return
    the bytes it released; after it runs the entry leaves the registry
    (a fault-back re-registers it)."""

    __slots__ = ("key", "nbytes", "tag", "spiller")

    def __init__(self, key, nbytes: int, tag: str,
                 spiller: Callable[[], int]):
        self.key = key
        self.nbytes = int(nbytes)
        self.tag = tag
        self.spiller = spiller


def register(key, nbytes: int, tag: str,
             spiller: Callable[[], int]) -> None:
    """Track a device resident as evictable; charges the budget (soft —
    registering a cache entry must not fail the query; pressure instead
    spills older residents, possibly including this one later)."""
    if not budget.active():
        return
    budget.charge(nbytes, tag=tag, strict=False)
    with budget._LOCK:
        _reg[key] = Resident(key, nbytes, tag, spiller)
        _reg.move_to_end(key)


def unregister(key, *, release: bool = True) -> None:
    """Drop a resident (evicted, died with its arrays, or spilled)."""
    with budget._LOCK:
        r = _reg.pop(key, None)
    if r is not None and release:
        budget.release(r.nbytes)


def touch(key) -> None:
    """Mark a resident most-recently-used."""
    with budget._LOCK:
        if key in _reg:
            _reg.move_to_end(key)


def registered_bytes() -> int:
    with budget._LOCK:
        return sum(r.nbytes for r in _reg.values())


def resident_count() -> int:
    return len(_reg)


def reset() -> None:
    """Forget every resident without spilling (tests)."""
    with budget._LOCK:
        _reg.clear()


def reclaim(nbytes_needed: int) -> int:
    """Spill LRU residents until ``nbytes_needed`` bytes were released
    (or the registry runs dry).  Returns bytes actually freed."""
    freed = 0
    while freed < nbytes_needed:
        with budget._LOCK:
            if not _reg:
                break
            key, r = next(iter(_reg.items()))
            _reg.pop(key, None)
        with metrics.span("arena.spill", tag=r.tag, bytes=r.nbytes):
            try:
                got = int(r.spiller())
            except Exception:
                got = 0
        budget.release(r.nbytes)
        freed += got or r.nbytes
        if metrics.recording():
            metrics.count("arena.spill.events")
            metrics.count("arena.spill.bytes", r.nbytes)
            metrics.count(f"arena.spill.{r.tag}")
    return freed


class SpillableArrays:
    """A named bundle of device arrays that can round-trip through host
    RAM bit-exactly (the generic resident payload: build-index lanes,
    promoted columns).

    ``get()`` returns the device-array dict, faulting back from the host
    copies when spilled (counted as ``arena.faultback.*``); ``spill()``
    moves every array to host and drops the device references."""

    __slots__ = ("tag", "_dev", "_host", "nbytes", "_mu")

    def __init__(self, tag: str, arrays: dict):
        self.tag = tag
        self._dev: Optional[dict] = {k: v for k, v in arrays.items()}
        self._host: Optional[dict] = None
        self.nbytes = sum(int(getattr(a, "nbytes", 0) or 0)
                          for a in arrays.values() if a is not None)
        self._mu = sanitize.tracked_rlock("memory.spill")

    @property
    def spilled(self) -> bool:
        return self._dev is None

    def spill(self) -> int:
        """Device → host; returns bytes released (0 when already host)."""
        with self._mu:
            if self._dev is None:
                return 0
            self._host = {k: (None if a is None else np.asarray(a))
                          for k, a in self._dev.items()}
            self._dev = None
            return self.nbytes

    def get(self) -> dict:
        """The device-array dict, faulting back if spilled.  A fault-back
        that cannot re-upload (device OOM mid-restore) is an incident —
        the resident's data survives on the host, but the query that
        touched it is about to fail with the arena in a pressure state
        worth a black-box snapshot."""
        with self._mu:
            if self._dev is None:
                import jax.numpy as jnp
                try:
                    with metrics.span("arena.faultback", tag=self.tag,
                                      bytes=self.nbytes):
                        self._dev = {
                            k: (None if a is None else jnp.asarray(a))
                            for k, a in self._host.items()}
                except BaseException as e:
                    self._dev = None   # stay spilled; host copy is intact
                    flight.incident("spill_faultback", tag=self.tag,
                                    nbytes=self.nbytes, error=repr(e))
                    raise
                self._host = None
                if metrics.recording():
                    metrics.count("arena.faultback.events")
                    metrics.count("arena.faultback.bytes", self.nbytes)
            return self._dev


class SpillableTable:
    """In-place host spill for a whole :class:`~..column.Table` (parquet
    fused-scan outputs, exec-prefetch staged request tables).

    :class:`SpillableArrays` works for payloads whose OWNER re-fetches
    lanes through ``get()``; a scan-output table is instead held directly
    by the caller, so eviction must work in place: :meth:`spill` replaces
    every column's device arrays with their host ``np`` copies (Column
    payload fields are plain dataclass attributes, and the op library
    accepts np arrays, re-uploading on next touch) — fault-back is
    therefore *implicit and bit-exact*: every payload in the engine is an
    integer/bit-pattern array (FLOAT64 rides as u32 bit pairs), so the
    host round trip preserves bits on every backend.  Offsets whose host
    mirror is already promoted into ``utils.hostcache`` spill for free
    when the mirror's dtype/shape match — the mirror IS the host copy.

    Holds only a weakref to the table: residency must not keep a dead
    request's working set alive."""

    __slots__ = ("tag", "_ref", "nbytes")

    def __init__(self, table, tag: str, on_death=None):
        self.tag = tag
        # the registry's spiller closure keeps THIS object (and so this
        # weakref + its death callback) alive exactly as long as the
        # registration itself
        self._ref = weakref.ref(table, on_death)
        self.nbytes = table_device_bytes(table)

    def spill(self) -> int:
        import jax

        from ..utils import hostcache
        t = self._ref()
        if t is None:
            return 0
        freed = 0
        for col in _concrete_columns(t):
            for field in _payload_fields(col):
                a = getattr(col, field, None)
                if a is None or not isinstance(a, jax.Array):
                    continue
                h = hostcache.peek(a)
                if (h is None or h.dtype != np.dtype(a.dtype)
                        or h.shape != a.shape):
                    h = np.asarray(a)
                setattr(col, field, h)
                freed += int(a.nbytes)
        if freed and metrics.recording():
            metrics.count("arena.spill.table_cols")
        return freed


def _payload_fields(col) -> tuple:
    """The column's spillable payload attributes.  Dict columns spill their
    CODES (touching ``data``/``offsets`` would materialize the byte payload
    — allocating under pressure, the opposite of spilling); the shared
    dictionary spills through its own entry in ``_concrete_columns``."""
    from ..column import DictColumn
    if isinstance(col, DictColumn):
        return ("codes", "validity")
    return ("data", "offsets", "validity")


def _concrete_columns(table):
    """The table's materialized columns, recursing into children; lazy
    columns that were never forced hold no device payload and are left
    untouched (forcing them here would ADD allocations under pressure)."""
    from ..column import DictColumn, LazyColumn
    out = []
    stack = list(table.columns)
    while stack:
        c = stack.pop()
        if isinstance(c, LazyColumn):
            if c._col is None:
                continue
            c = c._col
        out.append(c)
        if isinstance(c, DictColumn):
            stack.append(c.dictionary)
            if c._mat is not None:     # already-materialized bytes spill too
                stack.append(c._mat)
            continue
        if c.children:
            stack.extend(c.children)
    return out


def table_device_bytes(table) -> int:
    """Total bytes of the table's device-resident payload arrays."""
    import jax
    total = 0
    for col in _concrete_columns(table):
        for field in _payload_fields(col):
            a = getattr(col, field, None)
            if a is not None and isinstance(a, jax.Array):
                total += int(a.nbytes)
    return total


def register_table(table, tag: str) -> Optional[SpillableTable]:
    """Track a caller-held table's device payload as evictable (fused-scan
    outputs, staged request tables).  The registration dies with the
    table; a table touched again after spilling re-uploads implicitly and
    is NOT re-registered (the next scan/stage registers its own).  Returns
    the handle, or None when the arena is off / nothing is device-resident.
    """
    if not budget.active():
        return None
    with budget._LOCK:
        # idempotent per table object: a staged loader's scan output is
        # already registered — re-registering would double-charge it
        for r in _reg.values():
            s = getattr(r.spiller, "__self__", None)
            if isinstance(s, SpillableTable) and s._ref() is table:
                return s
    key = (tag, id(table))
    try:
        st = SpillableTable(table, tag, on_death=lambda _: unregister(key))
    except TypeError:
        return None
    if st.nbytes <= 0:
        return None
    register(key, st.nbytes, tag, st.spill)
    return st
