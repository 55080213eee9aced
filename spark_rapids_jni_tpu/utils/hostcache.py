"""Host mirrors of device metadata arrays (offsets), weakly cached.

Every ``np.asarray(device_array)`` is a device→host transfer and a sync.
The JCUDF variable-width paths need string
offsets host-side for batching and DMA geometry, but those offsets are
almost always *born* on the host (``strings_from_list``, Parquet decode,
``_slice_column`` arithmetic) — so producers seed this cache and consumers
get their host copy back for free instead of re-downloading it.

Entries are keyed by the device array's identity and dropped by a weakref
callback when the device array is garbage-collected.  The cache is an
optimization only — a miss falls back to the transfer.  The host-mirror
instance is additionally byte-capped (``SRJT_HOSTCACHE_CAP``, default
256 MiB): past the cap the least-recently-used mirror is dropped and
``arena.hostcache.evictions`` counts it — long scans over many files no
longer grow host RSS without bound.
"""

from __future__ import annotations

import os
import weakref
from collections import OrderedDict
from typing import Any, Callable, Optional

import numpy as np

from ..analysis import sanitize


class WeakIdMemo:
    """Weak cache keyed on the IDENTITY of one or more (device) arrays.

    The shared mechanism behind the host-mirror cache here and the
    dictionary/width memos in ``utils.syncs``: entries key on ``id()`` of
    the arrays, hold weakrefs with cleanup callbacks so values drop when
    any keyed array is garbage-collected, and an ``is``-identity check
    guards against id recycling.  Best-effort: non-weakref-able keys are
    simply not cached.

    ``cap_bytes`` (a value or a zero-arg callable, None = unbounded)
    turns the memo into a byte-capped LRU over ``value.nbytes``;
    ``on_evict`` fires once per capacity eviction (not for weakref
    deaths).

    Thread-safety: map mutation is guarded by an RLock (reentrant — a
    weakref death callback can fire at a GC point inside ``put`` on the
    thread already holding it).  ``on_evict`` callbacks fire AFTER the
    lock is released so they may take other locks (metrics, arena)
    without ordering against this one.
    """

    def __init__(self, cap_bytes=None,
                 on_evict: Optional[Callable[[], None]] = None) -> None:
        self._d: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._bytes = 0
        self._cap = cap_bytes
        self._on_evict = on_evict
        self._mu = sanitize.tracked_rlock("utils.hostcache.memo")

    def _cap_now(self) -> Optional[int]:
        c = self._cap
        return c() if callable(c) else c

    def _pop(self, key) -> None:
        with self._mu:
            entry = self._d.pop(key, None)
            if entry is not None:
                self._bytes -= entry[2]

    def get(self, arrays) -> Any:
        key = tuple(id(a) for a in arrays)
        with self._mu:
            entry = self._d.get(key)
            if entry is None:
                return None
            refs, value, _ = entry
            for r, a in zip(refs, arrays):
                if r() is not a:
                    return None
            self._d.move_to_end(key)
            return value

    def put(self, arrays, value) -> None:
        key = tuple(id(a) for a in arrays)
        try:
            refs = tuple(
                weakref.ref(a, lambda _, k=key: self._pop(k))
                for a in arrays)
        except TypeError:
            return
        nbytes = int(getattr(value, "nbytes", 0) or 0)
        evictions = 0
        with self._mu:
            self._pop(key)
            self._d[key] = (refs, value, nbytes)
            self._bytes += nbytes
            cap = self._cap_now()
            if cap is not None:
                while self._bytes > cap and len(self._d) > 1:
                    lru = next(iter(self._d))
                    if lru == key:
                        break
                    self._pop(lru)
                    evictions += 1
        if self._on_evict is not None:
            for _ in range(evictions):
                self._on_evict()

    def nbytes(self) -> int:
        return self._bytes


def _host_cap() -> Optional[int]:
    from ..memory.budget import parse_bytes
    from . import knobs
    return parse_bytes(knobs.get("SRJT_HOSTCACHE_CAP"))


def _count_host_eviction() -> None:
    from . import metrics
    if metrics.recording():
        metrics.count("arena.hostcache.evictions")


_HOST = WeakIdMemo(cap_bytes=_host_cap, on_evict=_count_host_eviction)


def seed(device_arr, host_arr: np.ndarray) -> None:
    """Record ``host_arr`` as the host mirror of ``device_arr``."""
    _HOST.put((device_arr,), host_arr)


def peek(device_arr):
    """The cached host mirror, or None — never triggers a transfer.

    Misses deliberately under syncs capture/replay: a mirror hit would let
    the capture run skip a size-resolution site that the replay trace (on
    fresh tracers) cannot skip, misaligning the recorded tape."""
    from . import syncs
    if syncs.mode() != "normal":
        return None
    return _HOST.get((device_arr,))


def host_i64(device_arr) -> np.ndarray:
    """Host int64 copy of a device int array, cached across calls."""
    h = peek(device_arr)
    if h is not None:
        return h if h.dtype == np.int64 else h.astype(np.int64)
    out = np.asarray(device_arr).astype(np.int64)
    seed(device_arr, out)
    return out
