"""Host-sync accounting + weak result caches for the two-phase ops.

Every device→host scalar sync stalls the dispatch pipeline for a round
trip, so a multi-op query plan's wall time is often `sync_count × RTT`
rather than compute.  Two countermeasures live here:

* :func:`scalar` — the ONE funnel for intentional scalar syncs (group
  counts, string widths, char totals).  It counts them, so a
  syncs-per-query figure can be reported and regressions are visible.
* weak per-array caches (:func:`memo_get` / :func:`memo_put`) keyed on
  device-array identity — dictionary encodes and string widths are pure
  functions of their column payloads, and analytics plans re-touch the
  same dimension columns — a repeated DIRECT touch of a base-table
  column skips its sync (post-gather copies are fresh arrays and
  legitimately re-resolve).  Entries drop with the arrays (weakrefs).
"""

from __future__ import annotations

import contextlib
import threading
# weakref handled by hostcache.WeakIdMemo
from typing import Any

from ..analysis import sanitize

# The sync counter is bumped from every exec-runtime worker thread; an
# unguarded `_count += 1` is a read-modify-write that loses updates under
# contention (found by srjt_lint conc-global-augassign; regression:
# tests/test_analysis.py::test_sync_count_thread_safe).
_count = 0
_count_mu = sanitize.tracked_lock("utils.syncs.count")

# --- capture/replay: compile a whole multi-op plan into ONE jit program ----
#
# Every dynamic size in the op library (join match totals, group counts,
# string widths, compaction counts) resolves through :func:`scalar`.  A
# *capture* run executes the plan eagerly and records the resolved sizes in
# order; a *replay* run pops them instead of syncing — so the same plan
# code traces under ``jax.jit`` with every shape static (the device value
# arriving at ``scalar`` is a tracer and is simply not synced).  Both modes
# disable the weak memos so capture and replay visit the SAME sequence of
# resolution sites (a memo hit in one mode but not the other would
# misalign the recorded sizes).  See ``models/compiled.py``.
#
# The mode and tape are THREAD-LOCAL: a jit trace executes its Python body
# on the calling thread, so a capture/replay on one exec-runtime worker
# must not flip the mode (or pop sizes from the tape) of a query running
# concurrently on another worker.

_tls = threading.local()    # .mode, .tape, .tape_pos, .seen


def mode() -> str:
    return getattr(_tls, "mode", "normal")


@contextlib.contextmanager
def capture(tape: list[int]):
    """Eager run recording every resolved size into ``tape`` (in order)."""
    if mode() != "normal":
        raise RuntimeError(f"cannot capture while in {mode()} mode")
    _tls.mode, _tls.tape = "capture", tape
    try:
        yield tape
    finally:
        _tls.mode, _tls.tape = "normal", []


@contextlib.contextmanager
def replay(tape: list[int], collect: list | None = None):
    """Traced run resolving sizes from ``tape`` instead of device syncs.

    ``collect``, when given, receives the value that ARRIVED at each
    :func:`scalar` call (a tracer under jit) in tape order — the raw
    material for a device-side size-vector program that can check a tape
    against refreshed data (``models/compiled.py`` staleness guard)."""
    if mode() != "normal":
        raise RuntimeError(f"cannot replay while in {mode()} mode")
    _tls.mode, _tls.tape, _tls.tape_pos, _tls.seen = \
        "replay", list(tape), 0, collect
    try:
        yield
        if _tls.tape_pos != len(_tls.tape):
            raise RuntimeError(
                f"replay consumed {_tls.tape_pos} of {len(_tls.tape)} "
                "recorded sizes — plan diverged from the capture run")
    finally:
        _tls.mode, _tls.tape, _tls.tape_pos, _tls.seen = \
            "normal", [], 0, None


def scalar(x) -> int:
    """int(x) with sync accounting — use for every intentional D2H scalar."""
    global _count
    if mode() == "replay":
        if _tls.tape_pos >= len(_tls.tape):
            raise RuntimeError(
                "replay tape exhausted — plan diverged from the capture run")
        if _tls.seen is not None:
            _tls.seen.append(x)
        v = _tls.tape[_tls.tape_pos]
        _tls.tape_pos += 1
        return v
    with _count_mu:
        _count += 1
    v = int(x)
    if mode() == "capture":
        _tls.tape.append(v)
    return v


def note_sync(k: int = 1) -> None:
    """Count ``k`` intentional D2H syncs that do not flow through
    :func:`scalar` (e.g. a stacked size-vector pull) — keeps the
    syncs-per-query funnel honest for non-scalar transfers."""
    global _count
    with _count_mu:
        _count += k


def sync_count() -> int:
    return _count


def reset_sync_count() -> int:
    global _count
    with _count_mu:
        old, _count = _count, 0
    return old


# --- weak memo keyed on device-array identity (shared mechanism with the
# host-mirror cache: utils.hostcache.WeakIdMemo) -----------------------------

from .hostcache import WeakIdMemo

_MEMOS: dict[str, WeakIdMemo] = {}


def memo_get(tag: str, arrays) -> Any:
    """Cached value for (tag, arrays) — None on miss or if any array died.
    Disabled under capture/replay (see the mode note above)."""
    if mode() != "normal":
        return None
    memo = _MEMOS.get(tag)
    return None if memo is None else memo.get(arrays)


def memo_put(tag: str, arrays, value) -> None:
    if mode() != "normal":
        return
    _MEMOS.setdefault(tag, WeakIdMemo()).put(arrays, value)
