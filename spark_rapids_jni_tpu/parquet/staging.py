"""Coalesced async host→device staging for the parquet scan.

Round 5 uploaded every raw page payload, level stream, and dictionary
with its own ``jnp.asarray`` — dozens of small synchronous transfers per
file, each paying the link's fixed cost for a few kilobytes.  The
reference stages a row group's pages into pinned host slabs and issues
ONE cudaMemcpyAsync per slab so the copy engine streams at link rate
(SURVEY §5.5); the PJRT analog is the same shape:

* :class:`SlabStager` queues host buffers and, on ``flush``, packs them
  into one contiguous slab **per dtype** (uint8 payloads, uint32 word
  views, int32/int64 metadata) and issues a single non-blocking
  ``jax.device_put`` per slab.  Each queued buffer resolves to a device
  *slice* of its slab — the per-buffer arrays the decode programs
  consume are cheap device-side slices, not separate transfers.
* ``flush`` is asynchronous: the host thread returns as soon as the
  transfers are enqueued, so a pipelined caller can walk/decompress the
  next row group while the current one is in flight (the overlap the
  scan pipeline measures through ``parquet.stage.overlap_ms``).
* Slabs are capped at ``SRJT_STAGE_SLAB_BYTES`` — a flush larger than
  the cap splits into multiple transfers rather than one giant
  allocation.

Metrics: ``parquet.stage.slab_bytes`` / ``parquet.stage.transfers`` /
``parquet.stage.buffers`` per flush; the flight recorder keeps a
``parquet.stage.flush`` breadcrumb per slab wave.  The scan's
``parquet.scan.upload`` span takes the stager's bytes, transfers and the
time its slab packing took (``pack_s``) as attributes.

``SRJT_STAGE_SLABS=0`` reverts every call site to the old per-buffer
``jnp.asarray`` uploads (the differential-testing baseline).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import flight, knobs, metrics


def enabled() -> bool:
    return bool(knobs.get("SRJT_STAGE_SLABS"))


def donate_enabled() -> bool:
    """SRJT_SCAN_DONATE: ``auto`` donates on non-CPU backends (CPU PJRT
    ignores donation and warns); ``1``/``on`` forces, ``0``/``off``
    disables."""
    raw = str(knobs.get("SRJT_SCAN_DONATE") or "auto").strip().lower()
    if raw in ("1", "on", "true", "force"):
        return True
    if raw in ("0", "off", "false", ""):
        return False
    return jax.default_backend() != "cpu"


class Handle:
    """One queued host buffer; resolves to a device slice after flush."""

    __slots__ = ("_stager", "_arr", "_slot", "_dev")

    def __init__(self, stager: "SlabStager", arr: np.ndarray):
        self._stager = stager
        self._arr = arr
        self._slot = None          # (slab index within dtype bucket, start)
        self._dev: Optional[jnp.ndarray] = None

    def get(self) -> jnp.ndarray:
        """The staged device array (flushes the owning stager if the
        buffer is still queued)."""
        if self._dev is None:
            self._stager.flush()
        return self._dev


class SlabStager:
    """Pack queued host buffers into per-dtype slabs; one async
    ``device_put`` per slab."""

    def __init__(self, slab_cap: Optional[int] = None):
        if slab_cap is None:
            slab_cap = knobs.get("SRJT_STAGE_SLAB_BYTES") or (64 << 20)
        self.slab_cap = max(int(slab_cap), 1 << 20)
        self._pending: list[Handle] = []
        self.slab_bytes = 0          # lifetime bytes shipped via slabs
        self.transfers = 0           # lifetime device_put count
        self.buffers = 0             # lifetime queued-buffer count
        self.queued_bytes = 0        # lifetime bytes queued by add()
        self.pack_s = 0.0            # lifetime seconds packing slabs

    # -- queueing ------------------------------------------------------------
    def add(self, arr: np.ndarray) -> Handle:
        """Queue a host array for the next flush; returns its handle."""
        arr = np.ascontiguousarray(arr)
        h = Handle(self, arr)
        if arr.size == 0:
            # degenerate: resolve immediately, never rides a slab
            h._dev = jnp.asarray(arr)
            h._arr = None
            return h
        self._pending.append(h)
        self.buffers += 1
        self.queued_bytes += arr.nbytes
        return h

    def asarray(self, arr: np.ndarray) -> Handle:
        return self.add(arr)

    # -- transfer ------------------------------------------------------------
    def flush(self) -> int:
        """Concatenate queued buffers per dtype and issue one non-blocking
        transfer per slab (split past ``slab_cap``).  Returns the number
        of transfers issued.  Handles resolve to device slices."""
        if not self._pending:
            return 0
        pending, self._pending = self._pending, []
        by_dtype: dict[np.dtype, list[Handle]] = {}
        for h in pending:
            by_dtype.setdefault(h._arr.dtype, []).append(h)
        issued = 0
        flush_bytes = 0
        for dt, handles in by_dtype.items():
            wave: list[Handle] = []
            wave_bytes = 0
            for h in handles:
                nb = h._arr.nbytes
                if wave and wave_bytes + nb > self.slab_cap:
                    issued += self._ship(dt, wave)
                    flush_bytes += wave_bytes
                    wave, wave_bytes = [], 0
                wave.append(h)
                wave_bytes += nb
            if wave:
                issued += self._ship(dt, wave)
                flush_bytes += wave_bytes
        self.slab_bytes += flush_bytes
        self.transfers += issued
        if metrics.recording():
            metrics.count("parquet.stage.slab_bytes", flush_bytes)
            metrics.count("parquet.stage.transfers", issued)
            metrics.count("parquet.stage.buffers", len(pending))
        flight.record("parquet.stage.flush", slabs=issued,
                      buffers=len(pending), bytes=flush_bytes)
        return issued

    def _ship(self, dt: np.dtype, wave: list[Handle]) -> int:
        if len(wave) == 1:
            # a lone buffer needs no repack — still one async transfer
            h = wave[0]
            h._dev = jax.device_put(h._arr)
            h._arr = None
            return 1
        t0 = time.perf_counter()
        slab = np.concatenate([h._arr.reshape(-1) for h in wave])
        self.pack_s += time.perf_counter() - t0
        dev = jax.device_put(slab)       # ONE transfer, non-blocking
        pos = 0
        for h in wave:
            n = h._arr.size
            shape = h._arr.shape
            sl = dev[pos:pos + n]
            h._dev = sl if len(shape) == 1 else sl.reshape(shape)
            h._arr = None
            pos += n
        return 1


def resolve(x):
    """``Handle`` → staged device array; anything else passes through.
    Spec builders queue uploads as handles so a whole file's metadata
    rides a few slabs; the scan resolves them after the final flush."""
    return x.get() if isinstance(x, Handle) else x


def asarray(arr: np.ndarray, stager: Optional[SlabStager] = None):
    """Upload ``arr``: queued on ``stager`` (deferred, coalesced) when
    one is given, else the eager per-buffer ``jnp.asarray``."""
    if stager is not None:
        return stager.add(arr)
    return jnp.asarray(arr)
