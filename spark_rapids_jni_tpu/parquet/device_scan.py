"""Device-side Parquet scan (BASELINE config #2 — "GB/s columnar scan").

Round 2 decoded every page byte in host NumPy loops and uploaded finished
columns (`decode.py`); the reference's scan is a GPU engine (libcudf decode
built into the artifact, ``build-libcudf.xml:48-64``).  This module moves
the byte-level decode ONTO the chip for the hot shapes:

  host (staging, like the reference's host buffers):
      footer/thrift parse → page walk → decompression (native snappy in
      ``libsrjt.so``) → concatenate raw PLAIN payloads / host-decode tiny
      run-length metadata (def levels, dictionary indices' RLE headers)
  device (one jitted program per column):
      PLAIN bitcast u8 → typed lanes  (f64 → u32 bit pairs, the Column
      invariant — no f64 arithmetic anywhere)
      dictionary index gather          (typed dict values resident)
      def-level expansion              (cumsum positions + masked gather)

Round 4 extends the device tier to PLAIN strings (the native
``srjt_byte_array_offsets`` walker stages the sequential offsets
recurrence; ONE device segmented gather strips the length prefixes —
``rowconv/xpack.segmented_gather``) and BOOLEAN bit-unpack.  Columns
outside the fast path (dictionary strings, INT96, DELTA_*, nested) fall
back to the host decoder transparently — correctness first, the fast path
covers the scan-heavy analytics shapes.

``scan_table`` mirrors ``decode.read_table`` and is differentially tested
against it (tests/test_device_scan.py).
"""

from __future__ import annotations

import functools
import struct as _struct
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..column import Column, DictColumn, Table
from ..utils import flight, knobs, metrics, syncs
from ..utils.tracing import traced
from . import decode as D
from . import staging
from .footer import extract_footer_bytes
from .thrift import parse_struct

_PLAIN_PHYS = {D.PT_INT32: 4, D.PT_INT64: 8, D.PT_FLOAT: 4, D.PT_DOUBLE: 8}


def _stage_wave(stager, *arrays):
    """Upload host arrays in ONE coalesced slab wave when a stager is
    given (queue all, then resolve — the first resolve flushes the whole
    wave), else the eager per-buffer ``jnp.asarray``."""
    if stager is None:
        return tuple(jnp.asarray(a) for a in arrays)
    hs = [staging.asarray(a, stager) for a in arrays]
    return tuple(staging.resolve(h) for h in hs)


def _resolve_args(args):
    return tuple(staging.resolve(a) for a in args)


class _WalkStats:
    """What one column's page walk read, summed in plain fields page by
    page and written once: onto the walk's span and into the counters the
    host decoder keeps for the same things (``decode.py``)."""

    __slots__ = ("pages_data", "pages_dict", "compressed", "uncompressed",
                 "decompress_s")

    def __init__(self):
        self.pages_data = self.pages_dict = 0
        self.compressed = self.uncompressed = 0
        self.decompress_s = 0.0

    def write(self, sp) -> None:
        ms = self.decompress_s * 1e3
        sp.annotate(pages=self.pages_data + self.pages_dict,
                    bytes_compressed=self.compressed,
                    bytes_uncompressed=self.uncompressed,
                    decompress_ms=round(ms, 3))
        if self.pages_data:
            metrics.count("parquet.pages.data", self.pages_data)
        if self.pages_dict:
            metrics.count("parquet.pages.dict", self.pages_dict)
        metrics.count("parquet.bytes.compressed", self.compressed)
        metrics.count("parquet.bytes.uncompressed", self.uncompressed)
        metrics.count("parquet.decompress_ms", ms)


def _walk_chunk_raw(file_bytes: bytes, chunk, max_def: int, max_rep: int,
                    type_len: int = 0, stats: Optional[_WalkStats] = None):
    """Page walk that KEEPS raw PLAIN payload bytes (or dictionary+index
    run plans) instead of decoding values.  Returns None when the chunk
    needs the host decoder (unsupported physical type / encoding /
    nesting).

    Definition levels and dictionary indices are *not* decoded here
    (round 5): only their run HEADERS are walked (``rle_device.parse_runs``
    — O(#runs) host metadata, like page headers) and the bit-stream
    payload expands on device.  ``present_count`` provides the per-page
    present-value total the payload slicing needs.  FIXED_LEN_BYTE_ARRAY
    chunks (width ≤ 16 — the parquet DECIMAL carrier) are fixed-width
    too: their payload is kept raw and assembled into decimal limbs on
    device.  ``stats`` (metrics recording) takes the chunk's pages, bytes
    and decompression time."""
    from . import rle_device as RLE
    md = chunk.get(D.CC.META_DATA)
    phys = md.get(D.CMD.TYPE)
    is_flba = (phys == D.PT_FIXED_LEN_BYTE_ARRAY
               and 0 < type_len <= 16)
    is_str = phys == D.PT_BYTE_ARRAY
    is_bool = phys == D.PT_BOOLEAN
    if (phys not in _PLAIN_PHYS and not (is_flba or is_str or is_bool)) \
            or max_rep > 0:
        return None
    width = (type_len if is_flba
             else _PLAIN_PHYS.get(phys, 0))
    codec = md.get(D.CMD.CODEC, 0)
    num_values = md.get(D.CMD.NUM_VALUES)
    start = md.get(D.CMD.DATA_PAGE_OFFSET)
    dict_off = md.get(D.CMD.DICT_PAGE_OFFSET)
    if dict_off is not None and dict_off < start:
        start = dict_off
    total = md.get(D.CMD.TOTAL_COMPRESSED_SIZE)
    stream = D._PageStream(file_bytes[start:start + total], codec)
    if stats is not None:
        stats.compressed += total

    def _inflate(buf: bytes, size: int) -> bytes:
        if stats is None:
            return D._decompress(buf, codec, size)
        t0 = time.perf_counter()
        out = D._decompress(buf, codec, size)
        stats.decompress_s += time.perf_counter() - t0
        return out

    # def-level streams expand on device only when the whole expansion is
    # a bit test (flat optional column, max_def == 1) and no host stage
    # needs the concrete mask; the PLAIN-string native offsets walker
    # scatters by validity on host, so string chunks keep np levels
    def_bw = D._bit_width(max_def)
    use_plan_defs = max_def == 1 and not is_str

    def _levels(buf: bytes, n: int):
        """→ (entry, n_present): entry is None | ("np", arr) |
        ("plan", RunPlan, n_present)."""
        if use_plan_defs:
            plan = RLE.parse_runs(buf, def_bw, n)
            if plan is not None:
                npres = RLE.present_count(plan, max_def)
                if npres == n:
                    return None, n               # no nulls in this page
                return ("plan", plan, npres), npres
        defs = D.decode_rle_bitpacked_hybrid(buf, def_bw, n)
        return ("np", defs == max_def), int((defs == max_def).sum())

    dictionary = None
    payloads, idx_parts, def_parts, ns, npres_l = [], [], [], [], []
    decoded = 0
    while decoded < num_values:
        header, raw = stream.next_page()
        ptype = header.get(D.PH.TYPE)
        usize = header.get(D.PH.UNCOMPRESSED_SIZE)
        if stats is not None and ptype in (D.PAGE_DATA, D.PAGE_DATA_V2,
                                           D.PAGE_DICTIONARY):
            if ptype == D.PAGE_DICTIONARY:
                stats.pages_dict += 1
            else:
                stats.pages_data += 1
            stats.uncompressed += usize or 0
        if ptype == D.PAGE_DICTIONARY:
            dph = header.get(D.PH.DICT_PAGE)
            data = _inflate(raw, usize)
            m = dph.get(D.DPH.NUM_VALUES)
            if is_bool:
                return None
            if is_str:
                # dictionary strings (round 5): keep the dict page RAW —
                # the native walker stages the sequential offsets
                # recurrence, chars stay bytes for the device gather
                offs = D.byte_array_offsets(data, m)
                if offs is None:
                    return None
                dictionary = (bytes(data), offs)
            elif is_flba:   # fixed-width byte strings -> host limb decode
                dictionary = D._be_decimal_to_lanes(
                    np.frombuffer(data, np.uint8, m * type_len), type_len)
            else:
                dictionary = np.frombuffer(
                    data, dtype=D._PHYS_NP[phys], count=m)
            continue
        if ptype == D.PAGE_DATA:
            dph = header.get(D.PH.DATA_PAGE)
            n = dph.get(D.DPH.NUM_VALUES)
            enc = dph.get(D.DPH.ENCODING)
            data = _inflate(raw, usize)
            pos = 0
            dentry, n_present = None, n
            if max_def > 0:
                (ln,) = _struct.unpack_from("<I", data, pos)
                pos += 4
                dentry, n_present = _levels(data[pos:pos + ln], n)
                pos += ln
            page_vals = data[pos:]
        elif ptype == D.PAGE_DATA_V2:
            dph = header.get(D.PH.DATA_PAGE_V2)
            n = dph.get(D.DPH2.NUM_VALUES)
            enc = dph.get(D.DPH2.ENCODING)
            dl_len = dph.get(D.DPH2.DEF_LEVELS_BYTE_LENGTH, 0)
            body = raw[dl_len:]
            if dph.get(D.DPH2.IS_COMPRESSED, True):
                body = _inflate(body, usize - dl_len)
            dentry, n_present = None, n
            if max_def > 0 and dl_len:
                dentry, n_present = _levels(raw[:dl_len], n)
            page_vals = body
        else:
            continue

        if enc == D.ENC_PLAIN and is_str:
            offs = D.byte_array_offsets(page_vals, n_present)
            if offs is None:
                return None              # no native walker: host path
            payloads.append((bytes(page_vals), offs))
            idx_parts.append(None)
        elif enc == D.ENC_PLAIN and is_bool:
            need = (n_present + 7) // 8
            if len(page_vals) < need:
                return None
            payloads.append(bytes(page_vals[:need]))
            idx_parts.append(None)
        elif enc == D.ENC_PLAIN:
            payloads.append(page_vals[:n_present * width])
            idx_parts.append(None)
        elif enc in (D.ENC_PLAIN_DICTIONARY, D.ENC_RLE_DICTIONARY):
            if dictionary is None:
                return None
            if len(page_vals) == 0:
                # zero present values / truncated page: degrade to the host
                # decoder like every other unsupported shape
                return None
            bw = page_vals[0]
            plan = RLE.parse_runs(bytes(page_vals[1:]), bw, n_present) \
                if n_present else RLE.parse_runs(b"", 0, 0)
            if plan is not None and n_present:
                idx_parts.append(("plan", plan))
            elif n_present:
                idx_parts.append(("np", D.decode_rle_bitpacked_hybrid(
                    page_vals[1:], bw, n_present).astype(np.int32)))
            else:
                idx_parts.append(("np", np.zeros(0, np.int32)))
            payloads.append(None)
        else:
            return None
        def_parts.append(dentry)
        ns.append(n)
        npres_l.append(n_present)
        decoded += n

    has_plain = any(p is not None for p in payloads)
    has_dict = any(i is not None for i in idx_parts)
    if has_plain and has_dict:
        return None                  # mixed-encoding chunk: host fallback
    n_total = int(sum(ns))
    valid = _assemble_valid(def_parts, ns, force_np=is_str)
    if has_dict:
        kind = "dict_str" if is_str else "dict"
        return (kind, phys, dictionary,
                [i for i in idx_parts if i is not None], valid, n_total)
    if is_str:
        # per-page (payload, offs) → one stream + global segment geometry
        base = 0
        starts_all, lens_all, bufs = [], [], []
        for payload_p, offs in payloads:
            k = offs.shape[0] - 1
            lens = offs[1:] - offs[:-1]
            starts_all.append(base + offs[:-1].astype(np.int64)
                              + 4 * np.arange(1, k + 1, dtype=np.int64))
            lens_all.append(lens)
            bufs.append(payload_p)
            base += len(payload_p)
        return ("plain_str", phys, None,
                (b"".join(bufs), np.concatenate(starts_all),
                 np.concatenate(lens_all)), valid, n_total)
    if is_bool:
        if len(payloads) > 1 and any(k % 8 for k in npres_l[:-1]):
            return None     # bit-misaligned page boundary: host path
        return ("plain_bool", phys, None, b"".join(payloads), valid,
                n_total)
    payload = b"".join(payloads)
    return ("plain", phys, None, payload, valid, n_total)


def _assemble_valid(def_parts, ns, force_np: bool):
    """Chunk-level validity from per-page level entries: None (no nulls),
    a host bool array, or ("plans", [(RunPlan|None, n)]) for device
    expansion."""
    if not any(d is not None for d in def_parts):
        return None
    if force_np or any(d is not None and d[0] == "np" for d in def_parts):
        from . import rle_device as RLE
        segs = []
        for d, k in zip(def_parts, ns):
            if d is None:
                segs.append(np.ones(k, bool))
            elif d[0] == "np":
                segs.append(d[1])
            else:
                segs.append(RLE.expand_np(d[1]) == 1)
        valid = np.concatenate(segs)
        return None if valid.all() else valid
    return ("plans", [(None if d is None else d[1], k)
                      for d, k in zip(def_parts, ns)])


def _u8_to_u32_flat(raw: jnp.ndarray) -> jnp.ndarray:
    """u8 [4k] → u32 [k] little-endian via wide-block strided slices —
    measured several times faster than the narrow-minor [k,4] bitcast on
    TPU (the relayout dominates; see xpack._u8_to_u32_rows).  Behind
    SRJT_PALLAS_TRANSPOSE the same combine runs as a blocked Pallas
    kernel (rowconv.xpallas.try_u8_to_u32) — bit-identical output."""
    from ..rowconv import xpallas
    k = raw.shape[0] // 4
    pad = (-raw.shape[0]) % 512
    b = jnp.pad(raw, (0, pad))
    w = xpallas.try_u8_to_u32(b)
    if w is not None:
        return w[:k]
    b = b.reshape(-1, 512)
    parts = [b[:, j::4].astype(jnp.uint32) for j in range(4)]
    w = (parts[0] | (parts[1] << 8) | (parts[2] << 16) | (parts[3] << 24))
    return w.reshape(-1)[:k]


def _word_pairs(words: jnp.ndarray) -> jnp.ndarray:
    """u32 [2k] → u32 [k, 2] (lo, hi) — the 8-byte value split.

    A lane-strided de-interleave on a [rows, 256] view, NOT
    ``reshape(-1, 2)``: the TPU compiler stages that reshape through a
    [k, 2]-minor temporary padded 64x, and with two or more of them in one
    program its compile time grows with k (12 minutes for the three 8-byte
    q6 columns at 6M rows).  This form compiles in seconds and is the
    fastest of the forms timed on a v5e (PERF.md, PR 23)."""
    k = words.shape[0] // 2
    rows = -(-words.shape[0] // 256)
    w2 = jnp.pad(words, (0, rows * 256 - words.shape[0])).reshape(rows, 256)
    lo = w2[:, 0::2].reshape(-1)[:k]
    hi = w2[:, 1::2].reshape(-1)[:k]
    return jnp.stack([lo, hi], axis=1)


@functools.partial(jax.jit, static_argnums=0)
def _device_plain_w(phys: int, words: jnp.ndarray,
                    valid: Optional[jnp.ndarray]):
    """u32 word payload [k*itemsize/4] → typed [k] (+ def-level
    expansion).  PLAIN fixed payloads are always 4-byte aligned, so the
    u8→u32 step happens on HOST as a free ``np.frombuffer`` view and the
    device decode collapses to bitcasts/reshapes (round 5 — the strided
    u8 lane extraction was the round-4 scan's cost center)."""
    if phys == D.PT_DOUBLE:
        typed = _word_pairs(words)         # IS the f64 bit-pair storage
    elif phys == D.PT_FLOAT:
        typed = jax.lax.bitcast_convert_type(words, jnp.float32)
    elif phys == D.PT_INT64:
        # bitcast packs the last axis LSW-first on the little-endian
        # backends, and saves the u64 shift/or assembly (not measured
        # from a caller's side: PERF.md §5 has the scan's breakdown)
        typed = jax.lax.bitcast_convert_type(_word_pairs(words),
                                             jnp.int64)
    else:
        typed = jax.lax.bitcast_convert_type(words, jnp.int32)
    if valid is None:
        return typed
    if typed.shape[0] == 0:
        shape = (valid.shape[0],) + typed.shape[1:]
        return jnp.zeros(shape, typed.dtype)
    pos = jnp.clip(jnp.cumsum(valid.astype(jnp.int32)) - 1, 0,
                   typed.shape[0] - 1)
    full = typed[pos]
    zero = jnp.zeros((), typed.dtype)
    if typed.ndim == 2:
        return jnp.where(valid[:, None], full, zero)
    return jnp.where(valid, full, zero)


@functools.partial(jax.jit, static_argnums=0)
def _device_plain(phys: int, raw: jnp.ndarray,
                  valid: Optional[jnp.ndarray]):
    """u8 payload [k*itemsize] → typed [k] (+ def-level expansion to the
    full row count when ``valid`` is given).

    FLOAT64 lands as u32 [n, 2] bit pairs (the Column invariant) — the
    decode is pure byte movement, exact on every backend."""
    if phys == D.PT_DOUBLE:
        typed = _word_pairs(_u8_to_u32_flat(raw))           # [k, 2]
    elif phys == D.PT_FLOAT:
        typed = jax.lax.bitcast_convert_type(_u8_to_u32_flat(raw),
                                             jnp.float32)
    elif phys == D.PT_INT64:
        w = _word_pairs(_u8_to_u32_flat(raw))
        typed = (w[:, 0].astype(jnp.uint64)
                 | (w[:, 1].astype(jnp.uint64) << 32)).astype(jnp.int64)
    else:
        typed = jax.lax.bitcast_convert_type(_u8_to_u32_flat(raw),
                                             jnp.int32)
    if valid is None:
        return typed
    if typed.shape[0] == 0:        # all-null column: nothing to gather
        shape = (valid.shape[0],) + typed.shape[1:]
        return jnp.zeros(shape, typed.dtype)
    # def-level expansion: present value i sits at the i-th valid slot
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    pos = jnp.clip(pos, 0, typed.shape[0] - 1)
    full = typed[pos]
    zero = jnp.zeros((), typed.dtype)
    if typed.ndim == 2:
        return jnp.where(valid[:, None], full, zero)
    return jnp.where(valid, full, zero)


@functools.partial(jax.jit, static_argnums=0)
def _device_dict(phys: int, dict_vals: jnp.ndarray, idx: jnp.ndarray,
                 valid: Optional[jnp.ndarray]):
    """Dictionary gather on device (+ def-level expansion)."""
    if valid is None:
        return dict_vals[idx]
    if idx.shape[0] == 0:          # all-null column: nothing to gather
        shape = (valid.shape[0],) + dict_vals.shape[1:]
        return jnp.zeros(shape, dict_vals.dtype)
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    pos = jnp.clip(pos, 0, idx.shape[0] - 1)
    full = dict_vals[idx[pos]]
    zero = jnp.zeros((), dict_vals.dtype)
    if full.ndim == 2:
        return jnp.where(valid[:, None], full, zero)
    return jnp.where(valid, full, zero)


@functools.partial(jax.jit, static_argnums=0)
def _device_flba_decimal(width: int, raw: jnp.ndarray,
                         valid: Optional[jnp.ndarray]):
    """FIXED_LEN_BYTE_ARRAY decimal payload (big-endian two's complement,
    ``width`` ≤ 16 bytes) → int64 [k, 2] (lo, hi) limb pairs on device —
    the DECIMAL128 Column payload — with sign extension and def-level
    expansion.  Mirrors the host oracle ``decode._be_decimal_to_lanes``."""
    b = raw.reshape(-1, width).astype(jnp.int64)          # BE bytes, [k, w]
    neg = b[:, 0] >= 128
    fill = jnp.where(neg, jnp.int64(0xFF), jnp.int64(0))

    def byte(i):                       # little-endian byte i of the value
        return b[:, width - 1 - i] if i < width else fill

    lo = byte(0)
    for i in range(1, 8):
        lo = lo | (byte(i) << (8 * i))
    hi = byte(8)
    for i in range(9, 16):
        hi = hi | (byte(i) << (8 * (i - 8)))
    typed = jnp.stack([lo, hi], axis=1)                   # [k, 2]
    if valid is None:
        return typed
    if typed.shape[0] == 0:
        return jnp.zeros((valid.shape[0], 2), jnp.int64)
    pos = jnp.clip(jnp.cumsum(valid.astype(jnp.int32)) - 1, 0,
                   typed.shape[0] - 1)
    return jnp.where(valid[:, None], typed[pos], jnp.int64(0))


@functools.partial(jax.jit, static_argnums=0)
def _device_bool(k: int, bits: jnp.ndarray,
                 valid: Optional[jnp.ndarray]):
    """BOOLEAN bit-unpack on device: packed LSB-first bits → u8 0/1 [k]
    (+ def-level expansion)."""
    b = bits[:(k + 7) // 8]
    vals = ((b[:, None] >> jnp.arange(8, dtype=jnp.uint8)[None, :]) & 1)
    vals = vals.reshape(-1)[:k].astype(jnp.uint8)
    if valid is None:
        return vals
    if k == 0:
        return jnp.zeros(valid.shape[0], jnp.uint8)
    pos = jnp.clip(jnp.cumsum(valid.astype(jnp.int32)) - 1, 0, k - 1)
    return jnp.where(valid, vals[pos], jnp.uint8(0))


def _upload_dict(phys: int, dictionary: np.ndarray, stager=None):
    """Typed dictionary page upload — a deferred slab Handle when a
    stager is given (the spec arg resolves after the file-wide flush)."""
    if phys == D.PT_DOUBLE:
        from ..utils import f64bits
        dictionary = f64bits.np_to_bits(dictionary)
    return staging.asarray(dictionary, stager)


def _valid_needs_np(parts) -> bool:
    return any(isinstance(p[4], np.ndarray) for p in parts)


def _valid_np_concat(parts):
    """Normalize all chunks' validity to one host bool array (or None)."""
    from . import rle_device as RLE
    if not any(p[4] is not None for p in parts):
        return None
    segs = []
    for p in parts:
        v = p[4]
        if v is None:
            segs.append(np.ones(p[5], bool))
        elif isinstance(v, np.ndarray):
            segs.append(v)
        else:
            for plan, k in v[1]:
                segs.append(np.ones(k, bool) if plan is None
                            else RLE.expand_np(plan) == 1)
    return np.concatenate(segs)


def _valid_device_concat(parts, stager=None):
    """Device validity: per-page def-level plans expand on chip (bit
    test), all-valid pages are ones.  None when no chunk has nulls."""
    from . import rle_device as RLE
    if not any(p[4] is not None for p in parts):
        return None
    segs = []
    for p in parts:
        v = p[4]
        if v is None:
            segs.append(jnp.ones(p[5], jnp.bool_))
        elif isinstance(v, np.ndarray):
            segs.append(_stage_wave(stager, v)[0])
        else:
            for plan, k in v[1]:
                segs.append(jnp.ones(k, jnp.bool_) if plan is None
                            else RLE.expand_device(plan, stager) == 1)
    return segs[0] if len(segs) == 1 else jnp.concatenate(segs)


def _idx_device_concat(entries, stager=None) -> jnp.ndarray:
    """Dictionary-index entries (("plan", RunPlan) | ("np", arr)) →
    one int32 device vector; run plans expand on chip."""
    from . import rle_device as RLE
    if all(e[0] == "plan" for e in entries):
        segs = [RLE.expand_device(e[1], stager) for e in entries]
        return segs[0] if len(segs) == 1 else jnp.concatenate(segs)
    return _stage_wave(stager, np.concatenate(
        [RLE.expand_np(e[1]) if e[0] == "plan" else e[1]
         for e in entries]).astype(np.int32))[0]


@functools.partial(jax.jit, static_argnums=(3,))
def _dict_str_rows(dict_lens: jnp.ndarray, idx: jnp.ndarray, valid,
                   g: int = 8):
    """Per-output-row dictionary entry + chars length (def-level expanded)
    and the packing stats — shared by the planning sync and the chars
    program so the two cannot drift."""
    from ..rowconv import xpack
    if valid is None:
        idx_full = idx
        lens_row = dict_lens[idx_full].astype(jnp.int32)
    else:
        pos = jnp.clip(jnp.cumsum(valid.astype(jnp.int32)) - 1, 0,
                       max(int(idx.shape[0]) - 1, 0))
        idx_full = jnp.where(valid, idx[pos] if idx.shape[0] else 0, 0)
        lens_row = jnp.where(valid, dict_lens[idx_full], 0).astype(
            jnp.int32)
    dst = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(lens_row)])
    return idx_full, lens_row, dst, xpack.dst_combine_stats(dst, g)


@functools.partial(jax.jit, static_argnums=(0,))
def _dict_str_chars(geom, dictmat: jnp.ndarray, dict_lens: jnp.ndarray,
                    idx: jnp.ndarray, valid):
    """Dictionary-string column body: padded dict rows [Ds, Lw] gathered
    per output row, then packed to the Arrow chars stream + offsets with
    the xpack combine — all on device, one program."""
    from ..rowconv import xpack, xpallas
    n, g, Bd, P, nwin, total = geom
    idx_full, lens_row, dst, _ = _dict_str_rows(dict_lens, idx, valid, g)
    piece = xpallas.try_gather_rows(dictmat, idx_full)
    if piece is None:
        piece = dictmat[idx_full]                   # [n, Lw] u32 rows
    chars = xpack._combine_to_stream(piece, lens_row, dst, n, g, Bd, P,
                                     nwin, total)
    return chars, dst


# --- per-file fused decode (round 5) ---------------------------------------
#
# The final per-column device programs join ONE jitted per-file program
# (the libcudf analog decodes a whole row group in one kernel wave): host
# staging + the small metadata programs (index expansion, packing stats)
# run eagerly per column, then every column's heavy decode body inlines
# into a single dispatch.  Builders take (statics, args) with the
# validity's presence encoded in statics so arg tuples stay None-free.

def _build_plain(statics, args):
    phys, dt, has_valid = statics
    raw, valid = (args[0], args[1] if has_valid else None)
    data = (_device_plain_w(phys, raw, valid)
            if raw.dtype == jnp.uint32 else _device_plain(phys, raw, valid))
    if dt.id != T.TypeId.FLOAT64 and data.dtype != jnp.dtype(dt.storage):
        data = data.astype(dt.storage)     # logical narrowing (date32 etc.)
    return data


def _build_flba(statics, args):
    width, dt, has_valid = statics
    raw, valid = (args[0], args[1] if has_valid else None)
    data = _device_flba_decimal(width, raw, valid)
    if dt.id == T.TypeId.DECIMAL128:
        return data
    return data[:, 0].astype(dt.storage)   # lo limb for <= 18 digits


def _build_bool(statics, args):
    k, has_valid = statics
    bits, valid = (args[0], args[1] if has_valid else None)
    return _device_bool(k, bits, valid)


def _build_dict(statics, args):
    phys, dt, is_flba, has_valid = statics
    dict_dev, idx = args[0], args[1]
    valid = args[2] if has_valid else None
    data = _device_dict(phys, dict_dev, idx, valid)
    if is_flba:
        if dt.id == T.TypeId.DECIMAL128:
            return data
        return data[:, 0].astype(dt.storage)
    if dt.id != T.TypeId.FLOAT64 and data.dtype != jnp.dtype(dt.storage):
        data = data.astype(dt.storage)
    return data


def _build_pstr(statics, args):
    from ..rowconv import xpack
    (geom,) = statics
    payload, st, ln, dst = args
    return xpack.segmented_gather(geom, payload, st, ln, dst)


def _build_dstr(statics, args):
    geom, has_valid = statics
    dictmat, dict_lens, idx = args[0], args[1], args[2]
    valid = args[3] if has_valid else None
    return _dict_str_chars(geom, dictmat, dict_lens, idx, valid)


def _build_dcode(statics, args):
    """Dictionary-string CODES column body: def-level expansion of the RLE
    index stream to one int32 code per output row (null slots hold 0) —
    the whole string decode when the scan keeps the dictionary
    (:class:`DictColumn` output; bytes materialize at the output boundary,
    if ever)."""
    (has_valid,) = statics
    idx = args[0]
    valid = args[1] if has_valid else None
    if valid is None:
        return idx.astype(jnp.int32)
    pos = jnp.clip(jnp.cumsum(valid.astype(jnp.int32)) - 1, 0,
                   max(int(idx.shape[0]) - 1, 0))
    filled = idx[pos] if idx.shape[0] else jnp.zeros_like(pos)
    return jnp.where(valid, filled, 0).astype(jnp.int32)


_BUILDERS = {"plain": _build_plain, "flba": _build_flba,
             "bool": _build_bool, "dict": _build_dict,
             "pstr": _build_pstr, "dstr": _build_dstr,
             "dcode": _build_dcode}


@functools.partial(jax.jit, static_argnums=(0,))
def _decode_file_jit(plan, arrays):
    """plan: tuple of (builder key, statics, n_args) per column; arrays:
    the flat device-arg tuple.  One dispatch decodes the whole file."""
    outs = []
    i = 0
    for key, statics, k in plan:
        outs.append(_BUILDERS[key](statics, arrays[i:i + k]))
        i += k
    return tuple(outs)


# per-builder donate pattern over the arg tuple (validity is always the
# LAST arg when present and is NEVER donated: the assemble closures keep
# it alive as the Column's validity).  Every other staged input — raw
# payload slabs, index vectors, dictionary pages, gather geometry — is
# consumed exactly once by the decode body, so its HBM can be handed to
# the outputs instead of doubling the scan footprint.
_DONATE = {"plain": (True, False), "flba": (True, False),
           "bool": (True, False), "dict": (True, True, False),
           "pstr": (True, True, True, True),
           "dstr": (True, True, True, False),
           "dcode": (True, False)}


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _decode_file_jit_donated(plan, donated, kept):
    """``_decode_file_jit`` with the single-use input buffers donated.
    plan entries carry (key, statics, donate mask); the flat args split
    into the donated tuple and the kept tuple (validity arrays)."""
    outs = []
    di = ki = 0
    for key, statics, mask in plan:
        args = []
        for m in mask:
            if m:
                args.append(donated[di])
                di += 1
            else:
                args.append(kept[ki])
                ki += 1
        outs.append(_BUILDERS[key](statics, tuple(args)))
    return tuple(outs)


def _dict_strings_enabled() -> bool:
    """SRJT_DICT_STRINGS: keep dictionary-encoded string columns as
    :class:`DictColumn` codes (default on; 0/off reverts to eager
    materialization for differential testing)."""
    return knobs.get("SRJT_DICT_STRINGS")


def _scan_dict_str(parts, jvalid, n_total: int, stager=None):
    """Dictionary-encoded strings fully on device (round 5).

    Host stages only metadata: the dict page's offsets recurrence (native
    walker) and the index run headers.  Device: one ``segmented_gather``
    strips the dict page's length prefixes to a contiguous chars stream,
    ``extract_group_windows`` widens it to a padded [D, Lw] row matrix,
    the RLE index runs expand to positions, and the xpack combine packs
    each row's dictionary entry into the Arrow chars stream + offsets.
    The only sync is ONE stacked packing-geometry pull — the libcudf
    dict-string decode analog (SURVEY §2.9)."""
    from ..rowconv import xpack, xpallas

    # merge per-chunk dictionaries (usually byte-identical)
    dicts = [p[2] for p in parts]
    base = dicts[0]
    same = all(d is base or (d[0] == base[0]
                             and np.array_equal(d[1], base[1]))
               for d in dicts[1:])
    merged = [base] if same else dicts
    payload = b"".join(d[0] for d in merged)
    pbase = 0
    starts_l, lens_l, entc = [], [], []
    for d in merged:
        offs = d[1]
        k = offs.shape[0] - 1
        lens = (offs[1:] - offs[:-1]).astype(np.int32)
        starts_l.append(pbase + offs[:-1].astype(np.int64)
                        + 4 * np.arange(1, k + 1, dtype=np.int64))
        lens_l.append(lens)
        entc.append(k)
        pbase += len(d[0])
    starts = np.concatenate(starts_l)
    lens = np.concatenate(lens_l)
    Ds = int(lens.shape[0])
    if Ds == 0:
        return None
    dict_offs = np.zeros(Ds + 1, np.int64)
    np.cumsum(lens, out=dict_offs[1:])
    if pbase >= 2**31 or int(dict_offs[-1]) >= 2**31:
        return None

    # indices (device), offset-rebased when dictionaries were merged
    idx_all = []
    off = 0
    for ci, p in enumerate(parts):
        part_idx = _idx_device_concat(p[3], stager)
        idx_all.append(part_idx + off if off else part_idx)
        if not same:
            off += entc[ci]
    idx = jnp.concatenate(idx_all) if len(idx_all) > 1 else idx_all[0]

    # device dict: strip prefixes → contiguous chars → padded row matrix
    total_chars = int(dict_offs[-1])
    Lmax = int(lens.max(initial=0))
    Lw = xpack._bucket(max(-(-Lmax // 4), 1), 4)
    if Lw > 512 and not _dict_strings_enabled():
        # the entry-width cap guards the padded [Ds, Lw] matrix of the
        # materializing path only — the codes path never builds it
        return xpack._reject("dict_str_entry_len", Lw=Lw)
    if total_chars:
        geom_sg = xpack.plan_segmented_gather(starts, lens, dict_offs)
        if geom_sg is None:
            return None
        jpay, jst, jln, jdo = _stage_wave(
            stager, np.frombuffer(payload, np.uint8),
            starts.astype(np.int32), lens, dict_offs.astype(np.int32))
        chars_dict = xpack.segmented_gather(geom_sg, jpay, jst, jln, jdo)
    else:
        chars_dict = jnp.zeros(0, jnp.uint8)

    if _dict_strings_enabled():
        # DICTIONARY FAST PATH (default): stop here.  The column stays as
        # int32 codes + the contiguous dictionary just built — no padded
        # row matrix, no packing-geometry sync, no chars stream.  Bytes
        # materialize lazily at the output boundary (DictColumn), and
        # predicates/joins/groupbys/sorts run on the codes.
        from ..utils import hostcache
        doffs32 = _stage_wave(stager, dict_offs.astype(np.int32))[0]
        hostcache.seed(doffs32, dict_offs.astype(np.int64))
        dict_col = Column(T.string, chars_dict, doffs32)
        metrics.count("plan.scan.dict_cols")
        statics = (jvalid is not None,)
        args = (idx,) + ((jvalid,) if jvalid is not None else ())

        def assemble_codes(out):
            return DictColumn(out, dict_col, jvalid)
        return ("dcode", statics, args, assemble_codes)

    # padded dictionary row matrix: Pallas row extraction (host offsets,
    # zero-padded rows — the combine masks each row to its length, so the
    # two builds yield byte-identical chars) or the XLA group windows
    dictmat = None
    if total_chars:
        xr = xpallas.try_extract_rows(chars_dict, dict_offs, Lw * 4)
        if xr is not None:
            dictmat = jax.lax.bitcast_convert_type(
                xr.reshape(Ds, Lw, 4), jnp.uint32)
    if dictmat is None:
        g = 8
        gidx = np.minimum(np.arange(0, Ds + g, g), Ds)
        span = int((dict_offs[gidx[1:]]
                    - dict_offs[gidx[:-1]]).max(initial=1))
        B = xpack._bucket(max(span, 64), 64)
        if B > (1 << 20):
            return xpack._reject("dict_str_slab", B=B)
        dictmat = xpack.extract_group_windows(
            chars_dict, _stage_wave(stager, dict_offs.astype(np.int32))[0],
            Ds, g, B, Lw)
    dict_lens = _stage_wave(stager, lens)[0]

    # packing geometry: one stacked sync per adaptive-g try (short dict
    # entries need LARGE groups or the window combine's P cap blows —
    # same adaptation as xpack.plan_from_rows)
    gs = (8, 32, 128)
    geom = None
    for g in gs:
        syncs.note_sync()
        stats = np.asarray(_dict_str_rows(dict_lens, idx, jvalid, g)[3])
        total, dspan, max_p = (int(x) for x in stats)
        if total >= 2**31:
            return None
        if total == 0:
            offs32 = jnp.zeros(n_total + 1, jnp.int32)
            col0 = Column(T.string, jnp.zeros(0, jnp.uint8), offs32,
                          jvalid)
            return ("const", (), (), lambda _out: col0)
        combine = xpack.plan_combine(total, dspan, max_p, "dict_str_caps",
                                     final=(g == gs[-1]))
        if combine is not None:
            Bd, P, nwin = combine
            geom = (n_total, g, Bd, P, nwin, total)
            break
    if geom is None:
        return None
    statics = (geom, jvalid is not None)
    args = (dictmat, dict_lens, idx) + ((jvalid,) if jvalid is not None
                                        else ())

    def assemble(out):
        chars, dst = out
        return Column(T.string, chars, dst, jvalid)
    return ("dstr", statics, args, assemble)


def scan_column_device(file_bytes: bytes, chunks, leaf) -> Optional[Column]:
    """All row groups of one column via the device path; None → fall back.
    Eager form of :func:`stage_column_device` (single-column callers)."""
    spec = stage_column_device(file_bytes, chunks, leaf)
    if spec is None:
        return None
    key, statics, args, assemble = spec
    if key == "const":
        return assemble(None)
    return assemble(_BUILDERS[key](statics, _resolve_args(args)))


def _walk_column(file_bytes: bytes, chunks, leaf, parent=None):
    """Host page walk for every chunk of one column — pure host work (no
    device calls), the producer half of the staged scan pipeline.
    None → host fallback.  ``parent`` is the scan's span where the walk
    runs on another thread than the scan."""
    with metrics.span("parquet.scan.walk", parent=parent,
                      column=leaf.name) as sp:
        stats = None if sp is None else _WalkStats()
        try:
            parts = []
            for chunk in chunks:
                part = _walk_chunk_raw(file_bytes, chunk, leaf.max_def,
                                       leaf.max_rep, leaf.type_len or 0,
                                       stats)
                if part is None:
                    return None
                parts.append(part)
            return parts
        finally:
            if stats is not None:
                stats.write(sp)


def stage_column_device(file_bytes: bytes, chunks, leaf, stager=None):
    """Host staging for one column → deferred decode spec
    (key, statics, device-arg tuple, assemble) or None (host fallback).
    The heavy decode body runs later — alone (scan_column_device) or
    inlined into the per-file fused program (_decode_file_jit).  With a
    ``staging.SlabStager`` the raw page buffers queue as slab handles
    (resolved by the caller after the file-wide flush)."""
    parts = _walk_column(file_bytes, chunks, leaf)
    if parts is None:
        return None
    return _stage_column_parts(parts, leaf, stager)


def _stage_column_parts(parts, leaf, stager=None):
    """Device staging from walked raw parts (the consumer half)."""
    with metrics.span("parquet.scan.stage", column=leaf.name) as sp:
        queued = stager.queued_bytes if stager is not None else 0
        spec = _stage_parts(parts, leaf, stager)
        if sp is not None and stager is not None:
            sp.annotate(bytes=stager.queued_bytes - queued)
    return spec


def _stage_parts(parts, leaf, stager=None):
    kinds = {p[0] for p in parts}
    physes = {p[1] for p in parts}
    if len(kinds) > 1 or len(physes) > 1:
        return None
    kind, phys = parts[0][0], parts[0][1]
    dt = leaf.logical_dtype()
    if dt.id == T.TypeId.LIST:
        return None
    is_flba = phys == D.PT_FIXED_LEN_BYTE_ARRAY
    if is_flba and not dt.is_decimal:
        return None   # non-decimal fixed-size binary (UUIDs): host path
    if kind in ("plain_str", "dict_str") and dt.id != T.TypeId.STRING:
        return None   # BYTE_ARRAY decimals etc.: host path

    n_total = int(sum(p[5] for p in parts))
    if kind == "plain_str":
        # the native offsets walker scatters by validity on HOST — np mask
        valid_np = _valid_np_concat(parts)
        jvalid = (None if valid_np is None
                  else _stage_wave(stager, valid_np)[0])
    else:
        # def levels expand ON DEVICE (bit test over the run plans)
        valid_np = None
        jvalid = _valid_device_concat(parts, stager)
    hv = jvalid is not None
    vtail = (jvalid,) if hv else ()

    if kind == "dict_str":
        return _scan_dict_str(parts, jvalid, n_total, stager)

    if kind == "plain_str":
        # strings fully on device: the char bytes never round through a
        # host loop — prefixes stripped by one segmented gather (the same
        # slab/roll machinery as the JCUDF transcode)
        from ..rowconv import xpack
        from ..utils import hostcache
        base = 0
        bufs, starts, lens = [], [], []
        for p in parts:
            payload_p, st, ln = p[3]
            bufs.append(payload_p)
            starts.append(st + base)
            lens.append(ln)
            base += len(payload_p)
        payload = b"".join(bufs)
        st = np.concatenate(starts) if starts else np.zeros(0, np.int64)
        ln = np.concatenate(lens) if lens else np.zeros(0, np.int32)
        dst = np.zeros(ln.shape[0] + 1, dtype=np.int64)
        np.cumsum(ln, out=dst[1:])
        geom = None
        if ln.shape[0] == 0 or dst[-1] == 0:
            chars = jnp.zeros(0, jnp.uint8)
        else:
            # the gather works in int32 positions; a concatenated multi-
            # chunk payload approaching 2 GiB would wrap the casts below
            # and corrupt the decode — fall back to the host path instead
            # (the native walker only guards per-page char totals)
            if (base >= 2**31 or int(dst[-1]) >= 2**31
                    or int(st.max(initial=0)) >= 2**31):
                return None
            geom = xpack.plan_segmented_gather(st, ln, dst)
            if geom is None:
                return None
            ln = ln.astype(np.int32)
            chars = None           # deferred: the fused segmented gather
        if valid_np is None:
            row_lens = ln
        else:
            row_lens = np.zeros(n_total, dtype=np.int64)
            row_lens[valid_np] = ln
        offs_np = np.zeros(n_total + 1, dtype=np.int64)
        np.cumsum(row_lens, out=offs_np[1:])
        joffs = jnp.asarray(offs_np.astype(np.int32))
        hostcache.seed(joffs, offs_np)
        if chars is not None:      # degenerate empty column: no jit body
            col0 = Column(T.string, chars, joffs, jvalid)
            return ("const", (), (), lambda _out: col0)
        # raw chars + gather geometry stay slab HANDLES until the caller's
        # file-wide flush — the whole file's strings ride a few transfers
        return ("pstr", (geom,),
                (staging.asarray(np.frombuffer(payload, np.uint8), stager),
                 staging.asarray(st.astype(np.int32), stager),
                 staging.asarray(ln, stager),
                 staging.asarray(dst.astype(np.int32), stager)),
                lambda out: Column(T.string, out, joffs, jvalid))

    if kind == "plain_bool":
        def _npres(p):
            v = p[4]
            if v is None:
                return p[5]
            if isinstance(v, np.ndarray):
                return int(v.sum())
            from . import rle_device as RLE
            return sum(k if plan is None else RLE.present_count(plan, 1)
                       for plan, k in v[1])
        npresent = [_npres(p) for p in parts]
        if len(parts) > 1 and any(k % 8 for k in npresent[:-1]):
            return None   # bit-misaligned chunk boundary: host path
        payload = b"".join(p[3] for p in parts)
        k = int(sum(npresent))
        bits = staging.asarray(np.frombuffer(payload, np.uint8), stager)
        return ("bool", (k, hv), (bits,) + vtail,
                lambda out: Column(T.bool8, out, validity=jvalid))

    if kind == "plain":
        payload = b"".join(p[3] for p in parts)
        if is_flba:
            raw = staging.asarray(np.frombuffer(payload, dtype=np.uint8),
                                  stager)
            return ("flba", (leaf.type_len, dt, hv), (raw,) + vtail,
                    lambda out: Column(dt, out, validity=jvalid))
        # 4/8-byte payloads are 4-aligned: the u8→u32 step is a FREE host
        # view, and the device decode is bitcasts/reshapes only
        raw = staging.asarray(np.frombuffer(payload, dtype=np.uint32)
                              if len(payload) % 4 == 0
                              else np.frombuffer(payload, dtype=np.uint8),
                              stager)
        return ("plain", (phys, dt, hv), (raw,) + vtail,
                lambda out: Column(dt, out, validity=jvalid))
    else:
        dicts = [p[2] for p in parts]
        base = dicts[0]
        if any(d is not base and not np.array_equal(d, base)
               for d in dicts[1:]):
            # per-row-group dictionaries differ: rebase indices (the
            # per-chunk run plans expand on device, offset added there)
            idx_all = []
            offset = 0
            merged = np.concatenate(dicts)
            for p in parts:
                part_idx = _idx_device_concat(p[3], stager)
                idx_all.append(part_idx + offset if offset else part_idx)
                offset += p[2].shape[0]
            dict_dev = _upload_dict(phys, merged, stager)
            idx = jnp.concatenate(idx_all) if len(idx_all) > 1 \
                else idx_all[0]
        else:
            dict_dev = _upload_dict(phys, base, stager)
            idx_all = [_idx_device_concat(p[3], stager) for p in parts]
            idx = jnp.concatenate(idx_all) if len(idx_all) > 1 \
                else idx_all[0]
        return ("dict", (phys, dt, is_flba, hv),
                (dict_dev, idx) + vtail,
                lambda out: Column(dt, out, validity=jvalid))


def _chunk_minmax(chunk, leaf):
    """(min, max) bounds from a column chunk's footer Statistics, or None
    when the stats are absent/undecodable.  INT32/INT64 decode to ints,
    BYTE_ARRAY returns raw bytes bounds (unsigned lexicographic — the
    UTF8 logical order), FLBA DECIMAL decodes big-endian two's-complement
    to the unscaled int the runtime predicate also compares against.

    BYTE_ARRAY/FLBA read ONLY the logical ``min_value``/``max_value``
    fields — the deprecated MIN/MAX pair used signed (or undefined) byte
    order and cannot be trusted for these types.  Writers may truncate
    the logical bounds (min rounded down, max rounded up): they remain
    valid BOUNDS, which is all a disjointness test needs."""
    md = chunk.get(D.CC.META_DATA)
    st = md.get(D.CMD.STATISTICS)
    if st is None:
        return None
    phys = leaf.phys
    if phys in (D.PT_INT32, D.PT_INT64):
        fmt, size = ("<i", 4) if phys == D.PT_INT32 else ("<q", 8)

        def dec(v):
            # explicit None check: b"\x00..." is a perfectly valid
            # (falsy-looking) PLAIN-encoded bound
            if v is None or not isinstance(v, (bytes, bytearray)) \
                    or len(v) != size:
                return None
            return _struct.unpack(fmt, bytes(v))[0]

        mn = dec(st.get(D.ST.MIN_VALUE))
        if mn is None:
            mn = dec(st.get(D.ST.MIN))
        mx = dec(st.get(D.ST.MAX_VALUE))
        if mx is None:
            mx = dec(st.get(D.ST.MAX))
    elif phys == D.PT_BYTE_ARRAY:
        mn = st.get(D.ST.MIN_VALUE)
        mx = st.get(D.ST.MAX_VALUE)
        mn = bytes(mn) if isinstance(mn, (bytes, bytearray)) else None
        mx = bytes(mx) if isinstance(mx, (bytes, bytearray)) else None
    elif phys == D.PT_FIXED_LEN_BYTE_ARRAY:
        try:
            if not leaf.logical_dtype().is_decimal:
                return None
        except Exception:
            return None
        width = leaf.type_len

        def dec(v):
            if not isinstance(v, (bytes, bytearray)) \
                    or (width and len(v) != width):
                return None
            return int.from_bytes(bytes(v), "big", signed=True)

        mn = dec(st.get(D.ST.MIN_VALUE))
        mx = dec(st.get(D.ST.MAX_VALUE))
    else:
        return None
    if mn is None or mx is None:
        return None
    return mn, mx


def _group_disjoint(mn, mx, op: str, val) -> bool:
    """True when NO value in [mn, mx] can satisfy ``col <op> val`` — the
    row group provably contains no matching rows.  Works for any totally
    ordered bound type (int bounds vs int literal, bytes bounds vs bytes
    literal).  Null rows need no consideration: planner predicates fail
    nulls, and parquet min/max statistics ignore them."""
    if op == "eq":
        return val < mn or val > mx
    if op == "lt":
        return mn >= val
    if op == "le":
        return mn > val
    if op == "gt":
        return mx <= val
    if op == "ge":
        return mx < val
    return False


def _prune_row_groups(groups_list, leaves, names, conds):
    """Indices of row groups that may contain matching rows.  ``conds``
    is a list of ``(column_name, op, value)`` conjuncts with int or bytes
    values (planner contract: ALL must hold, so any single disjoint
    conjunct drops the group).  Groups without usable statistics — or
    whose statistic type does not match the literal type — are always
    kept."""
    name_to_idx = {n: i for i, n in enumerate(names)}
    kept = []
    for gi, rg in enumerate(groups_list):
        chunks = rg.get(D.RG.COLUMNS).values
        drop = False
        for cname, op, val in conds:
            ci = name_to_idx.get(cname)
            if ci is None:
                continue
            mm = _chunk_minmax(chunks[ci], leaves[ci])
            if mm is None:
                continue
            if isinstance(val, bytes) != isinstance(mm[0], bytes):
                continue    # literal/statistic type mismatch: keep group
            if _group_disjoint(mm[0], mm[1], op, val):
                drop = True
                break
        if not drop:
            kept.append(gi)
    return kept


def _decode_deferred(deferred) -> dict:
    """The fused decode of a file's device-path columns: arena admission,
    one dispatch of ``_decode_file_jit`` (its single-use input slabs
    donated where the backend takes donations), and each column's
    assemble.  ``deferred`` is ``(col index, key, statics, resolved args,
    assemble)``; returns ``{col index: Column}``."""
    # admission for the fused scan's staged input slabs (the decode
    # outputs are the table itself — not ephemeral — so only the raw
    # page/dictionary buffers are reserved)
    from ..memory import arena
    scan_bytes = sum(int(getattr(a, "nbytes", 0) or 0)
                     for _, _, _, args, _ in deferred for a in args)
    with arena.reserve(scan_bytes, tag="parquet.scan"):
        if staging.donate_enabled():
            plan = tuple((key, statics, _DONATE[key][:len(args)])
                         for _, key, statics, args, _ in deferred)
            don, keep = [], []
            for _, key, _, args, _ in deferred:
                for a, m in zip(args, _DONATE[key][:len(args)]):
                    (don if m else keep).append(a)
            don_bytes = sum(int(getattr(a, "nbytes", 0) or 0)
                            for a in don)
            flight.record("parquet.scan.donate", buffers=len(don),
                          bytes=don_bytes)
            if metrics.recording():
                metrics.count("parquet.scan.donated_bytes", don_bytes)
                metrics.annotate(donated_bytes=don_bytes)
            import warnings
            with warnings.catch_warnings():
                # CPU PJRT ignores donation with a warning — forcing
                # the knob there is a test mode, keep it quiet
                warnings.filterwarnings("ignore",
                                        message=".*[Dd]onat.*")
                outs = _decode_file_jit_donated(plan, tuple(don),
                                                tuple(keep))
        else:
            plan = tuple((key, statics, len(args))
                         for _, key, statics, args, _ in deferred)
            flat = tuple(a for _, _, _, args, _ in deferred
                         for a in args)
            outs = _decode_file_jit(plan, flat)
    return {i: assemble(out)
            for (i, _, _, _, assemble), out in zip(deferred, outs)}


def _span_overlap_ms(a_spans, b_spans) -> float:
    """Σ pairwise intersection of two interval lists, in milliseconds —
    how long the host page walk ran concurrently with device staging."""
    total = 0.0
    for a0, a1 in a_spans:
        for b0, b1 in b_spans:
            total += max(0.0, min(a1, b1) - max(a0, b0))
    return total * 1000.0


@traced("parquet_scan_table_device")
def scan_table(file_bytes: bytes,
               columns: Optional[list[str]] = None,
               row_groups: Optional[list[int]] = None,
               rowgroup_predicate=None,
               row_predicate=None) -> Table:
    """``decode.read_table`` with the device fast path per column.

    All device-path columns decode in ONE fused jitted program per file
    (``_decode_file_jit``; ``SRJT_FUSED_SCAN=0`` reverts to per-column
    dispatches); host-fallback columns batch through ``decode.read_table``
    as before.  Raw page buffers upload through the slab stager
    (``SRJT_STAGE_SLABS``) — a few large coalesced transfers per file —
    and, under ``SRJT_STAGE_PIPELINE``, the host page walk of column k+1
    overlaps the device staging of column k (a producer thread feeds a
    bounded queue; ``parquet.stage.overlap`` flight events account the
    concurrency).  ``SRJT_SCAN_DONATE`` donates the single-use input
    slabs to the fused decode so the raw bytes don't double the scan's
    HBM footprint.

    ``row_groups`` selects row groups by index (None = all);
    ``rowgroup_predicate`` is a list of ``(column, op, int_value)``
    conjuncts (op in eq/lt/le/gt/ge) tested against footer statistics —
    row groups provably containing no matching rows are skipped BEFORE
    any page decode (the planner's filter-pushdown target; counters
    ``plan.scan.rowgroups_pruned`` / ``plan.scan.rowgroups_kept``).
    ``row_predicate`` (same conjunct shape, bytes literals allowed) goes
    further under ``SRJT_FUSED_FILTER``: supported conjuncts evaluate on
    the walked RAW parts — once per dictionary entry on dict columns —
    and prune rows before anything uploads or decodes (``parquet.
    rowfilter``).  The result table carries ``fused_filter_complete``
    so the planner knows whether a re-apply is still needed."""
    with metrics.span("parquet.scan.footer") as sp:
        meta = parse_struct(extract_footer_bytes(file_bytes))
        leaves = D._leaf_schema_elements(meta)
        names = [leaf.name for leaf in leaves]
        want = list(range(len(leaves))) if columns is None else [
            names.index(c) for c in columns]
        groups = meta.get(D.FMD.ROW_GROUPS)
        groups_list = list(groups.values)
        kept = (list(range(len(groups_list))) if row_groups is None
                else sorted(set(row_groups)))
        if rowgroup_predicate:
            stat_kept = set(_prune_row_groups(groups_list, leaves, names,
                                              rowgroup_predicate))
            pruned = [gi for gi in kept if gi not in stat_kept]
            kept = [gi for gi in kept if gi in stat_kept]
            if metrics.recording():
                metrics.count("plan.scan.rowgroups_pruned", len(pruned))
                metrics.count("plan.scan.rowgroups_kept", len(kept))
            metrics.profile_op("scan.prune", rowgroups_pruned=len(pruned),
                               rowgroups_kept=len(kept))
        selecting = len(kept) < len(groups_list)
        chunk_lists = {i: [] for i in want}
        for gi in kept:
            chunks = groups_list[gi].get(D.RG.COLUMNS).values
            for i in want:
                chunk_lists[i].append(chunks[i])
        if sp is not None:
            sp.annotate(row_groups=len(kept), columns=len(want))
    if not kept:
        # every row group pruned: zero-row table via the host assembler
        return D.read_table(
            file_bytes, row_groups=[],
            columns=None if columns is None else [names[i] for i in want])

    fused = knobs.get("SRJT_FUSED_SCAN")
    stager = staging.SlabStager() if staging.enabled() else None
    fallback: list[int] = []
    by_index: dict[int, Column] = {}
    deferred: list[tuple] = []          # (col index, key, statics, args,
    #                                      assemble)
    filter_state = None                 # (conds, complete) once pruned

    def _dispatch(i, spec):
        if spec is None:
            fallback.append(i)
            return
        key, statics, args, assemble = spec
        if key == "const":
            by_index[i] = assemble(None)
        elif fused:
            deferred.append((i, key, statics, args, assemble))
        else:
            by_index[i] = assemble(
                _BUILDERS[key](statics, _resolve_args(args)))

    use_filter = bool(row_predicate) and bool(knobs.get("SRJT_FUSED_FILTER"))
    pipelined = (stager is not None and not use_filter
                 and bool(knobs.get("SRJT_STAGE_PIPELINE"))
                 and len(want) > 1)
    if use_filter:
        # fused scan→filter: the predicate needs every wanted column's
        # walked parts before anything uploads; a host-fallback column
        # would re-read the file unpruned, so any fallback aborts the
        # prune (the planner re-applies the full mask as before)
        from . import rowfilter
        walked = {i: _walk_column(file_bytes, chunk_lists[i], leaves[i])
                  for i in want}
        if all(walked[i] is not None for i in want):
            pruned = rowfilter.apply(row_predicate, walked, leaves, names,
                                     want)
            if pruned is not None:
                walked, complete, n_kept = pruned
                filter_state = (complete,)
                flight.record("parquet.rowfilter", kept=n_kept,
                              complete=complete)
                if metrics.recording():
                    metrics.count("parquet.rowfilter.fused_scans")
                    metrics.count("parquet.rowfilter.rows_kept", n_kept)
        for i in want:
            _dispatch(i, None if walked[i] is None else
                      _stage_column_parts(walked[i], leaves[i], stager))
    elif pipelined:
        import queue as _qmod
        import threading
        depth = max(1, int(knobs.get("SRJT_STAGE_PIPELINE_DEPTH") or 2))
        ch: _qmod.Queue = _qmod.Queue(maxsize=depth)
        walk_spans: list[tuple[float, float]] = []
        # the walker's spans hang under this call's, and carry its id
        scan_span = metrics.current_span()

        def _producer():
            try:
                for i in want:
                    t0 = time.perf_counter()
                    parts = _walk_column(file_bytes, chunk_lists[i],
                                         leaves[i], parent=scan_span)
                    walk_spans.append((t0, time.perf_counter()))
                    ch.put((i, parts))
            except BaseException as exc:   # re-raised by the consumer
                ch.put((None, exc))

        th = threading.Thread(target=_producer, name="srjt-scan-walk",
                              daemon=True)
        stage_spans: list[tuple[float, float]] = []
        th.start()
        try:
            for _ in want:
                # the walk that the pipeline did not hide
                with metrics.span("parquet.scan.walk_wait"):
                    i, parts = ch.get()
                if i is None:
                    raise parts
                t0 = time.perf_counter()
                spec = (None if parts is None else
                        _stage_column_parts(parts, leaves[i], stager))
                stage_spans.append((t0, time.perf_counter()))
                _dispatch(i, spec)
        finally:
            # never leave the producer blocked on a bounded put
            while th.is_alive():
                try:
                    ch.get_nowait()
                except _qmod.Empty:
                    th.join(0.05)
            th.join()
        overlap_ms = _span_overlap_ms(walk_spans, stage_spans)
        flight.record("parquet.stage.overlap",
                      overlap_ms=round(overlap_ms, 3), columns=len(want))
        if metrics.recording():
            metrics.count("parquet.stage.overlap_ms",
                          int(round(overlap_ms)))
    else:
        for i in want:
            _dispatch(i, stage_column_device(file_bytes, chunk_lists[i],
                                             leaves[i], stager))
    with metrics.span("parquet.scan.upload") as sp:
        if stager is not None:
            stager.flush()             # file-wide slab wave (async)
        deferred = [(i, key, statics, _resolve_args(args), assemble)
                    for i, key, statics, args, assemble in deferred]
        if sp is not None and stager is not None:
            sp.annotate(bytes=stager.slab_bytes, transfers=stager.transfers,
                        pack_ms=round(stager.pack_s * 1e3, 3))
    if deferred:
        with metrics.span("parquet.scan.decode"):
            by_index.update(_decode_deferred(deferred))
    if metrics.recording():
        # device/host split per scan — the fast-path coverage counter
        metrics.count("parquet.device_cols", len(want) - len(fallback))
        metrics.count("parquet.host_fallback_cols", len(fallback))
        metrics.annotate(device_cols=len(want) - len(fallback),
                         fallback_cols=len(fallback))
    if fallback:
        host = D.read_table(file_bytes,
                            columns=[names[i] for i in fallback],
                            row_groups=kept if selecting else None)
        for j, i in enumerate(fallback):
            by_index[i] = host[j]
    out = Table([by_index[i] for i in want])
    metrics.profile_op("scan", rows_out=out.num_rows, cols=len(want),
                       rowgroups=len(kept), fallback_cols=len(fallback))
    if filter_state is not None:
        # the planner checks this to skip the redundant re-apply: True
        # means every conjunct was evaluated and pruned at scan time
        out.fused_filter_complete = filter_state[0]
    # fused-scan outputs are evictable residents (HBM-arena follow-on):
    # under budget pressure the decoded columns host-spill IN PLACE and
    # fault back bit-exactly on their next op touch (no-op when the arena
    # is off — register_table gates on budget.active())
    from ..memory import spill as mspill
    mspill.register_table(out, "parquet.scan_out")
    return out


# API mirror: callers swap `from ..parquet import decode` for this module
read_table = scan_table
