"""Device row↔column transcode (JCUDF) — the XLA path.

TPU-native reimplementation of the reference's CUDA engine
(``row_conversion.cu``; public surface ``row_conversion.hpp:27-49``).  Design
translation (see SURVEY §7):

* The reference hand-tiles shared memory and double-buffers
  ``cuda::memcpy_async`` (``row_conversion.cu:575-693,892-993``).  On TPU the
  fixed-width transcode works at u32-word granularity end to end: each row
  word is composed from a statically-planned set of column fragments
  (shift/or tree), and the column->row interleave is one layout-preserving
  3-D permute (or, for wide rows, one 2-D transpose) whose output minor
  dimension is a 128-lane multiple.  Strided lane writes and a final
  u32->u8 repack lost to these forms — which is why :class:`RowBatch`
  carries the row bytes AS u32 words (JCUDF rows are 8-byte aligned, so the
  words are exact).  What the path does from a caller's side, and where its
  time goes: PERF.md §5.
* The warp-ballot validity transpose (``row_conversion.cu:710-810``)
  becomes a weighted-sum bit pack (``utils.bitmask.pack_bool_matrix``).
* Variable-width (string) handling follows the reference's two-phase shape
  discipline (size pass → alloc → copy pass; the reference syncs on the total
  at ``row_conversion.cu:2215``): row sizes and char totals are resolved on
  host, then a statically-shaped jitted scatter/gather does the copies.
* Output is split into ≤2GB batches exactly like ``build_batches``
  (``row_conversion.cu:1460-1539``); ``convert_from_rows`` accepts exactly one
  batch (``row_conversion.cu:2124-2139``).  A row has no size limit of its
  own (the reference's ``convert_to_rows`` has none): it has to fit a batch.

Dynamic-shape note: everything under ``jit`` here is static-shaped; the only
host syncs are the same ones the reference performs (string totals).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..column import Column, DictColumn, Table, as_dict_column, force_column
from ..faultinj import fault_site
from ..utils import bitmask, knobs, metrics, syncs
from ..utils.tracing import traced
from .layout import (RowLayout, compute_row_layout, build_batches,
                     row_sizes_with_strings, MAX_BATCH_BYTES,
                     BATCH_ROW_MULTIPLE)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RowBatch:
    """One ≤2GB batch of JCUDF rows: the LIST<INT8> column analog
    (``row_conversion.cu:1869-1889``).

    ``data`` is the packed row byte stream, stored either as uint8
    [total_bytes] (variable-width batches, byte-granular DMA engine) or as
    uint32 [total_bytes/4] little-endian words (fixed-width batches — rows
    are 8-byte aligned so the word view is exact, and keeping words avoids
    a u32->u8 relayout pass over the whole batch on TPU).  Both views
    describe the identical JCUDF byte stream; :meth:`host_bytes` is the
    canonical byte materialization.
    """

    data: jnp.ndarray      # uint8 [total_bytes] or uint32 [total_bytes/4]
    offsets: jnp.ndarray   # int32 [num_rows + 1] byte offsets

    def tree_flatten(self):
        return (self.data, self.offsets), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)

    @property
    def num_rows(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def num_bytes(self) -> int:
        return self.data.shape[0] * self.data.dtype.itemsize

    def host_bytes(self) -> np.ndarray:
        """The JCUDF byte stream as host uint8 (exact for either storage)."""
        raw = np.ascontiguousarray(np.asarray(self.data))
        return raw.view(np.uint8)

    def device_u8(self) -> jnp.ndarray:
        """The byte stream as a device uint8 array (converts if u32)."""
        if self.data.dtype == jnp.uint8:
            return self.data
        return _words_to_bytes(self.data)


def _is_f64(storage: np.dtype) -> bool:
    return storage.kind == "f" and storage.itemsize == 8


def _byte_view(data: jnp.ndarray, storage: np.dtype) -> jnp.ndarray:
    """[n] fixed-width payload → uint8 [n, itemsize] (little-endian).

    FLOAT64 payloads are uint32 [n, 2] bit pairs by Column invariant
    (``utils.f64bits`` — XLA:TPU exposes no bit-level access to its emulated
    f64), so the transcode — which only moves bytes, never does arithmetic —
    works on the u32 halves.
    """
    if _is_f64(storage):
        return jax.lax.bitcast_convert_type(data, jnp.uint8).reshape(
            data.shape[0], 8)
    data = data.astype(storage)
    if storage.itemsize == 1:
        return data.view(jnp.uint8).reshape(-1, 1)
    return jax.lax.bitcast_convert_type(data, jnp.uint8)


def _from_bytes(b: jnp.ndarray, storage: np.dtype) -> jnp.ndarray:
    """uint8 [n, itemsize] → [n] payload (f64: uint32 [n,2] bit pairs)."""
    if _is_f64(storage):
        # flat u32 then reshape — the direct 3-D bitcast builds a narrow-
        # minor array, which the TPU's tiling pads out to 128 lanes
        return jax.lax.bitcast_convert_type(
            b.reshape(-1, 4), jnp.uint32).reshape(-1, 2)
    if storage.itemsize == 1:
        return b.reshape(-1).view(jnp.dtype(storage))
    return jax.lax.bitcast_convert_type(b, jnp.dtype(storage))


def _byte_view_dt(data: jnp.ndarray, dt) -> jnp.ndarray:
    """DType-aware ``_byte_view``: DECIMAL128 [n, 2] int64 → u8 [n, 16]."""
    if dt.id == T.TypeId.DECIMAL128:
        return jax.lax.bitcast_convert_type(data, jnp.uint8).reshape(
            data.shape[0], 16)
    return _byte_view(data, dt.storage)


def _from_bytes_dt(b: jnp.ndarray, dt) -> jnp.ndarray:
    """DType-aware ``_from_bytes``: u8 [n, 16] → DECIMAL128 [n, 2] int64."""
    if dt.id == T.TypeId.DECIMAL128:
        return jax.lax.bitcast_convert_type(b.reshape(-1, 2, 8), jnp.int64)
    return _from_bytes(b, dt.storage)


# ---------------------------------------------------------------------------
# fixed-width core: [cols…] → uint32 row words [n * W]
# ---------------------------------------------------------------------------

# Row-word count up to which interleaving takes the layout-preserving 3-D
# permute and not one big 2-D transpose.  The crossover is unmeasured from a
# caller's side: no cell has W <= 40, and the same permute in the other
# direction lost 7-11x once n was no power of two and went (PERF.md §6, PR
# 29).  ROADMAP S8 asks for the measurement that keeps one form.
_IL_PERM3_MAX_W = 40
_LANE = 128


def _interleave_words(words: list[jnp.ndarray], W: int) -> jnp.ndarray:
    """[W] u32 vectors of [n_pad] (n_pad % 128 == 0) → flat JCUDF word
    stream u32 [n_pad * W] with out[r*W + w] = words[w][r]."""
    x = jnp.stack(words, axis=0)                        # [W, n_pad]
    n_pad = x.shape[1]
    if W <= _IL_PERM3_MAX_W:
        # layout-preserving permute: every reshape boundary is a 128-lane
        # multiple, so XLA never materializes a padded-minor temporary
        return x.reshape(W, n_pad // 128, 128).transpose(1, 2, 0).reshape(-1)
    return x.T.reshape(-1)


def _deinterleave_words(flat: jnp.ndarray, W: int) -> jnp.ndarray:
    """Inverse of :func:`_interleave_words`: u32 [n*W] → word-major [W, n],
    one 2-D transpose at every width and any n (no padding to 128 rows).

    This is the one deinterleave of the fixed-width decode: every column and
    validity vector is then read from a contiguous, lane-dense word row
    ``t2[w]``.  Nothing of shape [n, k], k < 128, row-major is ever built:
    one word column sliced out of the [n, W] matrix is padded 128x under the
    (8,128) tiling (10.5 GiB of temporaries and 252 ms at 155 columns x 1M
    rows).  ``convert_from_rows`` + block, 1<<20 rows, median ms on one v5e
    (PERF.md §6 PR 29), transpose / 3-D permute / sliced matrix:
      W=14: 4.37 / 4.52 / 17.50,  W=48: 11.00 / 11.52 / 52.27,
      W=212: 37.17 / 43.22 / 251.78
    The 3-D permute also costs 3.3-6.2 GiB of temporaries at W=14 once n is
    not a power of two (compile rehearsal, PR 29)."""
    return flat.reshape(-1, W).T


# The two byte-boundary conversions keep 128 words (512 bytes) on the lanes:
# ``bitcast_convert_type`` between u32 [N] and u8 [N, 4] builds an array whose
# minor axis is 4, which the (8,128) tiling pads 32x — 68.7 GB for a batch of
# 2,147,466,240 B, refused by the compiler (compile rehearsal, PR 34).

def _words_to_u8(w: jnp.ndarray) -> jnp.ndarray:
    """u32 [N] → u8 [4N] little-endian (elementwise)."""
    pad = (-w.shape[0]) % _LANE
    w2 = jnp.pad(w, (0, pad)).reshape(-1, _LANE)
    out = jnp.zeros((w2.shape[0], 4 * _LANE), jnp.uint8)
    for k in range(4):
        out = out.at[:, k::4].set(((w2 >> (8 * k)) & 0xFF).astype(jnp.uint8))
    return out.reshape(-1)[:w.shape[0] * 4]


_words_to_bytes = jax.jit(_words_to_u8)   # byte-boundary use only


@jax.jit
def _bytes_to_words(b: jnp.ndarray) -> jnp.ndarray:
    """u8 [4N] → u32 [N] little-endian."""
    n = b.shape[0] // 4
    b2 = jnp.pad(b, (0, (-n) % _LANE * 4)).reshape(-1, 4 * _LANE)
    acc = b2[:, 0::4].astype(jnp.uint32)
    for k in range(1, 4):
        acc = acc | (b2[:, k::4].astype(jnp.uint32) << jnp.uint32(8 * k))
    return acc.reshape(-1)[:n]


def _word_plan(layout: RowLayout):
    """For each u32 word of the row, the static list of fragments.

    Fragment = (input_index, kind, arg):
      kind 'full'  — input is u32 [n], the whole word                (size 4)
      kind 'pair'  — input is u32 [n, k], arg selects the lane      (size 8/16)
      kind 'sub'   — input is zero-extended u32 [n], arg = byte shift (size <4)
      kind 'vbyte' — input is the validity byte k, arg = (k, shift)
    Input order: one staged array per column, then the validity bytes.
    Every fixed slot is aligned to its own size (compute_column_information,
    ``row_conversion.cu:1331-1370``), so fragments never straddle words.
    """
    W = layout.fixed_row_size // 4
    plan: list[list[tuple[int, str, object]]] = [[] for _ in range(W)]
    for ci, dt in enumerate(layout.schema):
        start = layout.column_starts[ci]
        size = layout.column_sizes[ci]
        if size == 16:   # DECIMAL128: staged u32 [n, 4], four words
            for j in range(4):
                plan[start // 4 + j].append((ci, "pair", j))
        elif size == 8:
            plan[start // 4].append((ci, "pair", 0))
            plan[start // 4 + 1].append((ci, "pair", 1))
        elif size == 4:
            plan[start // 4].append((ci, "full", None))
        else:  # 1 or 2; alignment keeps it inside one word
            plan[start // 4].append((ci, "sub", start % 4))
    vi = layout.num_columns
    vo = layout.validity_offset
    for k in range(layout.validity_bytes):
        byte = vo + k
        plan[byte // 4].append((vi, "vbyte", (k, byte % 4)))
    return plan


def _stage_column(data: jnp.ndarray, storage: np.dtype) -> jnp.ndarray:
    """Column payload → u32 staged form for the word plan: 8-byte columns as
    u32 [n, 2] halves, 4-byte bitcast, sub-word zero-extended.  FLOAT64 is
    already stored as u32 [n, 2] bit pairs (Column invariant)."""
    if _is_f64(storage):
        return data
    data = data.astype(storage)
    if storage.itemsize == 8:
        return jax.lax.bitcast_convert_type(data, jnp.uint32)   # [n, 2]
    if storage.itemsize == 4:
        return jax.lax.bitcast_convert_type(data, jnp.uint32)   # [n]
    unsigned = np.dtype(f"u{storage.itemsize}")
    return jax.lax.bitcast_convert_type(data, unsigned).astype(jnp.uint32)


def _stage_column_dt(data: jnp.ndarray, dt) -> jnp.ndarray:
    """DType-aware staging: DECIMAL128 [n, 2] int64 lanes → u32 [n, 4]."""
    if dt.id == T.TypeId.DECIMAL128:
        return jax.lax.bitcast_convert_type(
            data, jnp.uint32).reshape(data.shape[0], 4)
    return _stage_column(data, dt.storage)


def _pack_validity_words(layout: RowLayout,
                         valid: jnp.ndarray) -> list[jnp.ndarray]:
    """Per validity byte k: u32 [n] vector with the byte's bits in the low
    8."""
    n = valid.shape[0]
    out = []
    for k in range(layout.validity_bytes):
        acc = jnp.zeros((n,), jnp.uint32)
        for i in range(min(8, layout.num_columns - k * 8)):
            acc = acc | (valid[:, k * 8 + i].astype(jnp.uint32)
                         << jnp.uint32(i))
        out.append(acc)
    return out


@functools.partial(jax.jit, static_argnums=0)
def _to_rows_fixed_words(layout: RowLayout, datas: tuple[jnp.ndarray, ...],
                         valid: jnp.ndarray) -> jnp.ndarray:
    """Fixed-width columns + validity matrix → flat u32 row words [n*W].

    Compose each row word as a [n]-long u32 vector from statically-planned
    column fragments (one shift/or tree per word — the data transpose and
    the warp-ballot validity pack of ``row_conversion.cu:575-810`` fused
    into one pass), then interleave with a single layout-preserving permute.
    """
    n = valid.shape[0]
    W = layout.fixed_row_size // 4
    n_pad = -(-n // 128) * 128

    def padrows(x):
        return jnp.pad(x, [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1))

    staged = [padrows(_stage_column_dt(d, dt))
              for d, dt in zip(datas, layout.schema)]
    vbytes_w = [padrows(v) for v in _pack_validity_words(layout, valid)]

    words = _compose_row_words(layout, staged, vbytes_w, W, n_pad)
    flat = _interleave_words(words, W)
    return flat[:n * W] if n_pad != n else flat


def _compose_row_words(layout: RowLayout, staged, vbytes_w, W: int,
                       n: int) -> list[jnp.ndarray]:
    """The first ``W`` row words, each a u32 [n] vector OR-ed from its
    statically planned fragments (:func:`_word_plan`).  ``staged[ci]`` is
    the column's staged payload; a string column's is its (offset, length)
    slot as u32 [n, 2], two row words like any 8-byte column."""
    plan = _word_plan(layout)
    words = []
    for w in range(W):
        acc = None
        for ii, kind, arg in plan[w]:
            if kind == "vbyte":
                k, shift = arg
                v = vbytes_w[k] << jnp.uint32(shift * 8)
            else:
                x = staged[ii]
                if kind == "full":
                    v = x
                elif kind == "pair":
                    v = x[:, arg]
                else:
                    v = x << jnp.uint32(arg * 8)
            acc = v if acc is None else acc | v
        words.append(acc if acc is not None
                     else jnp.zeros((n,), jnp.uint32))
    return words


def _decode_row_words(layout: RowLayout, word, n: int):
    """Shared word-level row decoder.

    ``word(w)`` returns the u32 vector (length ≥ n) holding row word ``w``
    for every row — from the fixed-path deinterleave or from the xpack
    dense row-window matrix alike.  Returns ``(datas, valid, slots)`` where
    ``datas`` has ``None`` at variable-width columns and ``slots`` carries
    each variable column's (offset, length) u32 [n, 2] pairs.  Every fixed
    slot is aligned to its own size and string slots to 4
    (compute_column_information, ``row_conversion.cu:1331-1370``), so no
    fragment straddles a word.
    """
    datas, vcols, slots = _decode_row_columns(layout, word, n)
    return (datas, jnp.stack(vcols, axis=1),
            tuple(jnp.stack(s, axis=1) for s in slots))


def _decode_row_columns(layout: RowLayout, word, n: int):
    """:func:`_decode_row_words` with nothing stacked: ``(datas, per-column
    validity vectors, per-variable-column (offset, length) u32 vector
    pairs)``."""
    datas = []
    slots = []
    for ci, dt in enumerate(layout.schema):
        start = layout.column_starts[ci]
        size = layout.column_sizes[ci]
        if dt.is_variable_width:
            slots.append((word(start // 4)[:n], word(start // 4 + 1)[:n]))
            datas.append(None)
            continue
        if size == 16:   # DECIMAL128: four words → [n, 2] int64 lanes
            quad = jnp.stack([word(start // 4 + j) for j in range(4)],
                             axis=1)[:n]
            datas.append(jax.lax.bitcast_convert_type(
                quad.reshape(-1, 2, 2), jnp.int64))
            continue
        st = dt.storage
        if size == 8:
            pair = jnp.stack([word(start // 4), word(start // 4 + 1)],
                             axis=1)[:n]
            if _is_f64(st):
                datas.append(pair)           # u32 [n, 2] IS the f64 storage
            else:
                datas.append(jax.lax.bitcast_convert_type(pair,
                                                          jnp.dtype(st)))
        elif size == 4:
            datas.append(jax.lax.bitcast_convert_type(word(start // 4),
                                                      jnp.dtype(st))[:n])
        else:
            v = ((word(start // 4) >> jnp.uint32(8 * (start % 4)))
                 & jnp.uint32((1 << (8 * size)) - 1))
            unsigned = np.dtype(f"u{size}")
            datas.append(jax.lax.bitcast_convert_type(
                v.astype(jnp.dtype(unsigned)), jnp.dtype(st))[:n])
    vcols = []
    for c in range(layout.num_columns):
        byte = layout.validity_offset + c // 8
        bit = ((word(byte // 4) >> jnp.uint32(8 * (byte % 4) + c % 8))
               & jnp.uint32(1))
        vcols.append(bit.astype(jnp.bool_)[:n])
    return tuple(datas), vcols, tuple(slots)


# Fused whole-call cores for the public fixed-width path.  The orchestration
# around the reference's kernels is host code (offset columns built with
# Thrust + D2D copies, row_conversion.cu:1460-1539); here that host work
# (and its H2D offset upload) would dominate, so the full call —
# validity-matrix build, word compose, interleave, offsets arange — is one
# jit program and the only transfer is the column payloads already in HBM.

@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _to_rows_fixed_full(layout: RowLayout, has_valid: tuple[bool, ...],
                        lo: int, hi: int,
                        datas: tuple[jnp.ndarray, ...],
                        valids: tuple[jnp.ndarray, ...]):
    """Rows ``[lo, hi)`` of a fixed-width table → (flat u32 row words, int32
    row offsets), one dispatch.  The whole columns come in and the batch's
    rows are cut here, where the cut fuses into the staging: a batch of a
    table that takes several reads the resident columns in place, and the
    whole table's (``lo, hi == 0, n``) lowers to no cut at all.  ``valids``
    carries arrays only for columns where ``has_valid`` is True; all-valid
    columns get their ones generated (and fused away) on device."""
    datas = tuple(d[lo:hi] for d in datas)
    valids = tuple(v[lo:hi] for v in valids)
    n = hi - lo
    vi = iter(valids)
    cols_valid = [next(vi) if hv else jnp.ones((n,), dtype=jnp.bool_)
                  for hv in has_valid]
    valid = jnp.stack(cols_valid, axis=1)
    flat = _to_rows_fixed_words(layout, datas, valid)
    offsets = jnp.arange(n + 1, dtype=jnp.int32) * layout.fixed_row_size
    return flat, offsets


@functools.partial(jax.jit, static_argnums=0)
def _from_rows_fixed_full(layout: RowLayout, words: jnp.ndarray):
    """Flat u32 row words → (datas, per-column validity vectors)."""
    W = layout.fixed_row_size // 4
    t2 = _deinterleave_words(words, W)                   # [W, n]
    datas, vcols, _ = _decode_row_columns(layout, lambda w: t2[w],
                                          words.shape[0] // W)
    return datas, tuple(vcols)


@functools.partial(jax.jit, static_argnums=0)
def _from_rows_fixed_words(layout: RowLayout, flat: jnp.ndarray):
    """Flat u32 row words [n*W] → (datas tuple, valid bool [n, ncols])."""
    datas, vcols = _from_rows_fixed_full(layout, flat)
    return datas, jnp.stack(vcols, axis=1)


# ---------------------------------------------------------------------------
# variable-width core (strings): statically-shaped scatter/gather
# ---------------------------------------------------------------------------

def _segment_of(starts: jnp.ndarray, total: int) -> jnp.ndarray:
    """For each position in [0, total): the index of the sorted segment
    containing it.  ``starts`` is int32 [S+1] inclusive starts with a final
    sentinel == total.

    One tiny scatter-add (S markers) + one cumsum — the TPU-friendly
    replacement for a per-position binary search.  Empty segments (repeated
    starts) accumulate multiple increments at one position, so positions
    correctly skip past them.
    """
    markers = jnp.zeros((total,), dtype=jnp.int32).at[starts[1:-1]].add(1)
    return jnp.cumsum(markers)


# shared outside rowconv (DictColumn.materialize, ops.filter string
# gathers, rle_device run lookup): every per-position binary search in
# the package routes through this one primitive
segment_of = _segment_of


@functools.partial(jax.jit, static_argnums=0)
def _var_fixed_region(layout: RowLayout, datas: tuple[jnp.ndarray, ...],
                      str_offsets: tuple[jnp.ndarray, ...],
                      valid: jnp.ndarray) -> jnp.ndarray:
    """Dense fixed region [n, fixed_plus_validity] for a variable-width
    schema: column slots, string (offset,len) slot pairs, validity bytes.
    Pure vector ops — shared by the DMA and XLA string paths."""
    n = valid.shape[0]
    var_idx = layout.variable_column_indices
    nvar = len(var_idx)
    fpv = layout.fixed_plus_validity
    lens = jnp.stack(
        [str_offsets[vi][1:] - str_offsets[vi][:-1] for vi in range(nvar)],
        axis=1).astype(jnp.int32)                           # [n, nvar]
    prefix = jnp.cumsum(lens, axis=1) - lens
    fixed2d = jnp.zeros((n, fpv), dtype=jnp.uint8)
    vi_of_ci = {ci: vi for vi, ci in enumerate(var_idx)}
    for ci, dt in enumerate(layout.schema):
        start = layout.column_starts[ci]
        if dt.is_variable_width:
            vi = vi_of_ci[ci]
            slot_off = (fpv + prefix[:, vi]).astype(jnp.uint32)
            slot = jnp.stack([slot_off, lens[:, vi].astype(jnp.uint32)], axis=1)
            b = jax.lax.bitcast_convert_type(slot, jnp.uint8).reshape(n, 8)
        else:
            b = _byte_view_dt(datas[ci], dt)
        fixed2d = fixed2d.at[:, start:start + b.shape[1]].set(b)
    vbytes = bitmask.pack_bool_matrix(valid)
    return fixed2d.at[:, layout.validity_offset:
                      layout.validity_offset + layout.validity_bytes].set(vbytes)


# Above this many string columns the per-column segmented-copy passes (each
# touching the full char region) lose to the single-pass XLA gather path.
# The reference benchmark's strings table (15 string columns) is over it,
# but never gets here: xpack serves it in row tiles (strings155_roundtrip,
# PR 28: no fallback counted, neither DMA nor gather reached).
_DMA_MAX_VAR_COLS = 8

# from_rows DMA geometry needs per-row (offset, len) slots on the HOST;
# above this row count the device-side gather path (which syncs only
# per-column char totals) is taken instead.  (Threshold set for the old
# environment, not re-measured — ROADMAP S7; no cell reaches it: both cells
# of the transcode are served before the DMA path is asked.)
_DMA_FROM_ROWS_MAX_N = 1 << 16


def _to_rows_var_dma(layout: RowLayout, sub: "Table", valid: jnp.ndarray,
                     offs_np: np.ndarray) -> Optional[jnp.ndarray]:
    """Strings → JCUDF rows via the ragged DMA engine (TPU).

    The reference stages tiles in shared memory and memcpy_asyncs them out
    (``copy_strings_to_rows``, row_conversion.cu:827-875); here the char
    region is assembled as dense per-row byte matrices — one
    :func:`ragged.unpack` when there is a single string column (its chars
    are already per-row contiguous), else one :func:`ragged.segmented_copy`
    per column — and one :func:`ragged.pack` flattens the dense rows into
    the packed JCUDF buffer.  All heavy byte movement is aligned bulk DMA +
    in-register rolls.

    Returns ``None`` for shapes where the engine loses to the XLA gather
    formulation (> ``_DMA_MAX_VAR_COLS`` string columns): the per-column
    passes each traverse the whole char region, so cost grows with the
    column count while the gather path scales with total bytes only.
    """
    from . import ragged
    n = sub.num_rows
    var_idx = layout.variable_column_indices
    nvar = len(var_idx)
    if nvar > _DMA_MAX_VAR_COLS:
        return None
    fpv = layout.fixed_plus_validity
    offs_np = np.asarray(offs_np, dtype=np.int64)
    sizes_np = offs_np[1:] - offs_np[:-1]
    # bucketed to limit distinct jit/kernel shapes (extra columns are zero)
    M = -(-int(sizes_np.max(initial=8)) // 64) * 64
    Mc = M - fpv

    from ..utils import hostcache
    col_offs_np = [hostcache.host_i64(sub[ci].offsets) for ci in var_idx]
    lens_np = np.stack([o[1:] - o[:-1] for o in col_offs_np], axis=1)
    prefix_np = np.cumsum(lens_np, axis=1) - lens_np

    # var columns' char payloads are unread by _var_fixed_region (slots come
    # from the offsets); zero-size placeholders keep its jit cache keyed on
    # (layout, n) only instead of every distinct char-buffer length
    fixed2d = _var_fixed_region(
        layout,
        tuple(jnp.zeros(0, jnp.uint8) if c.dtype.is_variable_width
              else c.data for c in sub.columns),
        tuple(sub[ci].offsets for ci in var_idx), valid)

    total_chars = int(lens_np.sum())
    if Mc > 0 and total_chars:
        if nvar == 1:
            # single string column: chars are already per-row contiguous
            cr = ragged.unpack(sub[var_idx[0]].data, col_offs_np[0], Mc)
        else:
            acc = None
            row_base_c = np.arange(n, dtype=np.int64) * Mc
            for vi, ci in enumerate(var_idx):
                part = ragged.copy_segments(
                    sub[ci].data, col_offs_np[vi][:-1],
                    row_base_c + prefix_np[:, vi], lens_np[:, vi], n * Mc)
                acc = part if acc is None else (acc | part)
            cr = acc.reshape(n, Mc)
        dense = jnp.concatenate([fixed2d, cr], axis=1)
    elif Mc > 0:
        dense = jnp.concatenate(
            [fixed2d, jnp.zeros((n, Mc), jnp.uint8)], axis=1)
    else:
        dense = fixed2d[:, :M] if fpv >= M else jnp.concatenate(
            [fixed2d, jnp.zeros((n, M - fpv), jnp.uint8)], axis=1)
    return ragged.pack(dense, offs_np)


@functools.partial(jax.jit, static_argnums=0)
def _var_fixed_extract(layout: RowLayout, fixed_dense: jnp.ndarray):
    """Inverse of :func:`_var_fixed_region`: dense [n, fpv] → (fixed column
    payloads, validity matrix, per-var-column (offset,len) u32 slots)."""
    n = fixed_dense.shape[0]
    datas = []
    slots = []
    for ci, dt in enumerate(layout.schema):
        start = layout.column_starts[ci]
        if dt.is_variable_width:
            b = fixed_dense[:, start:start + 8].reshape(n, 2, 4)
            slots.append(jax.lax.bitcast_convert_type(b, jnp.uint32))
            datas.append(None)
        else:
            b = fixed_dense[:, start:start + layout.column_sizes[ci]]
            datas.append(_from_bytes_dt(b, dt))
    vbytes = fixed_dense[:, layout.validity_offset:
                         layout.validity_offset + layout.validity_bytes]
    valid = bitmask.unpack_bool_matrix(vbytes, layout.num_columns)
    return datas, valid, tuple(slots)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _to_rows_var(layout: RowLayout, total_bytes: int,
                 datas: tuple[jnp.ndarray, ...],
                 str_offsets: tuple[jnp.ndarray, ...],
                 valid: jnp.ndarray,
                 row_offsets: jnp.ndarray) -> jnp.ndarray:
    """Strings path: one gather pass over the output bytes.

    The reference's ``copy_to_rows``/``copy_strings_to_rows`` kernels
    (row_conversion.cu:575-693, 827-875) scatter from columns into rows; a
    scatter on TPU serializes, so this inverts the direction — every output
    byte *gathers* its source:

    1. the fixed region (column slots, string (offset,len) slots, validity) is
       built as a dense [n, fixed_plus_validity] matrix with vectorized
       column-slice writes;
    2. each flat output position finds its row via a marker-cumsum (no binary
       search), then either reads the fixed matrix or computes the (column,
       char) source for the string tail and reads the concatenated chars
       buffer.

    All heavy traffic is gathers + cumsums; the only scatters are the tiny
    segment-start markers.  The final assembly runs in fixed-size blocks
    (``lax.map``) so the [total_bytes]-sized int32 index temporaries never
    coexist — at 155-column/1M-row scale the unblocked formulation OOMs HBM.
    """
    n = valid.shape[0]
    var_idx = layout.variable_column_indices
    nvar = len(var_idx)
    fpv = layout.fixed_plus_validity
    if n == 0 or total_bytes == 0:
        return jnp.zeros((total_bytes,), dtype=jnp.uint8)
    row_offsets = row_offsets.astype(jnp.int32)             # batch ≤ 2^31-1
    row_base = row_offsets[:-1]                             # [n]

    # per-row, per-variable-column char lengths and exclusive prefix
    lens = jnp.stack(
        [str_offsets[vi][1:] - str_offsets[vi][:-1] for vi in range(nvar)],
        axis=1).astype(jnp.int32)                           # [n, nvar]
    prefix = jnp.cumsum(lens, axis=1) - lens                # exclusive, [n, nvar]
    row_lens = prefix[:, -1] + lens[:, -1]                  # chars per row [n]
    row_char_prefix = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(row_lens, dtype=jnp.int32)])            # [n+1]

    # dense fixed-region matrix [n, fpv]
    fixed2d = jnp.zeros((n, fpv), dtype=jnp.uint8)
    vi_of_ci = {ci: vi for vi, ci in enumerate(var_idx)}
    for ci, dt in enumerate(layout.schema):
        start = layout.column_starts[ci]
        if dt.is_variable_width:
            vi = vi_of_ci[ci]
            slot_off = (fpv + prefix[:, vi]).astype(jnp.uint32)
            slot = jnp.stack([slot_off, lens[:, vi].astype(jnp.uint32)], axis=1)
            b = jax.lax.bitcast_convert_type(slot, jnp.uint8).reshape(n, 8)
        else:
            b = _byte_view_dt(datas[ci], dt)
        fixed2d = fixed2d.at[:, start:start + b.shape[1]].set(b)
    vbytes = bitmask.pack_bool_matrix(valid)
    fixed2d = fixed2d.at[:, layout.validity_offset:
                         layout.validity_offset + layout.validity_bytes].set(vbytes)

    # interleaved chars buffer, ordered (row, var-col) — one segment per
    # (row, col) pair, located with a single segment-cumsum
    total_chars = int(sum(datas[ci].shape[0] for ci in var_idx))
    if total_chars:
        chars_concat = jnp.concatenate([datas[ci] for ci in var_idx])
        col_bases = jnp.asarray(np.concatenate(
            [[0], np.cumsum([datas[ci].shape[0] for ci in var_idx])]
        ).astype(np.int32))
        seg_start = jnp.concatenate([
            (row_char_prefix[:-1, None] + prefix).reshape(-1),
            jnp.full((1,), total_chars, jnp.int32)])        # [n*nvar + 1]
        seg_of = _segment_of(seg_start, total_chars)
        offs_at = jnp.stack([str_offsets[vi][:-1].astype(jnp.int32)
                             for vi in range(nvar)], axis=1).reshape(-1)
        q = jnp.arange(total_chars, dtype=jnp.int32)
        src = (col_bases[seg_of % nvar] + offs_at[seg_of]
               + (q - seg_start[seg_of]))
        ichars = chars_concat[src]
    else:
        ichars = jnp.zeros((1,), dtype=jnp.uint8)           # safe dummy gather

    row_of_all = _segment_of(row_offsets, total_bytes)      # [total_bytes]
    fixed_flat = fixed2d.reshape(-1)

    block = 1 << 22
    nblocks = -(-total_bytes // block)
    row_of_pad = jnp.pad(row_of_all, (0, nblocks * block - total_bytes))

    def assemble(b):
        o = b * block + jnp.arange(block, dtype=jnp.int32)
        ro = jax.lax.dynamic_slice(row_of_pad, (b * block,), (block,))
        w = o - row_base[ro]                                # offset within row
        in_fixed = w < fpv
        fval = fixed_flat[ro * fpv + jnp.clip(w, 0, fpv - 1)]
        u = jnp.maximum(w - fpv, 0)                         # char idx in row
        in_chars = (~in_fixed) & (u < row_lens[ro])         # excludes padding
        cidx = jnp.clip(row_char_prefix[ro] + u, 0, max(total_chars - 1, 0))
        return jnp.where(in_fixed, fval,
                         jnp.where(in_chars, ichars[cidx], jnp.uint8(0)))

    out = jax.lax.map(assemble, jnp.arange(nblocks, dtype=jnp.int32))
    return out.reshape(-1)[:total_bytes]


@functools.partial(jax.jit, static_argnums=0)
def _gather_var_slots(layout: RowLayout, data: jnp.ndarray,
                      row_offsets: jnp.ndarray):
    """Phase 1 of from_rows with strings: pull each row's (offset,len) slots."""
    row_base = row_offsets[:-1].astype(jnp.int64)
    slots = []
    for ci in layout.variable_column_indices:
        start = layout.column_starts[ci]
        pos = row_base[:, None] + start + jnp.arange(8)[None, :]
        b = data[pos.reshape(-1)].reshape(-1, 2, 4)
        slots.append(jax.lax.bitcast_convert_type(b, jnp.uint32))  # [n, 2]
    return tuple(slots)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _from_rows_var(layout: RowLayout, char_totals: tuple[int, ...],
                   data: jnp.ndarray, row_offsets: jnp.ndarray,
                   out_offsets: tuple[jnp.ndarray, ...],
                   slots: tuple[jnp.ndarray, ...]):
    """Phase 2: gather fixed slots, validity, and chars buffers.

    ``slots`` are the phase-1 (offset,len) uint32 pairs from
    ``_gather_var_slots`` — passed through rather than re-read from the row
    bytes."""
    row_base = row_offsets[:-1].astype(jnp.int64)
    n = row_base.shape[0]

    datas = []
    for ci, dt in enumerate(layout.schema):
        if dt.is_variable_width:
            datas.append(None)
            continue
        start = layout.column_starts[ci]
        sz = layout.column_sizes[ci]
        pos = row_base[:, None] + start + jnp.arange(sz)[None, :]
        b = data[pos.reshape(-1)].reshape(n, sz)
        datas.append(_from_bytes_dt(b, dt))

    pos = (row_base[:, None] + layout.validity_offset
           + jnp.arange(layout.validity_bytes)[None, :])
    vbytes = data[pos.reshape(-1)].reshape(n, layout.validity_bytes)
    valid = bitmask.unpack_bool_matrix(vbytes, layout.num_columns)

    chars_out = []
    for vi, ci in enumerate(layout.variable_column_indices):
        total = char_totals[vi]
        offs = out_offsets[vi].astype(jnp.int64)            # [n+1]
        slot = slots[vi]
        src_base = row_base + slot[:, 0].astype(jnp.int64)  # chars start per row
        if total == 0:
            chars_out.append(jnp.zeros((0,), dtype=jnp.uint8))
            continue
        # marker-cumsum segment lookup (see _segment_of) instead of a
        # per-char binary search
        row_of = _segment_of(offs.astype(jnp.int32), total)
        char_ids = jnp.arange(total, dtype=jnp.int64)
        src = src_base[row_of] + (char_ids - offs[row_of])
        chars_out.append(data[src])
    return tuple(datas), valid, tuple(chars_out)


# ---------------------------------------------------------------------------
# public API (row_conversion.hpp:27-49 surface)
# ---------------------------------------------------------------------------

def _table_valid_matrix(table: Table) -> jnp.ndarray:
    return jnp.stack([c.validity_or_true() for c in table.columns], axis=1)


@traced("convert_to_rows")
@fault_site("convert_to_rows")
def convert_to_rows(table: Table,
                    max_batch_bytes: Optional[int] = None) -> list[RowBatch]:
    """Table → JCUDF row batches (``convert_to_rows``, row_conversion.cu:1902-1960).

    Returns one or more :class:`RowBatch` (LIST<INT8> analog), each ≤2GB.
    """
    max_batch_bytes = max_batch_bytes or MAX_BATCH_BYTES
    layout = compute_row_layout(table.schema)
    n = table.num_rows

    if layout.fixed_width_only:
        # Constant row stride ⇒ batch boundaries are pure arithmetic (the
        # reference reaches the same boundaries by scanning a constant-valued
        # row_sizes vector, row_conversion.cu:1460-1539) and offsets are a
        # device-side arange — no host scan, no H2D offset upload.
        stride = layout.fixed_row_size
        if stride > max_batch_bytes:
            raise ValueError("a single row exceeds the maximum batch size")
        # Reference boundary rule (build_batches, row_conversion.cu:1460-1539,
        # mirrored by layout.build_batches): split while the remainder
        # overflows the cap, rounding each split to a 32-row multiple only
        # when more than one multiple fits; the final batch is never rounded.
        with metrics.span("rowconv.fixed.prepare") as prepare:
            boundaries = [0]
            while (n - boundaries[-1]) * stride > max_batch_bytes:
                k = max_batch_bytes // stride
                if k > BATCH_ROW_MULTIPLE:
                    k = k // BATCH_ROW_MULTIPLE * BATCH_ROW_MULTIPLE
                boundaries.append(boundaries[-1] + k)
            boundaries.append(n)
            # every batch's program takes the whole resident columns and
            # cuts its rows itself: one argument list a call, nothing sliced
            # (so nothing dispatched or copied) out here
            cols = table.columns
            has_valid = tuple(c.validity is not None for c in cols)
            datas = tuple(c.data for c in cols)
            valids = tuple(c.validity for c in cols if c.validity is not None)
            if prepare is not None:
                held = {id(leaf) for c in cols
                        for leaf in (c.data, c.validity)}
                prepare.annotate(
                    batches=len(boundaries) - 1,
                    eager_ops=sum(id(a) not in held for a in datas + valids))
        out = []
        for bi, (lo, hi) in enumerate(zip(boundaries[:-1], boundaries[1:])):
            with metrics.span("rowconv.fixed.launch", direction="to",
                              batch=bi, rows=hi - lo,
                              bytes=(hi - lo) * stride):
                data, offsets = _to_rows_fixed_full(layout, has_valid, lo, hi,
                                                    datas, valids)
            out.append(RowBatch(data, offsets))
        metrics.count("rowconv.fixed.batches.to", len(out))
        _record_transcode("rowconv.to_rows", n, out)
        return out

    # variable-width (strings) path: row sizes are data-dependent, so the
    # reference's scan + lower_bound batching applies as-is.  Offsets come
    # through the host-mirror cache — the arrays are host-born anyway, so
    # a device→host pull of 1M offsets would be pure waste.
    from ..utils import hostcache
    with metrics.span("rowconv.var.sizes", rows=n):
        # memoized on the offset arrays, like the engines' geometry: the
        # steady state re-converts the tables it holds
        key_arrays = [table[ci].offsets
                      for ci in layout.variable_column_indices]
        tag = f"var_batches:{hash(layout)}:{max_batch_bytes}"
        batches = syncs.memo_get(tag, key_arrays)
        if batches is None:
            total_lens = np.zeros(n, dtype=np.int64)
            for ci in layout.variable_column_indices:
                offs = hostcache.host_i64(table[ci].offsets)
                total_lens += offs[1:] - offs[:-1]
            batches = build_batches(
                row_sizes_with_strings(layout, total_lens), max_batch_bytes)
            syncs.memo_put(tag, key_arrays, batches)
        metrics.annotate(batches=batches.num_batches)
    from . import ragged, xpack
    use_dma = ragged.dma_supported()
    use_xpack = knobs.get("SRJT_XPACK")
    out = []
    for bi, (lo, hi) in enumerate(zip(batches.row_boundaries[:-1],
                                      batches.row_boundaries[1:])):
        sub = Table([_slice_column(c, lo, hi) for c in table.columns])
        boffs_np = batches.row_offsets_within_batch[bi]
        data = boffs = None
        engine = "xpack"
        if use_xpack:
            # primary engine (round 4): slab-gather + fused-roll program,
            # one jitted dispatch for the whole batch (see rowconv/xpack.py)
            col_offs = [hostcache.host_i64(sub[ci].offsets)
                        for ci in layout.variable_column_indices]
            res = xpack.to_rows_var_x(layout, sub, boffs_np, col_offs)
            if res is not None:
                data, boffs = res
        valid = None if data is not None else _table_valid_matrix(sub)
        if data is None and use_dma:
            engine = "dma"
            data = _to_rows_var_dma(layout, sub, valid, boffs_np)
        if data is None:
            engine = "gather"
            data = _to_rows_var(
                layout, batches.batch_bytes[bi],
                tuple(c.data for c in sub.columns),
                # _slice_column already rebases string offsets to zero
                tuple(sub[ci].offsets
                      for ci in layout.variable_column_indices),
                valid, jnp.asarray(boffs_np.astype(np.int64)))
        metrics.count(f"rowconv.var.engine.to.{engine}")
        if boffs is None:
            boffs = jnp.asarray(boffs_np)
        hostcache.seed(boffs, np.asarray(boffs_np, dtype=np.int64))
        out.append(RowBatch(data, boffs))
    _record_transcode("rowconv.to_rows", n, out)
    _record_chars(table.columns)
    return out


def _record_chars(columns) -> None:
    """String bytes moved, by either direction."""
    if metrics.recording():
        metrics.count("rowconv.var.chars_bytes",
                      sum(c.data.shape[0] for c in columns
                          if c.dtype.is_variable_width))


def _record_transcode(prefix: str, rows: int, batches) -> None:
    """rows/bytes transcoded counters (shared by both directions)."""
    if metrics.recording():
        nbytes = sum(b.num_bytes for b in batches)
        metrics.count(f"{prefix}.rows", rows)
        metrics.count(f"{prefix}.bytes", nbytes)
        metrics.count(f"{prefix}.batches", len(batches))
        metrics.annotate(rows=rows, row_bytes=nbytes)
    if metrics._profile_op_hook is not None:
        metrics.profile_op(prefix, rows=rows,
                           bytes=sum(b.num_bytes for b in batches),
                           batches=len(batches))


def _slice_column(col: Column, lo: int, hi: int) -> Column:
    if lo == 0 and hi == col.num_rows:
        return col          # full range: keep identity (and host mirrors)
    v = None if col.validity is None else col.validity[lo:hi]
    if col.dtype.is_variable_width:
        from ..utils import hostcache
        host = hostcache.host_i64(col.offsets)   # one pull, reused per batch
        clo, chi = int(host[lo]), int(host[hi])
        rebased = host[lo:hi + 1] - clo
        offs = jnp.asarray(rebased.astype(np.int32))
        hostcache.seed(offs, rebased)
        return Column(col.dtype, col.data[clo:chi], offs, v)
    return Column(col.dtype, col.data[lo:hi], validity=v)


# --- fixed-width rows as a dense feature matrix (ml/ handoff) ---------------


def fixed_rows_to_matrix(batch: RowBatch, layout: RowLayout) -> jnp.ndarray:
    """JCUDF fixed-width rows of an all-FLOAT32 schema → dense f32 [n, k].

    The JCUDF fixed-width row IS a dense feature matrix (PAPER.md §L1): for
    an all-f32 schema the k data slots sit at consecutive 4-byte offsets
    0,4,…,4(k-1), so the matrix is a pure reinterpretation of the row word
    stream — reshape to [n, row_words], slice the k leading words, bitcast
    to f32.  No gather, no arithmetic, no host sync; values are bit-identical
    to the source columns by construction.
    """
    if not layout.fixed_width_only:
        raise ValueError("fixed_rows_to_matrix requires a fixed-width layout")
    if any(dt.id != T.TypeId.FLOAT32 for dt in layout.schema):
        raise ValueError("fixed_rows_to_matrix requires an all-FLOAT32 schema")
    k = layout.num_columns
    W = layout.fixed_row_size // 4
    words = (batch.data if batch.data.dtype == jnp.uint32
             else _bytes_to_words(batch.data))
    m = words.reshape(-1, W)[:, :k]
    return jax.lax.bitcast_convert_type(m, jnp.float32)


# --- dictionary-codes passthrough (dict string fast path) -------------------
#
# A DictColumn reaching convert_to_rows materializes its bytes — correct
# (JCUDF rows must carry the strings) but back on the variable-width path
# (1.25 GB/s against 33.5 through the fixed one, from a caller's side:
# ledger, PR 31).  When BOTH endpoints speak this engine (shuffle, spill,
# cache), ship the CODES through the fixed-width path instead and send the
# tiny dictionaries out of band: string columns transcode at int32 speed.

def dict_encode_for_rows(table: Table) -> tuple[Table, dict[int, Column]]:
    """Swap every dict string column for its int32 codes column.

    Returns ``(codes_table, dicts)`` where ``dicts`` maps column index →
    dictionary Column.  With every string column dict-encoded the table
    becomes fixed-width-only and ``convert_to_rows`` takes the constant-
    stride JCUDF path; :func:`restore_dict_columns` re-attaches the
    dictionaries after ``convert_from_rows`` on the far side."""
    dicts: dict[int, Column] = {}
    cols: list[Column] = []
    for i, c in enumerate(table.columns):
        d = as_dict_column(c)
        if d is not None:
            dicts[i] = d.dictionary
            cols.append(Column(T.int32, d.codes, validity=d.validity))
        else:
            cols.append(c)
    if dicts:
        metrics.count("rowconv.dict_cols", len(dicts))
    return Table(cols), dicts


def restore_dict_columns(table: Table, dicts: dict[int, Column]) -> Table:
    """Inverse of :func:`dict_encode_for_rows` after a row round trip."""
    cols = list(table.columns)
    for i, dcol in dicts.items():
        c = force_column(cols[i])
        cols[i] = DictColumn(c.data.astype(jnp.int32), dcol, c.validity)
    return Table(cols)


@traced("convert_from_rows")
@fault_site("convert_from_rows")
def convert_from_rows(batch: RowBatch, schema: Sequence[T.DType]) -> Table:
    """JCUDF rows → Table (``convert_from_rows``, row_conversion.cu:2032-2250).

    Like the reference, accepts exactly one batch (row_conversion.cu:2124-2139).
    """
    schema = list(schema)
    layout = compute_row_layout(schema)
    n = batch.num_rows
    _record_transcode("rowconv.from_rows", n, [batch])

    if layout.fixed_width_only:
        if batch.num_bytes != n * layout.fixed_row_size:
            raise ValueError(
                f"row data holds {batch.num_bytes} bytes but offsets "
                f"describe {n} rows of {layout.fixed_row_size} bytes")
        words = (batch.data if batch.data.dtype == jnp.uint32
                 else _bytes_to_words(batch.data))
        with metrics.span("rowconv.fixed.launch", direction="from", rows=n,
                          bytes=batch.num_bytes):
            datas, valids = _from_rows_fixed_full(layout, words)
        metrics.count("rowconv.fixed.batches.from")
        cols = [Column(dt, datas[ci], validity=valids[ci])
                for ci, dt in enumerate(schema)]
        return Table(cols)

    from . import ragged, xpack
    from ..utils import hostcache
    if knobs.get("SRJT_XPACK"):
        # primary engine (round 5): the inverse xpack — one fused program
        # for the whole batch, one memoized stacked sync for the geometry
        # (copy_strings_from_rows + chars-scan analog,
        # row_conversion.cu:1131-1174, 2201-2246)
        res = xpack.from_rows_var_x(layout, batch)
        if res is not None:
            datas, valid, chars, out_offsets = res
            return _assembled("xpack", schema, datas, valid, chars,
                              list(out_offsets))
    bdata = batch.device_u8()   # var path is byte-granular (DMA engine)
    if (ragged.dma_supported()
            and len(layout.variable_column_indices) <= _DMA_MAX_VAR_COLS):
        # DMA path (copy_strings_from_rows analog, row_conversion.cu:
        # 1131-1174): the fixed region of every row is pulled into one
        # dense matrix (aligned-window DMA; the batch offsets' host mirror
        # is cache-seeded by convert_to_rows) and decomposed with static
        # slices.  Chars are then extracted per string column:
        #   * small n — host slot metadata is cheap: one stacked slot sync
        #     + one segmented-copy DMA kernel per column;
        #   * large n (> _DMA_FROM_ROWS_MAX_N) — per-row slots stay on
        #     DEVICE instead of a bulk D2H: output offsets are a device
        #     cumsum, chars come from the marker-cumsum gather, and the
        #     only sync is the per-column char totals (+ a violation
        #     count), mirroring the reference's sync on the scanned totals
        #     (row_conversion.cu:2215).
        offs_np = hostcache.host_i64(batch.offsets)
        row_base_np = offs_np[:-1]
        fixed_dense = ragged.unpack(bdata, offs_np,
                                    layout.fixed_plus_validity)
        datas, valid, slots = _var_fixed_extract(layout, fixed_dense)
        row_sizes_np = offs_np[1:] - offs_np[:-1]
        nvar = len(layout.variable_column_indices)
        out_offsets = []
        chars = []
        if n <= _DMA_FROM_ROWS_MAX_N:
            # ONE host sync for all columns' slots, counted in the
            # syncs-per-query funnel (eager path, never traced)
            syncs.note_sync()
            slots_np = (np.asarray(jnp.stack(slots), dtype=np.int64)  # srjt-lint: disable=trace-host-sync
                        if slots else np.zeros((0, n, 2), np.int64))
            for vi in range(nvar):
                s = slots_np[vi]
                lens = s[:, 1]
                # rows may be shuffle-received: validate the embedded slots
                # before sizing any allocation (same hardening as the C++
                # host engine, host_table.cpp srjt_from_rows)
                if ((s[:, 0] < layout.fixed_plus_validity)
                        | (s[:, 0] + lens > row_sizes_np)).any():
                    raise ValueError(
                        "corrupt row data: string slot outside its row")
                offs = np.zeros(n + 1, dtype=np.int64)
                np.cumsum(lens, out=offs[1:])
                joffs = jnp.asarray(offs)
                hostcache.seed(joffs, offs)   # host-born: free mirror
                out_offsets.append(joffs)
                chars.append(ragged.copy_segments(
                    bdata, row_base_np + s[:, 0], offs[:-1], lens,
                    int(offs[-1])))
        else:
            from . import xpack
            row_base = batch.offsets[:-1].astype(jnp.int64)
            row_sizes = (batch.offsets[1:]
                         - batch.offsets[:-1]).astype(jnp.int64)
            out_offsets = [
                jnp.concatenate([jnp.zeros((1,), jnp.int64),
                                 jnp.cumsum(s[:, 1].astype(jnp.int64))])
                for s in slots]
            fpv = layout.fixed_plus_validity
            viol = [jnp.sum(((s[:, 0] < fpv)
                             | (s[:, 0].astype(jnp.int64)
                                + s[:, 1] > row_sizes))
                            .astype(jnp.int32)) for s in slots]
            src_starts = [row_base + s[:, 0].astype(jnp.int64)
                          for s in slots]
            # one stacked tiny sync: totals + violation counts + the
            # segmented-gather geometry stats (device-computed maxima)
            syncs.note_sync()
            meta = np.asarray(jnp.stack(  # srjt-lint: disable=trace-host-sync
                [jnp.concatenate([
                    jnp.stack([o[-1], v.astype(jnp.int64)]),
                    xpack._seg_gather_stats(st, s[:, 1], o)])
                 for o, v, st, s in zip(out_offsets, viol, src_starts,
                                        slots)]))
            if meta[:, 1].any():
                raise ValueError(
                    "corrupt row data: string slot outside its row")
            for vi in range(nvar):
                geom = xpack.plan_from_device_stats(meta[vi, 2:], n)
                if geom is not None:
                    # segmented gather: slab/roll engine, ONE program
                    chars.append(xpack.segmented_gather(
                        geom, bdata, src_starts[vi].astype(jnp.int32),
                        slots[vi][:, 1], out_offsets[vi]))
                else:
                    chars.append(_gather_chars(
                        int(meta[vi, 0]), bdata, row_base, slots[vi],
                        out_offsets[vi]))
        return _assembled("dma", schema, datas, valid, tuple(chars),
                          [o.astype(jnp.int32) for o in out_offsets])

    row_offsets = batch.offsets.astype(jnp.int64)

    # XLA gather path (> _DMA_MAX_VAR_COLS string columns, or no DMA
    # backend): slot lengths stay on DEVICE; the only host sync is the
    # per-column char totals.
    slots = _gather_var_slots(layout, bdata, row_offsets)
    out_offsets = [
        jnp.concatenate([jnp.zeros((1,), jnp.int64),
                         jnp.cumsum(s[:, 1].astype(jnp.int64))])
        for s in slots]
    # the documented per-column char-total pull: one stacked sync, counted
    syncs.note_sync()
    with metrics.span("rowconv.var.totals_sync", bytes=8 * len(out_offsets)):
        totals_np = (np.asarray(jnp.stack([o[-1] for o in out_offsets]))  # srjt-lint: disable=trace-host-sync
                     if out_offsets else np.zeros((0,), np.int64))
    char_totals = [int(t) for t in totals_np]
    datas, valid, chars = _from_rows_var(
        layout, tuple(char_totals), bdata, row_offsets,
        tuple(out_offsets), slots)
    return _assembled("gather", schema, datas, valid, chars,
                      [o.astype(jnp.int32) for o in out_offsets])


def _gather_chars(total: int, data: jnp.ndarray, row_base: jnp.ndarray,
                  slot: jnp.ndarray, out_offs: jnp.ndarray) -> jnp.ndarray:
    """One string column's chars from packed rows, fully on device: char k
    belongs to the row found by the marker-cumsum (no per-char binary
    search) and reads ``data[row_start + slot_off + (k - out_offs[row])]``.

    The jitted body is compiled for a BUCKETED total (≤ ~12.5% over) and the
    result sliced — per-batch/per-column totals otherwise each pay a fresh
    XLA compile, which would dominate the very
    path this device-side gather exists to speed up.
    """
    if total == 0:
        return jnp.zeros((0,), jnp.uint8)
    from .ragged import _soft_bucket
    return _gather_chars_jit(_soft_bucket(total, 128), data, row_base,
                             slot, out_offs)[:total]


@functools.partial(jax.jit, static_argnums=0)
def _gather_chars_jit(padded: int, data: jnp.ndarray, row_base: jnp.ndarray,
                      slot: jnp.ndarray, out_offs: jnp.ndarray) -> jnp.ndarray:
    row_of = _segment_of(jnp.clip(out_offs, 0, padded).astype(jnp.int32),
                         padded)
    row_of = jnp.clip(row_of, 0, row_base.shape[0] - 1)
    k = jnp.arange(padded, dtype=jnp.int64)
    src = (row_base[row_of] + slot[row_of, 0].astype(jnp.int64)
           + (k - out_offs[row_of]))
    return data[jnp.clip(src, 0, data.shape[0] - 1)]


def _assembled(engine: str, schema, datas, valid, chars,
               out_offsets) -> Table:
    """The table a variable-width ``convert_from_rows`` returns, counted
    against the engine that served it."""
    table = _assemble(schema, datas, valid, chars, out_offsets)
    metrics.count(f"rowconv.var.engine.from.{engine}")
    _record_chars(table.columns)
    return table


def _assemble(schema, datas, valid, chars, out_offsets) -> Table:
    # Validity stays on device: the reference likewise always materializes a
    # null mask on this path ("always add it in", row_conversion.cu:1299-1301);
    # deciding all-valid here would force a D2H sync per conversion.
    # ``valid``: the bool matrix [n, ncols], or a vector per column.
    cols = []
    vi = 0
    for ci, dt in enumerate(schema):
        v = valid[ci] if isinstance(valid, tuple) else valid[:, ci]
        if dt.is_variable_width:
            cols.append(Column(dt, chars[vi], out_offsets[vi], v))
            vi += 1
        else:
            cols.append(Column(dt, datas[ci], validity=v))
    return Table(cols)


# Legacy-path parity aliases.  The reference keeps a second, simpler CUDA
# implementation for narrow fixed-width tables (row_conversion.cu:425-551,
# 1962-2030) and uses it as a differential oracle; on TPU there is one XLA
# path (the tiling split is a CUDA shared-memory artifact) and the NumPy
# oracle (reference.py) plays the differential role.

def convert_to_rows_fixed_width_optimized(table: Table) -> list[RowBatch]:
    if not all(c.dtype.is_fixed_width for c in table.columns):
        raise ValueError("fixed-width-optimized path requires fixed-width schema")
    return convert_to_rows(table)


def convert_from_rows_fixed_width_optimized(batch: RowBatch,
                                            schema: Sequence[T.DType]) -> Table:
    if not all(dt.is_fixed_width for dt in schema):
        raise ValueError("fixed-width-optimized path requires fixed-width schema")
    return convert_from_rows(batch, schema)
