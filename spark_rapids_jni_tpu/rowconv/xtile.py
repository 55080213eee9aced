"""xpack for wide rows: the batch goes through the engine in row tiles.

``xpack``'s whole-batch programs hold every intermediate at the batch's
size: at the reference benchmark's strings table (155 columns, 15 of them
strings, 1<<20 rows of 1040–1240 B) the padded row matrix alone is
``u32[1048576, 320]`` = 1.34 GB and the pack's slab gather several times
that.  Here one jitted program loops (``lax.fori_loop``) over tiles of
``T`` rows, so temporaries are a tile's, and writes each tile's packed
bytes into the one output buffer in place.  Chosen from the layout: a
schema whose fixed region alone fills a 512 B pack window
(:func:`serves`), so every row is at least a window and a window holds at
most two rows (``P == 2`` with no host pass over the windows).

What differs from the whole-batch programs besides the loop:

* the fixed region (column slots, each string's (offset, length) slot as
  two u32 words, validity bytes) is composed as u32 row words by the
  fixed-width engine's word plan (``convert._compose_row_words``) and
  transposed a tile at a time — no byte matrix, no per-column
  ``.at[].set``;
* all string columns share one geometry and one body, and the body keeps
  the strings on the lanes: a tile's strings are ordered (row of its group,
  column, group) and every per-string step — cutting a string's window out
  of its group's slab, the byte roll, the length mask, the funnel, placing
  it at its byte — works on ``u32[words, g, nvar * T // g]`` with the shift
  a slice of the major axis (``xpack``'s ``*_major`` helpers), so a level of
  a tree moves its data once instead of 128 lanes a handful of words.
  ``to_rows`` transposes the gathered slabs once (``[groups, B/2]`` →
  ``[B/2, groups]``), ORs the columns' placed chars into ``[Cw, g, T // g]``
  and transposes that once into the rows' chars frames ``[T, Cw]`` — as
  wide as the chars region, not as the row.  ``from_rows`` transposes the
  rows' chars frames once (``[T, Cw]`` → ``[Cw, g, T // g]``, one frame
  serving every column, never copied per column), accumulates each group's
  chars into ``[Bd, nl * T // g]`` and transposes that once into the group
  rows the window combine gathers.  Each transpose is pinned
  (``xpack._pin_words_major``): the compiler would otherwise relabel it
  and put the words back on the lanes;
* ``from_rows`` is two programs around the one sync it cannot avoid (the
  output's shapes are the per-column char totals): the first transposes
  every row's fixed region into ``u32[words, n]``, decodes the fixed
  columns and the slots from it and reduces the geometry stats; the
  second cuts each string's bytes out of the rows' chars frames and packs
  them into the columns' char streams.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .layout import RowLayout
from .xpack import (WIN_W, _bucket, _byte_funnel_right, _cut_strings_major,
                    _group_windows_words, _pad_to_blocks, _pin_words_major,
                    _put_strings_major, _reject, _take_words, pack_windows)

# u32 words of padded rows a tile holds ([T, Mw]): 16 MiB, 8192 rows of the
# strings table.  `transcode_gbps` of the strings cell on a v5e by rows a
# tile, 51-s windows (my chip run, PR 33; PERF.md §6): 16384 1.93 (traced),
# 8192 2.18 (traced and not), 4096 2.28 (untraced).  Not the fastest: a
# traced window at 8192 makes 5.5 M device-op events, which the profiler
# keeps to the window's end; half the rows a tile doubles them for 4.7%,
# under the metric's bound, and before PR 33 a window at 2048 rows a tile
# made twice the events the profiler kept (its trace ended at 23 s: PR 28).
# Before PR 33 a round trip took 2.38 s at 32768 rows a tile, 2.17 at 16384,
# 1.89 at 8192, 1.76 at 4096, 1.77 at 2048, 1.71 at 1024 (PR 28).
TILE_WORDS = 1 << 22
ROW_QUANTUM = 128           # T is a multiple: of every group size, of a lane
GROUP = 8                   # rows a char slab gather covers (to_rows)
FROM_GROUPS = (8, 32, 128)  # rows a char-stream group accumulates
# P buckets the stats program can tell apart (xpack._bucket(P, 2) values)
_P_STEPS = (2, 3, 4, 5, 6, 7, 8, 10, 12, 14, 16, 20, 24, 28, 32, 40, 48, 56,
            64)


def serves(layout: RowLayout) -> bool:
    """Rows of this layout are never narrower than one pack window."""
    return layout.fixed_plus_validity >= WIN_W * 4


def tile_rows(n: int, Mw: int) -> int:
    """Rows a tile: the power of two that keeps ``[T, Mw]`` within
    ``TILE_WORDS``, or the whole (padded) batch where that is smaller."""
    T = ROW_QUANTUM
    while T * 2 * Mw <= TILE_WORDS:
        T *= 2
    return min(T, -(-n // ROW_QUANTUM) * ROW_QUANTUM)


def _row_width(max_row_bytes: int, reason: str):
    Mw = _bucket(-(-max_row_bytes // 4), 8)
    if Mw * ROW_QUANTUM > TILE_WORDS:
        return _reject(reason, Mw=Mw)
    return Mw


def _pad_rows(x: jnp.ndarray, n_pad: int, axis: int = 0, edge: bool = False):
    pad = n_pad - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, mode="edge" if edge else "constant")


def _block_rows(flat_w: jnp.ndarray, Bw: int) -> jnp.ndarray:
    """u32 [N] → [nb + 1, Bw] blocks, one zero block after the last."""
    nb = -(-flat_w.shape[0] // Bw) + 1
    return jnp.pad(flat_w, (0, nb * Bw - flat_w.shape[0])).reshape(nb, Bw)


def _row_windows(blocks: jnp.ndarray, start_w: jnp.ndarray,
                 width: int) -> jnp.ndarray:
    """out[r] = the ``width`` words from word ``start_w[r]`` of the stream
    ``blocks`` holds (``width`` ≤ the block width): two block gathers and
    one narrowing take."""
    Bw = blocks.shape[1]
    blk = jnp.clip(start_w // Bw, 0, blocks.shape[0] - 2)
    slab = jnp.concatenate([blocks[blk], blocks[blk + 1]], axis=1)
    return _take_words(slab, start_w - blk * Bw, width)


# ---------------------------------------------------------------------------
# to_rows
# ---------------------------------------------------------------------------

def plan_to_rows(layout: RowLayout, n: int, offs_np: np.ndarray,
                 col_offs_np: list[np.ndarray]):
    """Static geometry ``(n, Mw, T, B, Lw, total_w)`` from the host row and
    char offsets, or None (counted) outside the engine's caps."""
    total = int(offs_np[-1])
    Mw = _row_width(int((offs_np[1:] - offs_np[:-1]).max()),
                    "to_rows_row_width")
    if Mw is None:
        return None
    lmax = span = 0
    for co in col_offs_np:
        lmax = max(lmax, int((co[1:] - co[:-1]).max(initial=0)))
        # any GROUP consecutive rows, wherever a tile's groups start
        k = min(GROUP, n)
        span = max(span, int((co[k:] - co[:-k]).max(initial=0)))
    if lmax == 0:
        B = Lw = 0
    else:
        B = _bucket(max(span, 64), 64)
        Lw = _bucket(-(-lmax // 4), 4)
        if B > (1 << 20) or Lw > 512:
            return _reject("to_rows_col_caps", B=B, Lw=Lw)
    return (n, Mw, tile_rows(n, Mw), B, Lw, total // 4)


def _string_lanes(x: jnp.ndarray, g: int) -> jnp.ndarray:
    """Per-(column, row) values [c, T] → [g, c * T // g]: the tile's strings
    as the major-axis helpers want them, row ``j`` of a group of ``g`` on
    sublane ``j``, the groups on the lanes in (column, group) order."""
    c, T = x.shape
    return x.reshape(c, T // g, g).transpose(2, 0, 1).reshape(g, c * (T // g))


def _chars_frame(blocks, at, lt, pos, B: int, Lw: int, Cw: int):
    """[T, Cw]: every string of a tile at its byte of the row's chars frame.
    ``at``: the strings' byte offsets in the joined chars ``blocks`` holds,
    ``lt`` their lengths, ``pos`` their bytes in the frame, [nvar, T] each.
    One slab gather a group of ``GROUP`` rows of a column, transposed once;
    from there the words run along the major axis (``[words, GROUP, nvar *
    T // GROUP]``) through the cut (take, roll) and the put (mask, funnel,
    place), the columns are OR-ed and the frame is transposed back into
    rows."""
    nvar, T = at.shape
    ng = T // GROUP
    starts = _string_lanes(at, GROUP)
    blk = starts[0] // B
    slab = _pin_words_major(
        blocks[jnp.clip(blk, 0, blocks.shape[0] - 1)].T)      # [B / 2, groups]
    piece = _cut_strings_major(slab[:, None], starts - blk * B, Lw)
    placed = _put_strings_major(piece, _string_lanes(lt, GROUP),
                                _string_lanes(pos, GROUP), Cw)
    frame = _pin_words_major(functools.reduce(
        jnp.bitwise_or,
        [placed[:, :, vi * ng:(vi + 1) * ng] for vi in range(nvar)]))
    return frame.transpose(2, 1, 0).reshape(T, Cw)


@functools.partial(jax.jit, static_argnums=(0, 1))
def to_rows_jit(layout: RowLayout, geom, datas, str_offsets, valid):
    """``(u32 row words [total_w], int32 row byte offsets [n + 1])``.
    ``datas``: a payload per column (a string column's chars); ``valid``:
    bool [n] or None per column."""
    from .convert import _compose_row_words, _stage_column_dt
    n, Mw, T, B, Lw, total_w = geom
    var_idx = layout.variable_column_indices
    nvar = len(var_idx)
    fpv = layout.fixed_plus_validity
    fpvw, cbase = -(-fpv // 4), fpv // 4
    Cw = Mw - cbase
    ntiles = -(-n // T)
    n_pad = ntiles * T

    # rows past n are empty-stringed rows of zeros past the batch's end
    offs = _pad_rows(jnp.stack([o.astype(jnp.int32) for o in str_offsets]),
                     n_pad + 1, axis=1, edge=True)            # [nvar, n_pad+1]
    lens = offs[:, 1:] - offs[:, :-1]
    prefix = jnp.cumsum(lens, axis=0) - lens                  # over columns
    row_b = fpv + prefix[-1] + lens[-1]
    dst_w = jnp.concatenate([
        jnp.zeros(1, jnp.int32),
        jnp.cumsum((row_b + 7) // 8 * 2, dtype=jnp.int32)])   # [n_pad + 1]
    fixed = [None if dt.is_variable_width
             else _pad_rows(_stage_column_dt(datas[ci], dt), n_pad)
             for ci, dt in enumerate(layout.schema)]
    vcols = [None if v is None else _pad_rows(v, n_pad) for v in valid]
    with_chars = Lw > 0
    if with_chars:
        sizes = [datas[ci].shape[0] for ci in var_idx]
        bases = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)[:-1]]),
                            jnp.int32)[:, None]
        blocks = _pad_to_blocks(
            jnp.concatenate([datas[ci].reshape(-1) for ci in var_idx]), B)

    def tile(t, out):
        r0 = t * T
        ot = jax.lax.dynamic_slice(offs, (0, r0), (nvar, T + 1))
        lt = ot[:, 1:] - ot[:, :-1]                           # [nvar, T]
        pt = jnp.cumsum(lt, axis=0) - lt
        staged = []
        for ci, dt in enumerate(layout.schema):
            if dt.is_variable_width:
                vi = var_idx.index(ci)
                staged.append(jnp.stack(
                    [(fpv + pt[vi]).astype(jnp.uint32),
                     lt[vi].astype(jnp.uint32)], axis=1))
            else:
                staged.append(jax.lax.dynamic_slice_in_dim(fixed[ci], r0, T))
        vbytes = []
        for k in range(layout.validity_bytes):
            acc = jnp.zeros((T,), jnp.uint32)
            for i in range(min(8, layout.num_columns - k * 8)):
                v = vcols[k * 8 + i]
                bit = (jnp.uint32(1) if v is None else
                       jax.lax.dynamic_slice_in_dim(v, r0, T)
                       .astype(jnp.uint32))
                acc = acc | (bit << jnp.uint32(i))
            vbytes.append(acc)
        words = _compose_row_words(layout, staged, vbytes, fpvw, T)
        dense = jnp.pad(jnp.stack(words, axis=0).T,
                        ((0, 0), (0, Mw - fpvw)))             # [T, Mw]
        if with_chars:
            dense = dense | jnp.pad(
                _chars_frame(blocks, ot[:, :T] + bases, lt, pt + fpv % 4,
                             B, Lw, Cw), ((0, 0), (cbase, 0)))
        dw = jax.lax.dynamic_slice_in_dim(dst_w, r0, T + 1)
        packed = pack_windows(dense, dw - dw[0], T * Mw, 2,
                              -(-T * Mw // WIN_W))
        return jax.lax.dynamic_update_slice(out, packed, (dw[0],))

    out = jax.lax.fori_loop(0, ntiles, tile,
                            jnp.zeros((total_w + T * Mw,), jnp.uint32))
    return out[:total_w], dst_w[:n + 1] * 4


# ---------------------------------------------------------------------------
# from_rows
# ---------------------------------------------------------------------------

def plan_from_rows_fixed(layout: RowLayout, n: int, offs_np: np.ndarray):
    """``(n, Mw, T, Bw)`` of the first program from the host row offsets,
    or None (counted)."""
    Mw = _row_width(int((offs_np[1:] - offs_np[:-1]).max(initial=8)),
                    "from_rows_row_width")
    if Mw is None:
        return None
    Bw = _bucket(-(-layout.fixed_plus_validity // 4), 128)
    return (n, Mw, tile_rows(n, Mw), Bw)


@functools.partial(jax.jit, static_argnums=(0, 1))
def from_rows_fixed_jit(layout: RowLayout, geom, words, offs):
    """The fixed half and the geometry stats: ``(datas — None at string
    columns, validity vector per column, string (offset, length) slots
    int32 [nvar, 2, n_pad], out offsets int32 [n + 1] per string column,
    stats int32 [nvar, 3 + len(FROM_GROUPS)])``; a stats row is ``[char
    total, slots outside their row, longest string, P bucket per group
    size]``."""
    from .convert import _decode_row_columns
    n, Mw, T, Bw = geom
    fpv = layout.fixed_plus_validity
    fpvw = -(-fpv // 4)
    ntiles = -(-n // T)
    n_pad = ntiles * T
    blocks = _block_rows(words, Bw)
    offs = _pad_rows(offs.astype(jnp.int32), n_pad + 1, edge=True)
    offs_w = offs // 4

    def tile(t, big):
        r0 = t * T
        fx = _row_windows(blocks,
                          jax.lax.dynamic_slice_in_dim(offs_w, r0, T), fpvw)
        return jax.lax.dynamic_update_slice(big, fx.T, (0, r0))

    big = jax.lax.fori_loop(0, ntiles, tile,
                            jnp.zeros((fpvw, n_pad), jnp.uint32))
    datas, vcols, pairs = _decode_row_columns(layout, lambda w: big[w], n)
    live = jnp.arange(n_pad) < n
    off = jnp.where(live, jnp.stack([_pad_rows(o, n_pad) for o, _ in pairs]),
                    jnp.uint32(0))                            # [nvar, n_pad]
    ln = jnp.where(live, jnp.stack([_pad_rows(x, n_pad) for _, x in pairs]),
                   jnp.uint32(0))
    # a slot inside its row, in u32 with nothing to overflow; with none
    # outside, a column's lengths sum to under the batch's bytes: int32
    size = (offs[1:] - offs[:-1]).astype(jnp.uint32)
    viol = jnp.sum((live & ((off < fpv) | (ln > size) | (off > size - ln)))
                   .astype(jnp.int32), axis=1)
    ln = ln.astype(jnp.int32)
    dst = jnp.concatenate([jnp.zeros((len(pairs), 1), jnp.int32),
                           jnp.cumsum(ln, axis=1, dtype=jnp.int32)], axis=1)
    stats = jnp.stack([dst[:, n], viol, jnp.max(ln, axis=1)]
                      + [_p_bucket(dst, n, n_pad, g) for g in FROM_GROUPS],
                      axis=1)
    slots = jnp.stack([off.astype(jnp.int32), ln], axis=1)    # [nvar, 2, n_pad]
    return (datas, tuple(vcols), slots,
            tuple(dst[vi, :n + 1] for vi in range(len(pairs))), stats)


def _p_bucket(dst: jnp.ndarray, n: int, n_pad: int, g: int) -> jnp.ndarray:
    """Per column, the smallest of ``_P_STEPS`` that bounds the groups (of
    ``g`` rows) a 512 B stretch of the char stream ``dst`` [nvar, n_pad + 1]
    touches, wherever the stretch starts; one past the largest where none
    does.  A stretch touches the group that straddles in and those that
    start inside, and ``b`` groups start inside one exactly where some
    ``s[k+b-1] - s[k]`` is under 512."""
    ng = n_pad // g
    k = jnp.arange(ng, dtype=jnp.int32)
    # a group that holds no live row starts past the stream, 512 B apart
    s = jnp.where(k * g < n, dst[:, :n_pad:g], dst[:, n:n + 1] + 512 * (k + 1))
    p = jnp.full((dst.shape[0],), _P_STEPS[-1] + 1, jnp.int32)
    for b in reversed(_P_STEPS):
        if b <= ng:
            crowded = jnp.any(s[:, b - 1:] - s[:, :ng - b + 1] < 512, axis=1)
            p = jnp.where(crowded, p, jnp.int32(b))
        else:
            p = jnp.full_like(p, b)
    return p


def plan_from_rows_chars(layout: RowLayout, geom_fixed, stats: np.ndarray):
    """``(n, Mw, T, Bc, Lw, g, Bd, P, live, totals)`` of the second program
    from the stats sync, or None (counted); ``live``: the string columns
    that hold a byte."""
    n, Mw, T, _ = geom_fixed
    totals = tuple(int(t) for t in stats[:, 0])
    live = tuple(vi for vi, t in enumerate(totals) if t)
    if not live:
        return (n, Mw, T, 0, 0, 0, 0, 0, live, totals)
    lmax = int(stats[list(live), 2].max())
    Lw = _bucket(-(-lmax // 4) + 1, 4)
    if Lw > 512:
        return _reject("from_rows_col_caps", Lw=Lw)
    Bc = _bucket(Mw - layout.fixed_plus_validity // 4, 128)
    for gi, g in enumerate(FROM_GROUPS):
        P = int(stats[list(live), 3 + gi].max())
        Bd = _bucket(-(-g * lmax // 4) + 1, 8)
        if P <= _P_STEPS[-1] and Bd <= 512:
            return (n, Mw, T, Bc, Lw, g, Bd, P, live, totals)
    return _reject("from_rows_col_caps", Bd=Bd, P=P)


def _group_chars(frame, rel, ln, dst, dstg, g: int, Lw: int, Bd: int):
    """[nl * T // g, Bd]: the chars of each group of ``g`` rows of a column
    at their bytes of the group's stretch of the char stream.  ``frame``:
    the rows' chars frames [T, Cw]; ``rel`` a string's byte in its frame,
    ``ln`` its length, ``dst`` its byte of the stream, [nl, T] each;
    ``dstg`` the groups' first bytes, [nl * T // g (+ 1)].  The frame is
    transposed once and serves every column from there (no [nl * T, Cw]
    copy); the words run along the major axis (``[words, g, nl * T // g]``)
    through the cut and the put, the ``g`` rows are OR-ed and the groups
    transposed back into rows."""
    nl, T = rel.shape
    ng = T // g
    frame_t = _pin_words_major(
        frame.reshape(ng, g, frame.shape[1]).transpose(2, 1, 0))
    piece = _cut_strings_major(
        frame_t[:, :, None], _string_lanes(rel, g).reshape(g, nl, ng),
        Lw).reshape(Lw, g, nl * ng)
    placed = _put_strings_major(
        piece, _string_lanes(ln, g),
        _string_lanes(dst, g) - dstg[None, :nl * ng], Bd)     # [Bd, g, groups]
    return _pin_words_major(jax.lax.reduce(
        placed, np.uint32(0), jax.lax.bitwise_or, (1,))).T


@functools.partial(jax.jit, static_argnums=(0, 1))
def from_rows_chars_jit(layout: RowLayout, geom, words, offs, slots,
                        out_offs):
    """The chars of every live string column, u8 [total] each."""
    n, Mw, T, Bc, Lw, g, Bd, P, live, totals = geom
    nl = len(live)
    cbase = layout.fixed_plus_validity // 4
    Cw = Mw - cbase
    ntiles = -(-n // T)
    n_pad = ntiles * T
    S = T * (Lw - 1) * 4            # a column's bytes of a tile, at most
    S = -(-S // 512) * 512
    Sw = S // 4
    blocks = _block_rows(words, Bc)
    offs_w = _pad_rows(offs.astype(jnp.int32), n_pad + 1, edge=True) // 4
    slots = slots[jnp.asarray(live)]                          # [nl, 2, n_pad]
    out_offs = _pad_rows(jnp.stack([out_offs[vi] for vi in live]), n_pad + 1,
                         axis=1, edge=True)
    col0 = (jnp.arange(nl, dtype=jnp.int32) * S)[:, None]

    def tile(t, bufs):
        r0 = t * T
        frame = _row_windows(
            blocks, jax.lax.dynamic_slice_in_dim(offs_w, r0, T) + cbase, Cw)
        st = jax.lax.dynamic_slice(slots, (0, 0, r0), (nl, 2, T))
        rel = jnp.clip(st[:, 0] - cbase * 4, 0, Cw * 4)
        ln = st[:, 1]                                         # [nl, T]
        # a column's bytes of the tile start at its S: rows past n start at
        # the next column's, where empty groups cost no window a pass
        live_row = (r0 + jnp.arange(T)) < n
        dst = jnp.where(live_row[None], jnp.cumsum(ln, axis=1) - ln, S) + col0
        dstg = jnp.concatenate([dst[:, ::g].reshape(-1),
                                jnp.full((1,), nl * S, jnp.int32)])
        acc = _group_chars(frame, rel, ln, dst, dstg, g, Lw, Bd)
        stream = _group_windows_words(acc, dstg, nl * (T // g), Bd, P,
                                      nl * S // 512)
        out = []
        for k, buf in enumerate(bufs):
            base = jax.lax.dynamic_slice(out_offs, (k, r0), (1, 1))[0, 0]
            part = _byte_funnel_right(stream[None, k * Sw:(k + 1) * Sw],
                                      (base % 4)[None])[0]    # [Sw + 1]
            at = base // 4
            held = jax.lax.dynamic_slice_in_dim(buf, at, Sw + 1)
            out.append(jax.lax.dynamic_update_slice(buf, held | part, (at,)))
        return tuple(out)

    bufs = jax.lax.fori_loop(
        0, ntiles, tile,
        tuple(jnp.zeros((-(-totals[vi] // 4) + Sw + 1,), jnp.uint32)
              for vi in live))
    return tuple(
        jax.lax.bitcast_convert_type(buf, jnp.uint8).reshape(-1)[:totals[vi]]
        for vi, buf in zip(live, bufs))
