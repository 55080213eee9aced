"""Variable-width JCUDF composition as ONE fused XLA program (round 4).

The round-3 string path moved bytes with per-(row|segment) machinery that
pays a fixed cost for every row (Pallas per-row rolls, XLA row-granular
gathers).  This module rebuilds the path on two primitives whose cost does
not grow with the row count (what the path does from a caller's side
today, and where its time goes: PERF.md §5):

* **slab gathers** — an XLA row gather costs by the *gathered row*, whatever
  the row's width, so all gathers here move WIDE slabs covering many
  logical rows: per-column char windows are
  gathered per GROUP of ``g`` rows (one slab covers the whole group's
  chars), and the output packing gathers one ``P``-row slab per 512 B
  output window.  Gather count is ``n/g + n_windows``, not ``n × pieces``.
* **log-shift rolls** — per-row dynamic byte placement is a select tree
  (log₂(width) word passes + a 4-variant byte funnel), pure elementwise,
  which XLA fuses into a handful of memory passes.  No scatter, no
  per-element gather, no serialization.

This is the same job as the reference's fused string kernels
(``copy_strings_to_rows``, row_conversion.cu:827-875, 1861: one launch
writes fixed slots, validity, and chars for a batch) — restructured so the
heavy traffic is aligned bulk reads + register shuffles, the TPU-friendly
shape of that computation.  Everything here is shape-static given the
geometry buckets, so the whole conversion runs as ONE jitted program per
(schema, geometry-bucket) with zero host syncs inside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from .convert import _words_to_u8
from .layout import RowLayout

LANE = 128
WIN_W = 128                    # output pack window: 128 u32 words = 512 B

# Fallback accounting (VERDICT r4 weak #3): every geometry-plan rejection
# increments a named counter and emits one structured-log event, so a bench
# or query run can say exactly WHY a conversion degraded to a slower path.
fallback_counts: dict[str, int] = {}


def _reject(reason: str, **fields):
    """Record a geometry-cap rejection; returns None (the plan result)."""
    fallback_counts[reason] = fallback_counts.get(reason, 0) + 1
    from ..utils import structured_log
    structured_log.event("xpack_fallback", reason=reason, **fields)
    return None


def _bucket(x: int, lo: int = 8) -> int:
    """≤ ~12.5% growth bucket (pow2/8 multiples) to bound jit variants."""
    if x <= lo:
        return lo
    p = lo
    while p < x:
        p <<= 1
    step = max(p // 8, 1)
    return -(-x // step) * step


def _u8_to_u32_rows(b: jnp.ndarray) -> jnp.ndarray:
    """u8 [n, 4W] → u32 [n, W] little-endian (elementwise, fused)."""
    n, w4 = b.shape
    parts = [b[:, k::4].astype(jnp.uint32) for k in range(4)]
    return (parts[0] | (parts[1] << 8) | (parts[2] << 16)
            | (parts[3] << 24))


def _nbits_for(W: int) -> int:
    b = 0
    while (1 << b) < W + 1:
        b += 1
    return b


def _select_digit(digit: jnp.ndarray, vs: list) -> jnp.ndarray:
    return jnp.where(digit == 1, vs[1],
                     jnp.where(digit == 2, vs[2],
                               jnp.where(digit == 3, vs[3], vs[0])))


def _take_words(m: jnp.ndarray, sh: jnp.ndarray, Wo: int) -> jnp.ndarray:
    """out[r, j] = m[r, sh[r] + j] for j < Wo (zeros beyond the source).

    NARROWING radix-4 select tree: the level handling digit weight 4^k
    works at width ``Wo + 4^k − 1`` — widths shrink geometrically, so the
    total vector traffic is ~(W/3 + Wo·log) per row instead of the naive
    W·log of a fixed-width tree."""
    W = m.shape[1]
    levels = []
    w = 1
    while w < W:
        levels.append(w)
        w *= 4
    cur = m
    for wk in reversed(levels):
        Wn = Wo + wk - 1
        digit = ((sh // wk) % 4).astype(jnp.int32)[:, None]
        vs = []
        for k in range(4):
            s0 = k * wk
            if s0 >= cur.shape[1]:
                vs.append(jnp.zeros((cur.shape[0], Wn), cur.dtype))
                continue
            sl = cur[:, s0:s0 + Wn]
            if sl.shape[1] < Wn:
                sl = jnp.pad(sl, ((0, 0), (0, Wn - sl.shape[1])))
            vs.append(sl)
        cur = _select_digit(digit, vs)
    return cur[:, :Wo]


def _place_words(m: jnp.ndarray, sh: jnp.ndarray, Wo: int) -> jnp.ndarray:
    """out[r, sh[r] + j] = m[r, j] (zeros elsewhere), out width Wo.

    WIDENING radix-4 tree (inverse of :func:`_take_words`): digits are
    applied low→high at geometrically growing widths, so only the final
    level touches the full output width."""
    cur = m
    wk = 1
    while True:
        last = wk * 4 >= Wo
        Wn = Wo if last else min(cur.shape[1] + 3 * wk, Wo)
        digit = ((sh // wk) % 4).astype(jnp.int32)[:, None]
        vs = []
        for k in range(4):
            keep = max(0, min(cur.shape[1], Wn - k * wk))
            if keep == 0:
                vs.append(jnp.zeros((cur.shape[0], Wn), cur.dtype))
                continue
            vs.append(jnp.pad(cur[:, :keep],
                              ((0, 0), (k * wk, Wn - k * wk - keep))))
        cur = _select_digit(digit, vs)
        if last:
            return cur
        wk *= 4


def _byte_mask(W: int, start_b: jnp.ndarray, end_b: jnp.ndarray):
    """u32 mask [n, W]: byte positions in [start, end) per row."""
    pos = (jnp.arange(W, dtype=jnp.int32) * 4)[None, :]
    s = start_b[:, None]
    e = end_b[:, None]
    m = jnp.zeros((start_b.shape[0], W), jnp.uint32)
    for k in range(4):
        inside = ((pos + k) >= s) & ((pos + k) < e)
        m = m | jnp.where(inside, jnp.uint32(0xFF << (8 * k)), jnp.uint32(0))
    return m


def _pad_to_blocks(flat_u8: jnp.ndarray, B: int) -> jnp.ndarray:
    """u8 [T] → u32 [nb, 2B/4]: B-byte blocks, each row concatenated with
    its successor so ONE gathered row covers any window of ≤ B bytes."""
    T = flat_u8.shape[0]
    nb = max(-(-T // B), 1)
    pad = nb * B - T
    b2 = jnp.pad(flat_u8, (0, pad)).reshape(nb, B)
    w = _u8_to_u32_rows(b2)                      # [nb, B/4]
    nxt = jnp.concatenate([w[1:], jnp.zeros((1, B // 4), jnp.uint32)])
    return jnp.concatenate([w, nxt], axis=1)     # [nb, B/2]


def extract_group_windows(chars_u8: jnp.ndarray, offs: jnp.ndarray,
                          n: int, g: int, B: int, Lw: int) -> jnp.ndarray:
    """Per-row char windows [n, Lw] u32 from a contiguous chars buffer.

    One slab gather per GROUP of ``g`` rows (the group's chars span ≤ B
    bytes — caller sizes B from the host geometry), then ``g`` fused
    byte-shifts pull each row's window out of its group slab.
    """
    ngroups = -(-n // g)
    v2 = _pad_to_blocks(chars_u8, B)             # [nb, B/2] u32
    gstart = offs[jnp.minimum(
        jnp.arange(ngroups, dtype=jnp.int32) * g, n)]
    blk = gstart // B
    slab = v2[jnp.clip(blk, 0, v2.shape[0] - 1)]  # [ngroups, B/2]
    outs = []
    for j in range(g):
        ridx = jnp.minimum(jnp.arange(ngroups, dtype=jnp.int32) * g + j,
                           n - 1) if n else jnp.zeros(0, jnp.int32)
        amt = offs[ridx] - blk * B               # byte offset, [0, 2B)
        w = _take_words(slab, amt // 4, Lw + 1)
        outs.append(_roll_left_bytes(w, Lw, amt % 4))
    out = jnp.stack(outs, axis=1).reshape(ngroups * g, Lw)
    return out[:n]


def _first_row_per_window(dst: jnp.ndarray, n: int, nwin: int,
                          win: int = WIN_W) -> jnp.ndarray:
    """fr[w] = last row r with dst[r] ≤ w·win (rows cover windows
    contiguously; ``dst``/``win`` share a unit — words or bytes).  Pure
    segment-sum/cumsum — no searchsorted."""
    win_of = (dst[:n] // win).astype(jnp.int32)
    h = jax.ops.segment_sum(jnp.ones(n, jnp.int32), win_of, nwin)
    lt = jnp.concatenate([jnp.zeros(1, jnp.int32),
                          jnp.cumsum(h)[:-1]])   # #rows with dst < w·win
    eq = jax.ops.segment_sum(
        ((dst[:n] % win) == 0).astype(jnp.int32), win_of, nwin)
    return lt + eq - 1


def pack_windows(dense: jnp.ndarray, dst_w: jnp.ndarray, total_w: int,
                 P: int, nwin: int) -> jnp.ndarray:
    """Pack padded rows [n, Mw] into flat words [total_w] (rows are
    8-byte-aligned so packing is word-granular).

    Output-window-centric: window w takes rows fr(w)..fr(w)+P-1 as ONE
    gathered slab from a P-wide shifted view of ``dense``, then places each
    row with a fused word-shift + mask + OR.

    ``SRJT_PALLAS_PACKWIN`` routes the same placement through the Mosaic
    kernel (one VMEM row-window DMA per 4 KiB output block instead of the
    P-wide slab re-read); geometry outside the kernel envelope falls back
    here."""
    from . import xpallas
    xout = xpallas.try_pack_windows(dense, dst_w, total_w, P, nwin)
    if xout is not None:
        return xout
    n, Mw = dense.shape
    # P-row slab view: VP[r] = dense[r] ++ dense[r+1] ++ … ++ dense[r+P-1]
    padded = jnp.pad(dense, ((0, P), (0, 0)))
    vp = jnp.concatenate([padded[p:n + p] for p in range(P)], axis=1)
    fr = _first_row_per_window(dst_w, n, nwin)
    fr = jnp.clip(fr, 0, max(n - 1, 0))
    slab = vp[fr]                                 # [nwin, P·Mw]

    F = WIN_W + 2 * Mw                            # frame with ±Mw slack
    wbase = jnp.arange(nwin, dtype=jnp.int32) * WIN_W
    acc = jnp.zeros((nwin, F), jnp.uint32)
    for p in range(P):
        r = jnp.minimum(fr + p, n - 1)
        d = dst_w[r] - wbase + Mw                 # biased frame offset ≥ 0
        live = (fr + p < n) & (dst_w[r] < wbase + WIN_W) & (d >= 0)
        piece = slab[:, p * Mw:(p + 1) * Mw]
        placed = _place_words(piece, jnp.where(live, d, 0), F)
        rw = dst_w[r + 1] - dst_w[r]
        mask = _byte_mask(F, d * 4, (d + rw) * 4)
        acc = acc | jnp.where(live[:, None], placed & mask, jnp.uint32(0))
    out = acc[:, Mw:Mw + WIN_W].reshape(-1)
    return out[:total_w]


def _roll_left_bytes(w: jnp.ndarray, Lw: int, rb: jnp.ndarray) -> jnp.ndarray:
    """[n, Lw+1] u32 word windows → [n, Lw]: shift each row LEFT by
    rb∈[0,4) bytes (the payload starts ``rb`` bytes into word 0).  The
    shared inner roll of every window-extraction site."""
    a, nxt = w[:, :Lw], w[:, 1:Lw + 1]
    rbc = rb.astype(jnp.uint32)[:, None]
    out = a
    for k in (1, 2, 3):
        v = (a >> jnp.uint32(8 * k)) | (nxt << jnp.uint32(32 - 8 * k))
        out = jnp.where(rbc == k, v, out)
    return out


def _byte_funnel_right(win: jnp.ndarray, rb: jnp.ndarray) -> jnp.ndarray:
    """[n, W] u32 → [n, W+1]: shift each row RIGHT by rb∈[0,4) bytes."""
    a = jnp.pad(win, ((0, 0), (0, 1)))
    prev = jnp.pad(win, ((0, 0), (1, 0)))
    rbc = rb.astype(jnp.uint32)[:, None]
    fun = a
    for k in (1, 2, 3):
        v = (a << jnp.uint32(8 * k)) | (prev >> jnp.uint32(32 - 8 * k))
        fun = jnp.where(rbc == k, v, fun)
    return fun


# ---------------------------------------------------------------------------
# major-axis twins: the same trees with the words on the major axis
# ---------------------------------------------------------------------------
#
# The helpers above shift along the MINOR axis of ``[strings, words]``: a
# handful of words there is padded to 128 lanes by the (8,128) tiling, so a
# level of a tree moves 10–30× its data.  These work on ``[words, *strings]``
# with the per-string shift shaped ``[*strings]``: the strings fill the
# lanes (and, with two or more string axes, the sublanes), a shift is a
# slice of the untiled major axis, and a level is elementwise on dense
# vregs.  Operands may broadcast against the shift along the string axes
# (one source serving several strings).  The tiled programs (``xtile``) use
# them; the whole-batch programs keep the minor-axis forms.

def _pin_words_major(x: jnp.ndarray) -> jnp.ndarray:
    """Hold ``x`` [words, *strings] in row-major order on the device.  The
    compiler lays intermediates out as it likes, and a transposed gather
    result it would rather relabel than move, which puts the words back on
    the lanes; this is where the one physical transpose lands."""
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _take_words_major(m: jnp.ndarray, sh: jnp.ndarray, Wo: int):
    """out[j, s] = m[sh[s] + j, s] for j < Wo (zeros beyond the source):
    :func:`_take_words` with the words on axis 0."""
    levels = []
    w = 1
    while w < m.shape[0]:
        levels.append(w)
        w *= 4
    cur = m
    for wk in reversed(levels):
        Wn = Wo + wk - 1
        digit = ((sh // wk) % 4).astype(jnp.int32)[None]
        vs = []
        for k in range(4):
            sl = cur[k * wk:k * wk + Wn]
            vs.append(jnp.pad(sl, ((0, Wn - sl.shape[0]),)
                              + ((0, 0),) * (cur.ndim - 1)))
        cur = _select_digit(digit, vs)
    return cur[:Wo]


def _place_words_major(m: jnp.ndarray, sh: jnp.ndarray, Wo: int):
    """out[sh[s] + j, s] = m[j, s] (zeros elsewhere), Wo words:
    :func:`_place_words` with the words on axis 0."""
    cur = m
    wk = 1
    while True:
        last = wk * 4 >= Wo
        Wn = Wo if last else min(cur.shape[0] + 3 * wk, Wo)
        digit = ((sh // wk) % 4).astype(jnp.int32)[None]
        vs = []
        for k in range(4):
            keep = max(0, min(cur.shape[0], Wn - k * wk))
            lead = min(k * wk, Wn)
            vs.append(jnp.pad(cur[:keep], ((lead, Wn - lead - keep),)
                              + ((0, 0),) * (cur.ndim - 1)))
        cur = _select_digit(digit, vs)
        if last:
            return cur
        wk *= 4


def _byte_mask_major(W: int, start_b: jnp.ndarray, end_b: jnp.ndarray):
    """u32 mask [W, *strings]: byte positions in [start, end) per string."""
    pos = (jnp.arange(W, dtype=jnp.int32) * 4).reshape(
        (W,) + (1,) * start_b.ndim)
    s, e = start_b[None], end_b[None]
    m = jnp.zeros((W,) + start_b.shape, jnp.uint32)
    for k in range(4):
        inside = ((pos + k) >= s) & ((pos + k) < e)
        m = m | jnp.where(inside, jnp.uint32(0xFF << (8 * k)), jnp.uint32(0))
    return m


def _roll_left_bytes_major(w: jnp.ndarray, Lw: int, rb: jnp.ndarray):
    """[Lw+1, *strings] → [Lw, *strings]: each string LEFT by rb∈[0,4)
    bytes."""
    a, nxt = w[:Lw], w[1:Lw + 1]
    rbc = rb.astype(jnp.uint32)[None]
    out = a
    for k in (1, 2, 3):
        v = (a >> jnp.uint32(8 * k)) | (nxt << jnp.uint32(32 - 8 * k))
        out = jnp.where(rbc == k, v, out)
    return out


def _byte_funnel_right_major(win: jnp.ndarray, rb: jnp.ndarray):
    """[W, *strings] → [W+1, *strings]: each string RIGHT by rb∈[0,4)
    bytes."""
    rest = ((0, 0),) * (win.ndim - 1)
    a = jnp.pad(win, ((0, 1),) + rest)
    prev = jnp.pad(win, ((1, 0),) + rest)
    rbc = rb.astype(jnp.uint32)[None]
    fun = a
    for k in (1, 2, 3):
        v = (a << jnp.uint32(8 * k)) | (prev >> jnp.uint32(32 - 8 * k))
        fun = jnp.where(rbc == k, v, fun)
    return fun


def _cut_strings_major(src: jnp.ndarray, at_b: jnp.ndarray, Lw: int):
    """[Lw, *strings]: the ``Lw`` words from byte ``at_b`` of each string's
    source ``src`` [W, *strings] (bytes past a string's end come along)."""
    return _roll_left_bytes_major(
        _take_words_major(src, at_b // 4, Lw + 1), Lw, at_b % 4)


def _put_strings_major(piece: jnp.ndarray, len_b: jnp.ndarray,
                       at_b: jnp.ndarray, Wo: int):
    """[Wo, *strings]: the first ``len_b`` bytes of each ``piece``
    [Lw, *strings] at byte ``at_b`` of ``Wo`` words, zeros elsewhere."""
    piece = piece & _byte_mask_major(piece.shape[0], jnp.zeros_like(len_b),
                                     len_b)
    return _place_words_major(_byte_funnel_right_major(piece, at_b % 4),
                              at_b // 4, Wo)


# ---------------------------------------------------------------------------
# segmented gather: ordered byte segments → packed stream (device)
# ---------------------------------------------------------------------------

def plan_segmented_gather(src_starts_np: np.ndarray, lens_np: np.ndarray,
                          dst_offs_np: np.ndarray):
    """Host geometry for :func:`segmented_gather` (bucketed statics), or
    None outside the supported buckets.  Segments must be ordered in the
    source (monotone starts) — true for parquet string payloads and JCUDF
    row streams alike."""
    n = int(lens_np.shape[0])
    total = int(dst_offs_np[-1])
    if n == 0 or total == 0:
        return None
    g = 8
    Lmax = int(lens_np.max(initial=0))
    Lw = _bucket(-(-max(Lmax, 1) // 4) + 1, 4)
    idx = np.minimum(np.arange(0, n + g, g), n)
    ends = src_starts_np + lens_np
    lo, hi = idx[:-1], idx[1:]
    nonempty = hi > lo
    span = int((ends[np.maximum(hi - 1, 0)] - src_starts_np[lo])
               [nonempty].max(initial=0))
    B = _bucket(max(span, 64), 64)
    gd = dst_offs_np[idx]
    Bd = _bucket(-(-int((gd[1:] - gd[:-1]).max(initial=1)) // 4) + 1, 8)
    nwin = -(-total // 512)
    fr = np.searchsorted(gd, np.arange(nwin, dtype=np.int64) * 512,
                         side="right") - 1
    lr = np.searchsorted(gd, np.minimum(
        np.arange(nwin, dtype=np.int64) * 512 + 512, total) - 1,
        side="right") - 1
    P = _bucket(int((lr - fr).max(initial=0)) + 1, 2)
    # the same caps as plan_from_device_stats: short-segment geometries
    # (P explodes with ~64 groups per window) must degrade to the caller's
    # fallback, not compile a P-times-unrolled combine
    if B > (1 << 20) or Lw > 512 or Bd > 512 or P > 64:
        return _reject("seg_gather_caps_host", B=B, Lw=Lw, Bd=Bd, P=int(P))
    return (n, g, B, Lw, Bd, int(P), nwin, total)


def dst_combine_stats(dst_offs: jnp.ndarray, g: int = 8):
    """Traceable destination-side packing stats for the group-accumulate
    + window combine: [total, max group dst span, max groups per 512B
    window].  Shared by every engine that packs ordered segments
    (segmented_gather, the from_rows inverse, dictionary strings)."""
    n = dst_offs.shape[0] - 1
    dst = dst_offs.astype(jnp.int64)
    ngroups = -(-n // g)
    gi = jnp.minimum(jnp.arange(ngroups + 1) * g, n)
    dstg = dst[gi]
    dspan = jnp.max(dstg[1:] - dstg[:-1])
    upto = jnp.searchsorted(dstg[:-1], dstg[:-1] + 512, side="left")
    max_p = jnp.max(upto - jnp.arange(ngroups)) + 1
    return jnp.stack([dst[-1], dspan, max_p])


def plan_combine(total: int, dspan: int, max_p: int, reject_tag: str,
                 final: bool = True):
    """Bucket the combine geometry (Bd, P, nwin) from destination stats;
    None outside the caps (with fallback accounting only when ``final`` —
    adaptive-g retries probe several group sizes before giving up)."""
    Bd = _bucket(-(-max(dspan, 1) // 4) + 1, 8)
    P = _bucket(max_p, 2)
    if Bd > 512 or P > 64:
        if final:
            return _reject(reject_tag, Bd=Bd, P=int(P))
        return None
    return (Bd, int(P), -(-total // 512))


@jax.jit
def _seg_gather_stats(src_starts, lens, dst_offs):
    """Device geometry stats for :func:`plan_from_device_stats`: ONE tiny
    stacked sync instead of pulling per-segment metadata to the host
    (g = 8).  Returns [total, Lmax, max group src span, max group dst
    span, max groups overlapping a 512B output window]."""
    g = 8
    n = lens.shape[0]
    src_starts = src_starts.astype(jnp.int64)
    lens = lens.astype(jnp.int64)
    dst_offs = dst_offs.astype(jnp.int64)
    ngroups = -(-n // g)
    gi = jnp.minimum(jnp.arange(ngroups + 1) * g, n)
    ends = src_starts + lens
    gstart = src_starts[jnp.minimum(gi[:-1], n - 1)]
    gend = ends[jnp.minimum(gi[1:] - 1, n - 1)]
    src_span = jnp.max(gend - gstart)
    dstg = dst_offs[gi]
    dspan = jnp.max(dstg[1:] - dstg[:-1])
    total = dst_offs[-1]
    # max groups overlapping any 512B output window: for each group k,
    # how many group starts fall inside [dstg[k], dstg[k] + 512)
    upto = jnp.searchsorted(dstg[:-1], dstg[:-1] + 512, side="left")
    max_p = jnp.max(upto - jnp.arange(ngroups)) + 1
    return jnp.stack([total, jnp.max(lens), src_span, dspan, max_p])


def plan_from_device_stats(stats, n: int):
    """:func:`segmented_gather` geom from the device-stats sync."""
    total, Lmax, src_span, dspan, max_p = (int(x) for x in stats)
    if n == 0 or total == 0:
        return None
    g = 8
    Lw = _bucket(-(-max(Lmax, 1) // 4) + 1, 4)
    B = _bucket(max(src_span, 64), 64)
    Bd = _bucket(-(-max(dspan, 1) // 4) + 1, 8)
    P = _bucket(max_p, 2)
    if B > (1 << 20) or Lw > 512 or Bd > 512 or P > 64:
        return _reject("seg_gather_caps_dev", B=B, Lw=Lw, Bd=Bd, P=int(P))
    nwin = -(-total // 512)
    return (n, g, B, Lw, Bd, int(P), nwin, total)


@functools.partial(jax.jit, static_argnums=0)
def segmented_gather(geom, src_u8: jnp.ndarray, src_starts: jnp.ndarray,
                     lens: jnp.ndarray, dst_offs: jnp.ndarray):
    """Pack ordered byte segments: out[dst_offs[i]:dst_offs[i]+lens[i]] =
    src[src_starts[i]:+lens[i]], fully on device — group-slab gathers and
    narrow/widening roll trees (same primitives as the to_rows engine).
    Returns u8 [total]."""
    n, g, B, Lw, Bd, P, nwin, total = geom
    src_starts = src_starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    dst_offs = dst_offs.astype(jnp.int32)
    ngroups = -(-n // g)
    v2 = _pad_to_blocks(src_u8, B)
    gidx = jnp.minimum(jnp.arange(ngroups, dtype=jnp.int32) * g, n - 1)
    gsrc0 = src_starts[gidx]
    blk = gsrc0 // B
    slab = v2[jnp.clip(blk, 0, v2.shape[0] - 1)]
    dstg = dst_offs[jnp.minimum(
        jnp.arange(ngroups + 1, dtype=jnp.int32) * g, n)]
    acc = jnp.zeros((ngroups, Bd), jnp.uint32)
    for j in range(g):
        ridx = jnp.minimum(jnp.arange(ngroups, dtype=jnp.int32) * g + j,
                           n - 1)
        live = (jnp.arange(ngroups, dtype=jnp.int32) * g + j) < n
        amt = src_starts[ridx] - blk * B
        w = _take_words(slab, amt // 4, Lw + 1)
        piece = _roll_left_bytes(w, Lw, amt % 4)
        drel = dst_offs[ridx] - dstg[:-1]
        fun = _byte_funnel_right(piece, drel % 4)
        placed = _place_words(fun, drel // 4, Bd)
        mask = _byte_mask(Bd, drel, drel + lens[ridx])
        acc = acc | jnp.where(live[:, None], placed & mask, jnp.uint32(0))

    return _group_windows_combine(acc, dstg, ngroups, Bd, P, nwin, total)


def _group_windows_combine(acc: jnp.ndarray, dstg: jnp.ndarray,
                           ngroups: int, Bd: int, P: int, nwin: int,
                           total: int) -> jnp.ndarray:
    """Window combine: group accumulators [ngroups, Bd] u32 at byte-granular
    group destinations ``dstg`` [ngroups+1] → packed u8 [total]."""
    return _words_to_u8(
        _group_windows_words(acc, dstg, ngroups, Bd, P, nwin))[:total]


def _group_windows_words(acc: jnp.ndarray, dstg: jnp.ndarray, ngroups: int,
                         Bd: int, P: int, nwin: int) -> jnp.ndarray:
    """The window combine as u32 words [nwin * WIN_W] (bytes past the last
    group's end are zero)."""
    fr = _first_row_per_window(dstg, ngroups, nwin, 512)
    fr = jnp.clip(fr, 0, ngroups - 1)
    padded = jnp.pad(acc, ((0, P), (0, 0)))
    vp = jnp.concatenate([padded[p:ngroups + p] for p in range(P)], axis=1)
    slab2 = vp[fr]
    F = WIN_W + 2 * Bd
    wbase = jnp.arange(nwin, dtype=jnp.int32) * 512
    out = jnp.zeros((nwin, F), jnp.uint32)
    for p in range(P):
        r = jnp.minimum(fr + p, ngroups - 1)
        d_b = dstg[r] - wbase + Bd * 4            # biased, ≥ 0 when live
        live = (fr + p < ngroups) & (dstg[r] < wbase + 512) & (d_b >= 0)
        piece = slab2[:, p * Bd:(p + 1) * Bd]
        fun = _byte_funnel_right(piece, d_b % 4)
        placed = _place_words(fun, d_b // 4, F)
        glen = dstg[r + 1] - dstg[r]
        mask = _byte_mask(F, d_b, d_b + glen)
        out = out | jnp.where(live[:, None], placed & mask, jnp.uint32(0))
    return out[:, Bd:Bd + WIN_W].reshape(-1)


# ---------------------------------------------------------------------------
# to_rows: whole-batch fused program
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1))
def _to_rows_x_jit(layout: RowLayout, geom, datas, str_offsets, valid):
    """geom: (n, Mw, P, nwin, total_w, g, per-col (B, Lw)) — all static.

    Everything — including the destination row offsets (the 8-byte-aligned
    cumsum the host batching derives the same way) — is computed on device:
    a warm call uploads NOTHING.
    """
    n, Mw, P, nwin, total_w, g, colgeo = geom
    var_idx = layout.variable_column_indices
    fpv = layout.fixed_plus_validity
    fpvw = -(-fpv // 4)
    str_offsets = tuple(o.astype(jnp.int32) for o in str_offsets)
    # valid: per-column bool [n] or None — the matrix builds in-trace (an
    # eager stack of 12 validity vectors costs a dispatch each)
    vmat = jnp.stack([jnp.ones((n,), jnp.bool_) if v is None else v
                      for v in valid], axis=1)

    from .convert import _var_fixed_region
    fixed2d = _var_fixed_region(layout, datas, str_offsets, vmat)
    fixed_w = _u8_to_u32_rows(
        jnp.pad(fixed2d, ((0, 0), (0, fpvw * 4 - fpv))))     # [n, fpvw]

    lens = jnp.stack(
        [str_offsets[vi][1:] - str_offsets[vi][:-1]
         for vi in range(len(var_idx))], axis=1).astype(jnp.int32)
    prefix = jnp.cumsum(lens, axis=1) - lens

    dense = jnp.pad(fixed_w, ((0, 0), (0, Mw - fpvw)))
    for vi in range(len(var_idx)):
        B, Lw = colgeo[vi]
        if Lw == 0:
            continue
        win = extract_group_windows(datas[var_idx[vi]].reshape(-1),
                                    str_offsets[vi], n, g, B, Lw)
        start_b = fpv + prefix[:, vi]
        # byte funnel at the NARROW width, then the widening word place
        a = jnp.pad(win, ((0, 0), (0, 1)))
        prev = jnp.pad(win, ((0, 0), (1, 0)))
        rb = (start_b % 4).astype(jnp.uint32)[:, None]
        fun = a
        for k in (1, 2, 3):
            v = ((a << jnp.uint32(8 * k))
                 | (prev >> jnp.uint32(32 - 8 * k)))
            fun = jnp.where(rb == k, v, fun)
        placed = _place_words(fun, start_b // 4, Mw)
        mask = _byte_mask(Mw, start_b, start_b + lens[:, vi])
        dense = dense | (placed & mask)

    # destination offsets: align8(fpv + Σ lens), cumulative — the same rule
    # as layout.row_sizes_with_strings (row_conversion.cu:216-261), in words
    row_b = fpv + prefix[:, -1] + lens[:, -1]
    rs_w = ((row_b + 7) // 8 * 8) // 4
    dst_w = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(rs_w, dtype=jnp.int32)])
    # (a pair-compaction level before the pack was tried and REJECTED: the
    # strided row split d[0::2]/d[1::2] alone cost more than the whole
    # frame-combine saving it buys)
    return pack_windows(dense, dst_w, total_w, P, nwin)


def _plan_geometry(layout: RowLayout, n: int, offs_np: np.ndarray,
                   col_offs_np: list[np.ndarray]):
    """Host geometry pass → static ``geom`` tuple (bucketed), or None when
    outside the supported buckets."""
    total = int(offs_np[-1])
    row_sizes = offs_np[1:] - offs_np[:-1]
    Mw = _bucket(-(-int(row_sizes.max()) // 4), 8)
    if Mw > 256:                                  # > 1KB rows: fall back
        return _reject("to_rows_row_width", Mw=Mw)
    nwin = -(-(total // 4) // WIN_W)
    # max rows overlapping one output window
    fr = np.searchsorted(offs_np, np.arange(nwin, dtype=np.int64) * 512,
                         side="right") - 1
    lr = np.searchsorted(offs_np,
                         np.minimum(np.arange(nwin, dtype=np.int64) * 512
                                    + 512, total) - 1, side="right") - 1
    P = _bucket(int((lr - fr).max(initial=0)) + 1, 2)
    g = 8
    colgeo = []
    for vi in range(len(layout.variable_column_indices)):
        co = col_offs_np[vi]
        clens = co[1:] - co[:-1]
        Lmax = int(clens.max(initial=0))
        if Lmax == 0:
            colgeo.append((0, 0))
            continue
        idx = np.minimum(np.arange(0, n + g, g), n)
        span = int((co[idx[1:]] - co[idx[:-1]]).max(initial=1))
        B = _bucket(max(span, 64), 64)
        Lw = _bucket(-(-Lmax // 4), 4)
        if B > (1 << 20) or Lw > 512:
            return _reject("to_rows_col_caps", col=vi, B=B, Lw=Lw)
        colgeo.append((B, Lw))
    return (n, Mw, int(P), nwin, total // 4, g, tuple(colgeo))


def to_rows_var_x(layout: RowLayout, sub, offs_np: np.ndarray,
                  col_offs_np: list[np.ndarray]):
    """Strings → packed JCUDF rows, one jitted dispatch.

    ``offs_np``: host row offsets [n+1] (8-byte-aligned rows).
    ``col_offs_np``: host char offsets per var column (geometry buckets).
    Returns ``(u32 words [total/4], device row offsets or None)``, or None
    when the geometry exceeds the supported buckets (caller falls back).
    A layout whose rows are never narrower than a pack window goes through
    the program in row tiles (``xtile``), which also hands back the row
    offsets it computed on the device.

    The host geometry pass is memoized on the string-offset device arrays
    (the analytics steady state re-converts the same tables), so a warm
    call is pure dispatch: no host scans, no device uploads.
    """
    from . import xtile
    from ..utils import metrics, syncs
    n = sub.num_rows
    var_idx = layout.variable_column_indices
    if n == 0 or int(offs_np[-1]) == 0:
        return None
    tiled = xtile.serves(layout)
    key_arrays = [sub[ci].offsets for ci in var_idx]
    # the geometry depends on the LAYOUT too (fpv feeds the row sizes), so
    # the memo tag carries it — the same string column objects reused under
    # a different schema must not hit a stale geometry
    tag = f"xpack_geom:{hash(layout)}"
    with metrics.span("rowconv.var.plan", direction="to"):
        geom = syncs.memo_get(tag, key_arrays)
        hit = geom is not None
        if not hit:
            geom = (xtile.plan_to_rows if tiled else _plan_geometry)(
                layout, n, offs_np, col_offs_np)
            if geom is not None:
                syncs.memo_put(tag, key_arrays, geom)
        metrics.annotate(memo_hit=int(hit),
                         Mw=geom[1] if geom else 0,
                         tiles=-(-n // geom[2]) if geom and tiled else 1)
    if geom is None:
        return None
    args = (tuple(c.data for c in sub.columns),
            tuple(sub[ci].offsets for ci in var_idx),
            tuple(c.validity for c in sub.columns))
    with metrics.span("rowconv.var.launch", direction="to"):
        if tiled:
            return xtile.to_rows_jit(layout, geom, *args)
        return _to_rows_x_jit(layout, geom, *args), None


# ---------------------------------------------------------------------------
# from_rows: whole-batch fused program (the inverse engine, round 5)
# ---------------------------------------------------------------------------
#
# Inverse of ``to_rows_var_x`` — the same job as the reference's
# ``copy_strings_from_rows`` + chars scan + make_strings_column
# (row_conversion.cu:1131-1174, 2201-2246): packed JCUDF rows → fixed
# column payloads + validity + per-column chars streams, all on device.
# Rows are ordered byte segments, so the to_rows primitives invert:
# row-slab gathers pull per-row word windows (rows are 8-byte aligned →
# word-granular, no byte funnel), the shared word decoder extracts fixed
# slots/validity/(offset,len) string slots, and each string column's chars
# are cut from the dense rows with a narrowing roll tree and re-packed at
# in-trace-cumsum destinations with the same group-accumulate + window
# combine as ``segmented_gather``.  ONE stacked scalar sync resolves the
# per-column char totals (the reference syncs on the same scanned totals,
# row_conversion.cu:2215); it is memoized on the batch arrays, so the
# analytics steady state is pure dispatch.


def _extract_row_windows(words: jnp.ndarray, offs: jnp.ndarray,
                         n: int, g: int, Bw: int, Mw: int) -> jnp.ndarray:
    """Per-row word windows [n, Mw] u32 from the flat row-word stream.

    One slab gather per GROUP of ``g`` rows (the group's rows span ≤ Bw
    words — caller sizes Bw from the host row offsets), then ``g`` fused
    word-shift takes pull each row's window out of its group slab.  Bytes
    beyond a row's true size are unspecified (callers mask by length).
    """
    ngroups = -(-n // g)
    T = words.shape[0]
    nb = max(-(-T // Bw), 1)
    w2 = jnp.pad(words, (0, nb * Bw - T)).reshape(nb, Bw)
    nxt = jnp.concatenate([w2[1:], jnp.zeros((1, Bw), jnp.uint32)])
    v2 = jnp.concatenate([w2, nxt], axis=1)           # [nb, 2Bw]
    offs_w = (offs // 4).astype(jnp.int32)
    gidx = jnp.minimum(jnp.arange(ngroups, dtype=jnp.int32) * g, n - 1)
    gstart = offs_w[gidx]
    blk = gstart // Bw
    slab = v2[jnp.clip(blk, 0, nb - 1)]               # [ngroups, 2Bw]
    outs = []
    for j in range(g):
        ridx = jnp.minimum(jnp.arange(ngroups, dtype=jnp.int32) * g + j,
                           n - 1)
        amt = offs_w[ridx] - blk * Bw
        outs.append(_take_words(slab, amt, Mw))
    out = jnp.stack(outs, axis=1).reshape(ngroups * g, Mw)
    return out[:n]


def _combine_to_stream(piece: jnp.ndarray, lens: jnp.ndarray,
                       dst_offs: jnp.ndarray, n: int, g: int, Bd: int,
                       P: int, nwin: int, total: int) -> jnp.ndarray:
    """Per-row byte pieces [n, Lw] u32 (payload starts at byte 0, ``lens``
    bytes live) → packed u8 [total] at byte destinations ``dst_offs``.
    Group-accumulate then window-combine — the segment-packing half of
    ``segmented_gather`` with the pieces already in hand."""
    return _words_to_u8(_combine_to_words(piece, lens, dst_offs, n, g, Bd,
                                          P, nwin))[:total]


def _combine_to_words(piece: jnp.ndarray, lens: jnp.ndarray,
                      dst_offs: jnp.ndarray, n: int, g: int, Bd: int,
                      P: int, nwin: int) -> jnp.ndarray:
    """:func:`_combine_to_stream` as u32 words [nwin * WIN_W]."""
    ngroups = -(-n // g)
    pad = ngroups * g - n
    piece3 = jnp.pad(piece, ((0, pad), (0, 0))).reshape(
        ngroups, g, piece.shape[1])
    lens2 = jnp.pad(lens, (0, pad)).reshape(ngroups, g)
    dstp = jnp.pad(dst_offs[:-1], (0, pad)).reshape(ngroups, g)
    gi = jnp.minimum(jnp.arange(ngroups + 1, dtype=jnp.int32) * g, n)
    dstg = dst_offs[gi]
    live_base = jnp.arange(ngroups, dtype=jnp.int32) * g
    acc = jnp.zeros((ngroups, Bd), jnp.uint32)
    for j in range(g):
        live = (live_base + j) < n
        drel = dstp[:, j] - dstg[:-1]
        fun = _byte_funnel_right(piece3[:, j], drel % 4)
        placed = _place_words(fun, drel // 4, Bd)
        mask = _byte_mask(Bd, drel, drel + lens2[:, j])
        acc = acc | jnp.where(live[:, None], placed & mask, jnp.uint32(0))
    return _group_windows_words(acc, dstg, ngroups, Bd, P, nwin)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _from_rows_x_stats(layout: RowLayout, geom_a, words, offs):
    """Device geometry stats for the inverse engine: [nvar, 5] int64 rows
    of [char total, slot-violation count, Lmax, max group dst span, max
    groups per 512B window] — resolved with ONE stacked host sync.  XLA
    dead-code-eliminates the fixed-column decode this shares with the main
    program."""
    from .convert import _decode_row_words
    n, Mw, g, Bw = geom_a
    dense = _extract_row_windows(words, offs, n, g, Bw, Mw)
    _, _, slots = _decode_row_words(layout, lambda w: dense[:, w], n)
    fpv = layout.fixed_plus_validity
    row_sizes = (offs[1:] - offs[:-1]).astype(jnp.int64)
    ngroups = -(-n // g)
    gi = jnp.minimum(jnp.arange(ngroups + 1) * g, n)
    rows = []
    for s in slots:
        off = s[:, 0].astype(jnp.int64)
        ln = s[:, 1].astype(jnp.int64)
        viol = jnp.sum(((off < fpv) | (off + ln > row_sizes))
                       .astype(jnp.int64))
        dst = jnp.concatenate([jnp.zeros(1, jnp.int64), jnp.cumsum(ln)])
        dstg = dst[gi]
        dspan = jnp.max(dstg[1:] - dstg[:-1])
        upto = jnp.searchsorted(dstg[:-1], dstg[:-1] + 512, side="left")
        max_p = jnp.max(upto - jnp.arange(ngroups)) + 1
        rows.append(jnp.stack([dst[-1], viol, jnp.max(ln), dspan, max_p]))
    return jnp.stack(rows)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _from_rows_x_jit(layout: RowLayout, geom, words, offs):
    """geom: (n, Mw, g, Bw, colgeo) with per-column (Lw, Bd, P, nwin,
    total) — all static.  Returns (datas — None at var columns, valid
    [n, ncols] bool, chars u8 tuple, out_offsets int32 [n+1] tuple), one
    dispatch, zero internal syncs."""
    from .convert import _decode_row_words
    n, Mw, g, Bw, colgeo = geom
    dense = _extract_row_windows(words, offs, n, g, Bw, Mw)
    datas, valid, slots = _decode_row_words(layout, lambda w: dense[:, w], n)
    chars = []
    out_offs = []
    for vi, s in enumerate(slots):
        Lw, Bd, P, nwin, total = colgeo[vi]
        lens = s[:, 1].astype(jnp.int32)
        dst = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(lens)])
        out_offs.append(dst)
        if total == 0:
            chars.append(jnp.zeros((0,), jnp.uint8))
            continue
        off_b = s[:, 0].astype(jnp.int32)
        w = _take_words(dense, off_b // 4, Lw + 1)
        piece = _roll_left_bytes(w, Lw, off_b % 4)
        chars.append(_combine_to_stream(piece, lens, dst, n, g, Bd, P,
                                        nwin, total))
    return datas, valid, tuple(chars), tuple(out_offs)


def _plan_from_rows_a(n: int, offs_np: np.ndarray, g: int = 8):
    """Row-extraction geometry (n, Mw, g, Bw) from the host row offsets
    alone — needed before the stats program can run.  None (with fallback
    accounting) outside the buckets.

    ``g`` (rows per slab-gather group) adapts to the geometry: short rows
    with tiny char spans need LARGE groups, or ~``512/span`` groups
    overlap each 512B output window and the combine's P-unrolled loop
    blows its cap (the mostly-empty-strings shape)."""
    row_sizes = offs_np[1:] - offs_np[:-1]
    Mw = _bucket(-(-int(row_sizes.max(initial=8)) // 4), 8)
    if Mw > 256:                                  # > 1KB rows
        return _reject("from_rows_row_width", Mw=Mw)
    idx = np.minimum(np.arange(0, n + g, g), n)
    span_w = int(((offs_np[idx[1:]] - offs_np[idx[:-1]]) // 4).max(initial=16))
    Bw = _bucket(max(span_w, 16), 16)
    if Bw * 4 > (1 << 20):
        return _reject("from_rows_slab", Bw=Bw)
    return (n, Mw, g, Bw)


def _plan_from_rows_cols(stats: np.ndarray, final: bool = True):
    """Per-column packing geometry from the device stats sync, or None."""
    colgeo = []
    for vi in range(stats.shape[0]):
        total, _viol, lmax, dspan, max_p = (int(x) for x in stats[vi])
        if total == 0:
            colgeo.append((0, 0, 0, 0, 0))
            continue
        # g-invariant caps reject immediately (retrying with a larger
        # group size cannot change the total or the entry length)
        if total >= (1 << 31):
            return _reject("from_rows_total", col=vi, total=total)
        Lw = _bucket(-(-max(lmax, 1) // 4) + 1, 4)
        if Lw > 512:
            return _reject("from_rows_col_caps", col=vi, Lw=Lw)
        combine = plan_combine(total, dspan, max_p, "from_rows_col_caps",
                               final)
        if combine is None:
            return None
        Bd, P, nwin = combine
        colgeo.append((Lw, Bd, P, nwin, total))
    return tuple(colgeo)


def batch_words(batch) -> jnp.ndarray:
    """The batch's JCUDF stream as u32 words (converts a u8 batch)."""
    from .convert import _bytes_to_words
    return (batch.data if batch.data.dtype == jnp.uint32
            else _bytes_to_words(batch.data))


def plan_from_rows(layout: RowLayout, batch, words: jnp.ndarray):
    """Full static geometry for the inverse engine, or None outside the
    buckets (with fallback accounting).

    Costs ONE stacked scalar sync (char totals + slot-bounds violations +
    packing spans, device-reduced) on a memo miss; memoized on the batch
    arrays, so the analytics steady state re-plans nothing.  Raises
    ``ValueError`` on corrupt embedded slots, same hardening as the host
    engine (rows may be shuffle-received).
    """
    from ..utils import hostcache, syncs
    n = batch.num_rows
    if n == 0:
        return None
    offs_np = hostcache.host_i64(batch.offsets)
    if int(offs_np[-1]) == 0 or int(offs_np[-1]) % 4:
        return None
    tag = f"xunpack_geom:{hash(layout)}"
    geom = syncs.memo_get(tag, [batch.data, batch.offsets])
    if geom is None:
        gs = (8, 32, 128)
        for trial, g in enumerate(gs):
            geom_a = _plan_from_rows_a(n, offs_np, g)
            if geom_a is None:
                break                      # Bw only grows with g: give up
            stats = np.asarray(_from_rows_x_stats(
                layout, geom_a, words, batch.offsets))   # one sync per try
            if trial == 0 and stats[:, 1].any():
                raise ValueError(
                    "corrupt row data: string slot outside its row")
            colgeo = _plan_from_rows_cols(stats, final=(g == gs[-1]))
            if colgeo is not None:
                geom = geom_a + (colgeo,)
                break
            if any(int(r[0]) >= (1 << 31)
                   or _bucket(-(-max(int(r[2]), 1) // 4) + 1, 4) > 512
                   for r in stats):
                break          # g-invariant rejection: retries cannot help
        # rejections memoize too (as "reject"): a repeat conversion of an
        # out-of-cap batch must not re-run the stats program + sync, nor
        # re-increment the fallback counters, on every call
        syncs.memo_put(tag, [batch.data, batch.offsets],
                       geom if geom is not None else "reject")
    return None if geom == "reject" else geom


def from_rows_var_x(layout: RowLayout, batch):
    """Packed JCUDF rows → (datas, valid, chars, out_offsets), one fused
    program; None (caller falls back) outside the geometry buckets.
    ``valid`` is the bool matrix [n, ncols], or a vector per column from
    the tiled programs (``xtile``: two programs around the totals sync)."""
    from . import xtile
    from ..utils import metrics
    words = batch_words(batch)
    if xtile.serves(layout):
        return _from_rows_tiled(layout, batch, words)
    with metrics.span("rowconv.var.plan", direction="from"):
        geom = plan_from_rows(layout, batch, words)
        metrics.annotate(Mw=geom[1] if geom else 0, tiles=1)
    if geom is None:
        return None
    with metrics.span("rowconv.var.launch", direction="from"):
        return _from_rows_x_jit(layout, geom, words, batch.offsets)


def _from_rows_tiled(layout: RowLayout, batch, words: jnp.ndarray):
    from . import xtile
    from ..utils import hostcache, metrics, syncs
    n = batch.num_rows
    if n == 0:
        return None
    offs_np = hostcache.host_i64(batch.offsets)
    if int(offs_np[-1]) == 0 or int(offs_np[-1]) % 4:
        return None
    tag = f"xunpack_geom:{hash(layout)}"
    keys = [batch.data, batch.offsets]
    with metrics.span("rowconv.var.plan", direction="from"):
        memo = syncs.memo_get(tag, keys)
        if memo == "reject":
            geom_a = None
        else:
            geom_a = (memo[0] if memo else
                      xtile.plan_from_rows_fixed(layout, n, offs_np))
        metrics.annotate(memo_hit=int(memo is not None),
                         Mw=geom_a[1] if geom_a else 0,
                         tiles=-(-n // geom_a[2]) if geom_a else 1)
    if geom_a is None:
        return None
    with metrics.span("rowconv.var.launch", direction="from"):
        datas, valid, slots, out_offs, stats = xtile.from_rows_fixed_jit(
            layout, geom_a, words, batch.offsets)
    if memo is None:
        # the one sync: char totals shape the output; slot violations and
        # the stream geometry ride along (row_conversion.cu:2215 syncs on
        # the same scanned totals)
        with metrics.span("rowconv.var.totals_sync", bytes=stats.nbytes):
            syncs.note_sync()
            stats_np = np.asarray(stats)  # srjt-lint: disable=trace-host-sync
        if stats_np[:, 1].any():
            raise ValueError("corrupt row data: string slot outside its row")
        with metrics.span("rowconv.var.plan", direction="from"):
            geom_b = xtile.plan_from_rows_chars(layout, geom_a, stats_np)
            metrics.annotate(memo_hit=0, Mw=geom_a[1],
                             tiles=-(-n // geom_a[2]))
        # rejections memoize too: a repeat conversion of an out-of-cap
        # batch must not re-count its fallback on every call
        syncs.memo_put(tag, keys,
                       (geom_a, geom_b) if geom_b is not None else "reject")
    else:
        geom_b = memo[1]
    if geom_b is None:
        return None
    live, totals = geom_b[-2:]
    chars = [jnp.zeros((0,), jnp.uint8)] * len(totals)
    if live:
        with metrics.span("rowconv.var.launch", direction="from"):
            for vi, c in zip(live, xtile.from_rows_chars_jit(
                    layout, geom_b, words, batch.offsets, slots, out_offs)):
                chars[vi] = c
    return datas, valid, tuple(chars), out_offs
