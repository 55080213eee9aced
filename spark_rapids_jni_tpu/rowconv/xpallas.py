"""Knob-gated Pallas TPU kernels for the byte-path hot loops.

The round-4 string engine (``xpack``) is pure XLA: the placement rolls are
select trees the compiler fuses well, but every window still round-trips
through HBM between the gather and the roll, and the per-window slab gather
re-reads up to ``P×`` the payload.  These kernels are the Mosaic versions
of the same inner loops, built on the DMA/roll idioms validated on chip by
``rowconv.ragged`` (PALLAS_TPU_CHECK.json): aligned window DMAs into VMEM,
``_byte_roll`` + ``_byte_keep_mask`` placement, scalar-prefetch block
metadata, lru-cached ``pallas_call`` builders (a fresh closure per call
would Mosaic-recompile every call).

Dispatch discipline — each kernel sits behind its own knob and NEVER
becomes the only path:

  SRJT_PALLAS_PACKWIN      pack_windows   (JCUDF var-width row packing)
  SRJT_PALLAS_EXTRACT      extract_rows   (flat bytes → padded row matrix)
  SRJT_PALLAS_DICT_GATHER  gather_rows    (dictionary row gather by code)
  SRJT_PALLAS_TRANSPOSE    u8_to_u32      (byte → word transcode)

Knob values: ``0`` (default) = off, ``1``/``on`` = kernel on real TPU
backends only, ``interpret`` = Pallas interpreter mode on any backend —
the CI parity mode (CPU runs the same kernel logic; no speed claim).
Every ``try_*`` entry point returns ``None`` when the kernel is off or
the geometry falls outside its envelope, and the caller keeps its lax/XLA
formulation as the fallback — counted in ``rowconv.pallas.fallbacks``
against ``rowconv.pallas.hits`` so a run can say which path it measured.

Caveat (same as ragged.py): Mosaic compile errors from an UNVALIDATED
geometry on chip surface inside the outer jit and are not catchable here;
that is why every knob defaults off and the envelope checks reject early
(ValueError → fallback) for everything the plan can see.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import flight, knobs, metrics
from .ragged import (LANE, _byte_keep_mask, _byte_roll, _pow2_bucket,
                     _round_up, _soft_bucket, u8_to_u32, u32_to_u8)

# NOTE on x64: unlike ragged's eager entry points (which flip
# ``enable_x64`` off around their pallas_call), these dispatchers run
# INSIDE outer jit traces (the fused file decode) where toggling the x64
# context mid-trace breaks lowering.  Every array and kernel constant
# here is dtype-explicit instead — nothing weak-typed reaches Mosaic.

_VMEM_CAP = 1 << 21           # per-buffer VMEM budget (same as ragged)

# hit/fallback tallies survive metrics being off: the flight recorder
# samples them into incident snapshots (and ops_report reads the probe)
_counts = {"hits": 0, "fallbacks": 0}
flight.register_probe("rowconv.pallas", lambda: dict(_counts))


def mode(knob: str) -> str:
    """Resolve a Pallas knob: ``off`` | ``on`` | ``interpret``.

    ``1``/``on`` asks for the real kernel and resolves to ``off`` (with a
    fallback tally) on non-TPU backends — requesting Mosaic on CPU is a
    misconfiguration, not a crash."""
    raw = str(knobs.get(knob) or "0").strip().lower()
    if raw in ("interpret", "interp"):
        return "interpret"
    if raw in ("1", "on", "true", "force"):
        # knob resolution is host-side planning, never inside a trace
        if jax.default_backend() == "tpu":  # srjt-lint: disable=trace-branch
            return "on"
        _tally(False)
        return "off"
    return "off"


def _tally(hit: bool) -> None:
    key = "hits" if hit else "fallbacks"
    _counts[key] += 1
    if metrics.recording():
        metrics.count(f"rowconv.pallas.{key}")


# ---------------------------------------------------------------------------
# pack_windows: padded rows [n, Mw] u32 + device dst offsets → flat words
#
# Same job as xpack.pack_windows (output-window-centric OR-accumulate), but
# the P-row shifted-view slab — which re-reads the dense matrix P times
# through HBM — becomes ONE VMEM row-window DMA per 4 KiB output block, and
# the place/mask select trees become in-register byte rolls.  Unlike
# ragged.pack_rows the row offsets are DEVICE values (they come out of the
# fused to_rows cumsum), so the per-block row ranges are computed on device
# and ride in as scalar-prefetch operands.
# ---------------------------------------------------------------------------

_B_PACK = 4096                # output block: 8 × 512 B windows
_SB_PACK = _B_PACK // 4 // LANE


def _first_row_per_boundary(dst_b: jnp.ndarray, n: int, nb: int,
                            win: int) -> jnp.ndarray:
    """fr[k] = last row r with dst_b[r] ≤ k·win, k ∈ [0, nb) — the device
    twin of xpack._first_row_per_window (segment-sum, no searchsorted)."""
    win_of = (dst_b[:n] // jnp.int32(win)).astype(jnp.int32)
    h = jax.ops.segment_sum(jnp.ones(n, jnp.int32), win_of, nb)
    lt = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(h)[:-1]])
    eq = jax.ops.segment_sum(
        ((dst_b[:n] % jnp.int32(win)) == 0).astype(jnp.int32), win_of, nb)
    return lt + eq - 1


def try_pack_windows(dense: jnp.ndarray, dst_w: jnp.ndarray, total_w: int,
                     P: int, nwin: int):
    """Pallas pack_windows, or None (knob off / geometry outside the
    envelope).  ``dense`` u32 [n, Mw] zero-padded rows, ``dst_w`` i32
    [n+1] device word offsets; returns u32 [total_w]."""
    m = mode("SRJT_PALLAS_PACKWIN")
    if m == "off":
        return None
    n, Mw = dense.shape
    if n == 0 or total_w == 0:
        return None
    # rows overlapping one 4 KiB block: ≤ P per 512 B window (the plan's
    # exact bound) × 8 windows, +8 for the sublane-aligned window start
    NR = _pow2_bucket(8 * P + 8, 8)
    MwS = -(-Mw // LANE)
    if MwS > _SB_PACK or NR * MwS * LANE * 4 > _VMEM_CAP:
        _tally(False)
        return None
    try:
        out = _pack_windows_pallas(dense, dst_w, total_w, NR, m == "interpret")
    except Exception:
        if m != "interpret":
            raise
        _tally(False)               # interpreter gap — degrade, count it
        return None
    _tally(True)
    return out


def _pack_windows_pallas(dense, dst_w, total_w, NR, interpret):
    n, Mw = dense.shape
    MwS = -(-Mw // LANE)
    nb = -(-total_w * 4 // _B_PACK)
    dst_b = (dst_w.astype(jnp.int32) * jnp.int32(4))

    frs = _first_row_per_boundary(dst_b, n, nb + 1, _B_PACK)
    rb = jnp.clip(frs[:nb], 0, n - 1)
    nr = jnp.clip(frs[1:] - rb + 1, 0, NR - 8)
    row0 = (rb // 8) * 8

    nblocks = _soft_bucket(nb, 1)
    rb = jnp.pad(rb, (0, nblocks - nb))
    nr = jnp.pad(nr, (0, nblocks - nb))
    row0 = jnp.pad(row0, (0, nblocks - nb))

    KOFF = _pow2_bucket(NR // LANE + 2, 2)
    n_pad = _soft_bucket(_round_up(n, 8) + NR)
    dense32 = jnp.pad(dense, ((0, n_pad - n), (0, MwS * LANE - Mw))
                      ).reshape(n_pad, MwS, LANE)
    offs_rows = _soft_bucket(-(-(n_pad + 1) // LANE) + KOFF + 1)
    offs2d = jnp.pad(dst_b, (0, offs_rows * LANE - (n + 1)),
                     mode="edge").reshape(offs_rows, LANE)

    out = _packwin_call(nblocks, MwS, NR, KOFF, interpret)(
        row0, rb, nr, offs2d, dense32)
    return out.reshape(-1)[:total_w]


@functools.lru_cache(maxsize=256)
def _packwin_call(nblocks, MwS, NR, KOFF, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    SB = _SB_PACK

    def kernel(r0_ref, rb_ref, nr_ref, offs_hbm, dense_hbm, out_ref,
               scratch, soffs, sems):
        b = pl.program_id(0)
        row0 = r0_ref[b]
        dma = pltpu.make_async_copy(dense_hbm.at[pl.ds(row0, NR)], scratch,
                                    sems.at[0])
        dma.start()
        orow0 = row0 // LANE
        for k in range(KOFF):
            pltpu.make_async_copy(offs_hbm.at[orow0 + k], soffs.at[k],
                                  sems.at[1 + k]).start()
        dma.wait()
        for k in range(KOFF):
            pltpu.make_async_copy(offs_hbm.at[orow0 + k], soffs.at[k],
                                  sems.at[1 + k]).wait()

        blk_start = b * _B_PACK
        pos4 = ((jax.lax.broadcasted_iota(jnp.int32, (SB, LANE), 0) * LANE
                 + jax.lax.broadcasted_iota(jnp.int32, (SB, LANE), 1)) * 4)

        def body(i, acc):
            r = rb_ref[b] + i
            lr = r - row0
            o_lo = soffs[(r // LANE) - orow0, r % LANE]
            o_hi = soffs[((r + 1) // LANE) - orow0, (r + 1) % LANE]
            rowvec = scratch[lr]                  # [MwS, LANE] u32
            ext = jnp.concatenate(
                [rowvec, jnp.zeros((SB - MwS, LANE), jnp.uint32)], axis=0) \
                if SB > MwS else rowvec[:SB]
            p = o_lo - blk_start                  # byte position, may be < 0
            rolled = _byte_roll(ext, p)
            keep = _byte_keep_mask(pos4, p, p + (o_hi - o_lo))
            return acc | (rolled & keep)

        acc = jax.lax.fori_loop(0, nr_ref[b], body,
                                jnp.zeros((SB, LANE), jnp.uint32))
        out_ref[...] = acc[None]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, SB, LANE), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((NR, MwS, LANE), jnp.uint32),
                        pltpu.SMEM((KOFF, LANE), jnp.int32),
                        pltpu.SemaphoreType.DMA((1 + KOFF,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((nblocks, SB, LANE), jnp.uint32),
        compiler_params=pltpu.CompilerParams(has_side_effects=True))


# ---------------------------------------------------------------------------
# extract_rows: flat bytes + HOST offsets → zero-padded row matrix [n, M]
#
# ragged.unpack_rows with an interpreter switch — used where the row
# geometry is host-resident (dictionary pages, row-group string payloads)
# to build the padded matrices the gather paths index into.
# ---------------------------------------------------------------------------

def try_extract_rows(flat: jnp.ndarray, row_offsets: np.ndarray, M: int):
    """Pallas row extraction, or None.  ``flat`` u8 device, ``row_offsets``
    HOST [n+1]; returns u8 [n, M], row r zero-padded past its length."""
    m = mode("SRJT_PALLAS_EXTRACT")
    if m == "off":
        return None
    offs = np.asarray(row_offsets, dtype=np.int64)
    n = offs.shape[0] - 1
    if n == 0 or int(offs[-1]) == 0:
        return None
    try:
        out = _extract_rows_impl(flat, offs, M, m == "interpret")
    except ValueError:              # span outside the VMEM envelope
        _tally(False)
        return None
    except Exception:
        if m != "interpret":
            raise
        _tally(False)
        return None
    _tally(True)
    return out


def _extract_rows_impl(flat, offs, M, interpret):
    RB = 8
    n = offs.shape[0] - 1
    total = int(offs[-1])
    Mp = max(512, _round_up(M, 512))
    MwS = Mp // 4 // LANE
    nblocks = _soft_bucket(-(-n // RB), 1)
    n_pad = nblocks * RB
    KOFF = _pow2_bucket(RB // LANE + 2, 2)

    offs_pad = np.pad(offs, (0, n_pad + 1 - offs.shape[0]), mode="edge")
    start_word_row = ((offs_pad[np.arange(nblocks) * RB] // 4) // LANE
                      ).astype(np.int32)
    spans = (offs_pad[np.minimum(np.arange(1, nblocks + 1) * RB, n_pad)]
             - start_word_row.astype(np.int64) * (LANE * 4))
    KS = _pow2_bucket(int(spans.max(initial=1)) // (LANE * 4) + 2, 8)
    KS = max(KS, _round_up(MwS, 8))
    if KS * LANE * 4 > _VMEM_CAP:
        raise ValueError("extract_rows: row span exceeds VMEM budget")
    flat_rows = _soft_bucket(-(-total // (LANE * 4)) + KS)
    flat_pad = jnp.pad(flat, (0, flat_rows * LANE * 4 - total))
    flat32 = u8_to_u32(flat_pad).reshape(flat_rows, LANE)

    offs32 = offs_pad.astype(np.int32)
    offs_rows = _soft_bucket(-(-(n_pad + 1) // LANE) + KOFF + 1)
    offs2d = jnp.asarray(
        np.pad(offs32, (0, offs_rows * LANE - offs32.shape[0]))
        .reshape(offs_rows, LANE))

    out = _extract_call(nblocks, RB, MwS, KS, KOFF, interpret)(
        jnp.asarray(start_word_row), offs2d, flat32)
    dense = u32_to_u8(out.reshape(-1)).reshape(n_pad, Mp)
    return dense[:n, :M]


@functools.lru_cache(maxsize=256)
def _extract_call(nblocks, RB, MwS, KS, KOFF, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(sw_ref, offs_hbm, flat_hbm, out_ref, win, soffs, sems):
        b = pl.program_id(0)
        dma = pltpu.make_async_copy(flat_hbm.at[pl.ds(sw_ref[b], KS)], win,
                                    sems.at[0])
        dma.start()
        orow0 = (b * RB) // LANE
        for k in range(KOFF):
            pltpu.make_async_copy(offs_hbm.at[orow0 + k], soffs.at[k],
                                  sems.at[1 + k]).start()
        dma.wait()
        for k in range(KOFF):
            pltpu.make_async_copy(offs_hbm.at[orow0 + k], soffs.at[k],
                                  sems.at[1 + k]).wait()
        w = win[...]
        pos4 = ((jax.lax.broadcasted_iota(jnp.int32, (MwS, LANE), 0) * LANE
                 + jax.lax.broadcasted_iota(jnp.int32, (MwS, LANE), 1)) * 4)
        base_b = sw_ref[b] * LANE * 4
        for lr in range(RB):
            r = b * RB + lr
            o_lo = soffs[(r // LANE) - orow0, r % LANE]
            o_hi = soffs[((r + 1) // LANE) - orow0, (r + 1) % LANE]
            q = o_lo - base_b
            rolled = _byte_roll(w, -q)[:MwS]
            keep = _byte_keep_mask(pos4, 0, o_hi - o_lo)
            out_ref[0, lr] = rolled & keep

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, RB, MwS, LANE), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((KS, LANE), jnp.uint32),
                        pltpu.SMEM((KOFF, LANE), jnp.int32),
                        pltpu.SemaphoreType.DMA((1 + KOFF,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((nblocks, RB, MwS, LANE), jnp.uint32),
        compiler_params=pltpu.CompilerParams(has_side_effects=True))


# ---------------------------------------------------------------------------
# gather_rows: padded row matrix [D, W] u32 + codes [n] → [n, W]
#
# XLA lowers `mat[idx]` to a row gather that costs by the row; on wide
# dictionaries the DMA engine can instead stream each selected row
# HBM→VMEM directly.  One block gathers 8 rows with 8 in-flight row DMAs
# (the rate at which DMAs issue bounds it, so it can only win on wide rows;
# never timed from a caller's side: ROADMAP S8).
# ---------------------------------------------------------------------------

def try_gather_rows(mat: jnp.ndarray, idx: jnp.ndarray):
    """Pallas dictionary row gather, or None.  ``mat`` u32 [D, W] (device),
    ``idx`` i32 [n] with values in [0, D); returns u32 [n, W]."""
    m = mode("SRJT_PALLAS_DICT_GATHER")
    if m == "off":
        return None
    D, W = mat.shape
    n = int(idx.shape[0])
    if D == 0 or n == 0:
        return None
    RB = 8
    MwS = -(-W // LANE)
    if RB * MwS * LANE * 4 > _VMEM_CAP:
        _tally(False)
        return None
    try:
        out = _gather_rows_impl(mat, idx, RB, MwS, m == "interpret")
    except Exception:
        if m != "interpret":
            raise
        _tally(False)
        return None
    _tally(True)
    return out[:n, :W]


def _gather_rows_impl(mat, idx, RB, MwS, interpret):
    D, W = mat.shape
    n = int(idx.shape[0])
    n_pad = _round_up(_soft_bucket(max(n, 1), LANE), LANE)
    nblocks = n_pad // RB
    mat3 = jnp.pad(mat, ((0, 0), (0, MwS * LANE - W))).reshape(D, MwS, LANE)
    idx2d = jnp.pad(idx.astype(jnp.int32), (0, n_pad - n)
                    ).reshape(n_pad // LANE, LANE)
    out = _gather_call(nblocks, RB, MwS, interpret)(idx2d, mat3)
    return out.reshape(n_pad, MwS * LANE)


@functools.lru_cache(maxsize=256)
def _gather_call(nblocks, RB, MwS, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(idx_hbm, mat_hbm, out_ref, scratch, sidx, sems):
        b = pl.program_id(0)
        irow = (b * RB) // LANE          # RB | LANE: one idx row per block
        pltpu.make_async_copy(idx_hbm.at[irow], sidx.at[0],
                              sems.at[RB]).start()
        pltpu.make_async_copy(idx_hbm.at[irow], sidx.at[0],
                              sems.at[RB]).wait()
        for j in range(RB):
            r = b * RB + j
            src = sidx[0, r % LANE]
            pltpu.make_async_copy(mat_hbm.at[src], scratch.at[j],
                                  sems.at[j]).start()
        for j in range(RB):
            r = b * RB + j
            src = sidx[0, r % LANE]
            pltpu.make_async_copy(mat_hbm.at[src], scratch.at[j],
                                  sems.at[j]).wait()
        out_ref[...] = scratch[...][None]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, RB, MwS, LANE), lambda b: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((RB, MwS, LANE), jnp.uint32),
                        pltpu.SMEM((1, LANE), jnp.int32),
                        pltpu.SemaphoreType.DMA((RB + 1,))])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((nblocks, RB, MwS, LANE), jnp.uint32),
        compiler_params=pltpu.CompilerParams(has_side_effects=True))


# ---------------------------------------------------------------------------
# u8 → u32 transcode: the scan's byte→word transpose, blocked through VMEM
#
# Semantically identical to ragged.u8_to_u32 (strided little-endian
# combine); the Pallas version pins the working set to one VMEM block so
# the transcode streams instead of materializing the four strided
# intermediates in HBM.
# ---------------------------------------------------------------------------

_TR_ROWS = 32                 # u8 block: 32 sublanes × 512 lanes = 16 KiB


def try_u8_to_u32(flat: jnp.ndarray):
    """Pallas byte→word transcode, or None.  ``flat`` u8 [4N] with
    4N % 512 == 0; returns u32 [N] little-endian."""
    m = mode("SRJT_PALLAS_TRANSPOSE")
    if m == "off":
        return None
    n4 = int(flat.shape[0])
    if n4 == 0 or n4 % (4 * LANE) != 0:
        return None
    try:
        out = _u8_to_u32_impl(flat, m == "interpret")
    except Exception:
        if m != "interpret":
            raise
        _tally(False)
        return None
    _tally(True)
    return out


def _u8_to_u32_impl(flat, interpret):
    n4 = int(flat.shape[0])
    R = n4 // (4 * LANE)
    R_pad = _soft_bucket(_round_up(R, _TR_ROWS), _TR_ROWS)
    R_pad = _round_up(R_pad, _TR_ROWS)
    x2 = jnp.pad(flat, (0, R_pad * 4 * LANE - n4)).reshape(R_pad, 4 * LANE)
    out = _transpose_call(R_pad // _TR_ROWS, interpret)(x2)
    return out.reshape(-1)[:n4 // 4]


@functools.lru_cache(maxsize=64)
def _transpose_call(nblocks, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref):
        x = x_ref[...].astype(jnp.uint32)        # [32, 512]
        o_ref[...] = (x[:, 0::4] | (x[:, 1::4] << jnp.uint32(8))
                      | (x[:, 2::4] << jnp.uint32(16))
                      | (x[:, 3::4] << jnp.uint32(24)))

    return pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((_TR_ROWS, 4 * LANE), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((_TR_ROWS, LANE), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks * _TR_ROWS, LANE),
                                       jnp.uint32),
        interpret=interpret)
