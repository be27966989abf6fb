"""Sub-sample motion search + compensation as a Pallas TPU kernel.

Replaces the r4 fused uniform-shift fori_loop in jaxinter._search_mc
(~171 sequential device steps per P frame — launch-bound at 1080p) with
ONE kernel launch per frame. The reference's analog is the motion
search inside its hardware/software encoders
(/root/reference/worker/tasks.py:1558-1586 — a black box to it; here it
is the hot op and is built TPU-first):

- 2D grid (MB row x 256-lane chunk): every VMEM buffer is chunk-sized,
  so the footprint is resolution-independent and far under the 16 MB
  physical VMEM (exceeding it silently corrupts rather than erroring
  when a raised vmem_limit_bytes "permits" the allocation).
- The per-MB SAD reduction rides the MXU, one matmul per ROW of
  candidates (fixed wy, 2..9 values of wx): the row's |cur - cand|
  planes (64 rows each) are laid under each other and multiplied by
  the constant 0/1 block-sum selector, `dot(stack(64 nx, 256),
  SS(256, 128))` — 39 matmuls per grid step for the 227 candidates —
  and a 16-row group sum leaves ONE sum a macroblock, on lane m of 128.
  absdiff values (<= 255) are exact in bf16 and the f32 accumulation
  is exact (< 2^24), so the SADs are integer-exact. The running best
  is per macroblock; a row's `take` masks reach the lanes of the
  predictions by a second 0/1 matmul, `dot(takes(32 nx, 128),
  EX(128, 384))` (pltpu.repeat is a TILE repeat, not the element
  repeat it looks like).
- Search centers are folded in on the XLA side: the wide-padded
  reference planes are re-anchored per center with dynamic slices and
  stacked (leading dim 3), so the kernel needs no dynamic shifts at
  all — a row of candidates is reached by constant-shift row rolls
  inside a per-parity-class fori_loop, and each candidate of the row
  is a STATIC lane window of that plane (the row is unrolled, so its
  chroma fractions and window offsets in x are static too).
- Half-pel candidates read H.264 6-tap interpolation planes (b/h/j,
  §8.4.2.2.1) built in-kernel over exactly the rows the windows touch;
  chroma prediction is the §8.4.2.2.2 eighth-pel bilinear (centers are
  even-pel, so candidate chroma fractions depend only on the window
  offset).
- Selection keeps a running per-MB best (cost, mv) and the running
  best PREDICTION planes — motion compensation never runs as a
  separate pass; the kernel emits pred ready for residual coding.

The same search semantics are also implemented in plain XLA
(`me_search_xla`) — the executable spec the kernel is validated
against, and the path used off-TPU (CPU tests). Both produce identical
(mv, pred).

MV units follow `subpel` (rdo.RdConfig.subpel, a compile-time value):
with "half" (the default) every vector in and out of this module is in
HALF-sample units and the entropy packers scale mvd by 2; with
"quarter" the table (offset_table("quarter")) is that one plus the
fine half-sample classes and §8.4.2.2.1's twelve quarter positions
(each the rounded mean of two of the G / b / h / j samples) round the
temporal-median centre, and those positions round the zero centre; and
every vector in and out — the candidates' cost, `pred_mv`, the returned
`mv` and the median — is in QUARTER-sample units, which is what mvd is
coded in.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.log import get_logging
from .rdo import MV_PER_PEL
from .stages import stage
from .tiles import _lane_pool, _lane_rows

_LOG = get_logging(__name__)

SEARCH_RANGE = 16          # max |mv| in integer pel
_WR = 4                    # integer window radius (pel) around each center
_HR = 3                    # fine half-pel window radius (half units)
_ZR = 2                    # zero-window radius (half units)
_QR = 4                    # quarter window radius (quarter units) round
                           # the temporal-median and the zero centre
_CLIM = SEARCH_RANGE - _WR     # center clamp (pel)

# MV-cost lambda per half-pel unit of |mv|, indexed by QP. Scales with
# the quantizer like x264's lambda (2^((qp-12)/6) per bit, ~2.5 bits
# per half unit of mvd): without QP scaling, half-pel candidates
# "denoise" the reference's quant error on static content and beat the
# zero vector, killing P_Skip runs.
LAMBDA_H = np.maximum(
    3, np.round(2.5 * 2.0 ** ((np.arange(52) - 12) / 6.0))).astype(np.int32)
# The same price per QUARTER-sample unit (subpel="quarter": |mv| counts
# twice as many units for the same displacement).
LAMBDA_Q = np.maximum(2, (LAMBDA_H + 1) // 2).astype(np.int32)

# Padded-layout constants (see _pad_luma/_pad_chroma): generous halos so
# center roll + window offset + 6-tap reach never leaves real samples.
_PV = 32                   # luma top pad rows (5 row-blocks of 16 in-kernel)
_PH = 24                   # luma left pad lanes
_PVC = 16                  # chroma top pad rows (5 row-blocks of 8)
_PHC = 16                  # chroma left pad lanes
# In-kernel row bases of the TRIMMED per-center planes (see run_center:
# interpolation planes keep only the 32 luma / 24 chroma rows a window
# can touch; trimming was the difference between fitting and
# overflowing the 16 MB physical VMEM).
_KPV = 8
_KPVC = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# offset tables (static; shared by kernel and XLA reference)
#
# Offsets around a center decompose into PARITY CLASSES — each class
# reads one interpolation plane (full-pel / b / h / j) and forms a
# regular grid whose luma row/lane step is exactly one sample of that
# plane. The kernel walks each class's rows with a fori_loop, stepping
# a rolled plane by one row per iteration, and unrolls the 2..9
# candidates of a row as static lane windows, so every candidate is a
# STATIC slice and the live set stays bounded by one row (a fully
# unrolled 267-candidate body made Mosaic's scoped-VMEM stack exceed
# the 16 MB physical VMEM).
# ---------------------------------------------------------------------------

def _window_classes(int_rad_pel: int, fine_rad_half: int
                    ) -> list[tuple[tuple[int, int], list[int], list[int]]]:
    """[(parity (py, px), wys, wxs)] — the ± `int_rad_pel` integer grid
    plus the ± `fine_rad_half` fine grid's non-integer parities, all in
    half-pel units."""
    ir, fr = int_rad_pel, fine_rad_half
    evens = [w for w in range(-fr, fr + 1) if w % 2 == 0]
    odds = [w for w in range(-fr, fr + 1) if abs(w) % 2 == 1]
    return [
        ((0, 0), [2 * d for d in range(-ir, ir + 1)],
         [2 * d for d in range(-ir, ir + 1)]),
        ((0, 1), evens, odds),      # horizontal half (b plane)
        ((1, 0), odds, evens),      # vertical half (h plane)
        ((1, 1), odds, odds),       # diagonal half (j plane)
    ]


CENTER_CLASSES = _window_classes(_WR, _HR)
#: under subpel="half" the temporal-median center keeps only its integer
#: window — its role is to re-acquire motion the probe missed; sub-pel
#: refinement around it duplicates work the probe/zero windows already
#: do (measured: no quality change, -15% kernel time). Under "quarter"
#: it is where the quarter rows go, the fine classes with them (CENTERS)
CENTER_B_CLASSES = CENTER_CLASSES[:1]
ZERO_CLASSES = _window_classes(_ZR // 2, _ZR)


def _class_offsets(classes) -> list[tuple[int, int]]:
    return [(wy, wx) for (_par, wys, wxs) in classes
            for wy in wys for wx in wxs]


# ---------------------------------------------------------------------------
# quarter-sample candidates (subpel="quarter")
#
# §8.4.2.2.1 makes each of the twelve quarter positions the rounded mean
# (p + q + 1) >> 1 of two samples of the half-sample grid: along the one
# odd axis its two neighbours (a c d n: G/b/h and the next integer
# sample; f i k q: j and b/h/m/s), on the diagonals the two of its four
# neighbours that are b- or h-type (e g p r). Those samples are what a
# HALF candidate's window holds, so a quarter candidate needs no plane
# the kernel has not already built.
#
# The offsets are walked as ROWS of constant qy, one yFrac (qy & 3) at
# a time: each xFrac of the row gets a plane of its own (the mean of the
# two planes its position names), and a candidate is a static window of
# it (_me_kernel.quarter_rows).
#
# They go round the two even-pel centres that are never more than a
# pixel from the motion they stand for — the temporal median, which
# under "quarter" takes the fine half-sample classes as well, and zero —
# in a window of that pixel (_QR). Not round the probe's: that centre is
# a multiple of _COARSE pixels, so a window of a pixel round it misses
# most displacements; it keeps the classes it has under "half", which is
# what refines motion the probe finds and the median does not (a GOP's
# first P frame, a second motion in the picture). Measured on five
# contents (PERF.md §6): with the fine classes on the median a wider
# window there (radius 5, 6) saves no more bytes on four of them;
# without them on the probe a fast pan's first P frame doubles.
# ---------------------------------------------------------------------------

def _quarter_pair(qy: int, qx: int) -> tuple[tuple[int, int], tuple[int, int]]:
    """The two half-unit offsets (hy, hx) whose samples' rounded mean is
    the sample at quarter offset (qy, qx); a point of the half grid is
    its own pair."""
    fy, fx = qy >> 1, qx >> 1
    if qy & 1 and qx & 1:                   # e g p r: the b/h-type pair
        if (fy + fx) & 1:
            return (fy, fx), (fy + 1, fx + 1)
        return (fy, fx + 1), (fy + 1, fx)
    return (fy, fx), (fy + (qy & 1), fx + (qx & 1))


def _quarter_rows(rad: int) -> list[tuple[int, list[int], list[int]]]:
    """[(yfrac, qys, qxs)] — every offset of the ± `rad` quarter window
    with an odd component, as rows of constant qy grouped by yfrac."""
    span = range(-rad, rad + 1)
    return [(yf, [q for q in span if q & 3 == yf],
             [q for q in span if (q | yf) & 1])
            for yf in range(4)]


#: by `subpel`, per centre — probe, temporal median, zero: (parity
#: classes in half units, quarter rows or None). Kernel and mirror walk
#: a centre's classes, then its rows.
CENTERS = {
    "half": ((CENTER_CLASSES, None), (CENTER_B_CLASSES, None),
             (ZERO_CLASSES, None)),
    "quarter": ((CENTER_CLASSES, None),
                (CENTER_CLASSES, _quarter_rows(_QR)),
                (ZERO_CLASSES, _quarter_rows(_QR))),
}


def _table(subpel: str) -> list[tuple[int, int, int]]:
    unit = MV_PER_PEL[subpel] // 2          # MV units per half sample
    return [
        (ci, oy, ox)
        for ci, (classes, rows) in enumerate(CENTERS[subpel])
        for (oy, ox) in (
            [(unit * wy, unit * wx) for (wy, wx) in _class_offsets(classes)]
            + [(qy, qx) for (_yf, qys, qxs) in rows or ()
               for qy in qys for qx in qxs])]


_TABLES = {subpel: _table(subpel) for subpel in CENTERS}
#: (center_index, wy, wx) of subpel="half", half units, in selection
#: order; strict '<' keeps the first best, so earlier entries win ties.
#: Center 2 is the zero vector.
OFFSET_TABLE: list[tuple[int, int, int]] = _TABLES["half"]
#: MV-cost lambda by QP, per unit of `subpel`
_LAMBDAS = {"half": LAMBDA_H, "quarter": LAMBDA_Q}


def offset_table(subpel: str = "half") -> list[tuple[int, int, int]]:
    """The candidates scored per macroblock under `subpel`, in selection
    order, in that value's MV units (rdo.MV_PER_PEL to a sample)."""
    if subpel not in _TABLES:
        raise ValueError(
            f"subpel must be one of {tuple(_TABLES)}, not {subpel!r}")
    return _TABLES[subpel]


# ---------------------------------------------------------------------------
# H.264 6-tap half-pel interpolation (§8.4.2.2.1) — shared math
# ---------------------------------------------------------------------------

def _tap6_lane(x, roll):
    """6-tap across lanes: out[l] = x[l-2] -5x[l-1] +20x[l] +20x[l+1]
    -5x[l+2] +x[l+3]. `roll(x, k)` must move element l to l+k."""
    return (roll(x, 2) - 5 * roll(x, 1) + 20 * x + 20 * roll(x, -1)
            - 5 * roll(x, -2) + roll(x, -3))


def _tap6_row(x, roll):
    return (roll(x, 2) - 5 * roll(x, 1) + 20 * x + 20 * roll(x, -1)
            - 5 * roll(x, -2) + roll(x, -3))


def _halfpel_planes(r32, roll_rows, roll_lanes):
    """(R, B, H, J) planes from an int32 full-pel plane. B = horizontal
    half (b), H = vertical half (h), J = diagonal (j, from the
    unrounded horizontal intermediates). Edge lanes/rows hold garbage
    within the pad halo — callers never slice them."""
    hb1 = _tap6_lane(r32, roll_lanes)
    b = jnp.clip((hb1 + 16) >> 5, 0, 255)
    vb1 = _tap6_row(r32, roll_rows)
    h = jnp.clip((vb1 + 16) >> 5, 0, 255)
    j1 = _tap6_row(hb1, roll_rows)
    j = jnp.clip((j1 + 512) >> 10, 0, 255)
    return (r32, b, h, j)


def _chroma_weights(wy: int, wx: int) -> tuple[int, int, int, int]:
    """Static §8.4.2.2.2 bilinear weights for a half-unit offset from an
    even-pel center: eighth-pel fracs are (w & 3) * 2."""
    ey, ex = (wy & 3) * 2, (wx & 3) * 2
    return ((8 - ex) * (8 - ey), ex * (8 - ey), (8 - ex) * ey, ex * ey)


# ---------------------------------------------------------------------------
# host/XLA-side padding + selector constants
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _geom(H: int, W: int):
    """Static geometry for a padded frame (H, W multiples of 16).

    The kernel runs on a 2D grid over (4-MB-row bands x 256-lane
    chunks): every VMEM buffer is band-sized, so the footprint is
    resolution-independent (a frame-wide variant overflowed the 16 MB
    physical VMEM at 1080p). The band is 64 rows, and the block-sum
    matmul takes a whole ROW of candidates (M = 64 nx = 128..576),
    because a matmul against a constant selector has a fixed cost
    worth about 90 rows (PR 27's step 0, with the (256, 384) selector
    of the time: about 120 ns + 1.4 ns per row in a serial loop on a
    v5e — 210 ns per 64 rows at one candidate a matmul, 102 ns at
    nine). Step 0 of PR 50 (PERF.md §5; `_me_pallas` alone at 1088 x
    1920, ten calls back to back, ms a call, one v5e): the (256, 384)
    selector 4.95-4.98 (9.44-9.45 under "quarter"), the (256, 128) one
    with the masks' expander 4.69-4.70 (9.12); 18.27 -> 17.31 at 2176 x
    3840 (35.09 -> 33.88)."""
    mbh, mbw = H // 16, W // 16
    H4 = _round_up(H, 64)               # band-padded height
    RG = H4 // 64                       # grid rows (bands)
    WcK = _round_up(W, 256)             # chunked luma width (16 MBs/chunk)
    nch = WcK // 256                    # grid chunks
    W2K = WcK + 256                     # wide luma ref lane width
    WcuK = WcK // 2                     # chroma pred width
    W2cK = WcuK + 128                   # wide chroma ref lane width
    return mbh, mbw, H4, RG, WcK, nch, W2K, WcuK, W2cK


#: kernel-local (per-band) lane widths: two ref lane-blocks each
_LWY = 512                  # luma: 2 x 256-lane blocks
_LWC = 256                  # chroma: 2 x 128-lane blocks


@functools.lru_cache(maxsize=None)
def _ss_np():
    """(256, 128) block-sum selector: column m < 16 is 1 on the 16 lanes
    of the chunk's macroblock m (out[l, m] = 1 iff l // 16 == m), the
    other 112 columns 0 — 128 is the narrowest tile the MXU takes.
    dot(ad, SS) followed by a 16-row group sum leaves ONE sum per
    macroblock, on lane m, so the running best is per macroblock. The
    left side is a row of candidates' |cur - cand| planes laid under
    each other (score_row), so SS is pushed into the MXU 39 times per
    grid step, not 227. With the masks' way back to the lanes (_ex_np)
    that is 227 GFLOP per 1080p P frame — 130 here, 97 there — where
    the (256, 384) selector that wrote every sum on 16 luma and 8
    chroma lanes took 388 (PERF.md §3, §5). Not built, because step 0
    of PR 50 read no lower with it: the 16-row group sum on the MXU as
    well (a 0/1 left factor on the stack, the sums then through SS as
    16 hi + lo): 4.82-4.84 ms a 1080p call for 4.69-4.72."""
    m = np.zeros((256, 128), np.float32)
    m[np.arange(256), np.arange(256) // 16] = 1.0
    return m


@functools.lru_cache(maxsize=None)
def _ex_np():
    """(128, 384) mask expander, `_ss_np`'s counterpart: row m < 16 is 1
    on macroblock m's 16 luma lanes (columns 16 m ... 16 m + 15) and on
    its 8 chroma lanes (columns 256 + 8 m ... 256 + 8 m + 7), so
    dot(takes, EX) widens a row of candidates' 0/1 `take` masks from
    macroblocks to the lanes the prediction selects run on, luma and
    chroma in one pass (select_row)."""
    m = np.zeros((128, 384), np.float32)
    m[np.arange(256) // 16, np.arange(256)] = 1.0
    m[np.arange(128) // 8, 256 + np.arange(128)] = 1.0
    return m


def _pad_luma_wide(p, H, H4, W, W2K):
    """(H, W) -> (H4 + 160, W2K + 128) edge-replicated int16 with 16
    rows/lanes of low-side margin so a per-center dynamic slice at
    (16 + cy, 16 + cx) re-anchors the plane (centers are clamped to
    ±_CLIM = ±12; slice row 0 is orig row cy - 32). Centering happens
    in XLA — the kernel contains no dynamic shifts (Mosaic's
    dynamic_rotate produced corrupted lanes in composed programs on
    v5e)."""
    out = jnp.pad(p, ((48, H4 + 112 - H), (_PH + 16, W2K + 88 - W)),
                  mode="edge")
    return out.astype(jnp.int16)


def _pad_chroma_wide(p, H, H4, W, W2cK):
    h2, w2 = H // 2, W // 2
    out = jnp.pad(p, ((24, H4 // 2 + 72 - h2), (_PHC + 8, W2cK + 104 - w2)),
                  mode="edge")
    return out.astype(jnp.int16)


def _center_stack(wide, starts_r, starts_c, rows, cols):
    """Stack per-center dynamic slices of a wide padded plane."""
    return jnp.stack([
        jax.lax.dynamic_slice(wide, (starts_r[i], starts_c[i]),
                              (rows, cols))
        for i in range(3)])


def _pad_cur(y, H, H4, W, WcK):
    if WcK == W and H4 == H:
        return y.astype(jnp.int16)
    return jnp.pad(y, ((0, H4 - H), (0, WcK - W)),
                   mode="edge").astype(jnp.int16)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _me_kernel(H: int, W: int, subpel: str = "half"):
    mbh, mbw, H4, RG, WcK, nch, W2K, WcuK, W2cK = _geom(H, W)
    quarter = MV_PER_PEL[subpel] == 4

    def mv_of(c, w):
        """Centre `c` (pel) + half-unit offset `w`, in `subpel`'s units."""
        return 4 * c + 2 * w if quarter else 2 * c + w

    def kernel(cent_ref,
               cur_ref,
               ry00, ry10, ry20, ry30, ry01, ry11, ry21, ry31,
               ru00, ru10, ru20, ru30, ru01, ru11, ru21, ru31,
               rv00, rv10, rv20, rv30, rv01, rv11, rv21, rv31,
               ss_ref, ex_ref, _dmv, _dpy, _dpu, _dpv,
               mv_ref, py_ref, pu_ref, pv_ref):
        # Inputs arrive PRE-CENTERED per search center (leading dim 3,
        # XLA-side dynamic slice of a wide pad): no dynamic shifts in
        # the kernel; all remaining rolls have CONSTANT shifts. The 128
        # rows x 512 lanes cover this band's windows + 6-tap reach.
        R3 = jnp.concatenate([
            jnp.concatenate([ry00[:], ry10[:], ry20[:], ry30[:]], axis=1),
            jnp.concatenate([ry01[:], ry11[:], ry21[:], ry31[:]], axis=1),
        ], axis=2)                                        # (3, 128, 512)
        CU3 = jnp.concatenate([
            jnp.concatenate([ru00[:], ru10[:], ru20[:], ru30[:]], axis=1),
            jnp.concatenate([ru01[:], ru11[:], ru21[:], ru31[:]], axis=1),
        ], axis=2)                                        # (3, 64, 256)
        CV3 = jnp.concatenate([
            jnp.concatenate([rv00[:], rv10[:], rv20[:], rv30[:]], axis=1),
            jnp.concatenate([rv01[:], rv11[:], rv21[:], rv31[:]], axis=1),
        ], axis=2)
        cur = cur_ref[:].astype(jnp.float32)              # (64, 256)
        SS = ss_ref[:]                                    # (256, 128) bf16
        EX = ex_ref[:]                                    # (128, 384) bf16
        lam = cent_ref[0, 6].astype(jnp.float32)

        # constant-shift rolls only; negative shifts wrap mod the size
        roll_rows = lambda x, k: pltpu.roll(x, k % x.shape[0], axis=0)
        roll_lanes = lambda x, k: pltpu.roll(x, k % x.shape[1], axis=1)

        def roll01_rows(x, flag):
            """Roll rows by a traced 0/1 without a dynamic rotate."""
            return jnp.where(flag > 0, roll_rows(x, -1), x)

        # ONE running best per MACROBLOCK (4 MB rows x the chunk's 16
        # MBs on lanes 0..15 of 128; the other lanes score 0 + the MV
        # cost and are never read): cost and vector. Luma and chroma
        # predictions follow it through the row's `take` masks, widened
        # from macroblocks to lanes once per row of candidates
        # (select_row), so the chroma prediction always matches the
        # coded luma MV.
        best = jnp.full((4, 128), 2.0**30, jnp.float32)
        bmy = jnp.zeros((4, 128), jnp.int32)
        bmx = jnp.zeros((4, 128), jnp.int32)
        py = jnp.zeros((64, 256), jnp.float32)
        pu = jnp.zeros((32, 128), jnp.int32)
        pv = jnp.zeros((32, 128), jnp.int32)
        state = (best, bmy, bmx, py, pu, pv)

        def score_row(cands):
            """The row's |cur - cand| planes laid under each other and
            block-summed by ONE matmul against SS: column m of the
            (64 nx, 128) result holds macroblock m's row sums."""
            stack = jnp.concatenate(
                [jnp.abs(cur - cand).astype(jnp.bfloat16)
                 for cand in cands], axis=0)              # (64 nx, 256)
            return jnp.dot(stack, SS, preferred_element_type=jnp.float32)

        def select_row(state, sad, cands, fracs, Cur, Cvr, ey, mvy, mvx_of):
            """Walk a scored row in table order. `fracs` holds each
            candidate's static chroma (ex, ox): eighth fraction and
            whole-sample offset in x; ey and mvy are the row's (traced),
            mvx_of(k) candidate k's."""
            # §8.4.2.2.2 bilinear at eighth fractions (exact for frac 0:
            # (64 * a + 32) >> 6 == a). ex is static per candidate, so a
            # full-pel column drops its two zero taps.
            def chroma_row(C):
                @functools.lru_cache(maxsize=None)
                def win(dr, dl):        # shared by neighbouring candidates
                    return jax.lax.slice(
                        C, (_KPVC + dr, _PHC + dl),
                        (_KPVC + dr + 32, _PHC + dl + 128))

                def cpred(ex, ox):
                    out = ((8 - ex) * (8 - ey) * win(0, ox)
                           + (8 - ex) * ey * win(1, ox) + 32)
                    if ex:
                        out = (out + ex * (8 - ey) * win(0, ox + 1)
                               + ex * ey * win(1, ox + 1))
                    return out >> 6
                return cpred

            cpred_u, cpred_v = chroma_row(Cur), chroma_row(Cvr)
            best, bmy, bmx, py, pu, pv = state
            # the serial chain runs on costs alone, one vreg a candidate;
            # each `take` goes on as 0/1, its macroblock rows 8 sublanes
            # deep (a vreg's)
            takes = []
            for k in range(len(cands)):
                sad4 = jax.lax.slice(sad, (64 * k, 0), (64 * k + 64, 128)
                                     ).reshape(4, 16, 128).sum(1)
                mvx = mvx_of(k)
                cost = sad4 + lam * (jnp.abs(mvy) + jnp.abs(mvx)
                                     ).astype(jnp.float32)
                take = cost < best                        # (4, 128) bool
                best = jnp.where(take, cost, best)
                bmy = jnp.where(take, mvy, bmy)
                bmx = jnp.where(take, mvx, bmx)
                takes.append(jnp.broadcast_to(
                    take.astype(jnp.float32)[:, None, :], (4, 8, 128)
                    ).reshape(32, 128))
            # the row's masks, macroblocks -> luma and chroma lanes, by
            # ONE 0/1 matmul: 32 rows a candidate come back as whole
            # vregs of the predictions' tiling — 8 of a macroblock row's
            # 16 luma rows (taken twice), its 8 chroma rows — and stay
            # f32 until they are used (a bool costs more to move than
            # to make)
            wide = jnp.dot(jnp.concatenate(takes, axis=0
                                           ).astype(jnp.bfloat16), EX,
                           preferred_element_type=jnp.float32)
            for k, (cand, frac) in enumerate(zip(cands, fracs)):
                tly = jax.lax.slice(wide, (32 * k, 0), (32 * k + 32, 256))
                tly = jnp.broadcast_to(tly.reshape(4, 1, 8, 256),
                                       (4, 2, 8, 256)).reshape(64, 256)
                py = jnp.where(tly > 0.5, cand, py)
                mc = jax.lax.slice(wide, (32 * k, 256),
                                   (32 * k + 32, 384)) > 0.5
                pu = jnp.where(mc, cpred_u(*frac), pu)
                pv = jnp.where(mc, cpred_v(*frac), pv)
            return (best, bmy, bmx, py, pu, pv)

        def row_body(state, Pl, Cur, Cvr, wy, wxs, cy, cx):
            """One ROW of candidates (fixed wy, every wx of `wxs`): Pl,
            Cur, Cvr are the class plane and the chroma planes rolled to
            this row, so candidate wx is the STATIC window of Pl at lane
            _PH + (wx >> 1) (chroma: _PHC + (wx >> 2), eighth fraction
            (wx & 3) * 2). wy, cy, cx traced; wxs static."""
            cands = [
                jax.lax.slice(Pl, (_KPV, _PH + (wx >> 1)),
                              (_KPV + 64, _PH + (wx >> 1) + 256))
                for wx in wxs]
            sad = score_row(cands)
            ey = (wy & 3) * 2
            return select_row(
                state, sad, cands, [((wx & 3) * 2, wx >> 2) for wx in wxs],
                Cur, Cvr, ey, mv_of(cy, wy), lambda k: mv_of(cx, wxs[k]))

        def class_scan(plane, CUc, CVc, cy, cx, wys, wxs, state):
            """Walk one parity class's (wys x wxs) grid, a row of
            candidates per fori_loop step. The plane and chroma planes
            are pre-rolled to the first row and roll by the grid's
            one-sample row stride per step, so every candidate is a
            static slice and the loop carries are band-sized; the row
            itself is unrolled (row_body) — rows, not classes: a fully
            unrolled class set overflows Mosaic's scoped VMEM."""
            wy0 = wys[0]
            Pl = roll_rows(plane, -(wy0 >> 1))
            Cur = roll_rows(CUc, -(wy0 >> 2))
            Cvr = roll_rows(CVc, -(wy0 >> 2))

            def outer(iy, carry):
                Pl, Cur, Cvr, state = carry
                wy = wy0 + 2 * iy
                state = row_body(state, Pl, Cur, Cvr, wy, wxs, cy, cx)
                rd = ((wy + 2) >> 2) - (wy >> 2)
                return (roll_rows(Pl, -1), roll01_rows(Cur, rd),
                        roll01_rows(Cvr, rd), state)

            _, _, _, state = jax.lax.fori_loop(
                0, len(wys), outer, (Pl, Cur, Cvr, state))
            return state

        def quarter_rows(planes, CUc, CVc, cy, cx, yf, qys, qxs, state):
            """One yfrac of the quarter window (`_quarter_rows`): its
            rows of candidates, qy a pixel further from row to row.
            Each xfrac of the rows first gets a plane of its own — the
            position's sample for every integer sample: §8.4.2.2.1's
            rounded mean of the two planes `_quarter_pair` names, the
            second moved a row or a lane where the pair says so — and a
            candidate is then ONE static window of it, as a parity
            class's is. The two or three rows are unrolled (static row
            offsets, static chroma fractions): as a `fori_loop` over
            planes rolled row by row, which is how the parity classes
            walk their nine rows, they took 1.5 ms a 1080p frame longer
            (PERF.md §6) — the carried planes cost more than so short a
            loop saves."""
            def moved(p, r, lane):
                x = roll_rows(planes[p], -r) if r else planes[p]
                return roll_lanes(x, -lane) if lane else x

            xfs = sorted({qx & 3 for qx in qxs})
            typed = [
                jnp.floor((moved(*a) + moved(*b) + 1.0) * 0.5)
                for (a, b) in (
                    [((hy & 1) * 2 + (hx & 1), hy >> 1, hx >> 1)
                     for (hy, hx) in _quarter_pair(yf, xf)] for xf in xfs)]
            for qy in qys:
                cands = [
                    jax.lax.slice(
                        typed[xfs.index(qx & 3)],
                        (_KPV + (qy >> 2), _PH + (qx >> 2)),
                        (_KPV + (qy >> 2) + 64, _PH + (qx >> 2) + 256))
                    for qx in qxs]
                state = select_row(
                    state, score_row(cands), cands,
                    [(qx & 7, qx >> 3) for qx in qxs],
                    roll_rows(CUc, -(qy >> 3)) if qy >> 3 else CUc,
                    roll_rows(CVc, -(qy >> 3)) if qy >> 3 else CVc,
                    qy & 7, 4 * cy + qy, lambda k: 4 * cx + qxs[k])
            return state

        def run_center(ci, classes, state, rows):
            cy = cent_ref[0, 2 * ci]
            cx = cent_ref[0, 2 * ci + 1]
            # Interpolation planes built DIRECTLY over the 80 rows the
            # windows slice (row base _KPV = band row -8); vertical
            # 6-taps as static row slices — no full-height temporaries.
            # R3[ci] local row 0 is band row -32.
            RcT = R3[ci].astype(jnp.int32)                # (128, 512)

            def vtap(x, r0, n):
                W_ = x.shape[1]
                return (jax.lax.slice(x, (r0 - 2, 0), (r0 - 2 + n, W_))
                        - 5 * jax.lax.slice(x, (r0 - 1, 0),
                                            (r0 - 1 + n, W_))
                        + 20 * jax.lax.slice(x, (r0, 0), (r0 + n, W_))
                        + 20 * jax.lax.slice(x, (r0 + 1, 0),
                                             (r0 + 1 + n, W_))
                        - 5 * jax.lax.slice(x, (r0 + 2, 0),
                                            (r0 + 2 + n, W_))
                        + jax.lax.slice(x, (r0 + 3, 0), (r0 + 3 + n, W_)))

            # hb1 rows cover band rows [-11, 75): local hb1 row i is
            # band row i - 11
            hb1 = _tap6_lane(jax.lax.slice(RcT, (21, 0), (107, _LWY)),
                             roll_lanes)
            p0 = jax.lax.slice(RcT, (24, 0), (104, _LWY)
                               ).astype(jnp.float32)
            b = jnp.clip((jax.lax.slice(hb1, (3, 0), (83, _LWY)) + 16)
                         >> 5, 0, 255).astype(jnp.float32)
            h = jnp.clip((vtap(RcT, 24, 80) + 16) >> 5, 0, 255
                         ).astype(jnp.float32)
            # j: vertical 6-tap of the unrounded horizontal
            # intermediates
            j = jnp.clip((vtap(hb1, 3, 80) + 512) >> 10, 0, 255
                         ).astype(jnp.float32)
            planes = (p0, b, h, j)
            # chroma local row 0 is band chroma row -16; trim to
            # [-8, 40) so _KPVC = 8 aligns with chroma row 0
            CUc = jax.lax.slice(CU3, (ci, 8, 0), (ci + 1, 56, _LWC)
                                )[0].astype(jnp.int32)    # (48, 256)
            CVc = jax.lax.slice(CV3, (ci, 8, 0), (ci + 1, 56, _LWC)
                                )[0].astype(jnp.int32)
            for (par, wys, wxs) in classes:
                plane = planes[par[0] * 2 + par[1]]
                state = class_scan(plane, CUc, CVc, cy, cx, wys, wxs,
                                   state)
            for (yf, qys, qxs) in rows or ():
                state = quarter_rows(planes, CUc, CVc, cy, cx, yf, qys,
                                     qxs, state)
            return state

        for ci, (classes, rows) in enumerate(CENTERS[subpel]):
            state = run_center(ci, classes, state, rows)
        _best, bmy, bmx, py, pu, pv = state

        mv_ref[0, 0, 0:4, :] = bmy
        mv_ref[0, 0, 4:8, :] = bmx
        py_ref[:] = py.astype(jnp.int16)
        pu_ref[:] = pu.astype(jnp.int16)
        pv_ref[:] = pv.astype(jnp.int16)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("H", "W", "interpret", "subpel"))
def _me_pallas(cent, cur, refy, refu, refv, ss, ex, *, H: int,
               W: int, interpret: bool, subpel: str = "half"):
    mbh, mbw, H4, RG, WcK, nch, W2K, WcuK, W2cK = _geom(H, W)
    vspec = lambda shape, imap: pl.BlockSpec(shape, imap,
                                             memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((1, 8), lambda r, c: (0, 0), memory_space=pltpu.SMEM),
        vspec((64, 256), lambda r, c: (r, c)),
    ]
    # luma ref: 4 x 32-row blocks x 2 lane-blocks, overlapping windows
    # via the multi-input trick (index maps may not overlap in a spec)
    for kl in range(2):
        for k in range(4):
            in_specs.append(vspec((3, 32, 256), functools.partial(
                lambda r, c, k=0, kl=0: (0, 2 * r + k, c + kl),
                k=k, kl=kl)))
    for plane in range(2):
        for kl in range(2):
            for k in range(4):
                in_specs.append(vspec((3, 16, 128), functools.partial(
                    lambda r, c, k=0, kl=0: (0, 2 * r + k, c + kl),
                    k=k, kl=kl)))
    in_specs += [vspec(m.shape, lambda r, c: (0, 0)) for m in (ss, ex)]

    # under shard_map the outputs vary over the same mesh axes as the
    # frame they are computed from (check_vma requires it to be said)
    vma = jax.typeof(cur).vma
    out_shape = (
        jax.ShapeDtypeStruct((RG, nch, 8, 128), jnp.int32, vma=vma),
        jax.ShapeDtypeStruct((H4, WcK), jnp.int16, vma=vma),
        jax.ShapeDtypeStruct((H4 // 2, WcuK), jnp.int16, vma=vma),
        jax.ShapeDtypeStruct((H4 // 2, WcuK), jnp.int16, vma=vma),
    )
    out_specs = (
        pl.BlockSpec((1, 1, 8, 128), lambda r, c: (r, c, 0, 0),
                     memory_space=pltpu.VMEM),
        vspec((64, 256), lambda r, c: (r, c)),
        vspec((32, 128), lambda r, c: (r, c)),
        vspec((32, 128), lambda r, c: (r, c)),
    )
    # Output buffers are pre-allocated as aliased dummy INPUTS: the
    # kernel reads overlapping reference windows across grid steps, so
    # its outputs must never share memory with its (dead-after-call)
    # ref operands — the aliased dummies' live ranges overlap every
    # operand's, forcing disjoint allocations. Data-dependent (not
    # constants) so XLA cannot CSE them.
    z16 = (cur[0, 0] * 0).astype(jnp.int16)
    dummies = (
        jnp.zeros((RG, nch, 8, 128), jnp.int32) + z16.astype(jnp.int32),
        jnp.zeros((H4, WcK), jnp.int16) + z16,
        jnp.zeros((H4 // 2, WcuK), jnp.int16) + z16,
        jnp.zeros((H4 // 2, WcuK), jnp.int16) + z16,
    )
    in_specs += list(out_specs)
    n_in = 28
    return pl.pallas_call(
        _me_kernel(H, W, subpel),
        grid=(RG, nch),
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
        input_output_aliases={n_in + i: i for i in range(4)},
    )(cent, cur,
      *[refy] * 8, *[refu] * 8, *[refv] * 8, ss, ex, *dummies)


# ---------------------------------------------------------------------------
# XLA reference implementation (identical semantics; CPU/conformance)
# ---------------------------------------------------------------------------

@stage("me_search")
def me_search_xla(cur_y, ref_y, ref_u, ref_v, centers, lam,
                  subpel: str = "half"):
    """Pure-XLA mirror of the kernel: same offset table, same strict-<
    selection, same interpolation — the executable spec the Pallas
    kernel is tested against, and the off-TPU path. Structured as a
    `fori_loop` over a device-side offset table (a fully unrolled graph
    compiles super-linearly on XLA CPU — measured minutes at 267
    offsets). cur_y int16 (H, W); ref planes int16; centers (3, 2)
    int32 even-pel. Returns (mv (mbh, mbw, 2) int32 in `subpel`'s
    units, pred_y, pred_u, pred_v int16)."""
    if subpel != "half":
        return _me_search_xla_quarter(cur_y, ref_y, ref_u, ref_v, centers,
                                      lam)
    H, W = cur_y.shape
    mbh, mbw = H // 16, W // 16
    cur = cur_y.astype(jnp.int32)
    ry = jnp.pad(ref_y, ((_PV, _PV), (_PH, _PH)),
                 mode="edge").astype(jnp.int32)
    ru = jnp.pad(ref_u, ((_PVC, _PVC + 8), (_PHC, _PHC + 8)),
                 mode="edge").astype(jnp.int32)
    rv = jnp.pad(ref_v, ((_PVC, _PVC + 8), (_PHC, _PHC + 8)),
                 mode="edge").astype(jnp.int32)
    roll_rows = lambda x, k: jnp.roll(x, k, axis=0)
    roll_lanes = lambda x, k: jnp.roll(x, k, axis=1)

    zero = (cur_y.reshape(-1)[0] * 0).astype(jnp.int32)
    bestc = jnp.full((mbh, mbw), 2**30, jnp.int32) + zero
    bmy = jnp.zeros((mbh, mbw), jnp.int32) + zero
    bmx = jnp.zeros((mbh, mbw), jnp.int32) + zero
    py = jnp.zeros((H, W), jnp.int32) + zero
    pu = jnp.zeros((H // 2, W // 2), jnp.int32) + zero
    pv = jnp.zeros((H // 2, W // 2), jnp.int32) + zero

    def mb_sad(ad):
        return ad.reshape(mbh, 16, mbw, 16).sum((1, 3))

    # Per-center static setup (3 centers), dynamic loop over offsets.
    for ci in range(3):
        cy, cx = centers[ci, 0], centers[ci, 1]
        Rc = roll_lanes(roll_rows(ry, -cy), -cx)
        planes = jnp.stack(_halfpel_planes(Rc, roll_rows, roll_lanes))
        CUc = roll_lanes(roll_rows(ru, -(cy >> 1)), -(cx >> 1))
        CVc = roll_lanes(roll_rows(rv, -(cy >> 1)), -(cx >> 1))
        offs = jnp.asarray([(wy, wx) for (c, wy, wx) in OFFSET_TABLE
                            if c == ci], jnp.int32)

        def body(i, state, planes=planes, CUc=CUc, CVc=CVc, offs=offs,
                 cy=cy, cx=cx):
            bestc, bmy, bmx, py, pu, pv = state
            wy, wx = offs[i, 0], offs[i, 1]
            my, mx = wy >> 1, wx >> 1
            plane = planes[(wy & 1) * 2 + (wx & 1)]
            cand = jax.lax.dynamic_slice(plane, (_PV + my, _PH + mx),
                                         (H, W))
            sad = mb_sad(jnp.abs(cur - cand))
            mvy = 2 * cy + wy
            mvx = 2 * cx + wx
            cost = sad + lam * (jnp.abs(mvy) + jnp.abs(mvx))
            take = cost < bestc
            bestc = jnp.where(take, cost, bestc)
            bmy = jnp.where(take, mvy, bmy)
            bmx = jnp.where(take, mvx, bmx)
            tly = jnp.broadcast_to(take[:, None, :, None],
                                   (mbh, 16, mbw, 16)).reshape(H, W)
            py = jnp.where(tly, cand, py)
            # §8.4.2.2.2 bilinear; weights (8-ex)(8-ey) etc. with
            # eighth-pel fracs (w & 3) * 2 — exact for frac 0 too.
            ey = (wy & 3) * 2
            ex = (wx & 3) * 2
            oy, ox = wy >> 2, wx >> 2

            def cpred(C):
                h2, w2 = H // 2, W // 2
                a = jax.lax.dynamic_slice(C, (_PVC + oy, _PHC + ox),
                                          (h2, w2))
                b = jax.lax.dynamic_slice(C, (_PVC + oy, _PHC + ox + 1),
                                          (h2, w2))
                c = jax.lax.dynamic_slice(C, (_PVC + oy + 1, _PHC + ox),
                                          (h2, w2))
                d = jax.lax.dynamic_slice(
                    C, (_PVC + oy + 1, _PHC + ox + 1), (h2, w2))
                return ((8 - ex) * (8 - ey) * a + ex * (8 - ey) * b
                        + (8 - ex) * ey * c + ex * ey * d + 32) >> 6

            tlc = jnp.broadcast_to(take[:, None, :, None],
                                   (mbh, 8, mbw, 8)).reshape(H // 2,
                                                             W // 2)
            pu = jnp.where(tlc, cpred(CUc), pu)
            pv = jnp.where(tlc, cpred(CVc), pv)
            return (bestc, bmy, bmx, py, pu, pv)

        bestc, bmy, bmx, py, pu, pv = jax.lax.fori_loop(
            0, offs.shape[0], body, (bestc, bmy, bmx, py, pu, pv))

    mv = jnp.stack([bmy, bmx], axis=-1)
    return (mv, py.astype(jnp.int16), pu.astype(jnp.int16),
            pv.astype(jnp.int16))


def _me_search_xla_quarter(cur_y, ref_y, ref_u, ref_v, centers, lam):
    """`me_search_xla` over offset_table("quarter"): every candidate, a
    point of the half grid or not, is the rounded mean of the two
    half-grid samples `_quarter_pair` names (a half-grid point pairs
    with itself: (p + p + 1) >> 1 = p), its chroma §8.4.2.2.2's
    bilinear at the eighth fractions qy & 7, qx & 7. Vectors in QUARTER
    units. It would take the half table too (in quarter units); the
    body above stays because the programs of subpel="half" are held to
    their jaxprs of before the setting (tests/test_subpel.py)."""
    H, W = cur_y.shape
    mbh, mbw = H // 16, W // 16
    h2, w2 = H // 2, W // 2
    cur = cur_y.astype(jnp.int32)
    ry = jnp.pad(ref_y, ((_PV, _PV), (_PH, _PH)),
                 mode="edge").astype(jnp.int32)
    ru = jnp.pad(ref_u, ((_PVC, _PVC + 8), (_PHC, _PHC + 8)),
                 mode="edge").astype(jnp.int32)
    rv = jnp.pad(ref_v, ((_PVC, _PVC + 8), (_PHC, _PHC + 8)),
                 mode="edge").astype(jnp.int32)
    roll_rows = lambda x, k: jnp.roll(x, k, axis=0)
    roll_lanes = lambda x, k: jnp.roll(x, k, axis=1)

    zero = (cur_y.reshape(-1)[0] * 0).astype(jnp.int32)
    state = (jnp.full((mbh, mbw), 2**30, jnp.int32) + zero,
             jnp.zeros((mbh, mbw), jnp.int32) + zero,
             jnp.zeros((mbh, mbw), jnp.int32) + zero,
             jnp.zeros((H, W), jnp.int32) + zero,
             jnp.zeros((h2, w2), jnp.int32) + zero,
             jnp.zeros((h2, w2), jnp.int32) + zero)

    for ci in range(3):
        cy, cx = centers[ci, 0], centers[ci, 1]
        Rc = roll_lanes(roll_rows(ry, -cy), -cx)
        planes = jnp.stack(_halfpel_planes(Rc, roll_rows, roll_lanes))
        CUc = roll_lanes(roll_rows(ru, -(cy >> 1)), -(cx >> 1))
        CVc = roll_lanes(roll_rows(rv, -(cy >> 1)), -(cx >> 1))
        # per candidate: qy, qx, then (plane, row, lane) of its two samples
        offs = jnp.asarray(
            [(qy, qx) + tuple(
                v for (hy, hx) in _quarter_pair(qy, qx)
                for v in ((hy & 1) * 2 + (hx & 1), hy >> 1, hx >> 1))
             for (c, qy, qx) in offset_table("quarter") if c == ci],
            jnp.int32)

        def body(i, state, planes=planes, CUc=CUc, CVc=CVc, offs=offs,
                 cy=cy, cx=cx):
            bestc, bmy, bmx, py, pu, pv = state
            o = offs[i]
            qy, qx = o[0], o[1]

            def sample(k):
                return jax.lax.dynamic_slice(
                    planes, (o[k], _PV + o[k + 1], _PH + o[k + 2]),
                    (1, H, W))[0]

            cand = (sample(2) + sample(5) + 1) >> 1
            sad = jnp.abs(cur - cand).reshape(mbh, 16, mbw, 16).sum((1, 3))
            mvy = 4 * cy + qy
            mvx = 4 * cx + qx
            cost = sad + lam * (jnp.abs(mvy) + jnp.abs(mvx))
            take = cost < bestc
            bestc = jnp.where(take, cost, bestc)
            bmy = jnp.where(take, mvy, bmy)
            bmx = jnp.where(take, mvx, bmx)
            tly = jnp.broadcast_to(take[:, None, :, None],
                                   (mbh, 16, mbw, 16)).reshape(H, W)
            py = jnp.where(tly, cand, py)
            ey, ex = qy & 7, qx & 7
            oy, ox = qy >> 3, qx >> 3

            def cpred(C):
                def tap(dy, dx):
                    return jax.lax.dynamic_slice(
                        C, (_PVC + oy + dy, _PHC + ox + dx), (h2, w2))
                return ((8 - ex) * (8 - ey) * tap(0, 0)
                        + ex * (8 - ey) * tap(0, 1)
                        + (8 - ex) * ey * tap(1, 0)
                        + ex * ey * tap(1, 1) + 32) >> 6

            tlc = jnp.broadcast_to(take[:, None, :, None],
                                   (mbh, 8, mbw, 8)).reshape(h2, w2)
            pu = jnp.where(tlc, cpred(CUc), pu)
            pv = jnp.where(tlc, cpred(CVc), pv)
            return (bestc, bmy, bmx, py, pu, pv)

        state = jax.lax.fori_loop(0, offs.shape[0], body, state)

    _bestc, bmy, bmx, py, pu, pv = state
    return (jnp.stack([bmy, bmx], axis=-1), py.astype(jnp.int16),
            pu.astype(jnp.int16), pv.astype(jnp.int16))


# ---------------------------------------------------------------------------
# centers: coarse global-motion probe + carried median, both batched
# ---------------------------------------------------------------------------

_COARSE = 4


def _box_sum(x, s: int):
    """(H, W) int16 plane -> (H // s, W // s) int32 sums of its s x s
    blocks. Rows are added in groups of s by row-strided slices, lanes
    pooled 128 -> 128 // s by the 0/1 matrix on the matrix unit
    (codecs/h264/tiles.py): exact in f32 while a sum stays under 2**24
    (s = 4: under 2**19 for any int16). The view (H // s, s, W // s,
    s) lays a minor dimension of s on 128 lanes — a relayout at 32
    times the plane's bytes, 1.47 ms of a 1080p frame (PERF.md §5)."""
    H, W = x.shape
    v = x.astype(jnp.int32)
    rows = v[::s]                                        # (H // s, W)
    for k in range(1, s):
        rows = rows + v[k::s]
    return _lane_pool(_lane_rows(rows), s).reshape(H // s, -1)[
        :, :W // s].astype(jnp.int32)


def coarse_probe(cur16, ref16, sr: int = SEARCH_RANGE):
    """Global-motion probe on box-summed quarter-res planes; batched
    static slices (the r4 fori_loop version was launch-bound). Returns
    a (2,) int32 center in pel, multiple of _COARSE (hence even)."""
    qs = _COARSE
    cq = _box_sum(cur16, qs)
    rq = _box_sum(ref16, qs)
    qsr = sr // qs
    rq_pad = jnp.pad(rq, qsr, mode="edge")
    qh, qw = cq.shape
    n = 2 * qsr + 1
    wins = jnp.stack([jax.lax.slice(rq_pad, (oy, ox), (oy + qh, ox + qw))
                      for oy in range(n) for ox in range(n)])
    cost = jnp.abs(cq[None] - wins).sum((1, 2))
    bi = jnp.argmin(cost).astype(jnp.int32)
    return jnp.stack([bi // n - qsr, bi % n - qsr]) * qs


@stage("me_median")
def hist_median(mv_flat, lim: int):
    """Per-component median of an (n, 2) int field via histogram +
    cumsum (jnp.median sorts — measured ~4 ms on TPU for 8K MBs)."""
    n = mv_flat.shape[0]
    bins = jnp.arange(-lim, lim + 1)
    cnt = (mv_flat[:, None, :] == bins[None, :, None]).sum(0)
    cum = jnp.cumsum(cnt, axis=0)
    return ((cum >= (n + 1) // 2).argmax(axis=0) - lim).astype(jnp.int32)


def _median_center(pred_mv, per_pel: int):
    """The previous frame's median MV (`per_pel` units a pixel) as the
    nearest even pel, clamped."""
    return jnp.clip((pred_mv + per_pel) >> (per_pel // 2 + 1),
                    -(_CLIM // 2), _CLIM // 2) * 2


def centers_from(cur16, ref16, pred_mv_h, per_pel: int = 2):
    """(3, 2) even-pel centers: probe, carried-median, zero.
    pred_mv_h is the previous frame's median MV, `per_pel` units a
    pixel (2: half units)."""
    probe = coarse_probe(cur16, ref16)
    med_pel = _median_center(pred_mv_h, per_pel)
    probe = jnp.clip(probe, -_CLIM, _CLIM)
    zero = jnp.zeros(2, jnp.int32) + (cur16.reshape(-1)[0] * 0).astype(
        jnp.int32)
    return jnp.stack([probe, med_pel, zero])


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

#: the motion search this process traced first ("pallas" | "xla"),
#: None until then — read by /metrics_snapshot (`motion_search`)
_CHOSEN: str | None = None


def use_pallas() -> bool:
    """The one switch between the kernel (TPU) and its XLA mirror (CPU).
    The choice is logged once per process and kept for `motion_search()`;
    any other backend raises — the mirror is the CPU path, not a
    fallback for a platform the kernel was never built for."""
    global _CHOSEN
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"motion search supports the tpu (Pallas kernel) and cpu "
            f"(XLA mirror) backends, not {backend!r}")
    if _CHOSEN is None:
        _CHOSEN = "pallas" if backend == "tpu" else "xla"
        _LOG.info("motion search: %s on %s (%s x%d)", _CHOSEN, backend,
                  jax.devices()[0].device_kind, len(jax.devices()))
    return backend == "tpu"


def motion_search() -> str | None:
    """Which motion search this process runs: "pallas", "xla", or None
    before the first P frame was traced."""
    return _CHOSEN


def me_search_pallas(cur_y16, ref_y16, ref_u16, ref_v16, centers, lam,
                     interpret: bool = False, subpel: str = "half"):
    """Kernel path: prep (pad + per-center dynamic slices — the kernel
    contains no dynamic shifts) + the Pallas call. `interpret=True`
    runs the kernel in the Pallas interpreter — the CPU parity test
    against `me_search_xla` (tests/test_jaxme.py) exercises exactly the
    production kernel code path."""
    H, W = cur_y16.shape
    mbh, mbw, H4, RG, WcK, nch, W2K, WcuK, W2cK = _geom(H, W)
    with stage("me_prep"):
        cent = jnp.concatenate(
            [centers[:2].reshape(-1), jnp.zeros(2, jnp.int32),
             lam.reshape(1), jnp.zeros(1, jnp.int32)]).reshape(1, 8)
        cur = _pad_cur(cur_y16, H, H4, W, WcK)
        wy_ = _pad_luma_wide(ref_y16, H, H4, W, W2K)
        wu_ = _pad_chroma_wide(ref_u16, H, H4, W, W2cK)
        wv_ = _pad_chroma_wide(ref_v16, H, H4, W, W2cK)
        cys = [16 + centers[i, 0] for i in range(3)]
        cxs = [16 + centers[i, 1] for i in range(3)]
        refy = _center_stack(wy_, cys, cxs, H4 + 128, W2K)
        ccys = [8 + (centers[i, 0] >> 1) for i in range(3)]
        ccxs = [8 + (centers[i, 1] >> 1) for i in range(3)]
        refu = _center_stack(wu_, ccys, ccxs, H4 // 2 + 64, W2cK)
        refv = _center_stack(wv_, ccys, ccxs, H4 // 2 + 64, W2cK)
        ss = jnp.asarray(_ss_np(), jnp.bfloat16)
        ex = jnp.asarray(_ex_np(), jnp.bfloat16)
    with stage("me_search"):
        mvo, py, pu, pv = _me_pallas(cent, cur, refy, refu, refv, ss, ex,
                                     H=H, W=W, interpret=interpret,
                                     subpel=subpel)
        # (RG, nch, 8, 128): rows 0:4 = bmy, 4:8 = bmx, one per MB row
        # of the band; the chunk's 16 MBs sit on lanes 0..15
        bmy = mvo[:, :, 0:4, :16]                 # (RG, nch, 4, 16)
        bmx = mvo[:, :, 4:8, :16]
        bmy = bmy.transpose(0, 2, 1, 3).reshape(4 * RG, nch * 16)
        bmx = bmx.transpose(0, 2, 1, 3).reshape(4 * RG, nch * 16)
        mv = jnp.stack([bmy[:mbh, :mbw], bmx[:mbh, :mbw]], axis=-1)
        return (mv, py[:H, :W].astype(jnp.int16),
                pu[:H // 2, :W // 2].astype(jnp.int16),
                pv[:H // 2, :W // 2].astype(jnp.int16))


# ---------------------------------------------------------------------------
# split-frame encoding (SFE): banded ME with ICI halo exchange
#
# One frame is sharded as horizontal MB-row bands, one device per band
# (parallel/dispatch.SfeShardEncoder). The search itself is the SAME
# kernel/XLA program as the full-frame path, run on a band extended by
# `halo` reference rows from each neighbor band (lax.ppermute over the
# mesh interconnect); the global-motion probe and the carried-median
# center are computed with cross-band psums, so every band searches
# exactly the centers the full-frame program would. With a halo that
# covers the full candidate reach (SEARCH_RANGE + window + 6-tap
# interpolation = halo_clamp's bound) the per-MB (mv, pred) results
# are bit-identical to full-frame `me_search`; a smaller halo clamps
# the VERTICAL center magnitude so no candidate ever reads past the
# halo — a documented bound, not silent drift.
# ---------------------------------------------------------------------------

def halo_clamp(halo_rows: int) -> int:
    """Largest even vertical center magnitude (pel) whose candidate
    window (± _WR pel) plus 6-tap interpolation reach (3 rows) stays
    inside a `halo_rows`-row halo. >= _CLIM means the banded search is
    unclamped (bit-identical to full-frame)."""
    return max(0, min(_CLIM, ((halo_rows - _WR - 3) // 2) * 2))


@stage("halo")
def band_halo_exchange(plane, halo: int, axis_name, num_bands: int,
                       top_ext=None, bot_ext=None,
                       edge_top: bool = True, edge_bot: bool = True):
    """(Hb, W) band plane → (Hb + 2*halo, W) extended with `halo` REAL
    rows from each neighbor band via `lax.ppermute`; the mesh-edge
    bands (no neighbor) edge-replicate their own boundary row, exactly
    matching the full-frame search's edge padding. `axis_name=None` (or
    one band) degrades to pure edge replication — the single-device
    form of the same program.

    Farm mode (cross-HOST bands, parallel/sfefarm.py): when this mesh
    only holds a CONTIGUOUS SLICE of the global band layout, the
    neighbor rows of the slice-edge bands live on another host and
    arrive as host-injected `top_ext` / `bot_ext` (halo, W) arrays
    (each band's shard of a band-sharded input; only the edge bands'
    slices are read). `edge_top=False` means the global layout
    continues above this slice — the first local band uses `top_ext`
    instead of edge replication — and symmetrically for `edge_bot`.
    The edge flags may be TRACED bool scalars (the farm steps pass
    them as inputs, not static args, so one compiled program serves a
    slice at ANY position — a worker re-claiming a different band
    slice must not recompile its whole step set). With the defaults
    the function is byte-identical to the original local-mesh
    exchange."""
    H, W = plane.shape
    if halo > H and axis_name is not None and num_bands > 1:
        # one ppermute hop reaches ONE neighbor: a halo deeper than the
        # band itself would need rows from two bands away. Callers clamp
        # (SfeShardEncoder caps halo_rows at the band height, shrinking
        # the vertical search bound instead of failing).
        raise ValueError(f"halo {halo} exceeds band height {H}")
    top_edge = jnp.broadcast_to(plane[:1], (halo, W))
    bot_edge = jnp.broadcast_to(plane[H - 1:], (halo, W))
    first_src = top_edge if top_ext is None \
        else jnp.where(edge_top, top_edge, top_ext)
    last_src = bot_edge if bot_ext is None \
        else jnp.where(edge_bot, bot_edge, bot_ext)
    if axis_name is None or num_bands <= 1:
        return jnp.concatenate([first_src, plane, last_src])
    down = [(i, i + 1) for i in range(num_bands - 1)]
    up = [(i + 1, i) for i in range(num_bands - 1)]
    # band b's top halo = band b-1's bottom rows; bottom halo = band
    # b+1's top rows. ppermute leaves non-receiving bands zero-filled;
    # those are exactly the mesh-edge bands replaced below.
    recv_top = jax.lax.ppermute(plane[H - halo:], axis_name, down)
    recv_bot = jax.lax.ppermute(plane[:halo], axis_name, up)
    idx = jax.lax.axis_index(axis_name)
    top = jnp.where(idx == 0, first_src, recv_top)
    bot = jnp.where(idx == num_bands - 1, last_src, recv_bot)
    return jnp.concatenate([top, plane, bot])


def banded_probe_cost(cur16, ref16, real_rows, axis_name,
                      num_bands: int, sr: int = SEARCH_RANGE,
                      top_ext=None, bot_ext=None,
                      edge_top: bool = True, edge_bot: bool = True):
    """The probe's per-window cost vector, psum'd over THIS mesh's
    bands: each band contributes the partial SAD of its REAL rows for
    every candidate window (halo cells arrive from the neighbors at
    quarter-res granularity, so the window slices see exactly the
    full-frame probe's padded plane). `real_rows` masks the last
    band's padding rows out of the cost, keeping the sums equal to the
    full-frame probe's.

    Farm mode: `top_ext`/`bot_ext` are host-injected neighbor
    reference PIXEL rows (≥ 16 per side) from the adjacent band slice
    on another host; their quarter-res cells substitute for the
    ppermute halo at the slice edges, so the partial sums of every
    host add up to exactly the full-mesh psum. The caller finishes the
    cross-host reduction and argmin (probe_center_from_cost)."""
    qs = _COARSE
    qsr = sr // qs
    with stage("me_prep"):
        cq = _box_sum(cur16, qs)
        rq = _box_sum(ref16, qs)
        hc, wc = cq.shape
        rows = jnp.arange(hc)
        real_c = jnp.maximum(real_rows // qs, 1)
        # cells at/past the band's real content hold padding: clamp
        # them to the last real cell row so (a) this band's cost rows
        # are masked anyway and (b) the halo cells it SENDS (and its
        # own bottom edge replication) equal the full-frame probe's
        # bottom edge padding.
        rq = jnp.take(rq, jnp.minimum(rows, real_c - 1), axis=0)
        # the injected neighbor rows are raw recon pixels (never a
        # padded band — only the global-last band pads, and it has no
        # neighbor below), so their box sums equal the neighbor's own
        # unclamped cells bit for bit
        top_cells = _box_sum(top_ext, qs)[-qsr:] if top_ext is not None \
            else None
        bot_cells = _box_sum(bot_ext, qs)[:qsr] if bot_ext is not None \
            else None
    rq_ext = band_halo_exchange(rq, qsr, axis_name, num_bands,
                                top_ext=top_cells, bot_ext=bot_cells,
                                edge_top=edge_top, edge_bot=edge_bot)
    with stage("me_prep"):
        rq_ext = jnp.pad(rq_ext, ((0, 0), (qsr, qsr)), mode="edge")
        mask = (rows < real_c)[:, None]
        n = 2 * qsr + 1
        wins = jnp.stack(
            [jax.lax.slice(rq_ext, (oy, ox), (oy + hc, ox + wc))
             for oy in range(n) for ox in range(n)])
        cost = (jnp.abs(cq[None] - wins) * mask[None]).sum((1, 2))
        if axis_name is not None and num_bands > 1:
            cost = jax.lax.psum(cost, axis_name)
    return cost


def banded_coarse_probe(cur16, ref16, real_rows, axis_name,
                        num_bands: int, sr: int = SEARCH_RANGE):
    """`coarse_probe` decomposed across bands: the psum'd per-window
    cost (banded_probe_cost) argmin'd — the SAME global-motion center
    on every band."""
    qs = _COARSE
    qsr = sr // qs
    n = 2 * qsr + 1
    cost = banded_probe_cost(cur16, ref16, real_rows, axis_name,
                             num_bands, sr=sr)
    with stage("me_prep"):
        bi = jnp.argmin(cost).astype(jnp.int32)
        return jnp.stack([bi // n - qsr, bi % n - qsr]) * qs


def probe_center_from_cost(cost, sr: int = SEARCH_RANGE):
    """Host-side tail of the split probe (numpy): argmin the summed
    per-window costs into the (2,) pel center — the exact mirror of
    banded_coarse_probe's device argmin (both resolve ties to the
    first minimum), run by the farm coordinator thread after the
    cross-host partial-cost reduction."""
    import numpy as _np

    qs = _COARSE
    qsr = sr // qs
    n = 2 * qsr + 1
    bi = int(_np.argmin(_np.asarray(cost)))
    return _np.asarray([bi // n - qsr, bi % n - qsr], _np.int32) * qs


def banded_centers_from(cur16, ref16, pred_mv_h, real_rows,
                        halo_rows: int, axis_name, num_bands: int,
                        probe=None, per_pel: int = 2):
    """(3, 2) even-pel centers for one band's search: psum'd probe,
    carried global median, zero — the banded mirror of `centers_from`,
    with the vertical component additionally clamped to
    `halo_clamp(halo_rows)` so every candidate read stays inside the
    exchanged halo. `probe` injects a pre-computed (unclamped) global
    center — the farm path, where the probe's cross-host psum resolves
    on the host (probe_center_from_cost) before the search program."""
    if probe is None:
        probe = banded_coarse_probe(cur16, ref16, real_rows, axis_name,
                                    num_bands)
    with stage("me_prep"):
        med_pel = _median_center(pred_mv_h, per_pel)
        lims = jnp.asarray([min(halo_clamp(halo_rows), _CLIM), _CLIM],
                           jnp.int32)
        probe = jnp.clip(probe, -lims, lims)
        med_pel = jnp.clip(med_pel, -lims, lims)
        zero = jnp.zeros(2, jnp.int32) + (cur16.reshape(-1)[0] * 0).astype(
            jnp.int32)
        return jnp.stack([probe, med_pel, zero])


@stage("me_median")
def hist_counts_banded(mv_flat, mb_mask, lim: int, axis_name,
                       num_bands: int):
    """Per-band MV histogram counts over the REAL macroblocks, psum'd
    over THIS mesh's bands: (2*lim+1, 2) counts + the masked MB count.
    The local path feeds them straight into the cumsum/argmax
    (hist_median_banded); the farm path ships each host's partial to
    its peers and finishes the median on the host
    (median_from_counts)."""
    bins = jnp.arange(-lim, lim + 1)
    cnt = ((mv_flat[:, None, :] == bins[None, :, None])
           & mb_mask[:, None, None]).sum(0)
    n = mb_mask.sum()
    if axis_name is not None and num_bands > 1:
        cnt = jax.lax.psum(cnt, axis_name)
        n = jax.lax.psum(n, axis_name)
    return cnt, n


def hist_median_banded(mv_flat, mb_mask, lim: int, axis_name,
                       num_bands: int):
    """`hist_median` decomposed across bands: per-band histogram counts
    over the REAL macroblocks psum before the cumsum/argmax, so every
    band carries the same global median (the next frame's temporal
    search center)."""
    cnt, n = hist_counts_banded(mv_flat, mb_mask, lim, axis_name,
                                num_bands)
    with stage("me_median"):
        cum = jnp.cumsum(cnt, axis=0)
        return ((cum >= (n + 1) // 2).argmax(axis=0)
                - lim).astype(jnp.int32)


def median_from_counts(cnt, n, lim: int):
    """Host-side tail of the split median (numpy): the exact mirror of
    hist_median_banded's cumsum/argmax over the cross-host-summed
    counts — every farm host derives the SAME (2,) int32 median the
    full-mesh psum would have carried on device."""
    import numpy as _np

    cum = _np.cumsum(_np.asarray(cnt, _np.int64), axis=0)
    return (_np.argmax(cum >= (int(n) + 1) // 2, axis=0)
            - lim).astype(_np.int32)


def me_search_banded(cur_y16, ref_y16, ref_u16, ref_v16, pred_mv_h, qp,
                     *, halo_rows: int, num_bands: int, axis_name,
                     real_rows, ext=None, edge_top: bool = True,
                     edge_bot: bool = True, probe=None,
                     return_hist: bool = False, subpel: str = "half"):
    """Full ME+MC for one P frame of ONE BAND (the SFE search).

    cur/ref planes are this band's (Hb, W) shard (Hb a multiple of 16);
    `halo_rows` (a multiple of 16) reference rows per side arrive from
    the neighbor bands via :func:`band_halo_exchange`; `real_rows` is
    the traced count of real pixel rows (the last band may carry
    padding rows — masked out of the probe and median, and their MBs
    are never entropy-coded by the host). The search runs the
    UNCHANGED kernel/XLA program on the extended planes and slices the
    band's MB rows back out; per-MB selection is independent, so the
    extended rows' results are simply discarded.

    Farm mode (cross-host band slices): `ext` = (top_y, bot_y, top_u,
    bot_u, top_v, bot_v) host-injected neighbor reference rows for the
    slice edges (with `edge_top`/`edge_bot` marking which edges are
    true frame edges), `probe` = the host-resolved global probe center
    (banded_probe_cost → cross-host sum → probe_center_from_cost), and
    `return_hist=True` swaps the on-device median for the per-host
    histogram partial (cnt, n) so the caller can finish the median
    across hosts (median_from_counts). With identical injected values
    the per-MB (mv, pred) results are bit-identical to the full-mesh
    psum/ppermute program.

    Returns (mv (Hb/16, mbw, 2) int32 in `subpel`'s units, as
    `pred_mv_h` is, pred_y, pred_u, pred_v int16 band planes, med_mv_h
    (2,) int32 — the GLOBAL median), or with `return_hist` (mv, py, pu,
    pv, cnt, n). The quarter window lies inside the integer one, so
    the halo a band needs does not grow with `subpel`."""
    Hb, W = cur_y16.shape
    if halo_rows <= 0 or halo_rows % 16:
        raise ValueError("halo_rows must be a positive multiple of 16")
    halo = halo_rows
    ty, by, tu, bu, tv, bv = ext if ext is not None else (None,) * 6
    ry_ext = band_halo_exchange(ref_y16, halo, axis_name, num_bands,
                                top_ext=ty, bot_ext=by,
                                edge_top=edge_top, edge_bot=edge_bot)
    ru_ext = band_halo_exchange(ref_u16, halo // 2, axis_name, num_bands,
                                top_ext=tu, bot_ext=bu,
                                edge_top=edge_top, edge_bot=edge_bot)
    rv_ext = band_halo_exchange(ref_v16, halo // 2, axis_name, num_bands,
                                top_ext=tv, bot_ext=bv,
                                edge_top=edge_top, edge_bot=edge_bot)
    with stage("me_prep"):
        # halo rows of CUR only feed the discarded extension MBs' SADs;
        # edge replication keeps them in range
        cur_ext = jnp.concatenate([
            jnp.broadcast_to(cur_y16[:1], (halo, W)), cur_y16,
            jnp.broadcast_to(cur_y16[Hb - 1:], (halo, W))])
    per_pel = MV_PER_PEL[subpel]
    centers = banded_centers_from(cur_y16, ref_y16, pred_mv_h, real_rows,
                                  halo, axis_name, num_bands, probe=probe,
                                  per_pel=per_pel)
    with stage("me_prep"):
        lam = jnp.asarray(_LAMBDAS[subpel])[jnp.clip(qp, 0, 51)]
    search = me_search_pallas if use_pallas() else me_search_xla
    mv_e, py_e, pu_e, pv_e = search(
        cur_ext, ry_ext, ru_ext, rv_ext, centers, lam, subpel=subpel)
    hm = halo // 16
    mbh_b = Hb // 16
    with stage("me_search"):
        mv = jax.lax.slice_in_dim(mv_e, hm, hm + mbh_b, axis=0)
        py = jax.lax.slice_in_dim(py_e, halo, halo + Hb, axis=0)
        pu = jax.lax.slice_in_dim(pu_e, halo // 2, (halo + Hb) // 2,
                                  axis=0)
        pv = jax.lax.slice_in_dim(pv_e, halo // 2, (halo + Hb) // 2,
                                  axis=0)
    with stage("me_median"):
        mb_mask = jnp.repeat(jnp.arange(mbh_b) * 16 < real_rows,
                             mv.shape[1])
        mv_flat = mv.reshape(-1, 2)
    if return_hist:
        cnt, n = hist_counts_banded(mv_flat, mb_mask,
                                    per_pel * SEARCH_RANGE,
                                    axis_name, num_bands)
        return mv, py, pu, pv, cnt, n
    med = hist_median_banded(mv_flat, mb_mask, per_pel * SEARCH_RANGE,
                             axis_name, num_bands)
    return mv, py, pu, pv, med


def me_search(cur_y16, ref_y16, ref_u16, ref_v16, pred_mv_h, qp,
              subpel: str = "half"):
    """Full ME+MC for one P frame. Inputs int16 planes (H, W multiples
    of 16); pred_mv_h (2,) int32 (previous frame's median, in
    `subpel`'s units as every vector here); qp the frame's quantizer
    (drives the MV-cost lambda).
    Returns (mv (mbh, mbw, 2) int32, pred_y, pred_u, pred_v int16,
    med_mv_h (2,) int32)."""
    per_pel = MV_PER_PEL[subpel]
    with stage("me_prep"):
        centers = centers_from(cur_y16, ref_y16, pred_mv_h, per_pel)
        lam = jnp.asarray(_LAMBDAS[subpel])[jnp.clip(qp, 0, 51)]
    search = me_search_pallas if use_pallas() else me_search_xla
    mv, pred_y, pred_u, pred_v = search(
        cur_y16, ref_y16, ref_u16, ref_v16, centers, lam, subpel=subpel)
    med = hist_median(mv.reshape(-1, 2), per_pel * SEARCH_RANGE)
    return mv, pred_y, pred_u, pred_v, med
