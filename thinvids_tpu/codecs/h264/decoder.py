"""H.264 baseline decoder (subset matching the encoder's profile).

Independent implementation of the decode direction — parses Annex-B
streams (SPS/PPS, IDR + non-IDR slices, CAVLC, I16x16, I_NxN (Intra4x4)
in I slices and P_L0_16x16,
Intra16x16 macroblocks inside P slices, multi-slice pictures) and
reconstructs frames. Used by tests as the
in-repo conformance check of encoder output (alongside the libavcodec
ctypes oracle — which this container may not have) and by the
stamp/seam verification tooling to decode without external binaries.

Scope grows with the encoder: one reference frame (the previous
decoded picture), whole-MB partitions, quarter-sample motion vectors
(§8.4.2.2.1 luma, §8.4.2.2.2 chroma at eighth fractions — every vector
here is in QUARTER-sample units, as mvd is coded, whatever `subpel` the
encoder ran with: a half-sample encoder's stream simply holds even
vectors), the in-loop filter as each slice signals it (idc 0, 1 or 2, offsets
0), and pictures split into any number of slices —
the split-frame-encoding path emits one slice per MB-row band, and
this decoder applies the same §7.4.3 cross-slice neighbor
unavailability the encoder's band packers assume.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ..h264 import cavlc
from ...core.types import ChromaFormat, Frame, VideoMeta
from ...io.bits import BitReader, split_annexb
from .headers import (
    NAL_PPS,
    NAL_SLICE_IDR,
    NAL_SLICE_NON_IDR,
    NAL_SPS,
    PPS,
    SLICE_TYPE_I,
    SLICE_TYPE_P,
    SPS,
    SliceHeader,
)
from .inter import _CODE_TO_CBP_INTER, _median3
from .intra import (
    CHROMA_BLOCK_ORDER,
    I4_DC,
    I4_NO_TOP_RIGHT,
    LUMA_BLOCK_ORDER,
    i4_neighbours,
    i4_pred_mode,
    predict_chroma8,
    predict_luma4,
    predict_luma16,
    reconstruct_chroma8,
    reconstruct_luma16,
    reconstruct_luma4,
)
from .transform import chroma_qp, dequant_4x4, inverse_4x4, inverse_zigzag

#: luma interpolation pad: |mv| <= 16 pel, a quarter position's next
#: sample (1) plus the 6-tap reach (3)
_MC_PAD = 24
_MC_PAD_C = 12


@dataclasses.dataclass
class DecodedStream:
    meta: VideoMeta
    frames: list[Frame]
    #: per picture its (mbh, mbw, 2) motion vectors (dy, dx) in
    #: quarter-sample units, skipped macroblocks' inferred ones
    #: included; None for an intra picture
    mvs: list = dataclasses.field(default_factory=list)
    #: per P picture the (mbh, mbw) bool map of its intra macroblocks
    #: (their `mvs` entries read 0); None for an intra picture
    intra_mbs: list = dataclasses.field(default_factory=list)
    #: per picture the (mbh, mbw) bool map of its Intra4x4 macroblocks
    #: and the (4 mbh, 4 mbw) Intra4x4PredMode of every block (DC
    #: outside them)
    i4_mbs: list = dataclasses.field(default_factory=list)
    i4_modes: list = dataclasses.field(default_factory=list)


class _Picture:
    """One picture being assembled from its (possibly many) slices."""

    def __init__(self, sps: SPS) -> None:
        self.mbw, self.mbh = sps.mb_width, sps.mb_height
        mbw, mbh = self.mbw, self.mbh
        self.y = np.zeros((16 * mbh, 16 * mbw), np.uint8)
        self.u = np.zeros((8 * mbh, 8 * mbw), np.uint8)
        self.v = np.zeros((8 * mbh, 8 * mbw), np.uint8)
        # CAVLC nC neighbor state (total_coeff per 4x4 block), shared
        # across the picture's slices; cross-slice neighbors are never
        # CONSULTED (availability checks below), matching §7.4.3.
        self.luma_counts = np.zeros((4 * mbh, 4 * mbw), np.int32)
        self.chroma_counts = np.zeros((2, 2 * mbh, 2 * mbw), np.int32)
        self.mv = np.zeros((mbh, mbw, 2), np.int32)     # (dy, dx) quarter
        # the P picture's intra macroblocks (§7.3.5 mb_type 5..30):
        # refIdx -1 to a neighbour's vector prediction, bS 3 / 4 to
        # the filter
        self.intra_mb = np.zeros((mbh, mbw), bool)
        # Intra4x4PredMode of every 4x4 block (§8.3.1.1 predicts a
        # block's from its neighbours'); DC wherever the macroblock is
        # not Intra4x4
        self.i4_mode = np.full((4 * mbh, 4 * mbw), I4_DC, np.int32)
        # per picture, for callers: the (mbh, mbw) map of Intra4x4
        # macroblocks
        self.i4_mb = np.zeros((mbh, mbw), bool)
        self.decoded = 0                                # MBs decoded so far
        # in-loop deblocking state: the effective QP_Y of every MB (the
        # running slice QP after mb_qp_delta; uncoded MBs keep the
        # running value — §8.7's QP for skipped MBs), the picture's
        # coding type, and of every MB the slice it came in and that
        # slice's disable_deblocking_filter_idc (1 until decoded).
        self.qp_mb = np.zeros((mbh, mbw), np.int32)
        self.intra = True
        self.slices = 0
        self.slice_of = np.zeros(mbh * mbw, np.int32)
        self.idc_of = np.ones(mbh * mbw, np.int32)

    def deblock_edges(self):
        """§8.7's filterInternalEdgesFlag / filterLeftMbEdgeFlag /
        filterTopMbEdgeFlag of every MB, (mbh, mbw) masks: idc 1 leaves
        an MB alone, idc 2 leaves its edges to another slice alone."""
        idc = self.idc_of.reshape(self.mbh, self.mbw)
        sl = self.slice_of.reshape(self.mbh, self.mbw)
        on = idc != 1
        left = np.zeros_like(on)
        top = np.zeros_like(on)
        left[:, 1:] = on[:, 1:] & ((idc[:, 1:] == 0)
                                   | (sl[:, 1:] == sl[:, :-1]))
        top[1:] = on[1:] & ((idc[1:] == 0) | (sl[1:] == sl[:-1]))
        return on, left, top


def _tap6(x: np.ndarray, axis: int) -> np.ndarray:
    """§8.4.2.2.1's 6-tap filter (1, -5, 20, 20, -5, 1) along `axis`,
    unrounded: out[l] lies half a sample past x[l], between x[l] and
    x[l+1]. Wrapped edge rows/lanes stay inside the MC pad and are
    never read."""
    r = lambda k: np.roll(x, k, axis=axis)
    return r(2) - 5 * r(1) + 20 * x + 20 * r(-1) - 5 * r(-2) + r(-3)


def _half_sample_planes(ref_y: np.ndarray):
    """(G, b, h, j) int32 planes over the edge-padded reference: the
    integer samples and the three half-sample positions of equations
    8-241..8-247 at every integer sample's offset (b to its right, h
    below it, j below and right)."""
    G = np.pad(ref_y.astype(np.int32), _MC_PAD, mode="edge")
    b1 = _tap6(G, axis=1)
    b = np.clip((b1 + 16) >> 5, 0, 255)
    h = np.clip((_tap6(G, axis=0) + 16) >> 5, 0, 255)
    j = np.clip((_tap6(b1, axis=0) + 512) >> 10, 0, 255)
    return G, b, h, j


# §8.4.2.2.1, Figure 8-4 and Table 8-12: the sixteen luma positions of
# a sample, one function each, by the names the figure gives them. A
# position function takes the (G, b, h, j) planes and the block's
# integer sample (r, c) in them. H, M are the integer samples right of
# and below G, m the h-type sample under H, s the b-type sample right
# of M; the twelve quarter positions are the rounded means of the two
# samples equations 8-250..8-261 name.

def _sample(plane: int, dr: int = 0, dc: int = 0):
    def position(planes, r, c):
        return planes[plane][r + dr:r + dr + 16, c + dc:c + dc + 16]
    return position


def _mean_of(p, q):
    def position(planes, r, c):
        return (p(planes, r, c) + q(planes, r, c) + 1) >> 1
    return position


_G, _b, _h, _j = (_sample(k) for k in range(4))
_H, _M = _sample(0, 0, 1), _sample(0, 1, 0)
_m, _s = _sample(2, 0, 1), _sample(1, 1, 0)
#: Table 8-12: [yFrac][xFrac] -> the position's function
_LUMA_POSITIONS = (
    (_G, _mean_of(_G, _b), _b, _mean_of(_H, _b)),                # G a b c
    (_mean_of(_G, _h), _mean_of(_b, _h), _mean_of(_b, _j),
     _mean_of(_b, _m)),                                          # d e f g
    (_h, _mean_of(_h, _j), _j, _mean_of(_j, _m)),                # h i j k
    (_mean_of(_M, _h), _mean_of(_h, _s), _mean_of(_j, _s),
     _mean_of(_m, _s)),                                          # n p q r
)


class _RefFrame:
    """Previous decoded picture + lazily-built interpolation planes."""

    def __init__(self, pic: _Picture) -> None:
        self.y, self.u, self.v = pic.y, pic.u, pic.v
        self._planes = None
        self._cu = None
        self._cv = None

    def luma_pred(self, my: int, mx: int, mv) -> np.ndarray:
        """The (16, 16) prediction at quarter-sample vector `mv`."""
        if self._planes is None:
            self._planes = _half_sample_planes(self.y)
        dy, dx = int(mv[0]), int(mv[1])
        r0 = _MC_PAD + 16 * my + (dy >> 2)
        c0 = _MC_PAD + 16 * mx + (dx >> 2)
        return _LUMA_POSITIONS[dy & 3][dx & 3](self._planes, r0, c0)

    def chroma_pred(self, my: int, mx: int, mv):
        """(pred_u, pred_v) via the §8.4.2.2.2 bilinear: a quarter luma
        sample is an eighth of a chroma sample."""
        if self._cu is None:
            self._cu = np.pad(self.u.astype(np.int32), _MC_PAD_C,
                              mode="edge")
            self._cv = np.pad(self.v.astype(np.int32), _MC_PAD_C,
                              mode="edge")
        dy, dx = int(mv[0]), int(mv[1])
        oy, ox = dy >> 3, dx >> 3
        ey, ex = dy & 7, dx & 7
        r0 = _MC_PAD_C + 8 * my + oy
        c0 = _MC_PAD_C + 8 * mx + ox

        def bil(C):
            a = C[r0:r0 + 8, c0:c0 + 8]
            b = C[r0:r0 + 8, c0 + 1:c0 + 9]
            c = C[r0 + 1:r0 + 9, c0:c0 + 8]
            d = C[r0 + 1:r0 + 9, c0 + 1:c0 + 9]
            return ((8 - ex) * (8 - ey) * a + ex * (8 - ey) * b
                    + (8 - ex) * ey * c + ex * ey * d + 32) >> 6

        return bil(self._cu), bil(self._cv)


def _mvp_and_skip(pic: _Picture, my: int, mx: int, slice_first: int):
    """(mvp, skip_mv) for MB (my, mx) — §8.4.1.3 median prediction with
    the C→D fallback and §8.4.1.1 P_Skip inference, neighbors limited
    to the CURRENT slice (the decoder-side mirror of inter.predict_mvs,
    which the band packers apply in band-local coordinates)."""
    mbw = pic.mbw
    mi = my * mbw + mx
    zero = np.zeros(2, np.int32)

    def neighbour(ny, nx):
        """(refIdx is 0, mv) of an available macroblock: an intra one
        has refIdx -1 and no vector (§8.4.1.3.2)."""
        if pic.intra_mb[ny, nx]:
            return False, zero
        return True, pic.mv[ny, nx]

    avail_a = mx > 0 and mi - 1 >= slice_first
    avail_b = my > 0 and mi - mbw >= slice_first
    ref_a, mva = neighbour(my, mx - 1) if avail_a else (False, zero)
    ref_b, mvb = neighbour(my - 1, mx) if avail_b else (False, zero)
    if my > 0 and mx + 1 < mbw and mi - mbw + 1 >= slice_first:
        avail_c = True
        ref_c, mvc = neighbour(my - 1, mx + 1)
    elif my > 0 and mx > 0 and mi - mbw - 1 >= slice_first:
        avail_c = True
        ref_c, mvc = neighbour(my - 1, mx - 1)
    else:
        avail_c, ref_c, mvc = False, False, zero
    if not avail_b and not avail_c and avail_a:
        ref_b, mvb, ref_c, mvc = ref_a, mva, ref_a, mva
    if int(ref_a) + int(ref_b) + int(ref_c) == 1:
        # the one neighbour that refers to the same picture
        p = mva if ref_a else (mvb if ref_b else mvc)
    else:
        p = np.array([_median3(int(mva[0]), int(mvb[0]), int(mvc[0])),
                      _median3(int(mva[1]), int(mvb[1]), int(mvc[1]))],
                     np.int32)
    if (not avail_a or not avail_b
            or (ref_a and mva[0] == 0 and mva[1] == 0)
            or (ref_b and mvb[0] == 0 and mvb[1] == 0)):
        skip = zero
    else:
        skip = p
    return np.asarray(p, np.int32), np.asarray(skip, np.int32)


def _decode_chroma_residual(br: BitReader, pic: _Picture, my: int, mx: int,
                            cbp_chroma: int, a_ok: bool, b_ok: bool):
    """(chroma_dc (2, 4), chroma_ac (2, 4, 15)) of one macroblock."""
    chroma_counts = pic.chroma_counts
    chroma_dc = np.zeros((2, 4), np.int32)
    if cbp_chroma > 0:
        for ci in range(2):
            chroma_dc[ci] = cavlc.decode_residual(br, -1, 4)
    chroma_ac = np.zeros((2, 4, 15), np.int32)
    cy0, cx0 = 2 * my, 2 * mx
    for ci in range(2):
        for bi, (bx, by) in enumerate(CHROMA_BLOCK_ORDER):
            gy, gx = cy0 + by, cx0 + bx
            if cbp_chroma == 2:
                na = (int(chroma_counts[ci, gy, gx - 1])
                      if gx > cx0 or a_ok else None) if gx > 0 else None
                nb = (int(chroma_counts[ci, gy - 1, gx])
                      if gy > cy0 or b_ok else None) if gy > 0 else None
                coeffs = cavlc.decode_residual(
                    br, cavlc.luma_nc(na, nb), 15)
                chroma_ac[ci, bi] = coeffs
                chroma_counts[ci, gy, gx] = sum(1 for c in coeffs if c)
            else:
                chroma_counts[ci, gy, gx] = 0
    return chroma_dc, chroma_ac


def _recon_intra_chroma(pic: _Picture, my: int, mx: int, chroma_mode: int,
                        chroma_dc, chroma_ac, qp: int, a_ok: bool,
                        b_ok: bool, d_ok: bool) -> None:
    """Both chroma planes of an intra macroblock of either kind:
    §8.3.4's prediction from the picture's unfiltered samples, plus
    the residual at the macroblock's chroma QP."""
    qpc = chroma_qp(qp)
    for ci, plane in enumerate((pic.u, pic.v)):
        ctop = plane[8 * my - 1, 8 * mx:8 * mx + 8] if b_ok else None
        cleft = plane[8 * my:8 * my + 8, 8 * mx - 1] if a_ok else None
        ctl = int(plane[8 * my - 1, 8 * mx - 1]) if d_ok else None
        cpred = predict_chroma8(chroma_mode, ctop, cleft, ctl)
        plane[8 * my:8 * my + 8, 8 * mx:8 * mx + 8] = reconstruct_chroma8(
            cpred, chroma_dc[ci], chroma_ac[ci], qpc)


def _decode_intra16_mb(br: BitReader, pic: _Picture, mi: int, first: int,
                       i_type: int, qp: int) -> int:
    """One Intra16x16 macroblock after its mb_type (`i_type`: Table
    7-11's, 1..24), of an I slice or — §7.3.5, Table 7-13: mb_type 5 +
    i_type — of a P slice: intra_chroma_pred_mode, mb_qp_delta, the
    residual, and the reconstruction predicted from the CURRENT
    picture's unfiltered samples, whatever kind the neighbours they
    belong to are (constrained_intra_pred_flag is 0). Returns the QP
    after the macroblock's delta."""
    mbw = pic.mbw
    my, mx = divmod(mi, mbw)
    y = pic.y
    luma_counts = pic.luma_counts
    luma_mode = (i_type - 1) % 4
    cbp_chroma = ((i_type - 1) // 4) % 3
    cbp_luma = 15 if (i_type - 1) >= 12 else 0
    chroma_mode = br.ue()
    qp += br.se()                       # mb_qp_delta
    pic.qp_mb[my, mx] = qp

    # in-slice neighbor availability (§7.4.3): an MB in another
    # slice is unavailable to prediction AND to nC derivation
    a_ok = mx > 0 and mi - 1 >= first
    b_ok = my > 0 and mi - mbw >= first
    d_ok = my > 0 and mx > 0 and mi - mbw - 1 >= first

    by0, bx0 = 4 * my, 4 * mx
    na = int(luma_counts[by0, bx0 - 1]) if a_ok else None
    nb = int(luma_counts[by0 - 1, bx0]) if b_ok else None
    luma_dc = np.array(
        cavlc.decode_residual(br, cavlc.luma_nc(na, nb), 16), np.int32)

    luma_ac = np.zeros((16, 15), np.int32)
    for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
        gy, gx = by0 + by, bx0 + bx
        if cbp_luma:
            na = (int(luma_counts[gy, gx - 1])
                  if gx > bx0 or a_ok else None) if gx > 0 else None
            nb = (int(luma_counts[gy - 1, gx])
                  if gy > by0 or b_ok else None) if gy > 0 else None
            coeffs = cavlc.decode_residual(br, cavlc.luma_nc(na, nb), 15)
            luma_ac[bi] = coeffs
            luma_counts[gy, gx] = sum(1 for c in coeffs if c)
        else:
            luma_counts[gy, gx] = 0

    chroma_dc, chroma_ac = _decode_chroma_residual(
        br, pic, my, mx, cbp_chroma, a_ok, b_ok)

    # Reconstruct.
    top = y[16 * my - 1, 16 * mx:16 * mx + 16] if b_ok else None
    left = y[16 * my:16 * my + 16, 16 * mx - 1] if a_ok else None
    tl = int(y[16 * my - 1, 16 * mx - 1]) if d_ok else None
    pred = predict_luma16(luma_mode, top, left, tl)
    y[16 * my:16 * my + 16, 16 * mx:16 * mx + 16] = reconstruct_luma16(
        pred, luma_dc, luma_ac, qp)
    _recon_intra_chroma(pic, my, mx, chroma_mode, chroma_dc, chroma_ac, qp,
                        a_ok, b_ok, d_ok)
    return qp


def _decode_intra4x4_mb(br: BitReader, pic: _Picture, mi: int, first: int,
                        qp: int) -> int:
    """One I_NxN macroblock after its mb_type (§7.3.5, transform 4x4):
    sixteen prev_intra4x4_pred_mode_flag / rem_intra4x4_pred_mode
    against §8.3.1.1's predicted mode, intra_chroma_pred_mode,
    coded_block_pattern as me(v) by Table 9-4's Intra column,
    mb_qp_delta ONLY where the pattern is not 0, then each block, in
    decoding order, predicted from the samples already reconstructed
    (§8.3.1.2) and rebuilt from its sixteen levels. Returns the QP
    after the macroblock."""
    from .encoder import CODE_TO_CBP_INTRA

    mbw = pic.mbw
    my, mx = divmod(mi, mbw)
    y = pic.y
    a_ok = mx > 0 and mi - 1 >= first
    b_ok = my > 0 and mi - mbw >= first
    c_ok = my > 0 and mx + 1 < mbw and mi - mbw + 1 >= first
    d_ok = my > 0 and mx > 0 and mi - mbw - 1 >= first
    by0, bx0 = 4 * my, 4 * mx

    modes = np.zeros(16, np.int32)
    for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
        gy, gx = by0 + by, bx0 + bx
        pm = i4_pred_mode(
            int(pic.i4_mode[gy, gx - 1]) if bx or a_ok else None,
            int(pic.i4_mode[gy - 1, gx]) if by or b_ok else None)
        if br.read_bit():
            mode = pm
        else:
            rem = br.read(3)
            mode = rem + (rem >= pm)
        modes[bi] = pic.i4_mode[gy, gx] = mode
    chroma_mode = br.ue()
    code = br.ue()
    if code > 47:
        raise ValueError(f"coded_block_pattern code {code} out of range")
    cbp = CODE_TO_CBP_INTRA[code]
    cbp_luma, cbp_chroma = cbp & 15, cbp >> 4
    if cbp:
        qp += br.se()                   # mb_qp_delta
    pic.qp_mb[my, mx] = qp
    pic.i4_mb[my, mx] = True

    levels = np.zeros((16, 16), np.int32)
    for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
        gy, gx = by0 + by, bx0 + bx
        if cbp_luma & (1 << (bi // 4)):
            na = int(pic.luma_counts[gy, gx - 1]) if bx or a_ok else None
            nb = int(pic.luma_counts[gy - 1, gx]) if by or b_ok else None
            coeffs = cavlc.decode_residual(br, cavlc.luma_nc(na, nb), 16)
            levels[bi] = coeffs
            pic.luma_counts[gy, gx] = sum(1 for c in coeffs if c)
        else:
            pic.luma_counts[gy, gx] = 0
    chroma_dc, chroma_ac = _decode_chroma_residual(
        br, pic, my, mx, cbp_chroma, a_ok, b_ok)

    for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
        gy, gx = by0 + by, bx0 + bx
        has_top, has_left = bool(by or b_ok), bool(bx or a_ok)
        if by:
            has_tr = bi not in I4_NO_TOP_RIGHT
        else:
            has_tr = b_ok if bx < 3 else c_ok
        # the corner's macroblock: this one, A, B or D (a slice may
        # start inside a row, where B is there and D is not)
        has_corner = (bool(bx) or a_ok) if by else (b_ok if bx else d_ok)
        top, left, corner = i4_neighbours(y, gx, gy, has_top, has_left,
                                          has_tr, has_corner)
        mode = int(modes[bi])
        if (mode in (0, 3, 7) and top is None) \
                or (mode in (1, 8) and left is None) \
                or (mode in (4, 5, 6) and corner is None):
            raise ValueError(
                f"Intra4x4 mode {mode} without its neighbours at MB {mi}")
        pred = predict_luma4(mode, top, left, corner)
        y[4 * gy:4 * gy + 4, 4 * gx:4 * gx + 4] = reconstruct_luma4(
            pred, levels[bi], qp)
    _recon_intra_chroma(pic, my, mx, chroma_mode, chroma_dc, chroma_ac, qp,
                        a_ok, b_ok, d_ok)
    return qp


def _decode_islice(br: BitReader, pic: _Picture,
                   header: SliceHeader) -> None:
    """Decode one I slice (any first_mb) into the picture state."""
    nmb = pic.mbw * pic.mbh
    qp = header.qp
    mi = header.first_mb
    while mi < nmb and br.more_rbsp_data():
        mb_type = br.ue()
        if mb_type == 0:
            qp = _decode_intra4x4_mb(br, pic, mi, header.first_mb, qp)
        elif not 1 <= mb_type <= 24:
            raise ValueError(f"unsupported I mb_type {mb_type}")
        else:
            qp = _decode_intra16_mb(br, pic, mi, header.first_mb, mb_type,
                                    qp)
        pic.decoded += 1
        mi += 1


def _recon_p_mb(pic: _Picture, ref: _RefFrame, my: int, mx: int, mv,
                luma16, chroma_dc, chroma_ac, qp: int) -> None:
    pred = ref.luma_pred(my, mx, mv)
    out = np.empty((16, 16), np.int32)
    for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
        z = inverse_zigzag(np.asarray(luma16[bi], np.int32))
        d = dequant_4x4(z, qp)                 # inter: no luma DC split
        r = (inverse_4x4(d) + 32) >> 6
        p = pred[4 * by:4 * by + 4, 4 * bx:4 * bx + 4]
        out[4 * by:4 * by + 4, 4 * bx:4 * bx + 4] = p + r
    pic.y[16 * my:16 * my + 16, 16 * mx:16 * mx + 16] = \
        np.clip(out, 0, 255).astype(np.uint8)
    qpc = chroma_qp(qp)
    pu, pv = ref.chroma_pred(my, mx, mv)
    for ci, (plane, cpred) in enumerate(((pic.u, pu), (pic.v, pv))):
        plane[8 * my:8 * my + 8, 8 * mx:8 * mx + 8] = reconstruct_chroma8(
            cpred, chroma_dc[ci], chroma_ac[ci], qpc)


def _decode_pslice(br: BitReader, pic: _Picture, header: SliceHeader,
                   ref: _RefFrame) -> None:
    """Decode one P slice (any first_mb): skip runs, P_L0_16x16 and
    Intra16x16 MBs."""
    mbw, mbh = pic.mbw, pic.mbh
    nmb = mbw * mbh
    first = header.first_mb
    qp = header.qp
    zero16 = np.zeros((16, 16), np.int32)
    zero_cdc = np.zeros((2, 4), np.int32)
    zero_cac = np.zeros((2, 4, 15), np.int32)

    mi = first
    while mi < nmb and br.more_rbsp_data():
        run = br.ue()                          # mb_skip_run
        for _ in range(run):
            if mi >= nmb:
                raise ValueError("mb_skip_run past end of picture")
            my, mx = divmod(mi, mbw)
            _, skip_mv = _mvp_and_skip(pic, my, mx, first)
            pic.mv[my, mx] = skip_mv
            pic.qp_mb[my, mx] = qp          # skip: running QP (§8.7)
            _recon_p_mb(pic, ref, my, mx, skip_mv, zero16, zero_cdc,
                        zero_cac, qp)
            pic.decoded += 1
            mi += 1
        if mi >= nmb or not br.more_rbsp_data():
            break                              # trailing skip run
        my, mx = divmod(mi, mbw)
        mb_type = br.ue()
        if 6 <= mb_type <= 29:
            # Table 7-13: an Intra16x16 macroblock, 5 + Table 7-11's
            qp = _decode_intra16_mb(br, pic, mi, first, mb_type - 5, qp)
            pic.intra_mb[my, mx] = True
            pic.mv[my, mx] = 0
            pic.decoded += 1
            mi += 1
            continue
        if mb_type != 0:
            raise ValueError(f"unsupported P mb_type {mb_type}")
        mvd_x = br.se()                        # quarter samples, x first
        mvd_y = br.se()
        mvp, _ = _mvp_and_skip(pic, my, mx, first)
        mv = np.array([mvp[0] + mvd_y, mvp[1] + mvd_x], np.int32)
        pic.mv[my, mx] = mv
        cbp = _CODE_TO_CBP_INTER[br.ue()]
        cbp_luma, cbp_chroma = cbp & 15, cbp >> 4
        if cbp:
            qp += br.se()                      # mb_qp_delta
        pic.qp_mb[my, mx] = qp

        a_ok = mx > 0 and mi - 1 >= first
        b_ok = my > 0 and mi - mbw >= first
        by0, bx0 = 4 * my, 4 * mx
        luma16 = np.zeros((16, 16), np.int32)
        for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
            gy, gx = by0 + by, bx0 + bx
            if cbp_luma & (1 << (bi // 4)):
                na = (int(pic.luma_counts[gy, gx - 1])
                      if gx > bx0 or a_ok else None) if gx > 0 else None
                nb = (int(pic.luma_counts[gy - 1, gx])
                      if gy > by0 or b_ok else None) if gy > 0 else None
                coeffs = cavlc.decode_residual(br, cavlc.luma_nc(na, nb), 16)
                luma16[bi] = coeffs
                pic.luma_counts[gy, gx] = sum(1 for c in coeffs if c)
            else:
                pic.luma_counts[gy, gx] = 0

        chroma_dc = np.zeros((2, 4), np.int32)
        if cbp_chroma > 0:
            for ci in range(2):
                chroma_dc[ci] = cavlc.decode_residual(br, -1, 4)
        chroma_ac = np.zeros((2, 4, 15), np.int32)
        cy0, cx0 = 2 * my, 2 * mx
        for ci in range(2):
            for bi, (bx, by) in enumerate(CHROMA_BLOCK_ORDER):
                gy, gx = cy0 + by, cx0 + bx
                if cbp_chroma == 2:
                    na = (int(pic.chroma_counts[ci, gy, gx - 1])
                          if gx > cx0 or a_ok else None) if gx > 0 else None
                    nb = (int(pic.chroma_counts[ci, gy - 1, gx])
                          if gy > cy0 or b_ok else None) if gy > 0 else None
                    coeffs = cavlc.decode_residual(
                        br, cavlc.luma_nc(na, nb), 15)
                    chroma_ac[ci, bi] = coeffs
                    pic.chroma_counts[ci, gy, gx] = sum(
                        1 for c in coeffs if c)
                else:
                    pic.chroma_counts[ci, gy, gx] = 0

        _recon_p_mb(pic, ref, my, mx, mv, luma16, chroma_dc, chroma_ac, qp)
        pic.decoded += 1
        mi += 1


def decode_annexb(stream: bytes) -> DecodedStream:
    """Decode an Annex-B byte stream produced by this package's encoder."""
    sps: SPS | None = None
    pps: PPS | None = None
    frames: list[Frame] = []
    mvs: list = []
    intra_mbs: list = []
    i4_mbs: list = []
    i4_modes: list = []
    pic: _Picture | None = None
    ref: _RefFrame | None = None

    def finish_picture() -> None:
        nonlocal pic, ref
        if pic is None:
            return
        if pic.decoded != pic.mbw * pic.mbh:
            raise ValueError(
                f"picture ended with {pic.decoded} of "
                f"{pic.mbw * pic.mbh} MBs decoded (missing slice?)")
        if (pic.idc_of != 1).any():
            # §8.7 in-loop filter over the whole decoded picture
            # (codecs/h264/deblock.py, the filter the encoder runs):
            # the filtered planes are both the output frame and the
            # next P picture's reference — exactly the encoder's recon
            # carry. Intra prediction inside the picture already ran
            # on unfiltered samples, as the spec requires.
            from .deblock import deblock_frame

            nz4 = None if pic.intra else (pic.luma_counts > 0)
            pic.y, pic.u, pic.v = deblock_frame(
                pic.y, pic.u, pic.v, pic.qp_mb, intra=pic.intra,
                nz4=nz4, mv=None if pic.intra else pic.mv, mv_per_pel=4,
                edges=pic.deblock_edges(),
                intra_mb=pic.intra_mb if pic.intra_mb.any() else None)
        w, h = sps.width, sps.height
        frames.append(Frame(
            pic.y[:h, :w], pic.u[:h // 2, :w // 2],
            pic.v[:h // 2, :w // 2], pts=len(frames)))
        mvs.append(None if pic.intra else pic.mv)
        intra_mbs.append(None if pic.intra else pic.intra_mb)
        i4_mbs.append(pic.i4_mb)
        i4_modes.append(pic.i4_mode)
        ref = _RefFrame(pic)                  # next P picture's reference
        pic = None

    for nal_ref_idc, nal_type, rbsp in split_annexb(stream):
        if nal_type == NAL_SPS:
            sps = SPS.parse_rbsp(rbsp)
        elif nal_type == NAL_PPS:
            pps = PPS.parse_rbsp(rbsp)
        elif nal_type in (NAL_SLICE_IDR, NAL_SLICE_NON_IDR):
            if sps is None or pps is None:
                raise ValueError("slice before parameter sets")
            br = BitReader(rbsp)
            header = SliceHeader.parse(br, sps, pps, nal_type, nal_ref_idc)
            if header.slice_type not in (SLICE_TYPE_I, SLICE_TYPE_P):
                raise ValueError(
                    f"unsupported slice type {header.slice_type}")
            if header.first_mb == 0:
                finish_picture()              # new access unit
                pic = _Picture(sps)
            elif pic is None:
                raise ValueError("slice with first_mb != 0 opens a picture")
            pic.intra = header.slice_type == SLICE_TYPE_I
            before = pic.decoded
            if header.slice_type == SLICE_TYPE_I:
                _decode_islice(br, pic, header)
            else:
                if ref is None:
                    raise ValueError("P slice without a reference frame")
                _decode_pslice(br, pic, header, ref)
            mbs = slice(header.first_mb,
                        header.first_mb + pic.decoded - before)
            pic.slice_of[mbs] = pic.slices
            pic.idc_of[mbs] = header.deblock_idc
            pic.slices += 1
    finish_picture()
    if sps is None:
        raise ValueError("no SPS in stream")
    meta = VideoMeta(width=sps.width, height=sps.height,
                     fps_num=sps.fps_num, fps_den=sps.fps_den,
                     num_frames=len(frames), chroma=ChromaFormat.YUV420,
                     codec="h264", size_bytes=len(stream))
    return DecodedStream(meta=meta, frames=frames, mvs=mvs,
                         intra_mbs=intra_mbs, i4_mbs=i4_mbs,
                         i4_modes=i4_modes)
