"""Plain reference of the in-loop deblocking filter (H.264 §8.7).

A per-macroblock raster-order loop in numpy that follows the clauses of
§8.7 one by one, with no thought for speed and no code shared with the
filter the encoder and decoder run (codecs/h264/deblock.py). It exists
to be compared with: tests/test_deblock.py holds the fast filter to it
on random fields, and both to libavcodec (tools/oracle.py) on encoded
streams.

Restrictions are those of this codec's streams: frame macroblocks,
4:2:0, 8 bit, transform_size_8x8_flag 0, one reference picture,
FilterOffsetA = FilterOffsetB = 0, chroma_qp_index_offset 0, pictures
that are all intra or all inter (P) macroblocks.
"""

from __future__ import annotations

import numpy as np

# Table 8-16: alpha' and beta' by indexA / indexB (8 bit: alpha = alpha')
_ALPHA = [0] * 16 + [4, 4, 5, 6, 7, 8, 9, 10, 12, 13, 15, 17, 20, 22, 25,
                     28, 32, 36, 40, 45, 50, 56, 63, 71, 80, 90, 101,
                     113, 127, 144, 162, 182, 203, 226, 255, 255]
_BETA = [0] * 16 + [2, 2, 2, 3, 3, 3, 3, 4, 4, 4, 6, 6, 7, 7, 8, 8, 9, 9,
                    10, 10, 11, 11, 12, 12, 13, 13, 14, 14, 15, 15, 16,
                    16, 17, 17, 18, 18]
# Table 8-17: tC0' by indexA, for bS = 1, 2, 3
_TC0 = {
    1: [0] * 17 + [0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2,
                   2, 2, 3, 3, 3, 4, 4, 4, 5, 6, 6, 7, 8, 9, 10, 11, 13],
    2: [0] * 17 + [0, 0, 0, 0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2,
                   3, 3, 3, 4, 4, 5, 5, 6, 7, 8, 8, 10, 11, 12, 13, 15,
                   17],
    3: [0] * 17 + [1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 4,
                   4, 4, 5, 6, 6, 7, 8, 9, 10, 11, 13, 14, 16, 18, 20,
                   23, 25],
}
# Table 8-15: QP_C by qP_I (chroma_qp_index_offset 0)
_QPC = list(range(30)) + [29, 30, 31, 32, 32, 33, 34, 34, 35, 35, 36, 36,
                          37, 37, 37, 38, 38, 38, 39, 39, 39, 39]


def _clip3(lo, hi, x):
    return lo if x < lo else hi if x > hi else x


def _filter_samples(p, q, bs, qp_p, qp_q, chroma):
    """§8.7.2.2 - 8.7.2.4 for one line of samples across an edge:
    p = [p0, p1, p2, p3], q = [q0, q1, q2, q3] (chroma passes two each).
    Returns the filtered (p', q') lists."""
    p, q = list(p), list(q)
    qpav = (qp_p + qp_q + 1) >> 1                       # (8-461)
    index_a = _clip3(0, 51, qpav)                       # offsets are 0
    index_b = _clip3(0, 51, qpav)
    alpha, beta = _ALPHA[index_a], _BETA[index_b]
    filter_samples = (bs != 0 and abs(p[0] - q[0]) < alpha
                      and abs(p[1] - p[0]) < beta
                      and abs(q[1] - q[0]) < beta)      # (8-468)
    if not filter_samples:
        return p, q
    if bs < 4:                                          # §8.7.2.3
        tc0 = _TC0[bs][index_a]
        if chroma:
            tc = tc0 + 1
            ap = aq = False
        else:
            ap = abs(p[2] - p[0]) < beta
            aq = abs(q[2] - q[0]) < beta
            tc = tc0 + int(ap) + int(aq)
        delta = _clip3(-tc, tc,
                       (((q[0] - p[0]) << 2) + (p[1] - q[1]) + 4) >> 3)
        new_p = [_clip3(0, 255, p[0] + delta)] + p[1:]
        new_q = [_clip3(0, 255, q[0] - delta)] + q[1:]
        if ap:
            new_p[1] = p[1] + _clip3(
                -tc0, tc0,
                (p[2] + ((p[0] + q[0] + 1) >> 1) - (p[1] << 1)) >> 1)
        if aq:
            new_q[1] = q[1] + _clip3(
                -tc0, tc0,
                (q[2] + ((p[0] + q[0] + 1) >> 1) - (q[1] << 1)) >> 1)
        return new_p, new_q
    # bS == 4: §8.7.2.4
    new_p, new_q = list(p), list(q)
    small = abs(p[0] - q[0]) < ((alpha >> 2) + 2)
    if not chroma and abs(p[2] - p[0]) < beta and small:
        new_p[0] = (p[2] + 2 * p[1] + 2 * p[0] + 2 * q[0] + q[1] + 4) >> 3
        new_p[1] = (p[2] + p[1] + p[0] + q[0] + 2) >> 2
        new_p[2] = (2 * p[3] + 3 * p[2] + p[1] + p[0] + q[0] + 4) >> 3
    else:
        new_p[0] = (2 * p[1] + p[0] + q[1] + 2) >> 2
    if not chroma and abs(q[2] - q[0]) < beta and small:
        new_q[0] = (p[1] + 2 * p[0] + 2 * q[0] + 2 * q[1] + q[2] + 4) >> 3
        new_q[1] = (p[0] + q[0] + q[1] + q[2] + 2) >> 2
        new_q[2] = (2 * q[3] + 3 * q[2] + q[1] + q[0] + p[0] + 4) >> 3
    else:
        new_q[0] = (2 * q[1] + q[0] + p[1] + 2) >> 2
    return new_p, new_q


def deblock_picture_plain(y, u, v, qp_map, *, intra, nz4=None, mv=None,
                          slice_of_mb_row=None, mv_per_pel=2):
    """Filter one picture as §8.7 orders it.

    y (16·mbh, 16·mbw), u, v (8·mbh, 8·mbw): the constructed samples;
    qp_map (mbh, mbw): QP_Y of every macroblock; `intra`: all
    macroblocks intra (else all inter, one reference); nz4 (4·mbh,
    4·mbw): the 4x4 luma block holds non-zero transform coefficients;
    mv (mbh, mbw, 2): motion vectors, `mv_per_pel` units to an integer
    sample (2: half-sample units, 4: quarter-sample units).
    `slice_of_mb_row`: None = disable_deblocking_filter_idc 0; else a
    sequence giving the slice of every macroblock row, for idc 2 (edges
    between slices are left alone). Returns new (y, u, v)."""
    y = np.array(y, dtype=np.int64)
    u = np.array(u, dtype=np.int64)
    v = np.array(v, dtype=np.int64)
    qp_map = np.asarray(qp_map).astype(np.int64)
    mbh, mbw = qp_map.shape
    if not intra:
        nz4 = np.asarray(nz4).astype(bool)
        mv = np.asarray(mv).astype(np.int64)

    def bs_of(mbx, mby, bx, by, vertical, mb_edge):
        """§8.7.2.1 for the edge on the left (vertical) or top side of
        4x4 luma block (bx, by) of macroblock (mbx, mby)."""
        if intra:
            return 4 if mb_edge else 3
        gx, gy = 4 * mbx + bx, 4 * mby + by             # q block
        px, py = (gx - 1, gy) if vertical else (gx, gy - 1)
        if nz4[gy, gx] or nz4[py, px]:
            return 2
        mv_q, mv_p = mv[gy // 4, gx // 4], mv[py // 4, px // 4]
        # >= 4 in quarter samples = >= 2 in half samples
        if (abs(mv_q[0] - mv_p[0]) >= mv_per_pel
                or abs(mv_q[1] - mv_p[1]) >= mv_per_pel):
            return 1
        return 0

    for mby in range(mbh):
        for mbx in range(mbw):
            # §8.7: which macroblock edges are filtered at all
            left = mbx > 0
            top = mby > 0
            if top and slice_of_mb_row is not None:
                top = slice_of_mb_row[mby] == slice_of_mb_row[mby - 1]
            qp_q = int(qp_map[mby, mbx])
            # luma, vertical edges left to right
            for e in range(4):
                if e == 0 and not left:
                    continue
                qp_p = int(qp_map[mby, mbx - 1]) if e == 0 else qp_q
                x0 = 16 * mbx + 4 * e
                for k in range(16):
                    r = 16 * mby + k
                    bs = bs_of(mbx, mby, e, k // 4, True, e == 0)
                    p = [int(y[r, x0 - 1 - i]) for i in range(4)]
                    q = [int(y[r, x0 + i]) for i in range(4)]
                    p, q = _filter_samples(p, q, bs, qp_p, qp_q, False)
                    for i in range(3):
                        y[r, x0 - 1 - i] = p[i]
                        y[r, x0 + i] = q[i]
            # luma, horizontal edges top to bottom
            for e in range(4):
                if e == 0 and not top:
                    continue
                qp_p = int(qp_map[mby - 1, mbx]) if e == 0 else qp_q
                y0 = 16 * mby + 4 * e
                for k in range(16):
                    c = 16 * mbx + k
                    bs = bs_of(mbx, mby, k // 4, e, False, e == 0)
                    p = [int(y[y0 - 1 - i, c]) for i in range(4)]
                    q = [int(y[y0 + i, c]) for i in range(4)]
                    p, q = _filter_samples(p, q, bs, qp_p, qp_q, False)
                    for i in range(3):
                        y[y0 - 1 - i, c] = p[i]
                        y[y0 + i, c] = q[i]
            # chroma: edges 0 and 2 of the luma grid, Cb then Cr; the
            # bS of a chroma line is that of the luma line it maps to
            for plane in (u, v):
                for e in (0, 2):
                    if e == 0 and not left:
                        continue
                    mbp = mbx - 1 if e == 0 else mbx
                    qc_p = _QPC[_clip3(0, 51, int(qp_map[mby, mbp]))]
                    qc_q = _QPC[_clip3(0, 51, qp_q)]
                    x0 = 8 * mbx + 2 * e
                    for k in range(8):
                        r = 8 * mby + k
                        bs = bs_of(mbx, mby, e, (2 * k) // 4, True, e == 0)
                        p = [int(plane[r, x0 - 1 - i]) for i in range(2)]
                        q = [int(plane[r, x0 + i]) for i in range(2)]
                        p, q = _filter_samples(p, q, bs, qc_p, qc_q, True)
                        plane[r, x0 - 1], plane[r, x0] = p[0], q[0]
                for e in (0, 2):
                    if e == 0 and not top:
                        continue
                    mbp = mby - 1 if e == 0 else mby
                    qc_p = _QPC[_clip3(0, 51, int(qp_map[mbp, mbx]))]
                    qc_q = _QPC[_clip3(0, 51, qp_q)]
                    y0 = 8 * mby + 2 * e
                    for k in range(8):
                        c = 8 * mbx + k
                        bs = bs_of(mbx, mby, (2 * k) // 4, e, False, e == 0)
                        p = [int(plane[y0 - 1 - i, c]) for i in range(2)]
                        q = [int(plane[y0 + i, c]) for i in range(2)]
                        p, q = _filter_samples(p, q, bs, qc_p, qc_q, True)
                        plane[y0 - 1, c], plane[y0, c] = p[0], q[0]
    return y, u, v
