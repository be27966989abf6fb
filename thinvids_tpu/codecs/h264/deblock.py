"""In-loop deblocking filter (H.264 §8.7), in the order §8.7 prescribes.

The spec filters macroblock by macroblock in raster order: the four
vertical luma edges of a macroblock left to right, then its four
horizontal edges top to bottom (chroma likewise, two edges each way),
every edge reading what earlier edges wrote. Macroblock (x, y)
therefore needs (x-1, y) finished — its left edge reads that
macroblock's filtered columns — and (x+1, y-1): its top edge reads the
rows above after THAT macroblock's left edge touched their last three
columns. Nothing else orders two macroblocks, so all macroblocks with
the same t = x + 2y are independent: a WAVEFRONT, mbw + 2(mbh - 1)
steps for a picture (254 at 1080p).

Layout. Rolling macroblock row y by 2y columns turns a wavefront into
one column of a skewed plane, `[t, r, c, y]` (sample (r, c) of
macroblock (t - 2y, y); the macroblock row is the minor axis), and
every neighbour into a static offset: the left macroblock is block
t-1 in the same lane, the one above block t-2 one lane down. The skew
is log2(mbh) conditional rolls of whole blocks along t — static slices
and selects, no index arrays — and one step of the loop is
`_wavefront_step`: elementwise arithmetic on static slices of three
blocks. Boundary strengths and the alpha / beta / tC0 thresholds are
computed for the whole picture beforehand, on per-block grids in the
skewed layout (`_edge_params`), and ride along packed in one int32 per
sample line.

One implementation, three users, through a tiny ops shim: the encoder's
device programs (jaxdeblock: `lax.scan` over t), the in-repo decoder
and the numpy reference encoder (a python loop over t). The plain
reference it is tested against is tools/deblock_plain.py; libavcodec
agrees with both sample for sample (tests/test_deblock.py).

Boundary strength (§8.7.2.1, restricted to this codec's streams — one
reference, 16x16 partitions, frame pictures):

    intra picture:  MB edge -> 4, internal edge -> 3
    P picture:      either side's 4x4 luma block coded -> 2,
                    |mv_p - mv_q| >= 1 integer pel (either comp) -> 1,
                    else 0
    P picture with intra macroblocks (`intra_mb`, rd.p_intra): an MB
                    edge with an intra macroblock on either side -> 4,
                    an edge inside one -> 3, the P rule elsewhere

A plane handed to `deblock_frame` is filtered as ONE slice whose first
row has nothing above it: a split-frame band filters its own rows and
signals disable_deblocking_filter_idc = 2. A decoder that holds a
picture of several slices passes `edges`, the per-macroblock masks of
which left / top / internal edges exist.
"""

from __future__ import annotations

import contextlib

import numpy as np

from .transform import CHROMA_QP_TABLE

# §8.7.2.2 threshold tables, filterOffsetA = filterOffsetB = 0.
ALPHA_TABLE = np.array(
    [0] * 16
    + [4, 4, 5, 6, 7, 8, 9, 10, 12, 13, 15, 17, 20, 22, 25, 28, 32,
       36, 40, 45, 50, 56, 63, 71, 80, 90, 101, 113, 127, 144, 162,
       182, 203, 226, 255, 255], np.int32)
BETA_TABLE = np.array(
    [0] * 16
    + [2, 2, 2, 3, 3, 3, 3, 4, 4, 4, 6, 6, 7, 7, 8, 8, 9, 9, 10, 10,
       11, 11, 12, 12, 13, 13, 14, 14, 15, 15, 16, 16, 17, 17, 18,
       18], np.int32)
# Table 8-17: tC0 by (bS - 1, indexA).
TC0_TABLE = np.array([
    [0] * 17 + [0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2,
                2, 2, 3, 3, 3, 4, 4, 4, 5, 6, 6, 7, 8, 9, 10, 11, 13],
    [0] * 17 + [0, 0, 0, 0, 1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2,
                3, 3, 3, 4, 4, 5, 5, 6, 7, 8, 8, 10, 11, 12, 13, 15,
                17],
    [0] * 17 + [1, 1, 1, 1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2, 3, 3, 3, 4,
                4, 4, 5, 6, 6, 7, 8, 9, 10, 11, 13, 14, 16, 18, 20,
                23, 25],
], np.int32)

assert ALPHA_TABLE.shape == (52,) and BETA_TABLE.shape == (52,)
assert TC0_TABLE.shape == (3, 52)

_QPC_NP = np.asarray(CHROMA_QP_TABLE, np.int32)

#: blocks that follow the last wavefront, to push the last two out of
#: the loop's carry (a block is final two steps after its own)
_FLUSH = 2


class _NumpyOps:
    """Backend shim: numpy. jaxdeblock provides the jnp twin."""

    xp = np

    @staticmethod
    def asarray(a):
        return np.asarray(a)

    @staticmethod
    def scope(name):
        """The device program's stage names (stages.py): none here."""
        return contextlib.nullcontext()

    @staticmethod
    def barrier(a):
        """`lax.optimization_barrier` under JAX; nothing to keep apart
        here."""
        return a

    @staticmethod
    def lanes(mbh: int) -> int:
        """Width of the minor (macroblock row) axis of the skewed
        layout: the device's kernel wants whole vector registers."""
        return mbh

    @staticmethod
    def scan(step, carry, xs):
        """out[t] = step's output at wavefront t, the carry (zeros at
        the start) handed from step to step: `lax.scan`'s contract."""
        outs = []
        for t in range(xs[0].shape[0]):
            carry, out = step(carry, tuple(x[t] for x in xs))
            outs.append(out)
        return tuple(np.stack(o) for o in zip(*outs))


NUMPY_OPS = _NumpyOps()


# ---------------------------------------------------------------------------
# the skewed layout
# ---------------------------------------------------------------------------

def _to_lanes(a, perm, lanes: int, ops):
    """A [y, ...] array with y moved to the minor axis by `perm` and
    padded to `lanes` blank macroblock rows. A transpose of its own:
    the barrier keeps the compiler from folding it into the rolls of
    `_skew`, which measured 5x the cost of the two apart on the v5e."""
    xp = ops.xp
    a = xp.transpose(a, perm)
    pad = [(0, 0)] * (a.ndim - 1) + [(0, lanes - a.shape[-1])]
    return ops.barrier(xp.pad(a, pad))


def _skew(a, mbh: int, xp):
    """[x, ..., y] -> [t, ..., y] with out[t] = the input at x = t - 2y
    (zeros where no such macroblock exists), t < mbw + 2(mbh-1) +
    _FLUSH: lane y rolled down the leading axis by 2y, one conditional
    roll of whole blocks per bit of y — static slices and selects, no
    index arrays. Lanes past mbh are blank and stay blank."""
    pad = [(0, 2 * (mbh - 1) + _FLUSH)] + [(0, 0)] * (a.ndim - 1)
    a = xp.pad(a, pad)
    lane = xp.arange(a.shape[-1])
    bit = 1
    while bit < mbh:
        a = xp.where((lane & bit) > 0, xp.roll(a, 2 * bit, axis=0), a)
        bit <<= 1
    return a


def _unskew(a, mbh: int, mbw: int, xp):
    """The inverse of `_skew` on the blocks before the flush."""
    lane = xp.arange(a.shape[-1])
    bit = 1
    while bit < mbh:
        a = xp.where((lane & bit) > 0, xp.roll(a, -2 * bit, axis=0), a)
        bit <<= 1
    return a[:mbw]


def _from_left(a, xp):
    """out[t] = a[t-1]: the value at the macroblock to the left."""
    return xp.concatenate([xp.zeros_like(a[:1]), a[:-1]], axis=0)


def _lane_down(a, xp):
    """out[..., y] = a[..., y-1] (lane 0 takes what its masks void)."""
    return xp.roll(a, 1, axis=-1)


def _lane_up(a, xp):
    return xp.roll(a, -1, axis=-1)


def _from_above(a, xp):
    """out[t, ..., y] = a[t-2, ..., y-1]: the macroblock above."""
    return _lane_down(_from_left(_from_left(a, xp), xp), xp)


# ---------------------------------------------------------------------------
# boundary strengths and thresholds of every edge, on per-block grids
# ---------------------------------------------------------------------------

def _lut(table, idx, xp):
    """table[idx] for a small constant table, as compares and a sum
    (no gather): idx int32 of any shape."""
    lead = (len(table),) + (1,) * idx.ndim      # the table axis leads:
    hit = idx[None] == xp.arange(len(table)).reshape(lead)  # no padding
    return xp.sum(xp.where(hit, xp.asarray(table).reshape(lead), 0), axis=0)


def _thresholds(qpav, xp):
    """(alpha, beta, tc0 for bS 1..3) of an average QP grid."""
    idx = xp.clip(qpav, 0, 51)
    return (_lut(ALPHA_TABLE, idx, xp), _lut(BETA_TABLE, idx, xp),
            [_lut(TC0_TABLE[k], idx, xp) for k in range(3)])


def _pack_params(bs, thresholds, xp):
    """One int32 per block edge: alpha | beta << 8 | tc0 << 13 |
    bS << 18 (`_unpack_params`). `thresholds` broadcast against bs."""
    alpha, beta, tc0s = thresholds
    tc0 = xp.where(bs == 1, tc0s[0], xp.where(bs == 2, tc0s[1], tc0s[2]))
    return alpha | (beta << 8) | (tc0 << 13) | (bs << 18)


def _unpack_params(prm):
    return prm & 255, (prm >> 8) & 31, (prm >> 13) & 31, prm >> 18


def _edge_params(grid, intra: bool, xp, mv_per_pel: int = 2):
    """The packed parameters of every sample line of every edge, in the
    skewed layout: [t, 10, 16, y] int32 — rows 0..3 the vertical luma
    edges of a macroblock (16 sample rows each), 4..7 the horizontal
    ones (16 columns), 8 and 9 the chroma edges (two of 8 lines each).

    `grid` is the skewed per-macroblock metadata [t, 22, y]: 16 flags
    "4x4 luma block coded" (raster), QP_Y, mv (2), and the masks
    `internal edges exist`, `left edge exists`, `top edge exists`; a
    P picture that may hold intra macroblocks has a 23rd, `is intra`."""
    T, mbh = grid.shape[0], grid.shape[-1]
    nz = grid[:, :16].reshape(T, 4, 4, mbh)          # [t, by, bx, y]
    qp = grid[:, 16]
    mv = grid[:, 17:19]
    on, left_ok, top_ok = grid[:, 19], grid[:, 20], grid[:, 21]
    first = (xp.arange(4) == 0)                      # the MB edge

    if intra:
        full = xp.zeros((T, 4, 4, mbh), xp.int32)
        bs_v = full + xp.where(first[None, None, :, None], 4, 3)
        bs_h = full + xp.where(first[None, :, None, None], 4, 3)
    else:
        def moved(other):
            d = xp.abs(mv - other)
            # >= 1 integer sample: 2 in half-sample units, 4 in
            # quarter-sample units (§8.7.2.1's own)
            return (xp.max(d, axis=1) >= mv_per_pel)[:, None, None, :]

        nz_l = xp.concatenate(
            [_from_left(nz, xp)[:, :, 3:], nz[:, :, :3]], axis=2)
        nz_t = xp.concatenate(
            [_from_above(nz, xp)[:, 3:], nz[:, :3]], axis=1)
        bs_v = xp.where(
            (nz | nz_l) > 0, 2,
            xp.where(moved(_from_left(mv, xp))
                     & first[None, None, :, None], 1, 0))
        bs_h = xp.where(
            (nz | nz_t) > 0, 2,
            xp.where(moved(_from_above(mv, xp))
                     & first[None, :, None, None], 1, 0))
        if grid.shape[1] > 22:
            own = grid[:, 22]

            def mixed(bs, other, mb_edge):
                either = ((own | other) > 0)[:, None, None, :]
                return xp.where(mb_edge, xp.where(either, 4, bs),
                                xp.where(own[:, None, None, :] > 0, 3, bs))

            bs_v = mixed(bs_v, _from_left(own, xp),
                         first[None, None, :, None])
            bs_h = mixed(bs_h, _from_above(own, xp),
                         first[None, :, None, None])
    exists_v = xp.where(first[None, None, :, None],
                        left_ok[:, None, None, :], on[:, None, None, :])
    exists_h = xp.where(first[None, :, None, None],
                        top_ok[:, None, None, :], on[:, None, None, :])
    bs_v = bs_v * exists_v                           # [t, by, bx=e, y]
    bs_h = bs_h * exists_h                           # [t, by=e, bx, y]

    qp_l, qp_t = _from_left(qp, xp), _from_above(qp, xp)
    qpc = _lut(_QPC_NP, xp.clip(qp, 0, 51), xp)
    qpc_l, qpc_t = _from_left(qpc, xp), _from_above(qpc, xp)

    th_luma, th_chroma = _thresholds(qp, xp), _thresholds(qpc, xp)

    def per_edge(own, th_own, other, axis_first):
        """Thresholds of the MB edge (QP averaged with the neighbour)
        and of the internal edges, selected by `axis_first`."""
        th_mb = _thresholds((own + other + 1) >> 1, xp)

        def pick(a_mb, a_own):
            return xp.where(axis_first, a_mb[:, None, None, :],
                            a_own[:, None, None, :])
        return (pick(th_mb[0], th_own[0]), pick(th_mb[1], th_own[1]),
                [pick(m, o) for m, o in zip(th_mb[2], th_own[2])])

    fv, fh = first[None, None, :, None], first[None, :, None, None]
    luma_v = _pack_params(bs_v, per_edge(qp, th_luma, qp_l, fv), xp)
    luma_h = _pack_params(bs_h, per_edge(qp, th_luma, qp_t, fh), xp)
    chroma_v = _pack_params(bs_v, per_edge(qpc, th_chroma, qpc_l, fv), xp)
    chroma_h = _pack_params(bs_h, per_edge(qpc, th_chroma, qpc_t, fh), xp)

    # per block edge -> per sample line: a luma line takes its 4x4
    # block's value, a chroma line that of the luma line it maps to
    # (2 chroma lines per block); chroma edges are luma edges 0 and 2
    lv = xp.repeat(xp.transpose(luma_v, (0, 2, 1, 3)), 4, axis=2)
    lh = xp.repeat(luma_h, 4, axis=2)
    cv = xp.repeat(xp.stack([chroma_v[:, :, 0], chroma_v[:, :, 2]], 1),
                   2, axis=2).reshape(T, 1, 16, mbh)
    ch = xp.repeat(xp.stack([chroma_h[:, 0], chroma_h[:, 2]], 1),
                   2, axis=2).reshape(T, 1, 16, mbh)
    return xp.concatenate([lv, lh, cv, ch], axis=1)


# ---------------------------------------------------------------------------
# the edge filters: one edge, all its sample lines at once
# ---------------------------------------------------------------------------

def _clip3(lo, hi, x, xp):
    return xp.minimum(hi, xp.maximum(lo, x))


def _filter_luma_edge(p, q, prm, xp):
    """§8.7.2.3 / 8.7.2.4 across one luma edge. p = (p0, p1, p2, p3),
    q likewise, each [lines, y] int32; prm the packed parameters of the
    lines. Returns ((p0', p1', p2'), (q0', q1', q2'))."""
    p0, p1, p2, p3 = p
    q0, q1, q2, q3 = q
    alpha, beta, tc0, bs = _unpack_params(prm)
    filt = ((bs > 0)
            & (xp.abs(p0 - q0) < alpha)
            & (xp.abs(p1 - p0) < beta)
            & (xp.abs(q1 - q0) < beta))
    ap = xp.abs(p2 - p0) < beta
    aq = xp.abs(q2 - q0) < beta

    # -- normal filter (bS 1..3) --
    tc = tc0 + ap.astype(xp.int32) + aq.astype(xp.int32)
    delta = _clip3(-tc, tc,
                   (((q0 - p0) << 2) + (p1 - q1) + 4) >> 3, xp)
    np0 = _clip3(0, 255, p0 + delta, xp)
    nq0 = _clip3(0, 255, q0 - delta, xp)
    hp = (p0 + q0 + 1) >> 1
    np1 = p1 + _clip3(-tc0, tc0, (p2 + hp - (p1 << 1)) >> 1, xp)
    nq1 = q1 + _clip3(-tc0, tc0, (q2 + hp - (q1 << 1)) >> 1, xp)
    normal = filt & (bs < 4)
    out_p0 = xp.where(normal, np0, p0)
    out_q0 = xp.where(normal, nq0, q0)
    out_p1 = xp.where(normal & ap, np1, p1)
    out_q1 = xp.where(normal & aq, nq1, q1)

    # -- strong filter (bS == 4) --
    strong = filt & (bs == 4)
    close = xp.abs(p0 - q0) < ((alpha >> 2) + 2)
    sp = strong & ap & close
    sq = strong & aq & close
    sp0 = (p2 + 2 * p1 + 2 * p0 + 2 * q0 + q1 + 4) >> 3
    sp1 = (p2 + p1 + p0 + q0 + 2) >> 2
    sp2 = (2 * p3 + 3 * p2 + p1 + p0 + q0 + 4) >> 3
    wp0 = (2 * p1 + p0 + q1 + 2) >> 2
    sq0 = (q2 + 2 * q1 + 2 * q0 + 2 * p0 + p1 + 4) >> 3
    sq1 = (q2 + q1 + q0 + p0 + 2) >> 2
    sq2 = (2 * q3 + 3 * q2 + q1 + q0 + p0 + 4) >> 3
    wq0 = (2 * q1 + q0 + p1 + 2) >> 2
    out_p0 = xp.where(strong, xp.where(sp, sp0, wp0), out_p0)
    out_p1 = xp.where(sp, sp1, out_p1)
    out_p2 = xp.where(sp, sp2, p2)
    out_q0 = xp.where(strong, xp.where(sq, sq0, wq0), out_q0)
    out_q1 = xp.where(sq, sq1, out_q1)
    out_q2 = xp.where(sq, sq2, q2)
    return (out_p0, out_p1, out_p2), (out_q0, out_q1, out_q2)


def _filter_chroma_edge(p0, p1, q0, q1, prm, xp):
    """Chroma edge (only p0 / q0 change). Samples [2, lines, y] (Cb,
    Cr), prm [lines, y]. Returns (p0', q0')."""
    alpha, beta, tc0, bs = _unpack_params(prm)
    filt = ((bs > 0)
            & (xp.abs(p0 - q0) < alpha)
            & (xp.abs(p1 - p0) < beta)
            & (xp.abs(q1 - q0) < beta))
    tc = tc0 + 1
    delta = _clip3(-tc, tc,
                   (((q0 - p0) << 2) + (p1 - q1) + 4) >> 3, xp)
    np0 = _clip3(0, 255, p0 + delta, xp)
    nq0 = _clip3(0, 255, q0 - delta, xp)
    sp0 = (2 * p1 + p0 + q1 + 2) >> 2
    sq0 = (2 * q1 + q0 + p1 + 2) >> 2
    normal = filt & (bs < 4)
    strong = filt & (bs == 4)
    out_p0 = xp.where(strong, sp0, xp.where(normal, np0, p0))
    out_q0 = xp.where(strong, sq0, xp.where(normal, nq0, q0))
    return out_p0, out_q0


# ---------------------------------------------------------------------------
# one wavefront
# ---------------------------------------------------------------------------

#: the axis the sample lines of an edge are counted along, in a luma
#: block [r, c, y] and in a chroma block [2, r, c, y] alike: a vertical
#: edge separates columns, a horizontal one rows
_COLS, _ROWS = -2, -3


def _span(blk, lo, hi, axis: int):
    return blk[(Ellipsis, slice(lo, hi)) + (slice(None),) * (-axis - 1)]


def _line(blk, k: int, axis: int):
    return blk[(Ellipsis, k) + (slice(None),) * (-axis - 1)]


def _put_lines(blk, first: int, lines, axis: int, xp):
    """`blk` with the consecutive lines from `first` on replaced."""
    parts = [_span(blk, 0, first, axis), xp.stack(lines, axis=axis),
             _span(blk, first + len(lines), None, axis)]
    return xp.concatenate([p for p in parts if p.shape[axis]], axis)


def _luma_mb_edge(near, cur, prm, axis: int, xp):
    """Edge 0 of `cur` against the last four lines of `near` (the
    macroblock to the left, or above)."""
    p = tuple(_line(near, 15 - i, axis) for i in range(4))
    q = tuple(_line(cur, i, axis) for i in range(4))
    new_p, new_q = _filter_luma_edge(p, q, prm, xp)
    return (_put_lines(near, 13, new_p[::-1], axis, xp),
            _put_lines(cur, 0, new_q, axis, xp))


def _luma_inner_edges(cur, prm, axis: int, xp):
    for edge in (1, 2, 3):
        at = 4 * edge
        p = tuple(_line(cur, at - 1 - i, axis) for i in range(4))
        q = tuple(_line(cur, at + i, axis) for i in range(4))
        new_p, new_q = _filter_luma_edge(p, q, prm[edge], xp)
        cur = _put_lines(cur, at - 3, new_p[::-1] + new_q, axis, xp)
    return cur


def _chroma_mb_edge(near, cur, prm, axis: int, xp):
    """The chroma twin of `_luma_mb_edge` (8 lines, p0 / q0 only)."""
    new_p0, new_q0 = _filter_chroma_edge(
        _line(near, 7, axis), _line(near, 6, axis),
        _line(cur, 0, axis), _line(cur, 1, axis), prm, xp)
    return (_put_lines(near, 7, (new_p0,), axis, xp),
            _put_lines(cur, 0, (new_q0,), axis, xp))


def _chroma_inner_edge(cur, prm, axis: int, xp):
    new_p0, new_q0 = _filter_chroma_edge(
        _line(cur, 3, axis), _line(cur, 2, axis),
        _line(cur, 4, axis), _line(cur, 5, axis), prm, xp)
    return _put_lines(cur, 3, (new_p0, new_q0), axis, xp)


def _wavefront_step(carry, blocks, xp):
    """All macroblocks of wavefront t at once.

    carry: the luma and chroma blocks of wavefronts t-1 (filtered but
    for what t and t+1 do to them) and t-2; blocks: wavefront t's
    unfiltered luma [16, 16, y] and chroma [2, 8, 8, y] and its packed
    edge parameters [10, 16, y]. Emits wavefront t-2, now final."""
    left_y, above_y, left_c, above_c = carry
    cur_y, cur_c, prm = blocks
    cur_y = cur_y.astype(xp.int32)
    cur_c = cur_c.astype(xp.int32)

    # vertical edges, left to right
    left_y, cur_y = _luma_mb_edge(left_y, cur_y, prm[0], _COLS, xp)
    cur_y = _luma_inner_edges(cur_y, prm[0:4], _COLS, xp)
    left_c, cur_c = _chroma_mb_edge(left_c, cur_c, prm[8, :8], _COLS, xp)
    cur_c = _chroma_inner_edge(cur_c, prm[8, 8:], _COLS, xp)

    # horizontal edges, top to bottom. The macroblock above sits one
    # lane down in block t-2.
    top_y, cur_y = _luma_mb_edge(_lane_down(above_y, xp), cur_y, prm[4],
                                 _ROWS, xp)
    cur_y = _luma_inner_edges(cur_y, prm[4:8], _ROWS, xp)
    top_c, cur_c = _chroma_mb_edge(_lane_down(above_c, xp), cur_c,
                                   prm[9, :8], _ROWS, xp)
    cur_c = _chroma_inner_edge(cur_c, prm[9, 8:], _ROWS, xp)
    done_y, done_c = _lane_up(top_y, xp), _lane_up(top_c, xp)
    return ((cur_y, left_y, cur_c, left_c),
            (done_y.astype(blocks[0].dtype), done_c.astype(blocks[1].dtype)))


# ---------------------------------------------------------------------------
# frame-level driver
# ---------------------------------------------------------------------------

def deblock_frame(y, u, v, qp_map, *, intra: bool, nz4=None, mv=None,
                  mb_row0=0, total_mb_rows: int | None = None,
                  edges=None, mv_per_pel: int = 2, intra_mb=None,
                  ops=NUMPY_OPS):
    """Deblock one (padded) frame, or one band slice by itself.

    y: (16·mbh_p, 16·mbw) luma plane (any int dtype; uint8 ok);
    u/v: (8·mbh_p, 8·mbw); qp_map: (mbh_p, mbw) int QP_Y per MB;
    `intra` selects the picture-homogeneous bS rule. For P pictures,
    nz4: (4·mbh_p, 4·mbw) any-nonzero per 4x4 luma block and
    mv: (mbh_p, mbw, 2) MVs, `mv_per_pel` units to an integer sample
    (2: half-sample units; 4: quarter), and `intra_mb`: None, or the
    (mbh_p, mbw) map of the P picture's intra macroblocks (whose nz4
    and mv are not read). `mb_row0` (may be traced) and
    `total_mb_rows` say where the plane's first macroblock row lies in
    the picture and how many the picture has: rows past the picture
    (band padding) are left alone. `edges` = (internal, left, top)
    (mbh_p, mbw) masks of the edges that exist, for a picture of
    several slices; the default is one slice. Returns filtered
    (y, u, v) in the input dtypes.
    """
    xp = ops.xp
    mbh, mbw = qp_map.shape[0], qp_map.shape[1]
    with ops.scope("deblock"):
        y, u, v = ops.asarray(y), ops.asarray(u), ops.asarray(v)
        rows = xp.arange(mbh)[:, None]
        live = xp.ones((mbh, mbw), xp.int32)
        if total_mb_rows is not None:
            live = live * (rows + mb_row0 < total_mb_rows)
        if edges is None:
            edges = (live, live * (xp.arange(mbw)[None, :] > 0),
                     live * (rows > 0))
        if intra:
            nz = xp.zeros((mbh, 4, mbw, 4), xp.int32)
            mv = xp.zeros((mbh, mbw, 2), xp.int32)
        else:
            if nz4 is None or mv is None:
                raise ValueError("P-frame deblock requires nz4 and mv")
            nz = ops.asarray(nz4).astype(xp.int32).reshape(mbh, 4, mbw, 4)
            mv = ops.asarray(mv).astype(xp.int32)
        lanes = ops.lanes(mbh)
        grid = xp.concatenate(                       # [y, 22 or 23, x]
            [xp.transpose(nz, (0, 1, 3, 2)).reshape(mbh, 16, mbw),
             ops.asarray(qp_map).astype(xp.int32)[:, None],
             xp.transpose(mv, (0, 2, 1))]
            + [ops.asarray(e).astype(xp.int32)[:, None] for e in edges]
            + ([] if intra or intra_mb is None
               else [ops.asarray(intra_mb).astype(xp.int32)[:, None]]),
            axis=1)
        prm = _edge_params(
            _skew(_to_lanes(grid, (2, 1, 0), lanes, ops), mbh, xp),
            intra, xp, mv_per_pel)
        # [y, r, x, c] -> [x, r, c, y] -> [t, r, c, y]; chroma
        # [2, y, r, x, c] -> [x, 2, r, c, y] -> [t, 2, r, c, y]
        ys = _skew(_to_lanes(y.reshape(mbh, 16, mbw, 16), (2, 1, 3, 0),
                             lanes, ops), mbh, xp)
        cs = _skew(_to_lanes(xp.stack([u, v]).reshape(2, mbh, 8, mbw, 8),
                             (3, 0, 2, 4, 1), lanes, ops), mbh, xp)
        zero_y = ys[0].astype(xp.int32) * 0
        zero_c = cs[0].astype(xp.int32) * 0

    done_y, done_c = ops.scan(
        lambda carry, blocks: _wavefront_step(carry, blocks, xp),
        (zero_y, zero_y, zero_c, zero_c), (ys, cs, prm))

    with ops.scope("deblock"):
        out_y = ops.barrier(_unskew(done_y[_FLUSH:], mbh, mbw, xp))
        out_c = ops.barrier(_unskew(done_c[_FLUSH:], mbh, mbw, xp))
        out_y = xp.transpose(out_y[..., :mbh], (3, 1, 0, 2)).reshape(y.shape)
        out_c = xp.transpose(out_c[..., :mbh], (1, 4, 2, 0, 3)).reshape(
            (2,) + u.shape)
        return out_y, out_c[0], out_c[1]
