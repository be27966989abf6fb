"""JAX/TPU inter-frame (P) encode compute: motion search, motion
compensation, residual transform/quant, closed-loop reconstruction.

Replaces the inter coding half of the reference's ffmpeg encode op point
(/root/reference/worker/tasks.py:1558-1586). TPU-shaped design:

- Motion estimation + compensation are ONE Pallas kernel pass per frame
  (codecs/h264/jaxme.py): MXU-matmul SAD over static candidate windows
  around dynamically re-anchored centers, half-pel 6-tap interpolation,
  and a running per-MB best-(cost, mv, pred) select — the kernel emits
  the final prediction planes, so MC never runs as a separate pass.
  MVs are in the units of `rd.subpel` throughout: half-sample units
  with "half", quarter-sample units with "quarter" (rd.mv_per_pel).
- Residual DCT/quant/dequant/IDCT run on the planes as they lie, taken
  once to their 128-lane column tiles (T, H, 128): the 4x4 butterflies,
  the chroma DC Hadamard, the per-block and per-MB reductions are
  constant block-diagonal matrices on the matrix unit, the quant tables
  selects on an iota. No plane-sized array of the stage has a minor
  dimension under 128, none is sliced with a lane stride; int16
  storage. (The blocked conformance path re-lays the finished levels
  for the host packer, after the stage's arithmetic.)
- Frames chain through a `lax.scan` carry holding the recon planes and
  the previous frame's median MV (the EPZS temporal predictor collapsed
  to its frame mode, as one search center).

The sequential P-slice entropy pack (skip runs, mvp/mvd, CBP) stays on
host: codecs/h264/inter.py.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .jaxcore import (
    _H4,
    _MF,
    _QPC,
    _V,
    _ZZ,
    _ZSCAN,
    _intra_core,
    _luma_dc_quant,
    _mode_tail,
    _pick3,
    _varying_zero,
)
from . import jaxdeblock, jaxme, rdo
from .rdo import RD_OFF
from .stages import stage
from .tiles import (
    _F32,
    _HI,
    _LANES,
    _SUM4,
    _from_tiles,
    _lane_mm,
    _lane_pool,
    _lane_spread,
    _row_mm,
    _tile_maps,
    _to_tiles,
)

SEARCH_RANGE = jaxme.SEARCH_RANGE      # integer-pel, each direction


# ---------------------------------------------------------------------------
# the residual's 4x4 structure on 128-lane tiles (codecs/h264/tiles.py:
# the plane taken ONCE to (T, H, 128), every 4x4 step a constant
# block-diagonal matrix on the matrix unit). Integers ride as f32 at
# Precision.HIGHEST, exact while every partial sum stays below 2**24:
# forward |r| <= 255 -> 1530 -> 9180; inverse |d| < 2**18 in (chroma DC,
# QP 0) -> under 2**22 out. tests/test_residual_planes.py holds the
# extremes at every QP against the numpy spec functions.
# ---------------------------------------------------------------------------

_CF4 = np.array([[1, 1, 1, 1], [2, 1, -1, -2], [1, -1, -1, 1],
                 [1, -2, 2, -1]], np.float32)
# jaxcore._inv4's butterfly, one 1-D pass: out = _INV_D @ d + _INV_H @ (d >> 1)
_INV_D = np.array([[1, 1, 1, 0], [1, 0, -1, -1], [1, 0, -1, 1],
                   [1, -1, 1, 0]], np.float32)
_INV_H = np.array([[0, 0, 0, 1], [0, 1, 0, 0], [0, -1, 0, 0],
                   [0, 0, 0, -1]], np.float32)
# 1-D half of the chroma DC Hadamard over the DC positions (0 and 4) of
# an 8-sample MB row or column; every other position reads 0
_HAD_DC = np.zeros((8, 8), np.float32)
_HAD_DC[0, 0] = _HAD_DC[0, 4] = _HAD_DC[4, 0] = 1
_HAD_DC[4, 4] = -1


def _fwd4_tiles(x):
    """W = CF @ x @ CF^T per 4x4 block (rows then lanes — the order of
    jaxcore._fwd4's einsum; exact either way)."""
    return _lane_mm(_row_mm(x, _CF4), _CF4).astype(jnp.int32)


def _inv4_tiles(d):
    """Inverse core transform: lanes then rows, the >> 1 terms shifted
    BEFORE each matmul — jaxcore._inv4's stage order and rounding."""
    f = (_lane_mm(d, _INV_D) + _lane_mm(d >> 1, _INV_H)).astype(jnp.int32)
    return (_row_mm(f, _INV_D) + _row_mm(f >> 1, _INV_H)).astype(jnp.int32)


def _chroma_dc_tiles(x):
    """2x2 Hadamard over the four DC positions of each 8x8 chroma MB,
    in place: (8i + 4u, 8j + 4v) gets coefficient (u, v); 0 elsewhere."""
    return _row_mm(_lane_mm(x, _HAD_DC), _HAD_DC).astype(jnp.int32)


def _class_tiles(tbl, shape):
    """A (4, 4) quant table over (T, H, 128) tiles: its three position
    classes are (row & 1) + (lane & 1), read off an iota."""
    cls = ((jax.lax.broadcasted_iota(jnp.int32, shape, 1) & 1)
           + (jax.lax.broadcasted_iota(jnp.int32, shape, 2) & 1))
    return jnp.where(cls == 0, tbl[0, 0],
                     jnp.where(cls == 1, tbl[0, 1], tbl[1, 1]))


def _quant_plane(w, mf_plane, qp, bias_div: int = 6):
    """Quantize an INTER coefficient plane with the f = (1 << qbits) / 6
    rounding bias (over-rounding inter residuals inflates levels and
    bitrate; the intra paths, jaxcore's and `_p_intra`'s `bias_div` 3,
    keep the standard 1/3)."""
    qbits = 15 + qp // 6
    f = (1 << qbits) // bias_div
    z = (jnp.abs(w) * mf_plane + f) >> qbits
    return jnp.where(w < 0, -z, z)


def _dequant_plane(z, v_plane, qp):
    return (z * v_plane) << (qp // 6)


def _nz4_tiles(z, mbw: int):
    """Luma level tiles -> (4·mbh, 4·mbw) any-nonzero per 4x4 block (the
    P-frame bS=2 input of the in-loop filter, from the same levels the
    packer ships)."""
    rows = _row_mm(z != 0, _SUM4)[:, ::4]                # (T, H/4, 128)
    return _tile_maps(_lane_pool(rows, 4), 4 * mbw) > 0.5


_DC_LANES = np.arange(0, _LANES, 4)                     # DC lanes of a tile


def _chroma_dc_levels(zc, mbw: int):
    """Chroma level tiles holding the Hadamard-domain DC levels at their
    DC positions -> (mbh, 4 * mbw): per MB its four [00 01 10 11]."""
    parts = []
    for u in (0, 1):
        pick = np.zeros((_LANES, _LANES // 2), np.float32)
        pick[_DC_LANES, 4 * (_DC_LANES // 8) + 2 * u + _DC_LANES % 8 // 4] = 1
        parts.append(jnp.einsum("trl,lm->trm", zc[:, 4 * u::8].astype(_F32),
                                jnp.asarray(pick), precision=_HI))
    return _tile_maps(parts[0] + parts[1], 4 * mbw).astype(jnp.int32)


# ---------------------------------------------------------------------------
# P-frame residual coding
# (motion search + compensation live in jaxme.me_search)
# ---------------------------------------------------------------------------

def _luma_plane_to_blocks(z, mbw: int, mbh: int):
    """(H, W) coeff plane → (nmb, 16, 16) z-scan blocks of zigzag
    coeffs (the packer's layout)."""
    x = z.reshape(mbh, 4, 4, mbw, 4, 4).transpose(0, 3, 1, 4, 2, 5)
    x = x.reshape(mbh * mbw, 16, 16)
    return x[:, _ZSCAN][..., _ZZ]


def _chroma_plane_to_blocks(z, mbw: int, mbh: int):
    """(H/2, W/2) coeff plane → (nmb, 4, 16) raster blocks of zigzag
    coeffs."""
    x = z.reshape(mbh, 2, 4, mbw, 2, 4).transpose(0, 3, 1, 4, 2, 5)
    x = x.reshape(mbh * mbw, 4, 16)
    return x[..., _ZZ]


def _encode_p_plane(cy, cu, cv, ry, ru, rv, pred_mv, qp, qpc, *, mbw: int,
                    mbh: int, blocked: bool = True, rd=RD_OFF):
    """One P frame given previous recon planes (int16). `pred_mv` is the
    previous frame's median MV (a search center), in rd.subpel's units
    as the returned `mv` and median are.

    Search and compensation are the kernel's (jaxme.me_search); the
    residual (:func:`_residual_p`) transforms, quantizes and
    reconstructs on the planes' 128-lane tiles and hands back (H, W)
    planes; with rd.deblock the in-loop filter then runs on the recon.

    `blocked=True` returns level arrays in the host packer's blocked
    layout (the conformance/host path: one relayout of the finished
    level planes). `blocked=False` returns raw coefficient PLANES — the
    sharded transfer path's format; the relayout then happens on host
    inside the pack pool (measured: the blocked transposes + zigzag
    gathers cost ~0.5 s per 1080p GOP on a v5e chip).

    With rd.p_intra every macroblock is then coded inter or Intra16x16
    (:func:`_p_intra`), the filter reads its bS from the mixed picture,
    and the tuple ends in `pmode`, the (nmb,) int16 kind channel
    (rdo.pmode_word; 0 = inter).
    """
    n = mbw * mbh
    with stage("layout"):
        cy16 = cy.astype(jnp.int16)
        cu16 = cu.astype(jnp.int16)
        cv16 = cv.astype(jnp.int16)
        qp32 = qp.astype(jnp.int32)

    mv, pred_y, pred_u, pred_v, med_mv = jaxme.me_search(
        cy16, ry, ru, rv, pred_mv, qp32, subpel=rd.subpel)

    (luma_levels, chroma_dc, chroma_ac, recon_y, recon_u, recon_v,
     nz4) = _residual_p(cy16, cu16, cv16, pred_y, pred_u, pred_v, qp,
                        qpc, mbw=mbw, mbh=mbh,
                        blocked=blocked and not rd.p_intra, rd=rd)
    pmode = intra_mb = None
    if rd.p_intra:
        (luma_levels, chroma_dc, chroma_ac, recon_y, recon_u, recon_v,
         mv, pmode) = _p_intra(
            (cy16, cu16, cv16), (pred_y, pred_u, pred_v),
            (luma_levels, chroma_dc, chroma_ac),
            (recon_y, recon_u, recon_v), mv, med_mv, qp, qpc, mbw=mbw,
            mbh=mbh, blocked=blocked, rd=rd)
        intra_mb = pmode.reshape(mbh, mbw) != 0
    if rd.deblock:
        with stage("deblock"):
            qp_map = jnp.broadcast_to(qp.astype(jnp.int32), (mbh, mbw))
        recon_y, recon_u, recon_v = jaxdeblock.deblock_frame_jax(
            recon_y, recon_u, recon_v, qp_map, intra=False, nz4=nz4,
            mv=mv, mv_per_pel=rd.mv_per_pel, intra_mb=intra_mb)
    with stage("layout"):
        mv = mv.reshape(n, 2)
    out = (mv, luma_levels, chroma_dc, chroma_ac,
           recon_y, recon_u, recon_v, med_mv)
    return out + (pmode,) if rd.p_intra else out


@stage("residual")
def _residual_p(cy16, cu16, cv16, pred_y, pred_u, pred_v, qp, qpc, *,
                mbw: int, mbh: int, blocked: bool = True, rd=RD_OFF):
    """Residual transform/quant/recon for one P frame given its
    prediction planes — the motion-search-free half of
    :func:`_encode_p_plane`, split out so the banded (SFE) path can
    pair it with `jaxme.me_search_banded`. Per-MB local math only: no
    cross-MB (or cross-band) dependencies.

    With ``rd.pskip`` an MB whose quantized residual is negligible
    (sum |level| <= rdo.PSKIP_SUM across all planes, every |level| <=
    1) drops the residual entirely: its recon becomes pure prediction
    — exactly what a decoder reconstructs for a P_Skip MB — and the
    entropy packer's §8.4.1.1 inference turns it into a skip run
    whenever its MV matches the skip predictor.

    Also returns nz4, the (4·mbh, 4·mbw) any-nonzero map of the FINAL
    luma levels (the deblocking filter's bS=2 input).

    Everything between the first and the last line works on (T, H, 128)
    tiles (:func:`_to_tiles`); each chroma plane is ONE level array
    there, its Hadamard-domain DC levels sitting at the DC positions of
    their 4x4 blocks until the outputs part them."""
    W = cy16.shape[1]
    n = mbw * mbh
    qp32 = qp.astype(jnp.int32)
    pred_y_t = _to_tiles(pred_y)
    pred_u_t, pred_v_t = _to_tiles(pred_u), _to_tiles(pred_v)
    ys, cs = pred_y_t.shape, pred_u_t.shape              # (T, H, 128)
    mf_c = _class_tiles(_MF[qpc % 6], cs)
    v_c = _class_tiles(_V[qpc % 6], cs)
    dc_pos = ((jax.lax.broadcasted_iota(jnp.int32, cs, 1) % 4 == 0)
              & (jax.lax.broadcasted_iota(jnp.int32, cs, 2) % 4 == 0))

    # --- quantize: luma tiles + both chroma planes' ------------------
    resid = (_to_tiles(cy16) - pred_y_t).astype(jnp.int32)
    z = _quant_plane(_fwd4_tiles(resid), _class_tiles(_MF[qp32 % 6], ys),
                     qp32)

    def chroma_quant(cplane16, pred_t):
        """One level plane: AC levels, and at each 4x4 block's DC
        position the MB's Hadamard-domain DC level."""
        wch = _fwd4_tiles((_to_tiles(cplane16) - pred_t).astype(jnp.int32))
        wd2 = _chroma_dc_tiles(wch)
        # chroma DC quant (jaxcore._chroma_dc_quant with the inter
        # rounding bias)
        qbits = 15 + qpc // 6
        f = (1 << qbits) // 6
        zdc = (jnp.abs(wd2) * _MF[qpc % 6, 0, 0] + 2 * f) >> (qbits + 1)
        zdc = jnp.where(wd2 < 0, -zdc, zdc)
        return jnp.where(dc_pos, zdc, _quant_plane(wch, mf_c, qpc))

    zu = chroma_quant(cu16, pred_u_t)
    zv = chroma_quant(cv16, pred_v_t)

    if rd.pskip:
        # P_Skip bias: per-MB level mass across every plane. A level
        # over 1 weighs PSKIP_SUM + 1, so one pooled sum holds both
        # tests (sum <= PSKIP_SUM, max <= 1); it stays under 3 * 384.
        def mass(zt, mb):
            a = jnp.abs(zt)
            a = jnp.where(a > 1, rdo.PSKIP_SUM + 1, a).astype(_F32)
            rows = a.reshape(a.shape[0], mbh, mb, _LANES).sum(axis=2)
            return _tile_maps(_lane_pool(rows, mb), mbw)

        keep = (mass(z, 16) + mass(zu, 8) + mass(zv, 8)
                ) > rdo.PSKIP_SUM                        # (mbh, mbw)

        def over_tiles(shape, mb):
            """`keep` over each MB's samples of a plane's tiles."""
            T, g = shape[0], _LANES // mb
            m = jnp.pad(keep, ((0, 0), (0, T * g - mbw)))
            m = m.reshape(mbh, T, g).transpose(1, 0, 2)  # (T, mbh, g)
            wide = _lane_spread(m, mb) > 0.5             # (T, mbh, 128)
            return jnp.broadcast_to(wide[:, :, None, :],
                                    (T, mbh, mb, _LANES)).reshape(shape)

        z = jnp.where(over_tiles(ys, 16), z, 0)
        keep_c = over_tiles(cs, 8)
        zu = jnp.where(keep_c, zu, 0)
        zv = jnp.where(keep_c, zv, 0)

    nz4 = _nz4_tiles(z, mbw)

    # --- reconstruct from the (possibly zeroed) levels ---------------
    d = _dequant_plane(z, _class_tiles(_V[qp32 % 6], ys), qp32)
    recon_y = _from_tiles(
        jnp.clip((_inv4_tiles(d) + 32 >> 6) + pred_y_t, 0, 255
                 ).astype(jnp.int16), W)
    luma_levels = _from_tiles(z.astype(jnp.int16), W)    # (H, W) coeff plane
    if blocked:
        luma_levels = _luma_plane_to_blocks(luma_levels, mbw, mbh
                                            ).astype(jnp.int32)

    def chroma_recon(pred_t, zc):
        # recon: dequant AC, dequantized DC back at its positions (zac
        # is 0 there: a select, no scatter), inverse
        zac = jnp.where(dc_pos, 0, zc)
        fdc = _chroma_dc_tiles(jnp.where(dc_pos, zc, 0))
        ls = _V[qpc % 6, 0, 0] * 16
        dcr = ((fdc * ls) << (qpc // 6)) >> 5
        dfull = jnp.where(dc_pos, dcr, _dequant_plane(zac, v_c, qpc))
        rec = _from_tiles(
            jnp.clip((_inv4_tiles(dfull) + 32 >> 6) + pred_t, 0, 255
                     ).astype(jnp.int16), W // 2)
        ac = _from_tiles(zac.astype(jnp.int16), W // 2)  # (H/2, W/2) plane
        if blocked:
            ac = _chroma_plane_to_blocks(ac, mbw, mbh)[..., 1:
                                                       ].astype(jnp.int32)
        return _chroma_dc_levels(zc, mbw).reshape(n, 4), ac, rec

    udc, uac, recon_u = chroma_recon(pred_u_t, zu)
    vdc, vac, recon_v = chroma_recon(pred_v_t, zv)
    if blocked:
        chroma_dc = jnp.stack([udc, vdc], axis=1)        # (n, 2, 4)
        chroma_ac = jnp.stack([uac, vac], axis=1)        # (n, 2, 4, 15)
    else:
        chroma_dc = jnp.stack([udc, vdc]).astype(jnp.int16)  # (2, n, 4)
        chroma_ac = jnp.stack([uac, vac])                # (2, H/2, W/2)

    return (luma_levels, chroma_dc, chroma_ac, recon_y, recon_u, recon_v,
            nz4)


# ---------------------------------------------------------------------------
# rd.p_intra: intra macroblocks in P pictures
# ---------------------------------------------------------------------------

_H4_NP = np.asarray(_H4, np.float32)
# 1-D half of the Intra16x16 luma DC Hadamard over the DC positions (0,
# 4, 8, 12) of a 16-sample MB row or column; every other position reads 0
_HAD16_DC = np.zeros((16, 16), np.float32)
_HAD16_DC[::4, ::4] = _H4_NP


def _mb_spread(m, shape, mb: int):
    """A (R, C) map over (T, R * mb, 128) tiles: entry (r, c) over the
    `mb` rows and `mb` lanes of its block (int32)."""
    T, g = shape[0], _LANES // mb
    R, C = m.shape
    m = jnp.pad(m, ((0, 0), (0, T * g - C))).reshape(R, T, g)
    wide = _lane_spread(m.transpose(1, 0, 2), mb)        # (T, R, 128)
    return jnp.broadcast_to(wide[:, :, None, :], (T, R, mb, _LANES)
                            ).reshape(shape).astype(jnp.int32)


def _mb_sum(x, mb: int, ncols: int):
    """(T, H, 128) tiles -> (H // mb, ncols) int32: the sum over every
    mb x mb block (exact below 2**24)."""
    T, H, _ = x.shape
    rows = x.astype(_F32).reshape(T, H // mb, mb, _LANES).sum(axis=2)
    return _tile_maps(_lane_pool(rows, mb), ncols).astype(jnp.int32)


def _intra_preds(rec_t, mb: int, mbw: int, mbh: int):
    """The V and H prediction tiles of every macroblock of a plane from
    its neighbours' reconstruction `rec_t` (§8.3.3), and the lines they
    were made from (for DC): (pred_v, pred_h, top, left) — `top` (T,
    mbh, 128), the sample row above each macroblock row (zeros above
    the first), `left` (H, mbw), the sample column left of each
    macroblock column (zeros left of the first), all int32."""
    T, H, _ = rec_t.shape
    g = _LANES // mb
    bottom = rec_t[:, mb - 1::mb].astype(jnp.int32)      # (T, mbh, 128)
    top = jnp.concatenate([jnp.zeros_like(bottom[:, :1]), bottom[:, :-1]],
                          axis=1)
    pick = jnp.asarray((np.arange(_LANES)[:, None]
                        == np.arange(mb - 1, _LANES, mb)[None, :]
                        ).astype(np.float32))
    right = _tile_maps(jnp.einsum("thl,lm->thm", rec_t.astype(_F32), pick,
                                  precision=_HI), mbw)   # (H, mbw)
    left = jnp.pad(right, ((0, 0), (1, 0)))[:, :mbw].astype(jnp.int32)
    pred_v = jnp.broadcast_to(top[:, :, None, :], (T, mbh, mb, _LANES)
                              ).reshape(T, H, _LANES)
    cols = jnp.pad(left, ((0, 0), (0, T * g - mbw))).reshape(H, T, g)
    pred_h = _lane_spread(cols.transpose(1, 0, 2), mb).astype(jnp.int32)
    return pred_v, pred_h, top, left


def _mb_cost(resid, mb: int, ncols: int, satd: bool):
    """Per-macroblock cost of residual tiles: SATD (4x4 Hadamard, / 2,
    jaxcore._satd16's) or SAD."""
    if satd:
        t = jnp.abs(_lane_mm(_row_mm(resid, _H4_NP), _H4_NP))
        return _mb_sum(t, mb, ncols) // 2
    return _mb_sum(jnp.abs(resid), mb, ncols)


def _se_bits(v):
    """Bits of se(v), v a small int32 array: 2 * floor(log2(2|v|)) + 1
    (1 for 0), the logarithm counted on thresholds."""
    x = 2 * jnp.abs(v)
    n = sum((x >= (1 << k)).astype(jnp.int32) for k in range(1, 11))
    return 2 * n + 1


def _intra_candidates(rec_t, mb: int, mbw: int, mbh: int, chroma: bool):
    """(V, H, DC) prediction tiles of every macroblock of one plane from
    the reconstruction `rec_t` of the macroblocks left of and above it,
    by §8.3.3 (luma, 16x16) or §8.3.4 (chroma, 8x8; DC per 4x4 block)."""
    shape = rec_t.shape
    pred_v, pred_h, top, left = _intra_preds(rec_t, mb, mbw, mbh)
    row = jax.lax.broadcasted_iota(jnp.int32, (mbh, mbw), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (mbh, mbw), 1)
    has_top, has_left = row > 0, col > 0
    both = has_top & has_left
    if not chroma:
        tsum = _tile_maps(_lane_pool(top, 16), mbw).astype(jnp.int32)
        lsum = left.reshape(mbh, 16, mbw).sum(axis=1)
        dc = jnp.where(both, (tsum + lsum + 16) >> 5,
                       jnp.where(has_top, (tsum + 8) >> 4,
                                 jnp.where(has_left, (lsum + 8) >> 4, 128)))
        return pred_v, pred_h, _mb_spread(dc, shape, 16)
    t = _tile_maps(_lane_pool(top, 4), 2 * mbw).astype(jnp.int32)
    t0, t1 = t[:, 0::2], t[:, 1::2]
    ls = left.reshape(mbh, 2, 4, mbw).sum(axis=2)
    l0, l1 = ls[:, 0], ls[:, 1]

    def one(tq, lq, first_top: bool):
        mean_t, mean_l = (tq + 2) >> 2, (lq + 2) >> 2
        a, b = (mean_t, mean_l) if first_top else (mean_l, mean_t)
        ok_a, ok_b = (has_top, has_left) if first_top \
            else (has_left, has_top)
        return jnp.where(ok_a, a, jnp.where(ok_b, b, 128))

    q00 = jnp.where(both, (t0 + l0 + 4) >> 3, one(t0, l0, True))
    q10 = one(t1, l0, True)                 # prefers its own top quarter
    q01 = one(t0, l1, False)                # prefers its own left quarter
    q11 = jnp.where(both, (t1 + l1 + 4) >> 3, one(t1, l1, True))
    quads = jnp.stack([jnp.stack([q00, q10], -1),
                       jnp.stack([q01, q11], -1)], 1
                      ).reshape(2 * mbh, 2 * mbw)
    return pred_v, pred_h, _mb_spread(quads, shape, 4)


def _by_mode(mode, which, shape, mb: int, preds):
    """Of `preds` (V, H, DC) every macroblock's own, by its `mode` map;
    `which` = the mode numbers of (V, H)."""
    m = _mb_spread(mode, shape, mb)
    return jnp.where(m == which[0], preds[0],
                     jnp.where(m == which[1], preds[1], preds[2]))


def _dc_positions(shape):
    return ((jax.lax.broadcasted_iota(jnp.int32, shape, 1) % 4 == 0)
            & (jax.lax.broadcasted_iota(jnp.int32, shape, 2) % 4 == 0))


def _intra16_luma(src_t, pred_t, qp32):
    """Intra16x16 luma of every macroblock of the tiles, in
    jaxcore._luma_mb_batch's arithmetic: (levels, recon), the levels'
    DC positions holding the Hadamard-domain DC levels (§8.5.10: level
    (u, v) of a macroblock at the DC position of its block (u, v))."""
    ys = src_t.shape
    dc_pos = _dc_positions(ys)
    w = _fwd4_tiles(src_t - pred_t)
    wd = _row_mm(_lane_mm(w, _HAD16_DC), _HAD16_DC).astype(jnp.int32) // 2
    z = jnp.where(dc_pos, _luma_dc_quant(wd, qp32),
                  _quant_plane(w, _class_tiles(_MF[qp32 % 6], ys), qp32,
                               bias_div=3))
    fdc = _row_mm(_lane_mm(jnp.where(dc_pos, z, 0), _HAD16_DC),
                  _HAD16_DC).astype(jnp.int32)
    # jaxcore._luma_dc_dequant's scaling of the inverse Hadamard
    ls = _V[qp32 % 6, 0, 0] * 16
    shift = jnp.maximum(6 - qp32 // 6, 1)
    dcr = jnp.where(qp32 >= 36,
                    (fdc * ls) << jnp.maximum(qp32 // 6 - 6, 0),
                    (fdc * ls + (1 << (shift - 1))) >> shift)
    d = jnp.where(dc_pos, dcr,
                  _dequant_plane(z, _class_tiles(_V[qp32 % 6], ys), qp32))
    return z, jnp.clip((_inv4_tiles(d) + 32 >> 6) + pred_t, 0, 255)


def _intra_chroma(src_t, pred_t, qpc):
    """One chroma plane of every macroblock coded intra, in
    jaxcore._chroma_mb_batch's arithmetic (the intra rounding, where
    `_residual_p` has the inter one): (levels with the Hadamard-domain
    DC levels at the DC positions, recon)."""
    cs = src_t.shape
    dc_pos = _dc_positions(cs)
    wch = _fwd4_tiles(src_t - pred_t)
    wd2 = _chroma_dc_tiles(wch)
    qbits = 15 + qpc // 6
    zdc = (jnp.abs(wd2) * _MF[qpc % 6, 0, 0] + 2 * ((1 << qbits) // 3)
           ) >> (qbits + 1)
    z = jnp.where(dc_pos, jnp.where(wd2 < 0, -zdc, zdc),
                  _quant_plane(wch, _class_tiles(_MF[qpc % 6], cs), qpc,
                               bias_div=3))
    dcr = ((_chroma_dc_tiles(jnp.where(dc_pos, z, 0))
            * (_V[qpc % 6, 0, 0] * 16)) << (qpc // 6)) >> 5
    d = jnp.where(dc_pos, dcr,
                  _dequant_plane(z, _class_tiles(_V[qpc % 6], cs), qpc))
    return z, jnp.clip((_inv4_tiles(d) + 32 >> 6) + pred_t, 0, 255)


@stage("p_intra")
def _p_intra(src, pred, levels, recon, mv, med_mv, qp, qpc, *, mbw: int,
             mbh: int, blocked: bool, rd):
    """rd.p_intra: of every macroblock of a P picture, inter as
    :func:`_residual_p` coded it or Intra16x16.

    src, pred: the (y, u, v) source and inter prediction planes;
    levels: `_residual_p`'s PLANE-layout (luma, chroma_dc, chroma_ac);
    recon: its unfiltered reconstruction; mv (mbh, mbw, 2) with the
    frame's median `med_mv`. Returns (luma, chroma_dc, chroma_ac,
    recon_y, recon_u, recon_v, mv, pmode): the mixed levels (blocked
    when `blocked`), the mixed unfiltered reconstruction, the vectors
    with the intra macroblocks' zeroed, and the (nmb,) int16 kind
    channel (rdo.pmode_word).

    An intra macroblock's luma levels lie where an inter one's do, the
    Hadamard-domain DC level (u, v) of §8.5.10 at the DC position of
    its 4x4 block (u, v) — as `_residual_p` keeps the chroma DC levels
    — so the transfer layouts carry both kinds in one plane.

    Schedule (no scan over macroblock rows: everything is
    plane-parallel). THE WISH: the three Intra16x16 predictions (V, H,
    DC, by availability) of EVERY macroblock from its A / B neighbours'
    all-inter reconstruction, at once; the cost of the best against the
    inter prediction's on one scale (SATD with rd.mode_decision, else
    SAD), each plus lambda times its side bits (rdo.P_INTRA_BITS; the
    vector's against the frame's median); ties stay inter. THE CODING,
    in rdo.P_INTRA_PASSES passes over the picture: pass k codes the
    wishing macroblocks of class (x + y) mod passes == k, each in the
    mode the wish chose, predicted from the reconstruction AS IT STANDS
    — its A / B neighbours are of class k - 1, coded in the pass before
    and final. Class 0's neighbours are of the last class, coded last:
    a macroblock of that class stays inter where the one right of it or
    below it went intra in pass 0, so what that one was predicted from
    stands. What is coded is thus what §8.3.3 makes a decoder form,
    sample for sample; in a solid region of wishes one macroblock in
    `passes` stays inter. A candidate with a level beyond the sparse
    wire's int8 (a DC step of 88 or more at QP 25) stays inter: one
    such level would send its whole wave through the dense fallback."""
    cy16, cu16, cv16 = src
    zy_p, cdc_p, cac_p = levels
    W = cy16.shape[1]
    n = mbw * mbh
    qp32 = qp.astype(jnp.int32)
    satd = rd.mode_decision
    srcs = [_to_tiles(c).astype(jnp.int32) for c in src]
    recs = tuple(_to_tiles(r).astype(jnp.int32) for r in recon)
    ys, cs = srcs[0].shape, srcs[1].shape
    row = jax.lax.broadcasted_iota(jnp.int32, (mbh, mbw), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (mbh, mbw), 1)
    has_top, has_left = row > 0, col > 0
    inf = jnp.int32(1 << 29)
    sizes = (16, 8, 8)

    def candidates(recs):
        return [_intra_candidates(r, mb, mbw, mbh, chroma=mb == 8)
                for r, mb in zip(recs, sizes)]

    # --- the wish: modes and costs from the all-inter neighbours -----
    cand = candidates(recs)
    costs = [[_mb_cost(s_t - p, mb, mbw, satd) for p in preds]
             for s_t, preds, mb in zip(srcs, cand, sizes)]
    (c_v, c_h, c_dc), cu, cv_ = costs
    # strict <, earlier wins: DC (always there), then V, then H
    best_y, ymode = _pick3(c_dc, 2, jnp.where(has_top, c_v, inf), 0,
                           jnp.where(has_left, c_h, inf), 1)
    best_c, cmode = _pick3(cu[2] + cv_[2], 0,
                           jnp.where(has_top, cu[0] + cv_[0], inf), 2,
                           jnp.where(has_left, cu[1] + cv_[1], inf), 1)
    inter = sum(_mb_cost(s_t - _to_tiles(p), mb, mbw, satd)
                for s_t, p, mb in zip(srcs, pred, sizes))
    lam = jnp.asarray(rdo.P_INTRA_LAMBDA, jnp.int32)[jnp.clip(qp32, 0, 51)]
    mvd = (mv.astype(jnp.int32) - med_mv.astype(jnp.int32)
           ) * (4 // rd.mv_per_pel)
    inter = inter + lam * (_se_bits(mvd[..., 0]) + _se_bits(mvd[..., 1]))
    wish = best_y + best_c + lam * rdo.P_INTRA_BITS < inter

    # --- the coding: one class of macroblocks a pass ------------------
    passes = rdo.P_INTRA_PASSES
    dc_pos_c = _dc_positions(cs)

    def code_class(k, state):
        zy, zu, zv, udc, vdc, recs, intra_mb = state
        cand = candidates(recs)
        zy_i, ry_i = _intra16_luma(
            srcs[0], _by_mode(ymode, (0, 1), ys, 16, cand[0]), qp32)
        zc_i, rc_i = zip(*(
            _intra_chroma(s_t, _by_mode(cmode, (2, 1), cs, 8, preds), qpc)
            for s_t, preds in zip(srcs[1:], cand[1:])))
        peak = _mb_sum(jnp.abs(zy_i) > 127, 16, mbw) + sum(
            _mb_sum(jnp.abs(z) > 127, 8, mbw) for z in zc_i)
        # the macroblock right of / below one that is intra already
        # was predicted from this one as it stands
        after = (jnp.pad(intra_mb, ((0, 0), (0, 1)))[:, 1:]
                 | jnp.pad(intra_mb, ((0, 1), (0, 0)))[1:])
        take = wish & ((row + col) % passes == k) & (peak == 0) & ~after
        take_y = _mb_spread(take, ys, 16) > 0
        take_c = _mb_spread(take, cs, 8) > 0
        take_mb = take.reshape(n, 1)
        zu, zv = (jnp.where(take_c, jnp.where(dc_pos_c, 0, z_i), z)
                  for z_i, z in zip(zc_i, (zu, zv)))
        udc, vdc = (jnp.where(take_mb,
                              _chroma_dc_levels(z_i, mbw).reshape(n, 4), dc)
                    for z_i, dc in zip(zc_i, (udc, vdc)))
        recs = tuple(jnp.where(t, r_i, r) for t, r_i, r in zip(
            (take_y, take_c, take_c), (ry_i, *rc_i), recs))
        return (jnp.where(take_y, zy_i, zy), zu, zv, udc, vdc, recs,
                intra_mb | take)

    none = (jnp.zeros((mbh, mbw), jnp.int32) + _varying_zero(cy16)) > 0
    state = (_to_tiles(zy_p).astype(jnp.int32),
             _to_tiles(cac_p[0]).astype(jnp.int32),
             _to_tiles(cac_p[1]).astype(jnp.int32),
             cdc_p[0].astype(jnp.int32), cdc_p[1].astype(jnp.int32),
             recs, none)
    zy, zu, zv, udc, vdc, recs, intra_mb = jax.lax.fori_loop(
        0, passes, code_class, state)

    zy = _from_tiles(zy.astype(jnp.int16), W)
    cac = [_from_tiles(z.astype(jnp.int16), W // 2) for z in (zu, zv)]
    cdc = [udc.astype(jnp.int16), vdc.astype(jnp.int16)]
    recon_y = _from_tiles(recs[0].astype(jnp.int16), W)
    recon_u = _from_tiles(recs[1].astype(jnp.int16), W // 2)
    recon_v = _from_tiles(recs[2].astype(jnp.int16), W // 2)
    pmode = jnp.where(intra_mb, rdo.pmode_word(ymode, cmode), 0
                      ).reshape(n).astype(jnp.int16)
    mv = jnp.where(intra_mb[..., None], 0, mv)
    if blocked:
        zy = _luma_plane_to_blocks(zy, mbw, mbh).astype(jnp.int32)
        chroma_dc = jnp.stack(cdc, axis=1).astype(jnp.int32)
        chroma_ac = jnp.stack(
            [_chroma_plane_to_blocks(a, mbw, mbh)[..., 1:] for a in cac],
            axis=1).astype(jnp.int32)
    else:
        chroma_dc, chroma_ac = jnp.stack(cdc), jnp.stack(cac)
    return (zy, chroma_dc, chroma_ac, recon_y, recon_u, recon_v, mv, pmode)


def _intra_frame_outputs(y, u, v, qp, *, mbw: int, mbh: int, rd):
    """Shared IDR half of the GOP programs: intra core + (optionally)
    deblocked recon carry + the pack-facing intra tuple (4 blocked
    arrays, or 6 with the per-MB [mode16 | dqp16] side channel when
    rd.ships_modes, 7 with rd.intra4x4's block-mode words)."""
    out = _intra_core(y, u, v, qp, mbw=mbw, mbh=mbh, rd=rd)
    il_dc, il_ac, ic_dc, ic_ac, ry, ru, rv = out[:7]
    qp_delta = out[9]
    with stage("intra"):
        ry = ry.astype(jnp.int16)
        ru = ru.astype(jnp.int16)
        rv = rv.astype(jnp.int16)
    if rd.deblock:
        with stage("deblock"):
            qp_map = (qp.astype(jnp.int32) + qp_delta).reshape(mbh, mbw)
        ry, ru, rv = jaxdeblock.deblock_frame_jax(
            ry, ru, rv, qp_map, intra=True)
    if rd.ships_modes:
        with stage("intra"):
            tail = _mode_tail(*out[7:])
            nmb = mbw * mbh
            intra = (il_dc, il_ac, ic_dc, ic_ac, tail[:nmb],
                     tail[nmb:2 * nmb])
            if rd.intra4x4:
                intra += (tail[2 * nmb:],)
    else:
        intra = (il_dc, il_ac, ic_dc, ic_ac)
    return intra, (ry, ru, rv)


@stage("layout")
def _gop_head(ys, us, vs, qp):
    """(qp int32, chroma qp, the IDR frame's three planes) of a GOP."""
    qp = qp.astype(jnp.int32)
    return qp, _QPC[jnp.clip(qp, 0, 51)], ys[0], us[0], vs[0]


@stage("layout")
def _scan_p_frames(p_step, recon, planes, n_frames=None):
    """Chain `p_step` over frames 1..F-1 of a GOP from the IDR's recon,
    or over the GOP's first `n_frames` alone (_loop_p_frames). The scope
    names the loop; the stages inside `p_step` keep their own names."""
    # Inits derived from data, not constants: jaxcore._varying_zero.
    zero = _varying_zero(recon[0])
    zero_mv = jnp.zeros(2, jnp.int32) + zero
    if n_frames is not None and planes[0].shape[0] > 1:
        return _loop_p_frames(p_step, (*recon, zero_mv), planes, n_frames)
    _, pouts = jax.lax.scan(
        p_step, (*recon, zero_mv), tuple(p[1:] for p in planes))
    return pouts


@functools.partial(jax.jit,
                   static_argnames=("mbw", "mbh", "emit_recon", "rd"))
def encode_gop_jit(ys, us, vs, qp, *, mbw: int, mbh: int,
                   emit_recon: bool = False, rd=RD_OFF):
    """Closed-GOP compute: frame 0 intra, frames 1..F-1 inter (P).

    ys: (F, H, W) uint8. Returns the intra frame's level arrays (plus
    the mode/dqp side channel when rd.ships_modes) and the P frames'
    (mv, luma16, chroma_dc, chroma_ac), with rd.p_intra also their
    `pmode` (F-1, nmb), stacked over F-1; with
    `emit_recon` also the per-frame reconstructed planes (tests/metrics
    — costs F x frame HBM, off by default). With rd.deblock the recon
    chained between frames (and emitted) is the §8.7-filtered plane —
    exactly what a conformant decoder holds.
    """
    qp, qpc, y0, u0, v0 = _gop_head(ys, us, vs, qp)
    intra, (ry, ru, rv) = _intra_frame_outputs(
        y0, u0, v0, qp, mbw=mbw, mbh=mbh, rd=rd)

    def p_step(carry, xs):
        ry, ru, rv, pred_mv = carry
        cy, cu, cv = xs
        (mv, l16, cdc, cac, ry2, ru2, rv2, med_mv, *pmode
         ) = _encode_p_plane(
            cy, cu, cv, ry, ru, rv, pred_mv, qp, qpc, mbw=mbw, mbh=mbh,
            rd=rd)
        outs = (mv, l16, cdc, cac, *pmode)
        if emit_recon:
            outs = outs + (ry2, ru2, rv2)
        return (ry2, ru2, rv2, med_mv), outs

    pouts = _scan_p_frames(p_step, (ry, ru, rv), (ys, us, vs))
    if emit_recon:
        *pouts, pry, pru, prv = pouts
        with stage("layout"):
            recon_y = jnp.concatenate([ry[None], pry]).astype(jnp.int32)
            recon_u = jnp.concatenate([ru[None], pru]).astype(jnp.int32)
            recon_v = jnp.concatenate([rv[None], prv]).astype(jnp.int32)
        return intra, tuple(pouts), (recon_y, recon_u, recon_v)
    return intra, tuple(pouts)


# Per-MB flat sizes for the plane-layout GOP transfer: the P part of the
# flat vector is (F-1) * nmb * _P_FLAT_MB int16 values laid out
# struct-of-arrays: all luma coeff planes, then u DC, v DC (hadamard
# domain), then u AC, v AC coeff planes (DC positions zeroed). The
# values live in the jax-free layout module (the host inverses read
# them without dragging jax in); re-exported here next to the encode
# that emits the layout.
from .layout import _INTRA_FLAT_MB, _P_FLAT_MB  # noqa: E402


def _check_mv8(rd) -> None:
    """The int8 MV transfer rides on search candidates being bounded by
    construction: centers clamp to ±(SEARCH_RANGE - window) pel, offsets
    add ≤ the window (the quarter window lies inside it), so |mv| ≤
    rd.mv_per_pel * SEARCH_RANGE units per frame — 32 half units, 64
    quarter units (a P frame references its predecessor: MVs never
    accumulate)."""
    if rd.mv_per_pel * SEARCH_RANGE > 127:
        raise ValueError("SEARCH_RANGE exceeds the int8 MV transfer")


def encode_gop_planes(ys, us, vs, qp, *, mbw: int, mbh: int, rd=RD_OFF,
                      n_frames=None):
    """Closed-GOP compute emitting PLANE-layout levels for the sharded
    transfer path: returns (mv (F-1, nmb, 2) int8, flat int16). With
    `n_frames` (int32 scalar, 1..F: the real length of a GOP staged to
    F by tail-repeat) frames from n_frames on are not encoded: zeros.
    flat layout (all reshape(-1), no relayout on device):
      [ intra il_dc | il_ac | ic_dc | ic_ac          (nmb * 384)
      | luma coeff planes   (F-1, H, W)
      | u DC (F-1, nmb, 4) | v DC (F-1, nmb, 4)
      | u AC plane (F-1, H/2, W/2) | v AC plane (F-1, H/2, W/2)
      | P pmode (F-1, nmb)                        — rd.p_intra only
      | intra mode16 (nmb) | intra dqp16 (nmb)   — rd.ships_modes only
      | intra block modes (nmb, 4)                — rd.intra4x4 only ]
    The host inverse is codecs/h264/layout.unflatten_gop.
    """
    _check_mv8(rd)
    qp, qpc, y0, u0, v0 = _gop_head(ys, us, vs, qp)
    intra, (ry, ru, rv) = _intra_frame_outputs(
        y0, u0, v0, qp, mbw=mbw, mbh=mbh, rd=rd)

    def p_step(carry, xs):
        ry, ru, rv, pred_mv = carry
        cy, cu, cv = xs
        (mv, lp, cdc, cac, ry2, ru2, rv2, med_mv, *pmode
         ) = _encode_p_plane(
            cy, cu, cv, ry, ru, rv, pred_mv, qp, qpc, mbw=mbw, mbh=mbh,
            blocked=False, rd=rd)
        return (ry2, ru2, rv2, med_mv), (mv.astype(jnp.int8), lp, cdc, cac,
                                         *pmode)

    mv8, lps, cdcs, cacs, *pmodes = _scan_p_frames(
        p_step, (ry, ru, rv), (ys, us, vs), n_frames)
    # cdcs: (F-1, 2, n, 4) int16; cacs: (F-1, 2, H/2, W/2) int16
    with stage("layout"):
        parts = [
            intra[0].reshape(-1).astype(jnp.int16),
            intra[1].reshape(-1).astype(jnp.int16),
            intra[2].reshape(-1).astype(jnp.int16),
            intra[3].reshape(-1).astype(jnp.int16),
            lps.reshape(-1),
            cdcs[:, 0].reshape(-1), cdcs[:, 1].reshape(-1),
            cacs[:, 0].reshape(-1), cacs[:, 1].reshape(-1),
        ]
        parts.extend(m.reshape(-1) for m in pmodes)
        parts.extend(intra[4:])
        flat = jnp.concatenate(parts)
    return mv8, flat


# ---------------------------------------------------------------------------
# split-frame encoding (SFE): per-band, per-FRAME step cores
#
# The GOP paths above amortize dispatch by batching a whole GOP per
# program; the SFE path instead steps ONE frame at a time so the
# per-frame glass-to-bitstream latency is a single device step + band
# fetch + band-slice pack (parallel/dispatch.SfeShardEncoder). Each
# core runs on one band's (Hb, W) shard under shard_map; the recon
# carry chains between steps ON DEVICE.
# ---------------------------------------------------------------------------


def _deblock_band(ry, ru, rv, qp, *, intra: bool, nz4, mv, mbw: int,
                  mbh_band: int, total_mb_rows: int, axis_name,
                  num_bands: int, mv_per_pel: int = 2):
    """Deblock one band's recon by itself: the band is a slice that
    signals disable_deblocking_filter_idc = 2, so a decoder filters no
    edge between two bands, and §8.7's order (each macroblock row needs
    the one above finished) ends at the band's first row. No sample and
    no bS metadata crosses bands. The last band's padding rows lie past
    the picture (`mb_row0`, traced through lax.axis_index so one
    program serves every band, against `total_mb_rows`) and are left
    alone."""
    banded = axis_name is not None and num_bands > 1
    with stage("deblock"):
        idx = jax.lax.axis_index(axis_name) if banded \
            else jnp.int32(0) + _varying_zero(ry)
        mb_row0 = idx * mbh_band
        qp_map = jnp.broadcast_to(qp.astype(jnp.int32), (mbh_band, mbw))
    return jaxdeblock.deblock_frame_jax(
        ry, ru, rv, qp_map, intra=intra, nz4=nz4, mv=mv,
        mb_row0=mb_row0, total_mb_rows=total_mb_rows,
        mv_per_pel=mv_per_pel)


@stage("halo")
def _fixup_band_recon(plane, real_rows, scale: int = 1):
    """Maintain the SFE recon invariant on a band plane: rows at/past
    this band's real content (the last band's MB padding) are the
    edge-replication of the last REAL row. The full-frame search pads
    its reference with edge replication below the frame; without this
    fixup the padding rows would instead hold the recon of replicated
    SOURCE rows — close, but not the bits the full-frame program (or a
    conformant decoder's edge clamp) sees."""
    H = plane.shape[0]
    real = jnp.maximum(real_rows // scale, 1)
    rows = jnp.arange(H)
    return jnp.take(plane, jnp.minimum(rows, real - 1), axis=0)


def _sfe_intra_common(y, u, v, qp, real_rows, *, mbw: int,
                      mbh_band: int, rd, total_mb_rows: int,
                      axis_name, num_bands: int):
    """Shared intra-band compute: slice-local core + recon fixup +
    (with rd.deblock) the band's own in-loop filter on the carry.
    Returns (core outputs, (ry, ru, rv, zero_mv))."""
    out = _intra_core(y, u, v, qp, mbw=mbw, mbh=mbh_band, rd=rd)

    def recon(plane, scale):
        with stage("intra"):
            plane = plane.astype(jnp.int16)
        return _fixup_band_recon(plane, real_rows, scale)

    ry, ru, rv = recon(out[4], 1), recon(out[5], 2), recon(out[6], 2)
    if rd.deblock:
        # SFE runs AQ-free (enforced at encoder construction), so the
        # band qp map is flat and no qp metadata crosses bands.
        ry, ru, rv = _deblock_band(
            ry, ru, rv, qp, intra=True, nz4=None, mv=None, mbw=mbw,
            mbh_band=mbh_band, total_mb_rows=total_mb_rows,
            axis_name=axis_name, num_bands=num_bands)
        ry = _fixup_band_recon(ry, real_rows)
        ru = _fixup_band_recon(ru, real_rows, 2)
        rv = _fixup_band_recon(rv, real_rows, 2)
    with stage("intra"):
        zero_mv = jnp.zeros(2, jnp.int32) + _varying_zero(ry)
    return out, (ry, ru, rv, zero_mv)


def sfe_intra_band(y, u, v, qp, real_rows, *, mbw: int, mbh_band: int,
                   rd=RD_OFF, total_mb_rows: int = 0, axis_name=None,
                   num_bands: int = 1):
    """One band's IDR step: slice-local intra prediction — the band's
    first MB row predicts like a frame's row 0 because the MBs above
    live in ANOTHER slice and are unavailable to intra prediction
    (§8.3: exactly what a conformant decoder reconstructs), so no
    cross-band exchange is needed on intra frames (the in-loop filter,
    when enabled, is slice-local too — _deblock_band).

    Returns (dense, rest, (ry, ru, rv, pred_mv)): dense is the
    hadamard-DC prefix [il_dc | ic_dc] shipped uncompressed (the only
    levels that exceed int8 at practical QPs — same rationale as
    dispatch._per_gop_sparse) plus, when rd.ships_modes, the per-MB
    [mode16 | dqp16] side channel; rest is [il_ac | ic_ac] for the
    sparse transfer, and the carry holds the fixed-up recon + a zero
    median MV (each GOP's temporal predictor restarts at its IDR)."""
    qp = qp.astype(jnp.int32)
    out, carry = _sfe_intra_common(
        y, u, v, qp, real_rows, mbw=mbw, mbh_band=mbh_band, rd=rd,
        total_mb_rows=total_mb_rows, axis_name=axis_name,
        num_bands=num_bands)
    il_dc, il_ac, ic_dc, ic_ac = out[:4]
    with stage("layout"):
        dense_parts = [il_dc.reshape(-1).astype(jnp.int16),
                       ic_dc.reshape(-1).astype(jnp.int16)]
        if rd.ships_modes:
            dense_parts.append(_mode_tail(out[7], out[8], out[9]))
        dense = jnp.concatenate(dense_parts)
        rest = jnp.concatenate([il_ac.reshape(-1).astype(jnp.int16),
                                ic_ac.reshape(-1).astype(jnp.int16)])
    return dense, rest, carry


def sfe_intra_band_dense(y, u, v, qp, real_rows, *, mbw: int,
                         mbh_band: int, rd=RD_OFF,
                         total_mb_rows: int = 0, axis_name=None,
                         num_bands: int = 1):
    """Dense-transfer variant of :func:`sfe_intra_band`: one flat int16
    vector in the standard intra layout (layout.unflatten_intra's
    inverse, mode/dqp tail appended when rd.ships_modes) — the escape
    fallback path."""
    qp = qp.astype(jnp.int32)
    out, carry = _sfe_intra_common(
        y, u, v, qp, real_rows, mbw=mbw, mbh_band=mbh_band, rd=rd,
        total_mb_rows=total_mb_rows, axis_name=axis_name,
        num_bands=num_bands)
    il_dc, il_ac, ic_dc, ic_ac = out[:4]
    with stage("layout"):
        parts = [
            il_dc.reshape(-1).astype(jnp.int16),
            il_ac.reshape(-1).astype(jnp.int16),
            ic_dc.reshape(-1).astype(jnp.int16),
            ic_ac.reshape(-1).astype(jnp.int16)]
        if rd.ships_modes:
            parts.append(_mode_tail(out[7], out[8], out[9]))
        flat = jnp.concatenate(parts)
    return flat, carry


def sfe_p_band(y, u, v, carry, qp, real_rows, *, mbw: int, mbh_band: int,
               halo_rows: int, num_bands: int, axis_name, ext=None,
               edge_top: bool = True, edge_bot: bool = True, probe=None,
               return_hist: bool = False, rd=RD_OFF,
               total_mb_rows: int = 0):
    """One band's P step: banded motion search (halo exchange + psum'd
    global centers/median, jaxme.me_search_banded) + the shared
    residual core, emitting PLANE-layout levels for the per-frame
    sparse transfer.

    Farm mode (parallel/sfefarm.py): `ext`/`edge_top`/`edge_bot`
    inject the cross-HOST neighbor reference rows, `probe` the
    host-resolved global probe center, and `return_hist=True` returns
    the per-host histogram partial instead of the on-device median
    (the host finishes it across peers and feeds it back as the next
    frame's `pred_mv`).

    Returns (mv8 (nmb, 2) int8, flat int16 [luma plane | u dc | v dc |
    u ac | v ac] — a single-frame slice of encode_gop_planes' P layout,
    so layout.unflatten_p_planes(flat, mv8, 2, ...) is the host
    inverse), plus the chained (ry, ru, rv, med_mv) carry; with
    `return_hist` the tail is (cnt, n, (ry, ru, rv, pred_mv))."""
    _check_mv8(rd)
    if rd.deblock and (ext is not None or probe is not None
                       or return_hist):
        # Farm band slices have never run with the in-loop filter
        # (their references cross hosts once per frame, before it):
        # the remote planner keeps GOP-range shards for deblock jobs.
        raise ValueError("deblock is not supported on cross-host band "
                         "slices; use GOP sharding for this job")
    ry, ru, rv, pred_mv = carry
    with stage("layout"):
        qp32 = qp.astype(jnp.int32)
        qpc = _QPC[jnp.clip(qp32, 0, 51)]
        cy16 = y.astype(jnp.int16)
        cu16 = u.astype(jnp.int16)
        cv16 = v.astype(jnp.int16)
    out = jaxme.me_search_banded(
        cy16, ry, ru, rv, pred_mv, qp32, halo_rows=halo_rows,
        num_bands=num_bands, axis_name=axis_name, real_rows=real_rows,
        ext=ext, edge_top=edge_top, edge_bot=edge_bot, probe=probe,
        return_hist=return_hist, subpel=rd.subpel)
    if return_hist:
        mv, py, pu, pv, cnt, n = out
    else:
        mv, py, pu, pv, med = out
    (lp, cdc, cac, ry2, ru2, rv2, nz4) = _residual_p(
        cy16, cu16, cv16, py, pu, pv, qp32, qpc, mbw=mbw, mbh=mbh_band,
        blocked=False, rd=rd)
    ry2 = _fixup_band_recon(ry2, real_rows)
    ru2 = _fixup_band_recon(ru2, real_rows, 2)
    rv2 = _fixup_band_recon(rv2, real_rows, 2)
    if rd.deblock:
        ry2, ru2, rv2 = _deblock_band(
            ry2, ru2, rv2, qp32, intra=False, nz4=nz4, mv=mv,
            mbw=mbw, mbh_band=mbh_band, total_mb_rows=total_mb_rows,
            axis_name=axis_name, num_bands=num_bands)
        ry2 = _fixup_band_recon(ry2, real_rows)
        ru2 = _fixup_band_recon(ru2, real_rows, 2)
        rv2 = _fixup_band_recon(rv2, real_rows, 2)
    with stage("layout"):
        flat = jnp.concatenate([
            lp.reshape(-1),
            cdc[0].reshape(-1), cdc[1].reshape(-1),
            cac[0].reshape(-1), cac[1].reshape(-1)])
        mv8 = mv.reshape(-1, 2).astype(jnp.int8)
    if return_hist:
        # the host owns the median in farm mode: carry the INPUT pred
        # (ignored — the next step receives the cross-host median as a
        # fresh input) so the carry shape matches the local chain's
        return mv8, flat, cnt, n, (ry2, ru2, rv2, pred_mv)
    return mv8, flat, (ry2, ru2, rv2, med)


def _loop_p_frames(p_step, carry, planes, n_frames):
    """:func:`_scan_p_frames` for a GOP staged to F frames of which the
    first `n_frames` (int32 scalar, traced, 1..F) are real and the rest
    repeats of the last that the host drops: the same `p_step` over
    frames 1..n_frames-1, a `while` whose bound is data, each frame's
    outputs written into zero (F-1, ...) buffers — what `lax.scan`
    lowers to with a static bound. Frames from n_frames on cost
    nothing and read zero. A program takes this form for every GOP of
    a plan made on scene cuts (SegmentPlan.pin_frames) and for no
    other plan.

    `p_step` is traced ONCE, to a jaxpr that gives the buffers their
    shapes and is then evaluated as the loop's body (the same equations,
    their stage names and source lines kept): the buffers have to exist
    before the `while` is built, and tracing `p_step` a second time for
    its shapes alone cost the serving set 11 s of every start on the
    chip, where its step holds two Pallas kernels (PERF.md §6, PR 39)."""
    from jax.extend.core import jaxpr_as_fun

    def frame(i):
        return tuple(jax.lax.dynamic_index_in_dim(p, i, keepdims=False)
                     for p in planes)

    zero = _varying_zero(carry[0])      # see _scan_p_frames
    traced, shapes = jax.make_jaxpr(p_step, return_shape=True)(
        carry, frame(1))
    step, tree = jaxpr_as_fun(traced), jax.tree.structure(shapes)
    outs = tuple(
        jnp.zeros((planes[0].shape[0] - 1, *o.shape), o.dtype)
        + zero.astype(o.dtype) for o in shapes[1])

    def body(i, state):
        carry, outs = state
        carry, out = jax.tree.unflatten(
            tree, step(*jax.tree.leaves((carry, frame(i + 1)))))
        return carry, tuple(
            jax.lax.dynamic_update_index_in_dim(o, x, i, 0)
            for o, x in zip(outs, out))

    return jax.lax.fori_loop(0, n_frames - 1, body, (carry, outs))[1]
