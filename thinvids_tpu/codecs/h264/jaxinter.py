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
    _MF,
    _QPC,
    _V,
    _ZZ,
    _ZSCAN,
    _intra_core,
    _mode_tail,
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


def _quant_plane(w, mf_plane, qp):
    """Quantize an INTER coefficient plane with the f = (1 << qbits) / 6
    rounding bias (over-rounding inter residuals inflates levels and
    bitrate; the intra paths in jaxcore keep the standard 1/3)."""
    qbits = 15 + qp // 6
    f = (1 << qbits) // 6
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
                        qpc, mbw=mbw, mbh=mbh, blocked=blocked, rd=rd)
    if rd.deblock:
        with stage("deblock"):
            qp_map = jnp.broadcast_to(qp.astype(jnp.int32), (mbh, mbw))
        recon_y, recon_u, recon_v = jaxdeblock.deblock_frame_jax(
            recon_y, recon_u, recon_v, qp_map, intra=False, nz4=nz4,
            mv=mv, mv_per_pel=rd.mv_per_pel)
    with stage("layout"):
        mv = mv.reshape(n, 2)
    return (mv, luma_levels, chroma_dc, chroma_ac,
            recon_y, recon_u, recon_v, med_mv)


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


def _intra_frame_outputs(y, u, v, qp, *, mbw: int, mbh: int, rd):
    """Shared IDR half of the GOP programs: intra core + (optionally)
    deblocked recon carry + the pack-facing intra tuple (4 blocked
    arrays, or 6 with the per-MB [mode16 | dqp16] side channel when
    rd.ships_modes)."""
    out = _intra_core(y, u, v, qp, mbw=mbw, mbh=mbh, rd=rd)
    il_dc, il_ac, ic_dc, ic_ac, ry, ru, rv = out[:7]
    luma_mode, chroma_mode, qp_delta = out[7:]
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
            tail = _mode_tail(luma_mode, chroma_mode, qp_delta)
            intra = (il_dc, il_ac, ic_dc, ic_ac,
                     tail[:mbw * mbh], tail[mbw * mbh:])
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
    (mv, luma16, chroma_dc, chroma_ac) stacked over F-1; with
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
        (mv, l16, cdc, cac, ry2, ru2, rv2, med_mv) = _encode_p_plane(
            cy, cu, cv, ry, ru, rv, pred_mv, qp, qpc, mbw=mbw, mbh=mbh,
            rd=rd)
        outs = (mv, l16, cdc, cac)
        if emit_recon:
            outs = outs + (ry2, ru2, rv2)
        return (ry2, ru2, rv2, med_mv), outs

    pouts = _scan_p_frames(p_step, (ry, ru, rv), (ys, us, vs))
    if emit_recon:
        mv, l16, cdc, cac, pry, pru, prv = pouts
        with stage("layout"):
            recon_y = jnp.concatenate([ry[None], pry]).astype(jnp.int32)
            recon_u = jnp.concatenate([ru[None], pru]).astype(jnp.int32)
            recon_v = jnp.concatenate([rv[None], prv]).astype(jnp.int32)
        return intra, (mv, l16, cdc, cac), (recon_y, recon_u, recon_v)
    mv, l16, cdc, cac = pouts
    return intra, (mv, l16, cdc, cac)


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
      | intra mode16 (nmb) | intra dqp16 (nmb)   — rd.ships_modes only ]
    The host inverse is codecs/h264/layout.unflatten_gop.
    """
    _check_mv8(rd)
    qp, qpc, y0, u0, v0 = _gop_head(ys, us, vs, qp)
    intra, (ry, ru, rv) = _intra_frame_outputs(
        y0, u0, v0, qp, mbw=mbw, mbh=mbh, rd=rd)

    def p_step(carry, xs):
        ry, ru, rv, pred_mv = carry
        cy, cu, cv = xs
        (mv, lp, cdc, cac, ry2, ru2, rv2, med_mv) = _encode_p_plane(
            cy, cu, cv, ry, ru, rv, pred_mv, qp, qpc, mbw=mbw, mbh=mbh,
            blocked=False, rd=rd)
        return (ry2, ru2, rv2, med_mv), (mv.astype(jnp.int8), lp, cdc, cac)

    mv8, lps, cdcs, cacs = _scan_p_frames(
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
        if rd.ships_modes:
            parts.extend([intra[4], intra[5]])
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
