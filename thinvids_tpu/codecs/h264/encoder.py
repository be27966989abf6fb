"""H.264 baseline intra encoder.

Architecture (TPU-first): the per-frame COMPUTE (prediction, forward
transform, quantization, closed-loop reconstruction) is separable from the
sequential entropy PACK. The compute path here has a numpy reference
implementation (`encode_frame_arrays`) and a jitted JAX implementation
(jaxcore.py) that must match it bit-exactly; the packer (`pack_slice`)
turns level arrays into a conformant CAVLC slice on the host.

Replaces the reference's ffmpeg encode op point
(/root/reference/worker/tasks.py:1558-1586) with an in-framework codec.

Mode policy (keeps macroblock rows data-parallel for the TPU scan):
- MB (0,0): DC prediction (no neighbors);
- row 0, col > 0: horizontal (left-only dependency);
- rows >= 1: vertical (depends only on the reconstructed row above).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

from ...core.types import Frame, VideoMeta
from ...io.bits import BitWriter, annexb_nal
from . import cavlc
from .headers import (
    NAL_SLICE_IDR,
    PPS,
    SLICE_TYPE_I,
    SPS,
    SliceHeader,
)
from .intra import (
    CHROMA_BLOCK_ORDER,
    CHROMA_DC,
    CHROMA_H,
    CHROMA_V,
    LUMA_BLOCK_ORDER,
    I4_DC,
    I4_NO_TOP_RIGHT,
    LUMA_DC,
    LUMA_H,
    LUMA_I4X4,
    LUMA_V,
    encode_luma4,
    i4_modes_allowed,
    i4_neighbours,
    i4_pred_mode,
    predict_chroma8,
    predict_luma4,
    predict_luma16,
    reconstruct_chroma8,
    reconstruct_luma16,
)
from .transform import (
    chroma_dc_forward,
    chroma_dc_quant,
    chroma_qp,
    forward_4x4,
    luma_dc_forward,
    luma_dc_quant,
    quant_4x4,
    zigzag,
)


@dataclasses.dataclass
class FrameLevels:
    """Quantized level arrays for one frame, MB raster order (nmb = mbw*mbh).

    This is the compute→pack interface; the JAX path produces the same
    structure. All zig-zag ordered as the packer expects. Level arrays
    may be int32 or int16 (CAVLC levels fit int16 at every legal QP;
    the transfer paths hand the packer int16 views and the native layer
    packs them without a widening copy).
    """

    luma_mode: np.ndarray    # (nmb,) int32
    chroma_mode: np.ndarray  # (nmb,) int32
    luma_dc: np.ndarray      # (nmb, 16)
    luma_ac: np.ndarray      # (nmb, 16, 15), z-scan block order
    chroma_dc: np.ndarray    # (nmb, 2, 4), raster DC order (Cb, Cr)
    chroma_ac: np.ndarray    # (nmb, 2, 4, 15)
    #: per-MB qp - slice qp (perceptual AQ; None = flat QP, the
    #: historical layout). Packers emit it as mb_qp_delta.
    qp_delta: np.ndarray | None = None
    #: (nmb, 16) Intra4x4PredMode of each block, z-scan order, read
    #: where luma_mode is LUMA_I4X4 (rd.intra4x4; None without). Such a
    #: macroblock keeps block b's sixteen zig-zag levels as
    #: luma_dc[mi, b] (the first) and luma_ac[mi, b] (the rest).
    i4_modes: np.ndarray | None = None


#: Table 9-4, the Intra_4x4 column (chroma_format_idc 1): codeNum ->
#: coded_block_pattern (the Inter column is inter._CODE_TO_CBP_INTER)
CODE_TO_CBP_INTRA = (
    47, 31, 15, 0, 23, 27, 29, 30, 7, 11, 13, 14, 39, 43, 45, 46,
    16, 3, 5, 10, 12, 19, 21, 26, 28, 35, 37, 42, 44, 1, 2, 4,
    8, 17, 18, 20, 24, 6, 9, 22, 25, 32, 33, 34, 36, 40, 38, 41,
)
CBP_INTRA_TO_CODE = [0] * 48
for _code, _cbp in enumerate(CODE_TO_CBP_INTRA):
    CBP_INTRA_TO_CODE[_cbp] = _code


def pack_i4_modes(modes: np.ndarray) -> np.ndarray:
    """(nmb, 16) block modes → the transfer's (nmb, 4) int16 words,
    four 4-bit modes a word, block 4k in the low nibble of word k."""
    m = np.asarray(modes, np.int32).reshape(-1, 4, 4)
    w = m[..., 0] | (m[..., 1] << 4) | (m[..., 2] << 8) | (m[..., 3] << 12)
    return w.astype(np.uint16).view(np.int16)


def unpack_i4_modes(words: np.ndarray) -> np.ndarray:
    """:func:`pack_i4_modes`' inverse → (nmb, 16) int32."""
    w = np.ascontiguousarray(words, np.int16).view(np.uint16) \
        .astype(np.int32).reshape(-1, 4)
    return np.stack([(w >> s) & 15 for s in (0, 4, 8, 12)],
                    axis=-1).reshape(-1, 16)


def _mode_policy(mbw: int, mbh: int) -> tuple[np.ndarray, np.ndarray]:
    """The FIXED mode raster (rd.mode_decision off): rows >= 1
    vertical, row 0 horizontal with DC at the slice corner. Row 0 here
    is SLICE-relative: a split-frame band slice passes its own band
    `mbh`, so its first MB row gets the H/DC policy exactly where the
    decoder finds the MBs above unavailable (§7.4.3)."""
    luma = np.full((mbh, mbw), LUMA_V, np.int32)
    luma[0, :] = LUMA_H
    luma[0, 0] = LUMA_DC
    chroma = np.full((mbh, mbw), CHROMA_V, np.int32)
    chroma[0, :] = CHROMA_H
    chroma[0, 0] = CHROMA_DC
    return luma.reshape(-1), chroma.reshape(-1)


def _greedy_allowed_np(desired: np.ndarray) -> np.ndarray:
    """Sequential mirror of jaxcore._greedy_allowed: allowed[c] =
    desired[c] & !allowed[c-1]."""
    allowed = np.zeros_like(desired)
    prev = False
    for c in range(len(desired)):
        allowed[c] = bool(desired[c]) and not prev
        prev = allowed[c]
    return allowed


def _encode_luma_mb_np(src, pred, qp: int):
    """One MB's luma transform/quant/recon at `qp` → (dc_lev (16,),
    ac_lev (16, 15), recon (16, 16) uint8)."""
    resid = src.astype(np.int32) - pred.astype(np.int32)
    blocks = np.stack([
        resid[4 * by:4 * by + 4, 4 * bx:4 * bx + 4]
        for bx, by in LUMA_BLOCK_ORDER
    ])                                             # (16,4,4) z-scan
    w = forward_4x4(blocks)
    dc_spatial = np.zeros((4, 4), np.int32)
    for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
        dc_spatial[by, bx] = w[bi, 0, 0]
    wd = luma_dc_forward(dc_spatial)
    dc_lev = zigzag(luma_dc_quant(wd, qp))
    z = quant_4x4(w, qp, intra=True, skip_dc=True)
    ac_lev = zigzag(z)[:, 1:]
    return dc_lev, ac_lev, reconstruct_luma16(pred, dc_lev, ac_lev, qp)


def _encode_chroma_mb_np(csrc, cpred, qpc: int):
    """One MB's single-plane chroma encode → (dc_lev (4,), ac_lev
    (4, 15), recon (8, 8) uint8)."""
    cres = csrc.astype(np.int32) - cpred.astype(np.int32)
    cblocks = np.stack([
        cres[4 * by:4 * by + 4, 4 * bx:4 * bx + 4]
        for bx, by in CHROMA_BLOCK_ORDER
    ])                                             # (4,4,4)
    cw = forward_4x4(cblocks)
    cdc = np.array([[cw[0, 0, 0], cw[1, 0, 0]],
                    [cw[2, 0, 0], cw[3, 0, 0]]], np.int32)
    wd2 = chroma_dc_forward(cdc)
    dc_lev = chroma_dc_quant(wd2, qpc).reshape(-1)
    cz = quant_4x4(cw, qpc, intra=True, skip_dc=True)
    ac_lev = zigzag(cz)[:, 1:]
    return dc_lev, ac_lev, reconstruct_chroma8(cpred, dc_lev, ac_lev, qpc)


def encode_frame_arrays(y: np.ndarray, u: np.ndarray, v: np.ndarray,
                        qp: int, rd=None
                        ) -> tuple[FrameLevels, tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Numpy reference of the intra compute path.

    Inputs are padded planes (y: multiple of 16, chroma: half). Returns
    the level arrays and the reconstructed planes (the decoder's exact
    output). `rd` (rdo.RdConfig) enables the per-MB SATD mode decision
    and/or perceptual AQ; the decision follows jaxcore._intra_core's
    two-stage row schedule EXACTLY (same candidates, same greedy
    left-neighbor constraint, same tie-breaks), so the device and
    reference paths stay bit-identical feature-on as well as off.
    """
    from . import rdo
    from .rdo import RD_OFF

    if rd is None:
        rd = RD_OFF
    mbh, mbw = y.shape[0] // 16, y.shape[1] // 16
    nmb = mbh * mbw
    if rd.aq_q > 0:
        qp_mb = rdo.clamp_qp_map(
            qp, rdo.aq_offsets_np(y, rd.aq_q, mbw, mbh))
    else:
        qp_mb = np.full(nmb, qp, np.int32)
    luma_mode, chroma_mode = _mode_policy(mbw, mbh)

    recon_y = np.zeros_like(y)
    recon_u = np.zeros_like(u)
    recon_v = np.zeros_like(v)
    levels = FrameLevels(
        luma_mode=luma_mode.copy(),
        chroma_mode=chroma_mode.copy(),
        luma_dc=np.zeros((nmb, 16), np.int32),
        luma_ac=np.zeros((nmb, 16, 15), np.int32),
        chroma_dc=np.zeros((nmb, 2, 4), np.int32),
        chroma_ac=np.zeros((nmb, 2, 4, 15), np.int32),
        qp_delta=(qp_mb - qp).astype(np.int32) if rd.ships_modes else None,
    )

    def store_mb(mi, my, mx, ymode, cmode, pred_y, pred_u, pred_v):
        q = int(qp_mb[mi])
        qc = chroma_qp(q)
        levels.luma_mode[mi] = ymode
        levels.chroma_mode[mi] = cmode
        dc, ac, rec = _encode_luma_mb_np(
            y[16 * my:16 * my + 16, 16 * mx:16 * mx + 16], pred_y, q)
        levels.luma_dc[mi] = dc
        levels.luma_ac[mi] = ac
        recon_y[16 * my:16 * my + 16, 16 * mx:16 * mx + 16] = rec
        for ci, (plane, recon, cpred) in enumerate(
                ((u, recon_u, pred_u), (v, recon_v, pred_v))):
            cdc, cac, crec = _encode_chroma_mb_np(
                plane[8 * my:8 * my + 8, 8 * mx:8 * mx + 8], cpred, qc)
            levels.chroma_dc[mi, ci] = cdc
            levels.chroma_ac[mi, ci] = cac
            recon[8 * my:8 * my + 8, 8 * mx:8 * mx + 8] = crec

    # --- row 0: sequential (left-only dependencies) ------------------
    for mx in range(mbw):
        mi = mx
        if mx == 0:
            store_mb(mi, 0, 0, LUMA_DC, CHROMA_DC,
                     np.full((16, 16), 128, np.uint8),
                     np.full((8, 8), 128, np.uint8),
                     np.full((8, 8), 128, np.uint8))
            continue
        left = recon_y[:16, 16 * mx - 1]
        cleft_u = recon_u[:8, 8 * mx - 1]
        cleft_v = recon_v[:8, 8 * mx - 1]
        pred_h = predict_luma16(LUMA_H, None, left, None)
        pred_hu = predict_chroma8(CHROMA_H, None, cleft_u, None)
        pred_hv = predict_chroma8(CHROMA_H, None, cleft_v, None)
        ymode, cmode = LUMA_H, CHROMA_H
        pred_y, pred_u, pred_v = pred_h, pred_hu, pred_hv
        if rd.mode_decision:
            src = y[:16, 16 * mx:16 * mx + 16].astype(np.int32)
            pred_dc = predict_luma16(LUMA_DC, None, left, None)
            c_h = rdo.satd16_np(src - pred_h.astype(np.int32))
            c_dc = rdo.satd16_np(src - pred_dc.astype(np.int32))
            if c_dc < c_h:
                ymode, pred_y = LUMA_DC, pred_dc
            pred_dcu = predict_chroma8(CHROMA_DC, None, cleft_u, None)
            pred_dcv = predict_chroma8(CHROMA_DC, None, cleft_v, None)
            su = u[:8, 8 * mx:8 * mx + 8].astype(np.int32)
            sv = v[:8, 8 * mx:8 * mx + 8].astype(np.int32)
            cc_h = (rdo.satd8_np(su - pred_hu.astype(np.int32))
                    + rdo.satd8_np(sv - pred_hv.astype(np.int32)))
            cc_dc = (rdo.satd8_np(su - pred_dcu.astype(np.int32))
                     + rdo.satd8_np(sv - pred_dcv.astype(np.int32)))
            if cc_dc < cc_h:
                cmode, pred_u, pred_v = CHROMA_DC, pred_dcu, pred_dcv
        store_mb(mi, 0, mx, ymode, cmode, pred_y, pred_u, pred_v)

    # --- rows >= 1: two-stage (vertical pass, then switched MBs) -----
    for my in range(1, mbh):
        top_y = recon_y[16 * my - 1]
        top_u = recon_u[8 * my - 1]
        top_v = recon_v[8 * my - 1]
        preds_v = []
        for mx in range(mbw):
            preds_v.append((
                predict_luma16(LUMA_V, top_y[16 * mx:16 * mx + 16],
                               None, None),
                predict_chroma8(CHROMA_V, top_u[8 * mx:8 * mx + 8],
                                None, None),
                predict_chroma8(CHROMA_V, top_v[8 * mx:8 * mx + 8],
                                None, None)))
        if not rd.mode_decision:
            for mx in range(mbw):
                py, pu, pv = preds_v[mx]
                store_mb(my * mbw + mx, my, mx, LUMA_V, CHROMA_V,
                         py, pu, pv)
            continue

        # stage 1: vertical candidate recon for the whole row
        vrec = []
        for mx in range(mbw):
            mi = my * mbw + mx
            q = int(qp_mb[mi])
            qc = chroma_qp(q)
            py, pu, pv = preds_v[mx]
            _, _, ry = _encode_luma_mb_np(
                y[16 * my:16 * my + 16, 16 * mx:16 * mx + 16], py, q)
            _, _, ru = _encode_chroma_mb_np(
                u[8 * my:8 * my + 8, 8 * mx:8 * mx + 8], pu, qc)
            _, _, rv = _encode_chroma_mb_np(
                v[8 * my:8 * my + 8, 8 * mx:8 * mx + 8], pv, qc)
            vrec.append((ry, ru, rv))

        # stage 2: per-MB candidate costs against the left neighbor's
        # VERTICAL recon (exact for switched MBs — greedy constraint)
        INF = 1 << 29
        desired = np.zeros(mbw, bool)
        choice = []
        for mx in range(mbw):
            mi = my * mbw + mx
            src = y[16 * my:16 * my + 16, 16 * mx:16 * mx + 16] \
                .astype(np.int32)
            su = u[8 * my:8 * my + 8, 8 * mx:8 * mx + 8].astype(np.int32)
            sv = v[8 * my:8 * my + 8, 8 * mx:8 * mx + 8].astype(np.int32)
            py, pu, pv = preds_v[mx]
            left = vrec[mx - 1][0][:, 15] if mx > 0 else None
            lu = vrec[mx - 1][1][:, 7] if mx > 0 else None
            lv = vrec[mx - 1][2][:, 7] if mx > 0 else None
            top16 = top_y[16 * mx:16 * mx + 16]
            ph = predict_luma16(LUMA_H, None, left, None) \
                if mx > 0 else None
            pdc = predict_luma16(LUMA_DC, top16, left, None)
            c_v = rdo.satd16_np(src - py.astype(np.int32))
            c_h = rdo.satd16_np(src - ph.astype(np.int32)) \
                if mx > 0 else INF
            c_dc = rdo.satd16_np(src - pdc.astype(np.int32))
            tu8 = top_u[8 * mx:8 * mx + 8]
            tv8 = top_v[8 * mx:8 * mx + 8]
            phu = predict_chroma8(CHROMA_H, None, lu, None) \
                if mx > 0 else None
            phv = predict_chroma8(CHROMA_H, None, lv, None) \
                if mx > 0 else None
            pdcu = predict_chroma8(CHROMA_DC, tu8, lu, None)
            pdcv = predict_chroma8(CHROMA_DC, tv8, lv, None)
            cc_v = (rdo.satd8_np(su - pu.astype(np.int32))
                    + rdo.satd8_np(sv - pv.astype(np.int32)))
            cc_h = (rdo.satd8_np(su - phu.astype(np.int32))
                    + rdo.satd8_np(sv - phv.astype(np.int32))) \
                if mx > 0 else INF
            cc_dc = (rdo.satd8_np(su - pdcu.astype(np.int32))
                     + rdo.satd8_np(sv - pdcv.astype(np.int32)))
            # strict-< argmin, candidate order (V, H, DC)
            best_y, ymode_alt, pya = c_v, LUMA_V, py
            if c_h < best_y:
                best_y, ymode_alt, pya = c_h, LUMA_H, ph
            if c_dc < best_y:
                best_y, ymode_alt, pya = c_dc, LUMA_DC, pdc
            best_c, cmode_alt, pua, pva = cc_v, CHROMA_V, pu, pv
            if cc_h < best_c:
                best_c, cmode_alt, pua, pva = cc_h, CHROMA_H, phu, phv
            if cc_dc < best_c:
                best_c, cmode_alt, pua, pva = cc_dc, CHROMA_DC, pdcu, pdcv
            desired[mx] = (best_y + best_c) < (c_v + cc_v)
            choice.append((ymode_alt, cmode_alt, pya, pua, pva))
        allowed = _greedy_allowed_np(desired)

        # stage 3: final encode (switched MBs re-encode; the rest keep
        # their vertical prediction)
        for mx in range(mbw):
            mi = my * mbw + mx
            if allowed[mx]:
                ymode_alt, cmode_alt, pya, pua, pva = choice[mx]
                store_mb(mi, my, mx, ymode_alt, cmode_alt, pya, pua, pva)
            else:
                py, pu, pv = preds_v[mx]
                store_mb(mi, my, mx, LUMA_V, CHROMA_V, py, pu, pv)
    if rd.intra4x4:
        _intra4x4_luma_np(y, qp, qp_mb, levels, recon_y, rd)
    return levels, (recon_y, recon_u, recon_v)


def _intra4x4_luma_np(y, qp: int, qp_mb, levels: FrameLevels, recon_y,
                      rd) -> None:
    """rd.intra4x4: the luma of an IDR picture coded again, macroblock
    by macroblock in raster order, each as Intra16x16 or Intra4x4 —
    the numpy twin of jaxcore._intra4x4_luma, which walks the same
    macroblocks as a wavefront. Overwrites `levels`' luma arrays,
    modes and QP deltas and `recon_y`; chroma is left as the
    Intra16x16 path coded it.

    A macroblock's Intra16x16 candidate is V, H or DC by SATD from its
    TRUE reconstructed neighbours (rd.mode_decision; else the raster
    policy's mode). Its Intra4x4 candidate takes each block, in
    decoding order, through the modes its neighbours allow: cost =
    sum |Hadamard| of source less prediction + 2 lambda * the mode's
    bits, strict-< from mode 0 up, then codes the block (closed loop:
    the next block predicts from this one's reconstruction). The kind
    is Intra4x4 where its summed cost + 2 lambda * I4X4_BITS is less
    than the Intra16x16 candidate's sum |Hadamard| (twice its SATD: no
    sum here is halved, so none rounds). An Intra4x4 macroblock with no
    level at all (luma and chroma) codes no mb_qp_delta, so its QP is
    the macroblock's before it (§7.4.5): `qp_delta` says so."""
    from . import rdo

    mbh, mbw = y.shape[0] // 16, y.shape[1] // 16
    policy_luma, _ = _mode_policy(mbw, mbh)
    levels.i4_modes = np.full((mbh * mbw, 16), I4_DC, np.int32)
    blk_mode = np.full((4 * mbh, 4 * mbw), I4_DC, np.int32)
    held = np.zeros(mbh * mbw, bool)
    for mi in range(mbh * mbw):
        my, mx = divmod(mi, mbw)
        q = int(qp_mb[mi])
        lam2 = 2 * rdo.P_INTRA_LAMBDA[q]
        ys, xs = slice(16 * my, 16 * my + 16), slice(16 * mx, 16 * mx + 16)
        src = y[ys, xs].astype(np.int32)
        top = recon_y[16 * my - 1, xs] if my else None
        left = recon_y[ys, 16 * mx - 1] if mx else None

        # Intra16x16 candidate
        def cost16(m):
            r = src - predict_luma16(m, top, left, None)
            return sum(rdo.sath4_np(r[4 * by:4 * by + 4, 4 * bx:4 * bx + 4])
                       for bx, by in LUMA_BLOCK_ORDER)

        mode16 = int(policy_luma[mi])
        c16 = None
        if rd.mode_decision:
            for m, ok in ((LUMA_V, my), (LUMA_H, mx), (LUMA_DC, True)):
                c = cost16(m) if ok else None
                if ok and (c16 is None or c < c16):
                    c16, mode16 = c, m
        else:
            c16 = cost16(mode16)
        pred16 = predict_luma16(mode16, top, left, None)

        # Intra4x4 candidate, coded into recon_y as it goes
        c4 = lam2 * rdo.I4X4_BITS
        lev4 = np.zeros((16, 16), np.int32)
        modes4 = np.zeros(16, np.int32)
        for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
            gx, gy = 4 * mx + bx, 4 * my + by
            has_top, has_left = gy > 0, gx > 0
            if by:
                has_tr = bi not in I4_NO_TOP_RIGHT
            else:
                has_tr = has_top and (bx < 3 or mx + 1 < mbw)
            nb = i4_neighbours(recon_y, gx, gy, has_top, has_left, has_tr)
            pm = i4_pred_mode(
                int(blk_mode[gy, gx - 1]) if has_left else None,
                int(blk_mode[gy - 1, gx]) if has_top else None)
            bsrc = src[4 * by:4 * by + 4, 4 * bx:4 * bx + 4]
            best = None
            for m in i4_modes_allowed(has_top, has_left):
                pred = predict_luma4(m, *nb)
                c = rdo.sath4_np(bsrc - pred.astype(np.int32)) \
                    + lam2 * rdo.I4X4_MODE_BITS[m != pm]
                if best is None or c < best:
                    best, bmode, bpred = c, m, pred
            c4 += best
            modes4[bi] = blk_mode[gy, gx] = bmode
            lev4[bi], rec = encode_luma4(bsrc, bpred, q)
            recon_y[4 * gy:4 * gy + 4, 4 * gx:4 * gx + 4] = rec

        if c4 < c16:
            levels.luma_mode[mi] = LUMA_I4X4
            levels.i4_modes[mi] = modes4
            levels.luma_dc[mi] = lev4[:, 0]
            levels.luma_ac[mi] = lev4[:, 1:]
            held[mi] = not (lev4.any() or levels.chroma_dc[mi].any()
                            or levels.chroma_ac[mi].any())
        else:
            blk_mode[4 * my:4 * my + 4, 4 * mx:4 * mx + 4] = I4_DC
            levels.luma_mode[mi] = mode16
            dc, ac, rec = _encode_luma_mb_np(y[ys, xs], pred16, q)
            levels.luma_dc[mi], levels.luma_ac[mi] = dc, ac
            recon_y[ys, xs] = rec
    # QP_Y of a macroblock that codes no delta is its predecessor's
    eff = np.asarray(qp_mb, np.int32).copy()
    prev = qp
    for mi in range(mbh * mbw):
        if held[mi]:
            eff[mi] = prev
        prev = eff[mi]
    levels.qp_delta = (eff - qp).astype(np.int32)


def mb_cbp(levels: FrameLevels, mi: int) -> tuple[int, int]:
    """(cbp_luma, cbp_chroma in {0,1,2}) for MB `mi`: cbp_luma in
    {0, 15} for Intra16x16, one bit per 8x8 quadrant (four blocks of
    the z-scan) for Intra4x4."""
    if levels.luma_mode[mi] == LUMA_I4X4:
        cbp_luma = sum(
            1 << k for k in range(4)
            if np.any(levels.luma_dc[mi, 4 * k:4 * k + 4])
            or np.any(levels.luma_ac[mi, 4 * k:4 * k + 4]))
    else:
        cbp_luma = 15 if np.any(levels.luma_ac[mi]) else 0
    if np.any(levels.chroma_ac[mi]):
        cbp_chroma = 2
    elif np.any(levels.chroma_dc[mi]):
        cbp_chroma = 1
    else:
        cbp_chroma = 0
    return cbp_luma, cbp_chroma


def pack_slice(levels: FrameLevels, mbw: int, mbh: int, sps: SPS, pps: PPS,
               qp: int, frame_num: int = 0, idr: bool = True,
               idr_pic_id: int = 0, native: bool | None = None,
               first_mb: int = 0, deblock_idc: int = 1) -> bytes:
    """Entropy-pack one I slice into an Annex-B NAL unit.

    `levels`/`mbw`/`mbh` describe the SLICE's macroblocks; with a
    nonzero `first_mb` (split-frame encoding: one horizontal MB-row
    band per slice) the slice covers MB raster addresses
    [first_mb, first_mb + mbw*mbh) of a larger picture, and the CAVLC
    nC / intra-prediction neighbor logic below — which treats the
    band's first row as having no MBs above — is exactly the §7.4.3
    cross-slice unavailability a decoder applies.

    `native=None` auto-selects the C++ packer when buildable; False forces
    the pure-Python reference path (both produce identical bits — tested).
    """
    bw = BitWriter()
    header = SliceHeader(
        slice_type=SLICE_TYPE_I, frame_num=frame_num, idr=idr, qp=qp,
        idr_pic_id=idr_pic_id, first_mb=first_mb,
        deblock_idc=deblock_idc,
    )
    header.write(bw, sps, pps)

    if native is not False:
        from ... import native as native_mod

        if native_mod.available():
            hdr_bytes, hdr_bits = bw.getvalue_unaligned()
            ebsp = native_mod.pack_islice(
                hdr_bytes, hdr_bits, levels.luma_mode, levels.chroma_mode,
                levels.luma_dc, levels.luma_ac, levels.chroma_dc,
                levels.chroma_ac, mbw, mbh, qp_delta=levels.qp_delta,
                i4_modes=levels.i4_modes)
            start = b"\x00\x00\x00\x01"
            nal_header = bytes([(3 << 5) | (NAL_SLICE_IDR if idr else 1)])
            return start + nal_header + ebsp
        if native:
            raise RuntimeError("native packer requested but unavailable")

    # nC neighbor maps: total_coeff per 4x4 luma / chroma block.
    luma_counts = np.zeros((4 * mbh, 4 * mbw), np.int32)
    chroma_counts = np.zeros((2, 2 * mbh, 2 * mbw), np.int32)

    # mb_qp_delta chains: each MB signals its qp relative to the
    # PREVIOUS MB's (§7.4.5); levels.qp_delta holds offsets vs the
    # slice qp, so the coded value is the successive difference.
    dqp = levels.qp_delta
    prev_off = 0
    # Intra4x4PredMode of every 4x4 block for §8.3.1.1's prediction;
    # an Intra16x16 macroblock's blocks read DC
    blk_mode = np.full((4 * mbh, 4 * mbw), I4_DC, np.int32)
    for my in range(mbh):
        for mx in range(mbw):
            mi = my * mbw + mx
            cbp_luma, cbp_chroma = mb_cbp(levels, mi)
            by0, bx0 = 4 * my, 4 * mx
            i4x4 = levels.luma_mode[mi] == LUMA_I4X4
            if i4x4:
                bw.ue(0)                         # mb_type I_NxN
                for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
                    gy, gx = by0 + by, bx0 + bx
                    mode = int(levels.i4_modes[mi, bi])
                    pm = i4_pred_mode(
                        int(blk_mode[gy, gx - 1]) if gx > 0 else None,
                        int(blk_mode[gy - 1, gx]) if gy > 0 else None)
                    blk_mode[gy, gx] = mode
                    if mode == pm:
                        bw.write_bit(1)   # prev_intra4x4_pred_mode_flag
                    else:
                        # the flag 0, then rem_intra4x4_pred_mode u(3)
                        bw.write(mode - (mode > pm), 4)
            else:
                bw.ue(1 + int(levels.luma_mode[mi]) + 4 * cbp_chroma
                      + 12 * (1 if cbp_luma else 0))
            bw.ue(int(levels.chroma_mode[mi]))   # intra_chroma_pred_mode
            if i4x4:
                bw.ue(CBP_INTRA_TO_CODE[cbp_luma | (cbp_chroma << 4)])
            if i4x4 and not (cbp_luma or cbp_chroma):
                # no mb_qp_delta (§7.3.5): the macroblock's QP is the
                # one before it, which is what its levels' dqp says
                if dqp is not None and int(dqp[mi]) != prev_off:
                    raise ValueError(
                        f"Intra4x4 MB {mi} codes no level but changes QP")
            elif dqp is None:
                bw.se(0)                         # mb_qp_delta
            else:
                bw.se(int(dqp[mi]) - prev_off)
                prev_off = int(dqp[mi])

            if not i4x4:
                # Luma DC: nC from blkIdx 0 neighbors.
                na = int(luma_counts[by0, bx0 - 1]) if bx0 > 0 else None
                nb = int(luma_counts[by0 - 1, bx0]) if by0 > 0 else None
                cavlc.encode_residual(bw, levels.luma_dc[mi].tolist(),
                                      cavlc.luma_nc(na, nb))

            # Luma blocks in z-scan order: Intra16x16's fifteen AC
            # levels, Intra4x4's sixteen where its quadrant's bit is set.
            for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
                gy, gx = by0 + by, bx0 + bx
                if cbp_luma & (1 << (bi // 4)):
                    na = int(luma_counts[gy, gx - 1]) if gx > 0 else None
                    nb = int(luma_counts[gy - 1, gx]) if gy > 0 else None
                    coeffs = levels.luma_ac[mi, bi].tolist()
                    if i4x4:
                        coeffs = [int(levels.luma_dc[mi, bi])] + coeffs
                    luma_counts[gy, gx] = cavlc.encode_residual(
                        bw, coeffs, cavlc.luma_nc(na, nb))
                else:
                    luma_counts[gy, gx] = 0

            # Chroma DC (both planes) then AC.
            if cbp_chroma > 0:
                for ci in range(2):
                    cavlc.encode_residual(
                        bw, levels.chroma_dc[mi, ci].tolist(), -1)
            cy0, cx0 = 2 * my, 2 * mx
            for ci in range(2):
                for bi, (bx, by) in enumerate(CHROMA_BLOCK_ORDER):
                    gy, gx = cy0 + by, cx0 + bx
                    if cbp_chroma == 2:
                        na = int(chroma_counts[ci, gy, gx - 1]) if gx > 0 else None
                        nb = int(chroma_counts[ci, gy - 1, gx]) if gy > 0 else None
                        tc = cavlc.encode_residual(
                            bw, levels.chroma_ac[mi, ci, bi].tolist(),
                            cavlc.luma_nc(na, nb))
                        chroma_counts[ci, gy, gx] = tc
                    else:
                        chroma_counts[ci, gy, gx] = 0

    bw.rbsp_trailing_bits()
    return annexb_nal(3, NAL_SLICE_IDR if idr else 1, bw.getvalue())


class H264Encoder:
    """Stateful per-job encoder: sequence headers + frame encode.

    v1 scope: intra-only (every frame IDR), 4:2:0, fixed qp, CAVLC.

    The jitted JAX compute path is the default engine (TPU-first); pass
    `use_jax=False` for the numpy reference implementation.
    """

    def __init__(self, meta: VideoMeta, qp: int = 27, use_jax: bool = True,
                 rd=None):
        from .rdo import RD_OFF

        self.meta = meta
        self.qp = qp
        self.use_jax = use_jax
        self.rd = rd if rd is not None else RD_OFF
        if self.rd.deblock or self.rd.pskip:
            # v1 all-intra scope: no recon chain to filter, no inter
            # MBs to skip — the GOP path (encode_gop / the sharded
            # encoders) carries those features.
            raise ValueError(
                "H264Encoder (all-intra) supports mode_decision/aq "
                "only; deblock/pskip need the GOP path")
        self.sps = SPS(width=meta.width, height=meta.height,
                       fps_num=meta.fps_num, fps_den=meta.fps_den)
        self.pps = PPS(init_qp=qp)
        self._jax_fn = None

    def _compute(self, y: np.ndarray, u: np.ndarray, v: np.ndarray) -> FrameLevels:
        if self.use_jax:
            from . import jaxcore

            if self._jax_fn is None:
                self._jax_fn = jaxcore.build_intra_encoder(
                    y.shape, self.qp, self.rd)
            return self._jax_fn(y, u, v)
        levels, _ = encode_frame_arrays(y, u, v, self.qp, rd=self.rd)
        return levels

    def encode_frame(self, frame: Frame, frame_num: int = 0,
                     idr_pic_id: int = 0, with_headers: bool = True) -> bytes:
        from ...core.types import ChromaFormat

        if frame.chroma is not ChromaFormat.YUV420:
            # The MB geometry below hard-assumes 4:2:0 (8x8 chroma per MB);
            # feeding 4:2:2/4:4:4 would silently mis-encode.
            raise ValueError(
                f"H264Encoder supports only 4:2:0 input, got "
                f"{frame.chroma.name}; convert before encoding")
        padded = frame.padded(16)
        levels = self._compute(padded.y, padded.u, padded.v)
        mbh, mbw = padded.y.shape[0] // 16, padded.y.shape[1] // 16
        slice_nal = pack_slice(levels, mbw, mbh, self.sps, self.pps, self.qp,
                               frame_num=0, idr=True,
                               idr_pic_id=idr_pic_id % 65536)
        if with_headers:
            return self.sps.to_nal() + self.pps.to_nal() + slice_nal
        return slice_nal


def encode_frames(frames: list[Frame], meta: VideoMeta, qp: int = 27,
                  use_jax: bool = True) -> bytes:
    """Encode a closed sequence of frames to one Annex-B byte stream
    (all-intra: every frame IDR)."""
    enc = H264Encoder(meta, qp=qp, use_jax=use_jax)
    out = []
    for i, frame in enumerate(frames):
        out.append(enc.encode_frame(frame, idr_pic_id=i,
                                    with_headers=(i == 0)))
    return b"".join(out)


def encode_gop(frames: list[Frame], meta: VideoMeta, qp: int = 27,
               idr_pic_id: int = 0, with_headers: bool = True,
               return_recon: bool = False, rd=None):
    """Encode a closed GOP: frame 0 IDR, frames 1..F-1 inter-coded (P).

    The whole GOP's compute (intra frame + motion search / compensation /
    transform chained through a `lax.scan` recon carry) is ONE jitted XLA
    program (jaxinter.encode_gop_jit); this host half packs the I-slice
    and P-slices. Replaces the reference's inter-coded ffmpeg op point
    (/root/reference/worker/tasks.py:1558-1586).
    """
    import jax
    import jax.numpy as jnp

    from ...core.types import ChromaFormat
    from . import jaxinter
    from .rdo import RD_OFF

    if rd is None:
        rd = RD_OFF
    if not frames:
        raise ValueError("empty GOP")
    bad = next((f for f in frames
                if f.chroma is not ChromaFormat.YUV420), None)
    if bad is not None:
        raise ValueError(
            f"encode_gop supports only 4:2:0 input, got {bad.chroma.name}")
    padded = [f.padded(16) for f in frames]
    ph, pw = padded[0].y.shape
    mbh, mbw = ph // 16, pw // 16
    ys = jnp.asarray(np.stack([p.y for p in padded]))
    us = jnp.asarray(np.stack([p.u for p in padded]))
    vs = jnp.asarray(np.stack([p.v for p in padded]))

    out = jaxinter.encode_gop_jit(ys, us, vs, jnp.asarray(qp),
                                  mbw=mbw, mbh=mbh,
                                  emit_recon=return_recon, rd=rd)
    if return_recon:
        (intra, pouts, recons) = jax.device_get(out)
    else:
        (intra, pouts) = jax.device_get(out)

    sps = SPS(width=meta.width, height=meta.height,
              fps_num=meta.fps_num, fps_den=meta.fps_den)
    pps = PPS(init_qp=qp)
    nals = pack_gop_slices(intra, pouts, len(frames), mbw, mbh, sps, pps,
                           qp, idr_pic_id, with_headers=with_headers,
                           rd=rd)
    stream = b"".join(nals)
    if return_recon:
        return stream, recons
    return stream


def unpack_mode16(mode16: np.ndarray):
    """The transfer's packed per-MB mode word → (luma_mode,
    chroma_mode) int32 arrays (jaxcore._mode_tail's inverse)."""
    m = np.asarray(mode16, np.int32)
    return m & 15, m >> 4


def _gop_slice_thunks(intra_of, pack_p, num_frames: int, mbw: int,
                      mbh: int, sps: SPS, pps: PPS, qp: int,
                      idr_pic_id: int, with_headers: bool, rd=None) -> list:
    """Per-slice pack closures for one GOP (IDR thunk first, then one
    per P frame). A GOP's slices are independent bit-strings until the
    final concat, so callers may run the thunks on a thread pool (the
    native packer releases the GIL for the C call); running them in
    order serially yields the same bytes. Every GOP-pack entry point
    funnels through here so the bit-identity contract between paths
    cannot drift in the IDR/header logic.

    `intra_of()` gives the IDR's levels when its thunk RUNS (a caller
    may unpack them there, on the packing thread): the 4-tuple of
    blocked level arrays, or — when the encode shipped the per-MB side
    channel (rd.ships_modes) — a 6-tuple with (mode16, dqp16)
    appended, or under rd.intra4x4 a 7-tuple with the blocks' modes as
    (nmb, 4) words (pack_i4_modes) after those."""
    from .rdo import RD_OFF

    if rd is None:
        rd = RD_OFF
    head = sps.to_nal() + pps.to_nal() if with_headers else b""
    deblock_idc = 0 if rd.deblock else 1

    def pack_idr():
        intra = intra_of()
        i4_modes = None
        if len(intra) >= 6:
            il_dc, il_ac, ic_dc, ic_ac, mode16, dqp16, *i4m = intra
            luma_mode, chroma_mode = unpack_mode16(mode16)
            if i4m:
                i4_modes = unpack_i4_modes(i4m[0])
            qp_delta = np.asarray(dqp16, np.int32)
            if not np.any(qp_delta):
                qp_delta = None
        else:
            il_dc, il_ac, ic_dc, ic_ac = intra
            luma_mode, chroma_mode = _mode_policy(mbw, mbh)
            qp_delta = None
        intra_levels = FrameLevels(
            luma_mode=luma_mode, chroma_mode=chroma_mode,
            luma_dc=il_dc, luma_ac=il_ac, chroma_dc=ic_dc, chroma_ac=ic_ac,
            qp_delta=qp_delta, i4_modes=i4_modes)
        return head + pack_slice(intra_levels, mbw, mbh, sps, pps, qp,
                                 frame_num=0, idr=True,
                                 idr_pic_id=idr_pic_id % 65536,
                                 deblock_idc=deblock_idc)

    thunks = [pack_idr]
    for i in range(num_frames - 1):
        thunks.append(functools.partial(pack_p, i, (i + 1) % 256))
    return thunks


def run_slice_thunks(thunks: list, pool=None) -> list[bytes]:
    """Evaluate slice-pack thunks in slice order; with `pool` (any
    Executor) the packs run concurrently, without it serially — the
    resulting bytes are identical either way."""
    if pool is None or len(thunks) <= 1:
        return [t() for t in thunks]
    return [f.result() for f in [pool.submit(t) for t in thunks]]


def _pack_gop_common(intra, pack_p, num_frames: int, mbw: int, mbh: int,
                     sps: SPS, pps: PPS, qp: int, idr_pic_id: int,
                     with_headers: bool, pool=None, rd=None) -> list[bytes]:
    """Shared host half of GOP entropy packing: IDR slice from blocked
    intra levels + one P slice per remaining frame via `pack_p(i,
    frame_num)`, optionally fanned across `pool` at slice granularity."""
    return run_slice_thunks(
        _gop_slice_thunks(lambda: intra, pack_p, num_frames, mbw, mbh,
                          sps, pps, qp, idr_pic_id, with_headers, rd=rd),
        pool)


def gop_slice_thunks_planes(intra, planes, num_frames: int, mbw: int,
                            mbh: int, sps: SPS, pps: PPS, qp: int,
                            idr_pic_id: int,
                            with_headers: bool = True, rd=None) -> list:
    """Per-slice pack thunks for one PLANE-layout GOP (see
    pack_gop_slices_planes for the array contract). dispatch.collect_wave
    submits these so slices from ALL of a wave's GOPs pack concurrently
    on the pack pool instead of GOP-by-GOP."""
    return gop_slice_thunks_frames(
        lambda: intra, lambda i: tuple(a[i] for a in planes), num_frames,
        mbw, mbh, sps, pps, qp, idr_pic_id, with_headers, rd=rd)


def gop_slice_thunks_frames(intra_of, frame_of, num_frames: int, mbw: int,
                            mbh: int, sps: SPS, pps: PPS, qp: int,
                            idr_pic_id: int, with_headers: bool = True,
                            rd=None) -> list:
    """:func:`gop_slice_thunks_planes` with the levels handed over
    slice by slice, when each thunk runs and on the thread that runs
    it: `intra_of()` the IDR's tuple, `frame_of(i)` P frame i's row of
    the plane arrays (mv8, lp, udc, vdc, uac, vac[, pmode]). A thunk's
    bytes own nothing of what it was handed, so a caller may give
    every slice of a thread the same memory (dispatch.collect_wave
    unpacks each slice's levels there)."""
    from . import inter as inter_mod

    deblock_idc = 0 if rd is not None and rd.deblock else 1
    mv_per_pel = rd.mv_per_pel if rd is not None else 2

    def pack_p(i, fn):
        mv, lp, udc, vdc, uac, vac, *pmode = frame_of(i)
        return inter_mod.pack_p_slice_plane(
            mv, lp, udc, vdc, uac, vac, mbw, mbh, sps, pps, qp,
            frame_num=fn, deblock_idc=deblock_idc, mv_per_pel=mv_per_pel,
            pmode=pmode[0] if pmode else None)

    return _gop_slice_thunks(intra_of, pack_p, num_frames, mbw, mbh, sps,
                             pps, qp, idr_pic_id, with_headers, rd=rd)


def pack_gop_slices_planes(intra, planes, num_frames: int, mbw: int,
                           mbh: int, sps: SPS, pps: PPS, qp: int,
                           idr_pic_id: int, with_headers: bool = True,
                           pool=None, rd=None) -> list[bytes]:
    """Entropy-pack one GOP whose P frames arrive as PLANE-layout level
    arrays (the sharded transfer format, jaxinter.encode_gop_planes):
    planes = (mv8 (F-1,nmb,2) int8, luma planes (F-1,H,W) int16,
    u_dc/v_dc (F-1,nmb,4) int16, u_ac/v_ac (F-1,H/2,W/2) int16[,
    pmode (F-1,nmb): the kind channel of rd.p_intra]).
    The intra frame stays blocked (jaxcore._intra_core emits blocked).
    Bit-identical to pack_gop_slices on the equivalent blocked arrays."""
    return run_slice_thunks(
        gop_slice_thunks_planes(intra, planes, num_frames, mbw, mbh, sps,
                                pps, qp, idr_pic_id, with_headers, rd=rd),
        pool)


def pack_gop_slices(intra, pouts, num_frames: int, mbw: int, mbh: int,
                    sps: SPS, pps: PPS, qp: int, idr_pic_id: int,
                    with_headers: bool = True, pool=None,
                    rd=None) -> list[bytes]:
    """Entropy-pack one GOP's slices from BLOCKED device level arrays
    (the single-device encode_gop path).

    intra: (luma_dc, luma_ac, chroma_dc, chroma_ac[, mode16, dqp16]);
    pouts: the P frames' (mv, luma16, chroma_dc, chroma_ac[, pmode]),
    leading dim >= num frames - 1 (extra tail-padding entries are
    ignored).
    """
    from . import inter as inter_mod

    deblock_idc = 0 if rd is not None and rd.deblock else 1
    mv_per_pel = rd.mv_per_pel if rd is not None else 2
    mv, l16, cdc, cac, *pmode = pouts
    return _pack_gop_common(
        intra,
        lambda i, fn: inter_mod.pack_p_slice(
            mv[i], l16[i], cdc[i], cac[i], mbw, mbh, sps, pps, qp,
            frame_num=fn, deblock_idc=deblock_idc,
            mv_per_pel=mv_per_pel,
            pmode=pmode[0][i] if pmode else None),
        num_frames, mbw, mbh, sps, pps, qp, idr_pic_id, with_headers,
        pool=pool, rd=rd)
