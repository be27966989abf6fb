"""Intra prediction (H.264 §8.3) and shared macroblock reconstruction.

I16x16 luma modes (0=V, 1=H, 2=DC, 3=plane), the nine Intra4x4 modes
of §8.3.1.2 and 8x8 chroma modes
(0=DC, 1=H, 2=V, 3=plane). The same reconstruction routines serve the
encoder (closed loop) and the decoder, so encoder recon is by construction
what a conformant decoder produces (deblocking disabled).
"""

from __future__ import annotations

import numpy as np

from .transform import (
    chroma_dc_dequant,
    dequant_4x4,
    forward_4x4,
    inverse_4x4,
    inverse_zigzag,
    luma_dc_dequant,
    quant_4x4,
    zigzag,
)

# Luma 4x4 block z-scan order within a MB: (x, y) block coords.
LUMA_BLOCK_ORDER: list[tuple[int, int]] = [
    (0, 0), (1, 0), (0, 1), (1, 1),
    (2, 0), (3, 0), (2, 1), (3, 1),
    (0, 2), (1, 2), (0, 3), (1, 3),
    (2, 2), (3, 2), (2, 3), (3, 3),
]
# Raster order of the 2x2 luma-DC layout is separate: DC coeff (x,y) of
# block grid is scanned zig-zag as a 4x4 "block" itself.

CHROMA_BLOCK_ORDER: list[tuple[int, int]] = [(0, 0), (1, 0), (0, 1), (1, 1)]

LUMA_V, LUMA_H, LUMA_DC, LUMA_PLANE = 0, 1, 2, 3
CHROMA_DC, CHROMA_H, CHROMA_V, CHROMA_PLANE = 0, 1, 2, 3
#: the value of a macroblock's `luma_mode` that says its KIND is
#: Intra4x4 (mb_type I_NxN): the level arrays and the transfer's mode16
#: word carry the kind there, beside Intra16x16's four modes; its
#: sixteen block modes ride apart (FrameLevels.i4_modes)
LUMA_I4X4 = 4
#: Intra4x4PredMode, Table 8-2
(I4_V, I4_H, I4_DC, I4_DDL, I4_DDR, I4_VR, I4_HD, I4_VL, I4_HU) = range(9)
#: §6.4.11.4 in decoding order: the blocks (z-scan index) whose upper
#: right neighbour is decoded later or lies in the macroblock to the
#: right; block 5's lies in the macroblock above and to the right
I4_NO_TOP_RIGHT = frozenset((3, 7, 11, 13, 15))


def predict_luma16(mode: int, top: np.ndarray | None, left: np.ndarray | None,
                   topleft: int | None) -> np.ndarray:
    """16x16 luma prediction. `top`/`left` are length-16 uint8 vectors of
    reconstructed neighbors (None when unavailable)."""
    if mode == LUMA_V:
        if top is None:
            raise ValueError("vertical prediction requires top neighbors")
        return np.tile(top.astype(np.uint8), (16, 1))
    if mode == LUMA_H:
        if left is None:
            raise ValueError("horizontal prediction requires left neighbors")
        return np.tile(left.astype(np.uint8)[:, None], (1, 16))
    if mode == LUMA_DC:
        if top is not None and left is not None:
            dc = (int(top.sum()) + int(left.sum()) + 16) >> 5
        elif left is not None:
            dc = (int(left.sum()) + 8) >> 4
        elif top is not None:
            dc = (int(top.sum()) + 8) >> 4
        else:
            dc = 128
        return np.full((16, 16), dc, np.uint8)
    if mode == LUMA_PLANE:
        if top is None or left is None or topleft is None:
            raise ValueError("plane prediction requires top+left+corner")
        t = top.astype(np.int32)
        l = left.astype(np.int32)
        tl = int(topleft)
        xs = np.arange(8)
        h = int((xs + 1) @ (t[8:16] - np.concatenate(([tl], t[0:7]))[::-1]))
        v = int((xs + 1) @ (l[8:16] - np.concatenate(([tl], l[0:7]))[::-1]))
        a = 16 * (int(l[15]) + int(t[15]))
        b = (5 * h + 32) >> 6
        c = (5 * v + 32) >> 6
        y, x = np.mgrid[0:16, 0:16]
        return np.clip((a + b * (x - 7) + c * (y - 7) + 16) >> 5, 0, 255).astype(np.uint8)
    raise ValueError(f"bad luma mode {mode}")


def predict_chroma8(mode: int, top: np.ndarray | None, left: np.ndarray | None,
                    topleft: int | None) -> np.ndarray:
    """8x8 chroma prediction for one plane."""
    if mode == CHROMA_V:
        if top is None:
            raise ValueError("vertical chroma prediction requires top")
        return np.tile(top.astype(np.uint8), (8, 1))
    if mode == CHROMA_H:
        if left is None:
            raise ValueError("horizontal chroma prediction requires left")
        return np.tile(left.astype(np.uint8)[:, None], (1, 8))
    if mode == CHROMA_DC:
        pred = np.empty((8, 8), np.uint8)
        for bx, by in ((0, 0), (1, 0), (0, 1), (1, 1)):
            t = top[4 * bx:4 * bx + 4].astype(np.int32) if top is not None else None
            l = left[4 * by:4 * by + 4].astype(np.int32) if left is not None else None
            if (bx, by) in ((0, 0), (1, 1)):
                if t is not None and l is not None:
                    dc = (int(t.sum()) + int(l.sum()) + 4) >> 3
                elif l is not None:
                    dc = (int(l.sum()) + 2) >> 2
                elif t is not None:
                    dc = (int(t.sum()) + 2) >> 2
                else:
                    dc = 128
            elif (bx, by) == (1, 0):  # prefers its own top quarter
                if t is not None:
                    dc = (int(t.sum()) + 2) >> 2
                elif l is not None:
                    dc = (int(l.sum()) + 2) >> 2
                else:
                    dc = 128
            else:                     # (0, 1): prefers its own left quarter
                if l is not None:
                    dc = (int(l.sum()) + 2) >> 2
                elif t is not None:
                    dc = (int(t.sum()) + 2) >> 2
                else:
                    dc = 128
            pred[4 * by:4 * by + 4, 4 * bx:4 * bx + 4] = dc
        return pred
    if mode == CHROMA_PLANE:
        if top is None or left is None or topleft is None:
            raise ValueError("plane chroma prediction requires top+left+corner")
        t = top.astype(np.int32)
        l = left.astype(np.int32)
        tl = int(topleft)
        xs = np.arange(4)
        h = int((xs + 1) @ (t[4:8] - np.concatenate(([tl], t[0:3]))[::-1]))
        v = int((xs + 1) @ (l[4:8] - np.concatenate(([tl], l[0:3]))[::-1]))
        a = 16 * (int(l[7]) + int(t[7]))
        b = (34 * h + 32) >> 6
        c = (34 * v + 32) >> 6
        y, x = np.mgrid[0:8, 0:8]
        return np.clip((a + b * (x - 3) + c * (y - 3) + 16) >> 5, 0, 255).astype(np.uint8)
    raise ValueError(f"bad chroma mode {mode}")


def reconstruct_luma16(pred: np.ndarray, dc_levels: np.ndarray,
                       ac_levels: np.ndarray, qp: int) -> np.ndarray:
    """Rebuild a 16x16 luma MB from signaled levels.

    dc_levels: (16,) zig-zag luma DC levels; ac_levels: (16, 15) per-block
    zig-zag AC levels in z-scan block order (all-zero when cbp_luma == 0).
    """
    dc_block = inverse_zigzag(dc_levels.astype(np.int32))     # (4,4) spatial
    dc_recon = luma_dc_dequant(dc_block, qp)                  # (4,4)
    out = np.empty((16, 16), np.int32)
    for bi, (bx, by) in enumerate(LUMA_BLOCK_ORDER):
        seq = np.zeros(16, np.int32)
        seq[1:] = ac_levels[bi]
        z = inverse_zigzag(seq)
        d = dequant_4x4(z, qp)
        d[0, 0] = dc_recon[by, bx]
        r = (inverse_4x4(d) + 32) >> 6
        p = pred[4 * by:4 * by + 4, 4 * bx:4 * bx + 4].astype(np.int32)
        out[4 * by:4 * by + 4, 4 * bx:4 * bx + 4] = p + r
    return np.clip(out, 0, 255).astype(np.uint8)


def reconstruct_chroma8(pred: np.ndarray, dc_levels: np.ndarray,
                        ac_levels: np.ndarray, qpc: int) -> np.ndarray:
    """Rebuild one 8x8 chroma plane of a MB.

    dc_levels: (4,) raster-scan 2x2 DC levels; ac_levels: (4, 15) per-block
    zig-zag AC levels in CHROMA_BLOCK_ORDER.
    """
    dc_recon = chroma_dc_dequant(dc_levels.astype(np.int32).reshape(2, 2), qpc)
    out = np.empty((8, 8), np.int32)
    for bi, (bx, by) in enumerate(CHROMA_BLOCK_ORDER):
        seq = np.zeros(16, np.int32)
        seq[1:] = ac_levels[bi]
        z = inverse_zigzag(seq)
        d = dequant_4x4(z, qpc)
        d[0, 0] = dc_recon[by, bx]
        r = (inverse_4x4(d) + 32) >> 6
        p = pred[4 * by:4 * by + 4, 4 * bx:4 * bx + 4].astype(np.int32)
        out[4 * by:4 * by + 4, 4 * bx:4 * bx + 4] = p + r
    return np.clip(out, 0, 255).astype(np.uint8)


def i4_modes_allowed(has_top: bool, has_left: bool) -> tuple[int, ...]:
    """The Intra4x4 modes whose samples a block has (§8.3.1.2.1-9), in
    mode order. In a slice that starts a row, top and left available
    means the corner is too."""
    modes = [I4_DC]
    if has_top:
        modes += [I4_V, I4_DDL, I4_VL]
    if has_left:
        modes += [I4_H, I4_HU]
    if has_top and has_left:
        modes += [I4_DDR, I4_VR, I4_HD]
    return tuple(sorted(modes))


def i4_pred_mode(mode_a: int | None, mode_b: int | None) -> int:
    """§8.3.1.1's predIntra4x4PredMode from the left (A) and upper (B)
    blocks' modes: None = that macroblock is not available (then DC
    whatever the other is), an Intra16x16 neighbour counts as DC."""
    if mode_a is None or mode_b is None:
        return I4_DC
    return min(mode_a, mode_b)


def predict_luma4(mode: int, top: np.ndarray | None,
                  left: np.ndarray | None, topleft: int | None) -> np.ndarray:
    """4x4 luma prediction (§8.3.1.2). `top` is the EIGHT samples above
    and above right (the caller substitutes the fourth for an
    unavailable upper right quartet), `left` the four to the left."""
    if top is not None:
        t = top.astype(np.int32)
    if left is not None:
        l = left.astype(np.int32)
    if mode == I4_V:
        return np.tile(t[:4], (4, 1)).astype(np.uint8)
    if mode == I4_H:
        return np.tile(l[:, None], (1, 4)).astype(np.uint8)
    if mode == I4_DC:
        if top is not None and left is not None:
            dc = (int(t[:4].sum()) + int(l.sum()) + 4) >> 3
        elif left is not None:
            dc = (int(l.sum()) + 2) >> 2
        elif top is not None:
            dc = (int(t[:4].sum()) + 2) >> 2
        else:
            dc = 128
        return np.full((4, 4), dc, np.uint8)
    pred = np.empty((4, 4), np.int32)
    if mode in (I4_DDL, I4_VL):
        for y in range(4):
            for x in range(4):
                if mode == I4_DDL:
                    if x == 3 and y == 3:
                        pred[y, x] = (t[6] + 3 * t[7] + 2) >> 2
                    else:
                        k = x + y
                        pred[y, x] = (t[k] + 2 * t[k + 1] + t[k + 2] + 2) >> 2
                else:
                    k = x + (y >> 1)
                    if y % 2 == 0:
                        pred[y, x] = (t[k] + t[k + 1] + 1) >> 1
                    else:
                        pred[y, x] = (t[k] + 2 * t[k + 1] + t[k + 2] + 2) >> 2
        return pred.astype(np.uint8)
    if mode == I4_HU:
        for y in range(4):
            for x in range(4):
                z = x + 2 * y
                k = y + (x >> 1)
                if z > 5:
                    pred[y, x] = l[3]
                elif z == 5:
                    pred[y, x] = (l[2] + 3 * l[3] + 2) >> 2
                elif z % 2 == 0:
                    pred[y, x] = (l[k] + l[k + 1] + 1) >> 1
                else:
                    pred[y, x] = (l[k] + 2 * l[k + 1] + l[k + 2] + 2) >> 2
        return pred.astype(np.uint8)
    # the three modes through the corner: e[k] runs up the left column,
    # through the corner (k = 4) and along the top row
    e = np.concatenate([l[::-1], [int(topleft)], t[:4]])
    f3 = lambda k: (e[k - 1] + 2 * e[k] + e[k + 1] + 2) >> 2
    f2 = lambda a, b: (e[a] + e[b] + 1) >> 1
    for y in range(4):
        for x in range(4):
            if mode == I4_DDR:
                pred[y, x] = f3(4 + x - y)
            elif mode == I4_VR:
                z = 2 * x - y
                if z >= 0 and z % 2 == 0:
                    k = 4 + x - (y >> 1)
                    pred[y, x] = f2(k, k + 1)
                elif z >= 0:
                    pred[y, x] = f3(4 + x - (y >> 1))
                elif z == -1:
                    pred[y, x] = f3(4)
                else:
                    pred[y, x] = f3(4 - (y - 1))
            elif mode == I4_HD:
                z = 2 * y - x
                if z >= 0 and z % 2 == 0:
                    k = 4 - (y - (x >> 1))
                    pred[y, x] = f2(k - 1, k)
                elif z >= 0:
                    pred[y, x] = f3(4 - (y - (x >> 1)))
                elif z == -1:
                    pred[y, x] = f3(4)
                else:
                    pred[y, x] = f3(4 + x - 1)
            else:
                raise ValueError(f"bad Intra4x4 mode {mode}")
    return pred.astype(np.uint8)


def encode_luma4(src: np.ndarray, pred: np.ndarray, qp: int):
    """One Intra4x4 block: all sixteen coefficients through the plain
    4x4 transform (no DC Hadamard) → (levels (16,) zig-zag, recon
    (4, 4) uint8)."""
    w = forward_4x4(src.astype(np.int32) - pred.astype(np.int32))
    lev = zigzag(quant_4x4(w, qp, intra=True, skip_dc=False))
    return lev, reconstruct_luma4(pred, lev, qp)


def reconstruct_luma4(pred: np.ndarray, levels: np.ndarray,
                      qp: int) -> np.ndarray:
    """Rebuild one Intra4x4 block from its sixteen zig-zag levels."""
    d = dequant_4x4(inverse_zigzag(np.asarray(levels, np.int32)), qp)
    r = (inverse_4x4(d) + 32) >> 6
    return np.clip(pred.astype(np.int32) + r, 0, 255).astype(np.uint8)


def i4_neighbours(y: np.ndarray, gx: int, gy: int, has_top: bool,
                  has_left: bool, has_topright: bool,
                  has_corner: bool | None = None):
    """(top8, left4, corner) of the 4x4 block at block coordinates
    (gx, gy) of the reconstructed plane `y`, None where unavailable;
    an unavailable upper right quartet repeats the top's last sample
    (§8.3.1.2). `has_corner` None: the corner is there where top and
    left are (a slice that starts a macroblock row)."""
    x0, y0 = 4 * gx, 4 * gy
    top = left = corner = None
    if has_top:
        t = y[y0 - 1, x0:x0 + 4]
        tr = (y[y0 - 1, x0 + 4:x0 + 8] if has_topright
              else np.full(4, t[3], y.dtype))
        top = np.concatenate([t, tr])
    if has_left:
        left = y[y0:y0 + 4, x0 - 1]
    if has_top and has_left if has_corner is None else has_corner:
        corner = int(y[y0 - 1, x0 - 1])
    return top, left, corner
