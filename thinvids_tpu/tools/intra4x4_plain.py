"""Plain reference of Intra4x4 prediction (H.264 §8.3.1), one block at
a time.

Written from the standard's text and from nothing in this package: the
neighbour derivation of §6.4.11.4 in decoding order, the predicted mode
of §8.3.1.1 and the nine sample predictors of §8.3.1.2.1-9 as the
equations stand, a sample at a time, in plain Python on a dict of
neighbouring samples p[x, y]. No function is shared with
`codecs/h264/intra.py` (numpy, the encoder's and the decoder's) or
`codecs/h264/jaxcore.py` (the device's): tests/test_intra4x4.py holds
both to this file at every availability pattern.

Departures from the text, each noted where it applies: a picture is one
slice or slices of whole macroblock rows (what this package emits), so
a macroblock's availability is its position's alone; constrained
intra prediction is off (the package's PPS); only frame macroblocks.
"""

from __future__ import annotations

#: Table 8-2
MODES = ("vertical", "horizontal", "dc", "diagonal_down_left",
         "diagonal_down_right", "vertical_right", "horizontal_down",
         "vertical_left", "horizontal_up")
DC = 2


def block_xy(blk: int) -> tuple[int, int]:
    """§6.4.3: the upper-left luma sample (x, y) of 4x4 block `blk`
    inside its macroblock (the inverse 4x4 luma block scan)."""
    x = 4 * ((blk // 4) % 2 * 2 + blk % 2 % 2)
    y = 4 * ((blk // 4) // 2 * 2 + blk % 4 // 2)
    return x, y


def block_at(x: int, y: int) -> int:
    """§6.4.13.1: the index of the 4x4 luma block covering (x, y)."""
    return 8 * (y // 8) + 4 * (x // 8) + 2 * ((y % 8) // 4) + (x % 8) // 4


def neighbour(blk: int, which: str, mb_avail: dict) -> tuple | None:
    """§6.4.11.4 with §6.4.12: the neighbouring 4x4 luma block `which`
    ('A' left, 'B' above, 'C' above right, 'D' above left) of block
    `blk`: (macroblock 'cur' | 'A' | 'B' | 'C' | 'D', block index), or
    None where it is not available. `mb_avail` says which of the
    neighbouring MACROBLOCKS A, B, C, D are available. A block of the
    current macroblock that follows `blk` in decoding order is not
    available (it is "not yet decoded")."""
    x, y = block_xy(blk)
    dx, dy = {"A": (-1, 0), "B": (0, -1), "C": (4, -1), "D": (-1, -1)}[which]
    xn, yn = x + dx, y + dy
    # §6.4.12, Table 6-4 for maxW = maxH = 16
    if yn < 0:
        mb = "D" if xn < 0 else ("B" if xn <= 15 else "C")
    elif yn <= 15:
        mb = "A" if xn < 0 else ("cur" if xn <= 15 else None)
    else:
        mb = None
    if mb is None or (mb != "cur" and not mb_avail[mb]):
        return None
    idx = block_at(xn % 16, yn % 16)
    if mb == "cur" and idx > blk:
        return None
    return mb, idx


def predicted_mode(mode_a: int | None, mode_b: int | None) -> int:
    """§8.3.1.1: predIntra4x4PredMode. `mode_a` / `mode_b`: the
    Intra4x4PredMode of the neighbouring blocks A and B; the string
    'not_i4' for a block of a macroblock coded in another way
    (Intra16x16 here: intraMxMPredModeN = 2); None where the
    macroblock is not available (dcPredModePredictedFlag = 1)."""
    if mode_a is None or mode_b is None:
        return DC
    a = DC if mode_a == "not_i4" else mode_a
    b = DC if mode_b == "not_i4" else mode_b
    return min(a, b)


def coded_mode(mode: int, pred: int) -> tuple[int, int | None]:
    """§7.3.5.1 / §8.3.1.1 from the encoder's side:
    (prev_intra4x4_pred_mode_flag, rem_intra4x4_pred_mode or None)."""
    if mode == pred:
        return 1, None
    return 0, mode if mode < pred else mode - 1


def decoded_mode(flag: int, rem: int | None, pred: int) -> int:
    """§8.3.1.1's last step, the decoder's."""
    if flag:
        return pred
    return rem if rem < pred else rem + 1


def samples(plane, x0: int, y0: int, has: dict) -> dict:
    """§8.3.1.2: the 13 neighbouring samples p[x, -1], x = -1..7 and
    p[-1, y], y = 0..3 of the block whose upper-left sample is (x0,
    y0) of `plane` (rows of ints), None where "not available for
    Intra_4x4 prediction"; `has` says which of the neighbouring blocks
    A, B, C, D are available. Samples p[x, -1], x = 4..7, that are not
    available while p[3, -1] is, take p[3, -1]'s value."""
    p = {}
    for y in range(4):
        p[-1, y] = plane[y0 + y][x0 - 1] if has["A"] else None
    for x in range(4):
        p[x, -1] = plane[y0 - 1][x0 + x] if has["B"] else None
    for x in range(4, 8):
        p[x, -1] = plane[y0 - 1][x0 + x] if has["C"] else None
    p[-1, -1] = plane[y0 - 1][x0 - 1] if has["D"] else None
    if p[4, -1] is None and p[3, -1] is not None:
        for x in range(4, 8):
            p[x, -1] = p[3, -1]
    return {k: (None if v is None else int(v)) for k, v in p.items()}


def usable(mode: int, p: dict) -> bool:
    """Whether `mode` may be used: the samples its clause of §8.3.1.2
    says "shall be available" are."""
    top = all(p[x, -1] is not None for x in range(4))
    top8 = all(p[x, -1] is not None for x in range(8))
    left = all(p[-1, y] is not None for y in range(4))
    corner = p[-1, -1] is not None
    return {0: top, 1: left, 2: True, 3: top8, 4: top and left and corner,
            5: top and left and corner, 6: top and left and corner,
            7: top8, 8: left}[mode]


def predict(mode: int, p: dict) -> list[list[int]]:
    """§8.3.1.2.1-9: pred4x4L[x, y], returned as rows [y][x]."""
    if not usable(mode, p):
        raise ValueError(f"mode {MODES[mode]} without its samples")
    pred = [[0] * 4 for _ in range(4)]
    for y in range(4):
        for x in range(4):
            if mode == 0:                                   # (8-46)
                v = p[x, -1]
            elif mode == 1:                                 # (8-47)
                v = p[-1, y]
            elif mode == 2:                                 # (8-48..51)
                top = p[0, -1] is not None
                left = p[-1, 0] is not None
                if top and left:
                    v = (sum(p[i, -1] for i in range(4))
                         + sum(p[-1, i] for i in range(4)) + 4) >> 3
                elif left:
                    v = (sum(p[-1, i] for i in range(4)) + 2) >> 2
                elif top:
                    v = (sum(p[i, -1] for i in range(4)) + 2) >> 2
                else:
                    v = 128
            elif mode == 3:                                 # (8-52, 53)
                if x == 3 and y == 3:
                    v = (p[6, -1] + 3 * p[7, -1] + 2) >> 2
                else:
                    v = (p[x + y, -1] + 2 * p[x + y + 1, -1]
                         + p[x + y + 2, -1] + 2) >> 2
            elif mode == 4:                                 # (8-54..56)
                if x > y:
                    v = (p[x - y - 2, -1] + 2 * p[x - y - 1, -1]
                         + p[x - y, -1] + 2) >> 2
                elif x < y:
                    v = (p[-1, y - x - 2] + 2 * p[-1, y - x - 1]
                         + p[-1, y - x] + 2) >> 2
                else:
                    v = (p[0, -1] + 2 * p[-1, -1] + p[-1, 0] + 2) >> 2
            elif mode == 5:                                 # (8-57..60)
                z = 2 * x - y
                if z in (0, 2, 4, 6):
                    v = (p[x - (y >> 1) - 1, -1] + p[x - (y >> 1), -1]
                         + 1) >> 1
                elif z in (1, 3, 5):
                    v = (p[x - (y >> 1) - 2, -1]
                         + 2 * p[x - (y >> 1) - 1, -1]
                         + p[x - (y >> 1), -1] + 2) >> 2
                elif z == -1:
                    v = (p[-1, 0] + 2 * p[-1, -1] + p[0, -1] + 2) >> 2
                else:
                    v = (p[-1, y - 1] + 2 * p[-1, y - 2] + p[-1, y - 3]
                         + 2) >> 2
            elif mode == 6:                                 # (8-61..64)
                z = 2 * y - x
                if z in (0, 2, 4, 6):
                    v = (p[-1, y - (x >> 1) - 1] + p[-1, y - (x >> 1)]
                         + 1) >> 1
                elif z in (1, 3, 5):
                    v = (p[-1, y - (x >> 1) - 2]
                         + 2 * p[-1, y - (x >> 1) - 1]
                         + p[-1, y - (x >> 1)] + 2) >> 2
                elif z == -1:
                    v = (p[-1, 0] + 2 * p[-1, -1] + p[0, -1] + 2) >> 2
                else:
                    v = (p[x - 1, -1] + 2 * p[x - 2, -1] + p[x - 3, -1]
                         + 2) >> 2
            elif mode == 7:                                 # (8-65, 66)
                if y in (0, 2):
                    v = (p[x + (y >> 1), -1] + p[x + (y >> 1) + 1, -1]
                         + 1) >> 1
                else:
                    v = (p[x + (y >> 1), -1]
                         + 2 * p[x + (y >> 1) + 1, -1]
                         + p[x + (y >> 1) + 2, -1] + 2) >> 2
            elif mode == 8:                                 # (8-67..70)
                z = x + 2 * y
                if z in (0, 2, 4):
                    v = (p[-1, y + (x >> 1)] + p[-1, y + (x >> 1) + 1]
                         + 1) >> 1
                elif z in (1, 3):
                    v = (p[-1, y + (x >> 1)]
                         + 2 * p[-1, y + (x >> 1) + 1]
                         + p[-1, y + (x >> 1) + 2] + 2) >> 2
                elif z == 5:
                    v = (p[-1, 2] + 3 * p[-1, 3] + 2) >> 2
                else:
                    v = p[-1, 3]
            else:
                raise ValueError(f"no Intra4x4PredMode {mode}")
            pred[y][x] = v
    return pred


def mb_availability(mx: int, my: int, mbw: int) -> dict:
    """§6.4.8 / §6.4.9 for a picture that is one slice (or slices of
    whole macroblock rows, numbered from the slice's first row): which
    of the macroblocks A (left), B (above), C (above right), D (above
    left) of macroblock (mx, my) are available."""
    return {"A": mx > 0, "B": my > 0, "C": my > 0 and mx + 1 < mbw,
            "D": my > 0 and mx > 0}


def block_prediction(plane, mx: int, my: int, blk: int, mode: int,
                     mbw: int) -> list[list[int]]:
    """The prediction of block `blk` of macroblock (mx, my) in `mode`
    from the samples `plane` holds (the picture constructed so far,
    before the deblocking filter)."""
    avail = mb_availability(mx, my, mbw)
    has = {n: neighbour(blk, n, avail) is not None for n in "ABCD"}
    x, y = block_xy(blk)
    return predict(mode, samples(plane, 16 * mx + x, 16 * my + y, has))
