"""Plain reference of the scene-cut rule (the `scenecut` setting).

A per-frame, per-block numpy loop with no thought for speed and no code
shared with the detector and planner the executor runs
(parallel/scenecut.py, parallel/planner.take_cuts / plan_segments). It
exists to be compared with: tests/test_scenecut.py holds the served
pair to it cut for cut and GOP start for GOP start on seeded content.

The rule is x264's `scenecut` (slicetype.c, scenecut_internal): a frame
whose inter cost is not well below its intra cost starts a new GOP.

    frame t is a cut  iff  inter_t > 0 and
                           100 * inter_t >= (100 - bias_t) * intra_t

`bias_t` ramps with the distance d from the last GOP start the
detector knows of: 0 at d = 0, a quarter of `scenecut` at
`min_gop = max(1, gop_frames // 10)` (x264's `min-keyint` auto), the
whole of it from `gop_frames` on, linear in between. A cut closer than
`min_gop` to the last one is not taken. The cuts taken split the clip
into shots; a shot of L frames becomes ceil(L / gop_frames) GOPs of
floor or ceil of L over that count, the longer ones first.

Departures from x264, all of them:

1. Costs. x264 searches motion on half-resolution frames and costs 8x8
   blocks (SATD of the best inter and intra prediction). Here the
   inter cost is the zero-vector one and a block is the SUM of 32x32
   luma samples: `sum |B_t - B_{t-1}|` against half the sum of
   `|B - left|` and `|B - upper|`. Sums over 1,024 samples put grain
   that is new on every frame (sigma 5: +-160 on a block sum) and fine
   static texture under the picture's structure, which 8x8 costs
   without a search do not (a 3 px pan reads 0.60 x intra on 8x8 sums,
   a false cut at scenecut 40; 0.18 on 32x32), and a pan shows as a
   cut only near 32 px a frame. Luma only, integers only, frames t and
   t-1 alone.
2. `inter_t > 0`: x264's intra cost is never 0 (every block costs
   bits); a block-sum cost is, on a flat picture, and a run of equal
   flat frames (a fade's end) must not read as a run of cuts.
3. The ramp runs from the last cut taken (or frame 0), not from the
   last keyframe: x264 restarts it at the IDRs that `keyint` forces,
   whose places it knows as it goes; here the GOP starts inside a shot
   are placed once the shot's end is known (the even split above), so
   the detector cannot know them. Past `gop_frames` the bias stays
   whole. No GOP start of the split lies closer than `min_gop` to the
   next cut, so "not closer than min_gop to the last GOP start" holds
   of every cut taken all the same.
4. Below `min_gop` x264 holds the bias at a sixteenth of `scenecut`
   up to `min_gop / 4`; here it ramps from 0. It decides nothing: a
   cut there is not taken either way, only counted as suppressed.
5. A suppressed cut: x264 codes a non-IDR I frame there. This encoder
   has none, so the frame stays a P frame.
6. No lookahead: x264 also asks whether the frames after t go back to
   the picture before it (a flash) and drops such a cut.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

BLOCK = 32


def block_sums(y) -> list[list[int]]:
    """Sums of the plane `y` over BLOCK x BLOCK blocks, block by
    block; edge blocks take what samples there are."""
    y = np.asarray(y)
    h, w = y.shape
    return [[int(y[r:r + BLOCK, c:c + BLOCK].sum(dtype=np.int64))
             for c in range(0, w, BLOCK)]
            for r in range(0, h, BLOCK)]


def costs(b, last) -> tuple[int, int]:
    """(inter, intra) cost of a frame with block sums `b` after one
    with `last`."""
    rows, cols = len(b), len(b[0])
    inter = sum(abs(b[r][c] - last[r][c])
                for r in range(rows) for c in range(cols))
    left = sum(abs(b[r][c] - b[r][c - 1])
               for r in range(rows) for c in range(1, cols))
    upper = sum(abs(b[r][c] - b[r - 1][c])
                for r in range(1, rows) for c in range(cols))
    return inter, (left + upper) // 2


def bias(distance: int, gop_frames: int, scenecut: int) -> Fraction:
    lo = max(1, gop_frames // 10)
    quarter = Fraction(scenecut, 4)
    if distance >= gop_frames:
        return Fraction(scenecut)
    if distance < lo:
        return quarter * Fraction(distance, lo)
    return quarter + 3 * quarter * Fraction(distance - lo, gop_frames - lo)


def scene_cuts(planes, gop_frames: int, scenecut: int
               ) -> tuple[list[int], list[int]]:
    """(cuts taken, cuts suppressed) of a clip given as luma planes."""
    lo = max(1, gop_frames // 10)
    taken, suppressed = [], []
    last_b, last_cut = None, 0
    for t, y in enumerate(planes):
        b = block_sums(y)
        if last_b is not None:
            inter, intra = costs(b, last_b)
            limit = (100 - bias(t - last_cut, gop_frames, scenecut)) * intra
            if inter > 0 and 100 * inter >= limit:
                if t - last_cut >= lo:
                    taken.append(t)
                    last_cut = t
                else:
                    suppressed.append(t)
        last_b = b
    return taken, suppressed


def gop_starts(num_frames: int, gop_frames: int, cuts) -> list[int]:
    """First frame of every GOP of a clip cut into shots at `cuts`."""
    starts = []
    bounds = [0, *cuts, num_frames]
    for a, b in zip(bounds, bounds[1:]):
        count = -(-(b - a) // gop_frames)
        short, longer = divmod(b - a, count)
        for k in range(count):
            starts.append(a)
            a += short + (1 if k < longer else 0)
    return starts
