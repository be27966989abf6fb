"""Scene-cut detector: the per-frame costs `planner.take_cuts` decides on.

Host side, jax-free, luma only. It reads the source once, in the
executor's `segment` stage (cluster/executor.py, stage `scenecut`),
before any frame is staged: a cut is a GOP boundary, so the plan needs
the whole list first. Per frame t >= 1, in integers:

- `B_t`: the luma plane reduced to sums over 32x32 blocks (34x60 at
  1080p; the last row and column of blocks take what samples there
  are);
- inter cost `sum |B_t - B_{t-1}|`: what predicting the frame from the
  last one with no motion leaves;
- intra cost: half the sum of the absolute differences of `B_t` with
  its left and its upper neighbour: what predicting a block from its
  neighbours leaves.

A sum over 1,024 samples puts grain and fine static texture under the
picture's structure, and a pan shows as a cut only near a block's width
a frame (a 3 px pan reads inter = 0.17-0.28 x intra at 1080p, a cut
between two shots 2-12 x; tests/test_scenecut.py holds the margin).
`tools/scenecut_plain.py` is the plain form of the rule, with x264's
own noted beside every departure; tier-1 holds this module to it cut
for cut.
"""

from __future__ import annotations

import numpy as np

from .planner import take_cuts

BLOCK = 32


def lumas(frames):
    """Luma planes of a clip, in order: straight from the file where
    the source can (`FrameSource.iter_luma`: a .y4m reads no chroma),
    else from its frames."""
    it = getattr(frames, "iter_luma", None)
    if it is not None:
        return it()
    return (f.y for f in frames)


def block_sums(y: np.ndarray) -> np.ndarray:
    """`y` (uint8) reduced to int64 sums over BLOCK x BLOCK blocks,
    rows first: 32 samples fit a uint16."""
    h, w = y.shape
    hb, wb = h // BLOCK, w // BLOCK
    rows = []
    if hb:
        rows.append(y[:hb * BLOCK].reshape(hb, BLOCK, w)
                    .sum(1, dtype=np.uint16))
    if h % BLOCK:
        rows.append(y[hb * BLOCK:].sum(0, dtype=np.uint16)[None])
    rows = np.concatenate(rows)
    cols = []
    if wb:
        cols.append(rows[:, :wb * BLOCK].reshape(-1, wb, BLOCK)
                    .sum(-1, dtype=np.int64))
    if w % BLOCK:
        cols.append(rows[:, wb * BLOCK:].sum(-1, dtype=np.int64)[:, None])
    return np.concatenate(cols, axis=1)


def frame_costs(planes) -> tuple[list[int], list[int]]:
    """(inter, intra) cost of every frame of `planes` (an iterable of
    luma planes); entry 0 of both is 0: the first frame has no last
    one."""
    inter, intra = [], []
    last = None
    for y in planes:
        b = block_sums(np.asarray(y))
        if last is None:
            inter.append(0)
            intra.append(0)
        else:
            inter.append(int(np.abs(b - last).sum()))
            intra.append((int(np.abs(np.diff(b, axis=1)).sum())
                          + int(np.abs(np.diff(b, axis=0)).sum())) // 2)
        last = b
    return inter, intra


def detect(frames, gop_frames: int, scenecut: int
           ) -> tuple[tuple[int, ...], int]:
    """(cuts taken, cuts suppressed) of a clip: a pure function of its
    luma, `gop_frames` and `scenecut` — not of the mesh, the wave
    order, a shard or a resume."""
    inter, intra = frame_costs(lumas(frames))
    return take_cuts(inter, intra, gop_frames, scenecut)
