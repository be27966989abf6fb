"""Seeded screen content: a desktop of overlapping windows with text, a
video pane, typing, a scroll and a pointer.

Every other generator here is one smooth camera scene, on which a
16x16 intra predictor does well. A screen capture is text, window
edges and flat fills: detail finer than a macroblock in the IDR, and P
pictures that are nearly all skip. No grain: a capture has none.

- desktop: a two-axis luma ramp 60 -> 110, constant chroma;
- `windows` opaque windows, window k a function of k alone
  (`default_rng(k)`): 480..1,100 wide, 300..700 high, placed at any
  pixel (never on a multiple of 16), z-order the index, a 1-pixel
  border, a 28-pixel flat title bar; bodies alternately light (paper
  238, ink 35) and dark (paper 32, ink 220);
- text in every window but 1: a font of 96 glyphs, each a 7x11 bitmap
  from `default_rng(12345)` at 42 % ink, on a 9x18 pitch (neither a
  multiple of 4), words of 2..9 glyphs, ragged lines of 40..100 % of the
  body, then one horizontal [1, 2, 1] / 4 pass (anti-aliasing). Where
  the words and lines lie is a function of the window and the line
  (`default_rng([k, line])`); THE SEED DRAWS WHICH GLYPHS AND NOTHING
  ELSE (`default_rng([seed, k, line])`): a scene whose structure moved
  with the seed spread an encode's bits over their bound (PERF.md §6,
  PR 32);
- window 1 is a video pane: `pan.py`'s scene without its grain, 640x360
  on a periodic canvas, panning `pane_pan` whole pixels a frame;
- events, by t alone: typing in window 0 below its text, one glyph every
  `type_every` frames (a space after every fifth) at a caret that blinks
  15 frames on, 15 off; window 2 scrolls up `scroll_px` pixels a frame
  during frames 40..57 of every 64 (18 frames, four lines at 4), new
  lines entering below; a 12x19 pointer on a closed path at about 5
  pixels a frame.

Window sizes, the title bar, the pane, the pointer's path and speeds are
stated at 1920 wide and scale by `width / 1920` (a window is at least 40
by 32 pixels, so a 128x128 rehearsal clip still holds a window with
text); glyphs and their pitch do not scale. Chroma is composed at
floor(x / 2), floor(y / 2).

Frame t is a function of (t, width, height, seed, windows, pane_pan,
type_every, scroll_px) alone: the first k frames of a longer call are
the shorter call's.
The harness's own copy of `thinvids_tpu/tools/screen.py`;
`tests/test_intra4x4.py` holds the two to the same bytes.
"""

import numpy as np

GLYPH_W, GLYPH_H = 7, 11
PITCH_X, PITCH_Y = 9, 18
FONT = (np.random.default_rng(12345).random((96, GLYPH_H, GLYPH_W))
        < 0.42)
PAPER_INK = ((238, 35), (32, 220))        # light, dark
TITLE = (205, 64)
BORDER = 90
DESKTOP_UV = (120, 134)
#: frames of a scroll cycle, its first scrolling frame, scrolling frames
SCROLL_CYCLE, SCROLL_FROM, SCROLL_FRAMES = 64, 40, 18
CARET_BLINK = 15
TYPED_ROWS = 4


def _whole(size, per_sample):
    """`per_sample` rounded so that `size` samples hold a whole number
    (at least one) of periods of 2 pi."""
    return 2 * np.pi * max(1, round(size * per_sample / (2 * np.pi))) / size


def _pane(width, height):
    """`pan.py`'s scene without grain on a periodic canvas (y, u, v)."""
    yy, xx = np.mgrid[0:height, 0:width]
    ramp = 256 * (max(1, round(width * 0.1 / 256)) * xx / width
                  + max(1, round(height * 0.05 / 256)) * yy / height)
    scene = ramp % 256 + 24.0 * np.sin(xx * _whole(width, 0.07)) \
        * np.cos(yy * _whole(height, 0.05))
    h2, w2 = height // 2, width // 2
    cy, cx = np.mgrid[0:h2, 0:w2]
    return (np.clip(scene, 0, 255).astype(np.uint8),
            np.clip(128 + 30 * np.sin(cx * _whole(w2, 0.02)),
                    0, 255).astype(np.uint8),
            np.clip(128 + 30 * np.cos(cy * _whole(h2, 0.02)),
                    0, 255).astype(np.uint8))


def window(k, width, height):
    """Window k of a `width` x `height` picture: (x0, y0, w, h, title
    bar height), a function of k and the picture's size alone."""
    rng = np.random.default_rng(k)
    scale = width / 1920.0
    w = min(width - 2, max(40, int(round(rng.integers(480, 1101) * scale))))
    h = min(height - 2, max(32, int(round(rng.integers(300, 701) * scale))))
    x0 = int(rng.integers(0, width - w + 1))
    y0 = int(rng.integers(0, height - h + 1))
    x0 += (x0 % 16 == 0) and x0 + w < width       # never on the MB grid
    y0 += (y0 % 16 == 0) and y0 + h < height
    return int(x0), int(y0), w, h, max(3, int(round(28 * scale)))


def _smooth(a):
    """One horizontal [1, 2, 1] / 4 pass, edges repeated."""
    p = np.pad(a.astype(np.int32), ((0, 0), (1, 1)), mode="edge")
    return ((p[:, :-2] + 2 * p[:, 1:-1] + p[:, 2:] + 2) >> 2).astype(np.uint8)


def _put_glyphs(line, glyphs, cols, ink):
    """Glyph `glyphs[i]` at column `cols[i]` of one text line (PITCH_Y
    high), top-aligned 3 pixels down."""
    for g, c in zip(glyphs, cols):
        x = c * PITCH_X + 1
        cell = line[3:3 + GLYPH_H, x:x + GLYPH_W]
        cell[FONT[g][:, :cell.shape[1]]] = ink


def text_line(k, j, seed, body_w, dark):
    """Line j of window k's text, (PITCH_Y, body_w) uint8: where its
    words lie from (k, j), which glyphs from (seed, k, j)."""
    paper, ink = PAPER_INK[dark]
    line = np.full((PITCH_Y, body_w), paper, np.uint8)
    ncols = body_w // PITCH_X
    if ncols < 1:
        return line
    lay = np.random.default_rng([k, j])
    used = max(1, int(ncols * lay.uniform(0.4, 1.0)))
    cols, c = [], 0
    while c < used:
        word = int(lay.integers(2, 10))
        cols.extend(range(c, min(c + word, used)))
        c += word + 1
    glyphs = np.random.default_rng([seed, k, j]).integers(0, 96, len(cols))
    _put_glyphs(line, glyphs, cols, ink)
    return _smooth(line)


def typed_lines(t, seed, body_w, dark, type_every):
    """The typing area of window 0 at frame t, (TYPED_ROWS * PITCH_Y,
    body_w): t // type_every glyphs so far, a space after every fifth,
    a new page when the area is full, and the caret."""
    paper, ink = PAPER_INK[dark]
    area = np.full((TYPED_ROWS * PITCH_Y, body_w), paper, np.uint8)
    ncols = body_w // PITCH_X
    if ncols < 2:
        return area
    room = ncols * TYPED_ROWS
    count = t // max(1, int(type_every))
    page, shown = divmod(count, room)
    glyphs = np.random.default_rng([seed, 1 << 20, page]).integers(0, 96, room)
    for r in range(TYPED_ROWS):
        cells = [c for c in range(ncols)
                 if r * ncols + c < shown and (r * ncols + c) % 6 != 5]
        _put_glyphs(area[r * PITCH_Y:(r + 1) * PITCH_Y],
                    glyphs[r * ncols:(r + 1) * ncols][cells], cells, ink)
    area = _smooth(area)
    if t % (2 * CARET_BLINK) < CARET_BLINK:
        r, c = divmod(shown, ncols)
        area[r * PITCH_Y + 3:r * PITCH_Y + 3 + GLYPH_H,
             c * PITCH_X + 1:c * PITCH_X + 3] = ink
    return area


def scrolled(t, scroll_px):
    """Pixels window 2's text has moved up by frame t."""
    cycle, within = divmod(t, SCROLL_CYCLE)
    return int(scroll_px) * (cycle * SCROLL_FRAMES + min(
        max(within - SCROLL_FROM + 1, 0), SCROLL_FRAMES))


#: the pointer: an arrow head (a right triangle, upright edge on the
#: left) over a four-pixel shaft
POINTER = np.array([[x * 19 <= y * 12 if y < 14 else 4 <= x < 8
                     for x in range(12)] for y in range(19)], bool)


def _paste(plane, patch, x, y, mask=None):
    """`patch` onto `plane` with its corner at (x, y), clipped to the
    plane; where `mask` is given, only its set samples."""
    H, W = plane.shape
    h, w = patch.shape
    x0, y0, x1, y1 = max(x, 0), max(y, 0), min(x + w, W), min(y + h, H)
    if x0 >= x1 or y0 >= y1:
        return
    part = patch[y0 - y:y1 - y, x0 - x:x1 - x]
    if mask is None:
        plane[y0:y1, x0:x1] = part
    else:
        m = mask[y0 - y:y1 - y, x0 - x:x1 - x]
        plane[y0:y1, x0:x1][m] = part[m]


def planes(n, width, height, seed, windows=5, pane_pan=2, type_every=2,
           scroll_px=4):
    """Iterator over the (y, u, v) uint8 planes of frames 0..n-1."""
    scale = width / 1920.0
    yy, xx = np.mgrid[0:height, 0:width]
    desk = (60 + 25 * xx / max(1, width - 1)
            + 25 * yy / max(1, height - 1)).astype(np.uint8)
    h2, w2 = height // 2, width // 2
    desk_u = np.full((h2, w2), DESKTOP_UV[0], np.uint8)
    desk_v = np.full((h2, w2), DESKTOP_UV[1], np.uint8)
    pane_w = max(16, int(round(640 * scale)) // 2 * 2)
    pane_h = max(16, int(round(360 * scale)) // 2 * 2)
    pane = _pane(pane_w, pane_h)

    frames, bodies, lines = [], {}, {}
    for k in range(int(windows)):
        x0, y0, w, h, bar = window(k, width, height)
        dark = k % 2
        frame = np.full((h, w), PAPER_INK[dark][0], np.uint8)
        frame[1:1 + bar, 1:-1] = TITLE[dark]
        frame[0], frame[-1], frame[:, 0], frame[:, -1] = (BORDER,) * 4
        bw, bh = w - 2 - 2 * 4, h - 2 - bar - 4      # a 4-pixel margin
        frames.append((x0, y0, frame, (5, 1 + bar + 2, max(bw, 0),
                                       max(bh, 0)), dark))
        if k != 1 and bw > 0 and bh > 0:
            lines[k] = {}
            rows = bh // PITCH_Y - (TYPED_ROWS + 1 if k == 0 else 0)
            if k != 2 and rows > 0:
                bodies[k] = np.concatenate(
                    [text_line(k, j, seed, bw, dark) for j in range(rows)])

    def line_of(k, j, bw, dark):
        if j not in lines[k]:
            lines[k][j] = text_line(k, j, seed, bw, dark)
        return lines[k][j]

    rx, ry = 0.3 * width, 0.25 * height
    period = max(8, int(round(2 * np.pi * np.sqrt((rx * rx + ry * ry) / 2)
                              / max(1.0, 5 * scale))))
    for t in range(n):
        y, u, v = desk.copy(), desk_u.copy(), desk_v.copy()
        for k, (x0, y0, frame, (bx, by, bw, bh), dark) in enumerate(frames):
            win = frame.copy()
            body = win[by:by + bh, bx:bx + bw]
            # chroma samples the window covers, grey but for the pane
            cu = np.full(((y0 + win.shape[0] - 1) // 2 - y0 // 2 + 1,
                          (x0 + win.shape[1] - 1) // 2 - x0 // 2 + 1),
                         128, np.uint8)
            cv = cu.copy()
            if k == 1:
                d = pane_pan * t
                hh, ww = min(bh, pane_h), min(bw, pane_w)
                body[:hh, :ww] = np.roll(pane[0], (-d, -d), (0, 1))[:hh, :ww]
                for dst, src in ((cu, pane[1]), (cv, pane[2])):
                    dst[by // 2:by // 2 + hh // 2,
                        bx // 2:bx // 2 + ww // 2] = np.roll(
                            src, (-(d // 2), -(d // 2)),
                            (0, 1))[:hh // 2, :ww // 2]
            elif k in bodies:
                body[:bodies[k].shape[0]] = bodies[k]
                if k == 0 and bh >= (TYPED_ROWS + 1) * PITCH_Y:
                    top = bodies[k].shape[0] + PITCH_Y
                    body[top:top + TYPED_ROWS * PITCH_Y] = typed_lines(
                        t, seed, bw, dark, type_every)
            elif k == 2 and k in lines:
                first, off = divmod(scrolled(t, scroll_px), PITCH_Y)
                doc = np.concatenate(
                    [line_of(k, first + j, bw, dark)
                     for j in range(-(-(bh + off) // PITCH_Y))])
                body[:] = doc[off:off + bh]
            _paste(y, win, x0, y0)
            _paste(u, cu, x0 // 2, y0 // 2)
            _paste(v, cv, x0 // 2, y0 // 2)
        a = 2 * np.pi * t / period
        _paste(y, np.full(POINTER.shape, 250, np.uint8),
               int(round(width / 2 + rx * np.cos(a))),
               int(round(height / 2 + ry * np.sin(a))), POINTER)
        yield y, u, v
