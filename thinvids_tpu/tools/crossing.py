"""Seeded action footage: `tools/pan.py`'s scene panning behind opaque
rectangles that move their own ways, cross, cover and uncover each
other.

The content the `p_intra` tests share: the background is `pan.py`'s
scene on a periodic canvas the size of the picture, panned by whole
pixels; over it `sprites` opaque rectangles, each a function of its
index alone but for its static grain (the seed's), moving by whole
pixels a frame — even ones faster than the motion search reaches
(7..13 pixels a frame at 1920 wide), odd ones inside it (1..4) — and
wrapping round the picture. The benchmark's `generators/crossing.py`
is the same function of (n, width, height, seed, pan, sprites), held to
it by tests/test_p_intra.py, and says more about the scene. jax-free.
"""

from __future__ import annotations


import numpy as np

from ..core.types import Frame

LEVELS = (50, 80, 150, 190)


def _whole(size, per_sample):
    """`per_sample` rounded so that `size` samples hold a whole number
    (at least one) of periods of 2 pi."""
    return 2 * np.pi * max(1, round(size * per_sample / (2 * np.pi))) / size


def _canvases(width, height, seed):
    """The periodic background (luma, u, v), uint8: `pan.py`'s scene,
    every slope and frequency rounded to whole periods (no seam)."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:height, 0:width]
    ramp = 256 * (max(1, round(width * 0.1 / 256)) * xx / width
                  + max(1, round(height * 0.05 / 256)) * yy / height)
    scene = ramp % 256 \
        + 24.0 * np.sin(xx * _whole(width, 0.07)) \
        * np.cos(yy * _whole(height, 0.05)) \
        + rng.normal(0, 6.0, (height, width))
    h2, w2 = height // 2, width // 2
    cy, cx = np.mgrid[0:h2, 0:w2]
    return (np.clip(scene, 0, 255).astype(np.uint8),
            np.clip(128 + 30 * np.sin(cx * _whole(w2, 0.02)),
                    0, 255).astype(np.uint8),
            np.clip(128 + 30 * np.cos(cy * _whole(h2, 0.02)),
                    0, 255).astype(np.uint8))


def sprite(k, width, seed):
    """Sprite k of a picture `width` wide: (luma (h, w) uint8, u level,
    v level, (x0, y0), (vx, vy)) — everything but the grain a function
    of k alone."""
    rng = np.random.default_rng(k)
    scale = width / 1920.0
    size = rng.integers(96, 385, 2)
    size += size % 16 == 0                     # no multiple of 16
    w, h = (max(8, int(round(s * scale))) for s in size)
    slope = rng.uniform(-0.08, 0.08, 2)
    lam = rng.uniform(125.0, 315.0)
    theta = rng.uniform(0.0, 2 * np.pi)
    phase = rng.uniform(0.0, 2 * np.pi)
    cu, cv = (int(c) for c in rng.integers(64, 193, 2))
    start = rng.uniform(0.0, 1.0, 2)
    sign = rng.choice((-1, 1), 2)
    if k % 2 == 0:                             # fast, mostly horizontal
        speed = int(rng.integers(7, 14))
        vel = (speed, speed // 2)
    else:                                      # slow
        vel = (int(rng.integers(1, 5)), int(rng.integers(1, 5)))
    vx, vy = (int(s) * max(1, int(round(v * scale)))
              for s, v in zip(sign, vel))
    yy, xx = np.mgrid[0:h, 0:w]
    body = LEVELS[k % 4] + slope[0] * (xx - w / 2) + slope[1] * (yy - h / 2) \
        + 6.0 * np.sin(2 * np.pi * (xx * np.cos(theta) + yy * np.sin(theta))
                       / lam + phase) \
        + np.random.default_rng([seed, k]).normal(0, 6.0, (h, w))
    return (np.clip(body, 0, 255).astype(np.uint8), cu, cv,
            (float(start[0]), float(start[1])), (vx, vy))


def _paste(plane, patch, x, y):
    """`patch` onto `plane` with its corner at (x, y), clipped to the
    plane."""
    H, W = plane.shape
    h, w = patch.shape
    x0, y0, x1, y1 = max(x, 0), max(y, 0), min(x + w, W), min(y + h, H)
    if x0 < x1 and y0 < y1:
        plane[y0:y1, x0:x1] = patch[y0 - y:y1 - y, x0 - x:x1 - x]


def planes(n, width, height, seed, pan=3, sprites=24):
    """Iterator over the (y, u, v) uint8 planes of frames 0..n-1."""
    back = _canvases(width, height, seed)
    things = []
    for k in range(int(sprites)):
        body, cu, cv, start, vel = sprite(k, width, seed)
        half = (body.shape[0] // 2, body.shape[1] // 2)
        things.append((body, np.full(half, cu, np.uint8),
                       np.full(half, cv, np.uint8), start, vel))
    for t in range(n):
        d = pan * t
        y = np.roll(back[0], (-d, -d), (0, 1))
        u = np.roll(back[1], (-(d // 2), -(d // 2)), (0, 1))
        v = np.roll(back[2], (-(d // 2), -(d // 2)), (0, 1))
        for body, body_u, body_v, (sx, sy), (vx, vy) in things:
            h, w = body.shape
            x = (int(sx * (width + w)) + vx * t) % (width + w) - w
            yy = (int(sy * (height + h)) + vy * t) % (height + h) - h
            _paste(y, body, x, yy)
            _paste(u, body_u, x // 2, yy // 2)
            _paste(v, body_v, x // 2, yy // 2)
        yield y, u, v


def make_frames(n: int, w: int, h: int, seed: int = 0, **params
                ) -> list[Frame]:
    """`n` 4:2:0 frames of the crossing clip (`params`: `pan`,
    `sprites`). Frame t is a function of (t, w, h, seed, params)
    alone."""
    return [Frame(y=y, u=u, v=v) for y, u, v in planes(n, w, h, seed,
                                                       **params)]
