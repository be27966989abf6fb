"""Seeded diagonal camera pan over a fixed detailed scene.

The content the encoder tests and `chip_smoke.py` share: frame i + 1 is
frame i moved `pan` pixels up and left (the camera goes down and right),
so motion search has a true vector to find — unlike per-frame iid
noise, which no codec can inter-predict. jax-free.
"""

from __future__ import annotations

import numpy as np

from ..core.types import Frame


def make_frames(n: int, w: int, h: int, seed: int = 0,
                pan: int = 3) -> list[Frame]:
    """`n` 4:2:0 frames of a `w`x`h` window panning `pan` px/frame
    diagonally over gradient + texture + static grain. A function of
    its arguments alone; the planes are views into one scene."""
    rng = np.random.default_rng(seed)
    pad = pan * n + 2
    yy, xx = np.mgrid[0:h + pad, 0:w + pad]
    scene = (xx * 0.1 + yy * 0.05) % 256 \
        + 24.0 * np.sin(xx * 0.07) * np.cos(yy * 0.05) \
        + rng.normal(0, 6.0, (h + pad, w + pad))
    scene = np.clip(scene, 0, 255).astype(np.uint8)
    scene_u = np.clip(128 + 30 * np.sin(xx[::2, ::2] * 0.01),
                      0, 255).astype(np.uint8)
    scene_v = np.clip(128 + 30 * np.cos(yy[::2, ::2] * 0.01),
                      0, 255).astype(np.uint8)
    frames = []
    for i in range(n):
        dy = dx = pan * i
        frames.append(Frame(
            y=scene[dy:dy + h, dx:dx + w],
            u=scene_u[dy // 2:dy // 2 + h // 2, dx // 2:dx // 2 + w // 2],
            v=scene_v[dy // 2:dy // 2 + h // 2, dx // 2:dx // 2 + w // 2],
        ))
    return frames
