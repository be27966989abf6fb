"""Seeded diagonal camera pan over a fixed detailed scene.

The content the encoder tests and `chip_smoke.py` share: frame i + 1 is
frame i moved `pan` pixels up and left (the camera goes down and right),
so motion search has a true vector to find — unlike per-frame iid
noise, which no codec can inter-predict. `grain` adds exactly that on
top: film grain that is new on every frame, the content that leaves
the sparse transfer budgets (parallel/dispatch.start_fetch). jax-free.
"""

from __future__ import annotations

import numpy as np

from ..core.types import Frame


def _grainy(plane: np.ndarray, rng, sigma: float) -> np.ndarray:
    noisy = np.rint(plane + rng.normal(0.0, sigma, plane.shape))
    return np.clip(noisy, 0, 255).astype(np.uint8)


def make_frames(n: int, w: int, h: int, seed: int = 0,
                pan: int = 3, grain=0.0) -> list[Frame]:
    """`n` 4:2:0 frames of a `w`x`h` window panning `pan` px/frame
    diagonally over gradient + texture + static grain. A function of
    its arguments alone; without `grain` the planes are views into one
    scene.

    `grain` is the sigma (8-bit code values) of white noise drawn anew
    for every frame: `rng.normal(0, sigma)` on luma and `sigma / 2` on
    each chroma plane, from the clip's one generator after the scene's
    draw, in frame order, rounded and clipped to 0-255. A sequence
    gives frame i its own sigma (a clip whose GOPs fall on both sides
    of the sparse budgets); 0 draws nothing. The benchmark's
    `generators/grain.py` is the same function of (n, w, h, seed, pan,
    sigma), held to it by tests/test_grain.py."""
    sigmas = np.broadcast_to(np.asarray(grain, np.float64), (n,))
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
        y = scene[dy:dy + h, dx:dx + w]
        u = scene_u[dy // 2:dy // 2 + h // 2, dx // 2:dx // 2 + w // 2]
        v = scene_v[dy // 2:dy // 2 + h // 2, dx // 2:dx // 2 + w // 2]
        sigma = float(sigmas[i])
        if sigma:
            y = _grainy(y, rng, sigma)
            u = _grainy(u, rng, sigma / 2)
            v = _grainy(v, rng, sigma / 2)
        frames.append(Frame(y=y, u=u, v=v))
    return frames
