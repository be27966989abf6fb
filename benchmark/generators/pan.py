"""Seeded diagonal camera pan over a fixed detailed scene.

Copied from `bench.make_frames` (the repo's one sound content
generator; the original is listed in PERF.md for a later PR to
delete): gradient + texture + static grain, `pan` px/frame diagonal.
Motion-predictable like real footage, unlike per-frame iid noise,
which no encoder can inter-predict.

A generator is a module with one function:

    planes(n, width, height, seed, **params) -> iterator of (y, u, v)

uint8 4:2:0 planes, `n` of them, the same for the same arguments. The
first k frames do not depend on anything but the arguments, so a
shorter clip cut from the same call is a prefix of the longer one.
"""

import numpy as np


def planes(n, width, height, seed, pan=3):
    rng = np.random.default_rng(seed)
    pad = pan * n + 2
    yy, xx = np.mgrid[0:height + pad, 0:width + pad]
    scene = (xx * 0.1 + yy * 0.05) % 256 \
        + 24.0 * np.sin(xx * 0.07) * np.cos(yy * 0.05) \
        + rng.normal(0, 6.0, (height + pad, width + pad))
    scene = np.clip(scene, 0, 255).astype(np.uint8)
    scene_u = np.clip(128 + 30 * np.sin(xx[::2, ::2] * 0.01),
                      0, 255).astype(np.uint8)
    scene_v = np.clip(128 + 30 * np.cos(yy[::2, ::2] * 0.01),
                      0, 255).astype(np.uint8)
    h2, w2 = height // 2, width // 2
    for i in range(n):
        d = pan * i
        yield (scene[d:d + height, d:d + width],
               scene_u[d // 2:d // 2 + h2, d // 2:d // 2 + w2],
               scene_v[d // 2:d // 2 + h2, d // 2:d // 2 + w2])
