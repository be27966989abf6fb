"""`pan.py`'s scene, pan and chroma planes with film grain that is new
on every frame.

Stands for film scans, high-ISO and water or crowd footage (the
Xiph/derf SVT 1080p set: crowd_run, park_joy, ducks_take_off,
old_town_cross): the picture moves with the camera and motion search
finds it, the grain does not and no search can predict it, so every P
frame carries a dense residual. Per frame one independent draw
`rng.normal(0, sigma)` on luma and `sigma / 2` on each chroma plane,
added to the panned planes and rounded before the clip to 0-255. The
draws come from the clip's one seeded generator after the scene's, in
frame order (y, u, v). The scene's size depends on `n`, as in pan.py,
so the harness cuts its shorter clips from the long one
(`sources.cut_prefix`). `sigma` 0 gives pan.py's planes to the byte.
The harness's own copy of `thinvids_tpu/tools/pan.py::make_frames(...,
grain=sigma)`; `tests/test_grain.py` holds the two to the same bytes.
"""

import numpy as np


def _grainy(plane, rng, sigma):
    noisy = np.rint(plane + rng.normal(0.0, sigma, plane.shape))
    return np.clip(noisy, 0, 255).astype(np.uint8)


def planes(n, width, height, seed, pan=3, sigma=5.0):
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
        y = scene[d:d + height, d:d + width]
        u = scene_u[d // 2:d // 2 + h2, d // 2:d // 2 + w2]
        v = scene_v[d // 2:d // 2 + h2, d // 2:d // 2 + w2]
        if sigma:
            y = _grainy(y, rng, sigma)
            u = _grainy(u, rng, sigma / 2)
            v = _grainy(v, rng, sigma / 2)
        yield y, u, v
