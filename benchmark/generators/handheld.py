"""Seeded hand-held camera over a fixed detailed scene: motion that is
no whole number of pixels.

`pan.py`'s scene — sawtooth gradient + product-of-sines texture +
static grain of sigma 6, and its two chroma planes — laid on a PERIODIC
canvas the size of the picture (every slope and frequency is rounded to
a whole number of periods, so the canvas has no seam), viewed by a
camera at the real-valued position, in pixels,

    p(t) = (vx * t + ax * sin(2 pi t / Tx), vy * t + ay * sin(2 pi t / Ty))

x first: a drift with a slow sway on top, what a hand-held or a
drifting shot does. Frame t is the canvas translated by exactly p(t) —
a Fourier shift, the band-limited resampler that is exact for a
periodic signal — with chroma at half the displacement, rounded and
clipped to uint8. With the defaults a frame moves against the last by
|dx| <= 2.77, |dy| <= 1.13 pixels, on no half- or quarter-pixel grid,
so neither vector precision is favoured by construction.

The path is a function of t alone and the grain of the seed (a scene
whose STRUCTURE moved with the seed spread an encode's bits over its
bound, PERF.md §6, PR 32). Frame t depends on (t, width, height, seed,
path) and on nothing else: the first k frames of a longer call are the
shorter call's.
The harness's own copy of `thinvids_tpu/tools/handheld.py`;
`tests/test_pan.py` holds the two to the same bytes.
"""

import numpy as np

PATH = {"vx": 2.3, "vy": 0.9, "ax": 1.7, "Tx": 23.0, "ay": 1.1, "Ty": 31.0}


def position(t, vx=2.3, vy=0.9, ax=1.7, Tx=23.0, ay=1.1, Ty=31.0):
    """The camera's (x, y) at frame `t`, in luma pixels."""
    return (vx * t + ax * np.sin(2 * np.pi * t / Tx),
            vy * t + ay * np.sin(2 * np.pi * t / Ty))


def _whole(size, per_sample):
    """`per_sample` rounded so that `size` samples hold a whole number
    (at least one) of periods of 2 pi."""
    return 2 * np.pi * max(1, round(size * per_sample / (2 * np.pi))) / size


def _canvases(width, height, seed):
    """The periodic (luma, u, v) canvases, uint8, as `pan.py` builds
    its scene."""
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


class _Shifter:
    """A periodic plane and its translations by real-valued offsets."""

    def __init__(self, plane):
        self.shape = plane.shape
        self.spectrum = np.fft.rfft2(plane.astype(np.float64))
        self.ky = 2j * np.pi * np.fft.fftfreq(plane.shape[0])[:, None]
        self.kx = 2j * np.pi * np.fft.rfftfreq(plane.shape[1])[None, :]

    def at(self, dx, dy):
        """out[y, x] = plane(y + dy, x + dx), rounded, as uint8."""
        moved = np.fft.irfft2(
            self.spectrum * np.exp(self.ky * dy) * np.exp(self.kx * dx),
            s=self.shape)
        return np.clip(np.rint(moved), 0, 255).astype(np.uint8)


def planes(n, width, height, seed, **path):
    y, u, v = (_Shifter(c)
               for c in _canvases(width, height, seed))
    for t in range(n):
        px, py = position(t, **path)
        yield y.at(px, py), u.at(px / 2, py / 2), v.at(px / 2, py / 2)
