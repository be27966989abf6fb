"""Seeded diagonal camera pan over a fixed detailed scene.

The content the encoder tests and `chip_smoke.py` share: frame i + 1 is
frame i moved `pan` pixels up and left (the camera goes down and right),
so motion search has a true vector to find — unlike per-frame iid
noise, which no codec can inter-predict. `grain` adds exactly that on
top: film grain that is new on every frame, the content that leaves
the sparse transfer budgets (parallel/dispatch.start_fetch). `cuts`
makes edited footage of it: a few shots with hard cuts between them,
each a scene of its own (the `scenecut` setting's content). jax-free.
"""

from __future__ import annotations

import numpy as np

from ..core.types import Frame


def _grainy(plane: np.ndarray, rng, sigma: float) -> np.ndarray:
    noisy = np.rint(plane + rng.normal(0.0, sigma, plane.shape))
    return np.clip(noisy, 0, 255).astype(np.uint8)


def shot_ends(n: int, shots) -> list[int]:
    """End frame of each shot of an `n`-frame clip whose shot lengths
    are in the proportions `shots`, rounded half up."""
    total, ends, run = sum(shots), [], 0
    for length in shots:
        run += length
        ends.append((run * n + total // 2) // total)
    return ends


def cut_frames(n: int, shots) -> list[int]:
    """First frame of every shot but the first."""
    return [e for e in dict.fromkeys(shot_ends(n, shots)) if 0 < e < n]


def _shot(rng, k: int, frames: int, w: int, h: int, pan: int):
    """(y, u, v) planes of shot `k`: a scene of pan.py's kind built
    anew — triangle gradient + plane-wave texture + static grain — with
    its own slopes, phase, contrast, level (dark and bright shots take
    turns, so a cut always moves it), wave, chroma levels and pan
    direction: its own STRUCTURE, where two seeds of the one-shot
    scene differ in their noise texture alone. The structure is a
    function of `k` alone; `rng` (the seed's) draws the grain."""
    look = np.random.default_rng(k)     # the structure: of k alone
    gx = look.uniform(0.06, 0.16) * look.choice((-1, 1))
    gy = look.uniform(0.03, 0.10) * look.choice((-1, 1))
    fx = look.uniform(0.03, 0.11) * look.choice((-1, 1))
    fy = look.uniform(0.03, 0.08) * look.choice((-1, 1))
    phase, contrast = look.uniform(0, 512), look.uniform(0.25, 0.5)
    amp = look.uniform(12, 28)
    level = 128 + (-1) ** k * look.uniform(24, 44)
    cu, cv = look.uniform(108, 148), look.uniform(108, 148)
    sx, sy = look.choice((-1, 1)), look.choice((-1, 1))
    pad = pan * frames + 2
    yy, xx = np.mgrid[0:h + pad, 0:w + pad]
    ramp = np.abs((xx * gx + yy * gy + phase) % 512 - 256.0)
    scene = level + (ramp - 128.0) * contrast \
        + amp * np.sin(xx * fx + yy * fy + phase) \
        + rng.normal(0, 6.0, (h + pad, w + pad))
    scene = np.clip(scene, 0, 255).astype(np.uint8)
    scene_u = np.clip(cu + 30 * np.sin(xx[::2, ::2] * 0.01 + phase),
                      0, 255).astype(np.uint8)
    scene_v = np.clip(cv + 30 * np.cos(yy[::2, ::2] * 0.01 + phase),
                      0, 255).astype(np.uint8)
    for i in range(frames):
        dx = pan * i if sx > 0 else pan * (frames - i)
        dy = pan * i if sy > 0 else pan * (frames - i)
        yield (scene[dy:dy + h, dx:dx + w],
               scene_u[dy // 2:dy // 2 + h // 2, dx // 2:dx // 2 + w // 2],
               scene_v[dy // 2:dy // 2 + h // 2, dx // 2:dx // 2 + w // 2])


def _cut_frames(n: int, w: int, h: int, seed: int, pan: int, shots
                ) -> list[Frame]:
    frames, start = [], 0
    for k, end in enumerate(shot_ends(n, shots)):
        if end > start:
            rng = np.random.default_rng([seed, k])
            frames.extend(Frame(y=y, u=u, v=v) for y, u, v in
                          _shot(rng, k, end - start, w, h, pan))
        start = max(start, end)
    return frames


def make_frames(n: int, w: int, h: int, seed: int = 0,
                pan: int = 3, grain=0.0, cuts=None) -> list[Frame]:
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
    sigma), held to it by tests/test_grain.py.

    `cuts` (shot lengths, in the proportions of a clip they sum to:
    `(72, 40, 88, 56)` is the benchmark's) makes the clip a run of
    shots instead, shot k a scene of its own (`_shot`: its structure
    a function of k, its grain from `default_rng([seed, k])`), panned
    `pan` px a frame its own way; `cut_frames` says where they start.
    Neither the lengths nor the structures are drawn from the seed.
    The benchmark's `generators/cuts.py` is the same function, held
    to it by tests/test_scenecut.py. Not with `grain`."""
    if cuts is not None:
        if np.any(grain):
            raise ValueError("cuts and grain do not combine")
        return _cut_frames(n, w, h, seed, pan, tuple(cuts))
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
