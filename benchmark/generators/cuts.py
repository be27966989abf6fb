"""Edited footage: a few shots with hard cuts between them, each shot a
scene of its own panned `pan` px a frame.

Stands for films and episodes (the Xiph/derf full-length open films at
1080p: Tears of Steel, Sintel, Big Buck Bunny), whose picture changes
whole every second or three. A shot is a scene of `pan.py`'s kind —
gradient + texture + static grain sigma 6 — built anew: a triangle
gradient with its own slopes, phase and contrast, a plane wave with
its own wave vector and amplitude, its own mean level (dark and
bright shots take turns, so a cut always moves it), chroma levels and
pan direction. Its own STRUCTURE, because two of pan.py's scenes
differ in their noise texture alone, which no detector should call a
cut and no viewer would. The structure of shot k is a function of k
alone (`default_rng(k)`), as pan.py's is of nothing; the seed draws
the grain (`default_rng([seed, k])`), so the bits of an encode move
with the seed as little as the pan's do (a first form drew the
structure from the seed too, and six seeds spread 3.4 % in kbit per
frame on the chip, PERF.md §6, PR 32). (A triangle and a plane wave where pan.py has a sawtooth and a
product of sines: a sawtooth's jump is a hard edge that sweeps a small
picture, and a product has nodal lines along which the texture
vanishes; both fool a detector on the 128-pixel rehearsal clip, not at
1080p.)

`shots` are the shot lengths of a 256-frame clip, or of whatever they
sum to; for another `n` the shot ends scale by `n / sum(shots)`, rounded
half up, so a 16-frame rehearsal clip keeps its three cuts. They are
NOT drawn from the seed: the GOP count of the encode, and with it the
cell's frames per second, must not move with it. `cut_frames` gives
the first frame of every shot but the first. A shorter clip is not a
prefix of a longer one.
The harness's own copy of `thinvids_tpu/tools/pan.py::make_frames(...,
cuts=shots)`; `tests/test_scenecut.py` holds the two to the same bytes.
"""

import numpy as np

SHOTS = (72, 40, 88, 56)


def shot_ends(n, shots=SHOTS):
    """End frame of each shot in an `n`-frame clip."""
    total, ends, run = sum(shots), [], 0
    for length in shots:
        run += length
        ends.append((run * n + total // 2) // total)
    return ends


def cut_frames(n, shots=SHOTS):
    return [e for e in dict.fromkeys(shot_ends(n, shots)) if 0 < e < n]


def _shot(rng, k, frames, width, height, pan):
    """Iterator over the (y, u, v) of shot `k`, its grain from `rng`."""
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
    yy, xx = np.mgrid[0:height + pad, 0:width + pad]
    ramp = np.abs((xx * gx + yy * gy + phase) % 512 - 256.0)
    scene = level + (ramp - 128.0) * contrast \
        + amp * np.sin(xx * fx + yy * fy + phase) \
        + rng.normal(0, 6.0, (height + pad, width + pad))
    scene = np.clip(scene, 0, 255).astype(np.uint8)
    scene_u = np.clip(cu + 30 * np.sin(xx[::2, ::2] * 0.01 + phase),
                      0, 255).astype(np.uint8)
    scene_v = np.clip(cv + 30 * np.cos(yy[::2, ::2] * 0.01 + phase),
                      0, 255).astype(np.uint8)
    h2, w2 = height // 2, width // 2
    for i in range(frames):
        dx = pan * i if sx > 0 else pan * (frames - i)
        dy = pan * i if sy > 0 else pan * (frames - i)
        yield (scene[dy:dy + height, dx:dx + width],
               scene_u[dy // 2:dy // 2 + h2, dx // 2:dx // 2 + w2],
               scene_v[dy // 2:dy // 2 + h2, dx // 2:dx // 2 + w2])


def planes(n, width, height, seed, pan=3, shots=SHOTS):
    start = 0
    for k, end in enumerate(shot_ends(n, shots)):
        if end > start:
            yield from _shot(np.random.default_rng([seed, k]), k,
                             end - start, width, height, pan)
        start = max(start, end)
