"""The pan generator (tools/pan.py): what every motion-search test and
the chip smoke take for granted about their content."""

import numpy as np
import pytest

from thinvids_tpu.tools.pan import make_frames


def test_frames_are_a_function_of_the_arguments():
    a, b = make_frames(4, 70, 50, seed=5), make_frames(4, 70, 50, seed=5)
    for fa, fb in zip(a, b):
        for plane in "yuv":
            pa, pb = getattr(fa, plane), getattr(fb, plane)
            assert pa.dtype == np.uint8 and np.array_equal(pa, pb)
    assert not np.array_equal(a[0].y, make_frames(4, 70, 50, seed=6)[0].y)
    # odd half sizes too: chroma is floor(size / 2)
    assert a[0].y.shape == (50, 70)
    assert a[0].u.shape == a[0].v.shape == (25, 35)


@pytest.mark.parametrize("w,h,pan", [(64, 48, 3), (70, 50, 2),
                                     (192, 160, 5)])
def test_each_frame_is_the_last_one_moved_by_pan(w, h, pan):
    """Frame i + 1 is frame i moved `pan` pixels up and left in luma —
    the true vector the ME tests expect the search to find — and by
    (pan * (i + 1)) // 2 - (pan * i) // 2 in chroma."""
    frames = make_frames(5, w, h, pan=pan)
    for i, (a, b) in enumerate(zip(frames, frames[1:])):
        assert np.array_equal(b.y[:-pan, :-pan], a.y[pan:, pan:])
        assert not np.array_equal(b.y, a.y)
        s = (pan * (i + 1)) // 2 - (pan * i) // 2
        for pa, pb in ((a.u, b.u), (a.v, b.v)):
            assert np.array_equal(pb[:h // 2 - s, :w // 2 - s],
                                  pa[s:, s:])


# ---------------------------------------------------------------------------
# the hand-held generator (tools/handheld.py): motion that is no whole
# number of pixels — the quarter-sample tests' and the benchmark's
# ---------------------------------------------------------------------------

def _harness_handheld():
    import importlib.util
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_gen_handheld",
        os.path.join(root, "benchmark", "generators", "handheld.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("n,w,h,seed,path", [
    (6, 128, 128, 2**31 + 5, {}),
    (5, 70, 50, 7, {}),
    (4, 96, 64, 3, {"vx": -1.3, "vy": 0.4, "ax": 0.0, "Ty": 7}),
    (3, 64, 48, 11, {"vx": 6.7, "vy": 0.3, "Tx": 19.0}),
])
def test_handheld_harness_copy_is_the_same_generator(n, w, h, seed, path):
    from thinvids_tpu.tools import handheld

    ours = handheld.make_frames(n, w, h, seed=seed, **path)
    theirs = list(_harness_handheld().planes(n, w, h, seed, **path))
    assert len(ours) == len(theirs) == n
    for f, (y, u, v) in zip(ours, theirs):
        assert f.y.dtype == np.uint8 and f.y.shape == (h, w)
        assert f.u.shape == f.v.shape == (h // 2, w // 2)
        assert np.array_equal(f.y, y) and np.array_equal(f.u, u) \
            and np.array_equal(f.v, v)


def test_handheld_first_frames_are_a_prefix_and_the_seed_draws_grain():
    from thinvids_tpu.tools import handheld

    long = handheld.make_frames(7, 96, 64, seed=9)
    short = handheld.make_frames(3, 96, 64, seed=9)
    for a, b in zip(short, long):
        assert np.array_equal(a.y, b.y) and np.array_equal(a.u, b.u) \
            and np.array_equal(a.v, b.v)
    other = handheld.make_frames(1, 96, 64, seed=10)[0]
    assert not np.array_equal(other.y, long[0].y)
    # the structure is not the seed's: chroma carries no grain
    assert np.array_equal(other.u, long[0].u)


def test_handheld_moves_by_no_whole_or_half_number_of_pixels():
    """Frame-to-frame displacement of the benchmark's path over its 256
    frames: inside the search range, never on the half-pixel grid (nor
    the quarter-pixel one) in either component."""
    from thinvids_tpu.tools import handheld

    x, y = handheld.position(np.arange(257), **handheld.PATH)
    d = np.stack([np.diff(x), np.diff(y)], axis=1)
    assert np.abs(d[:, 0]).max() <= 2.77 and np.abs(d[:, 1]).max() <= 1.13
    assert np.abs(d[:, 0]).min() > 1.8 and np.abs(d[:, 1]).min() > 0.6
    for grid in (2, 4):
        off = np.abs(d * grid - np.rint(d * grid)) / grid
        assert off.min() > 1e-3


def test_handheld_translates_the_canvas_exactly():
    """A path of whole pixels is a plain roll of the periodic canvas:
    the resampler adds nothing of its own."""
    from thinvids_tpu.tools import handheld

    path = {"vx": 2.0, "vy": -1.0, "ax": 0.0, "ay": 0.0}
    frames = handheld.make_frames(4, 64, 48, seed=2, **path)
    for t, f in enumerate(frames):
        assert np.array_equal(f.y, np.roll(frames[0].y, (t, -2 * t), (0, 1)))
    # and a half-pixel step of a smooth plane lands between its samples
    half = handheld.make_frames(2, 64, 48, seed=2, vx=0.5, vy=0.0,
                                ax=0.0, ay=0.0)
    u0, u1 = half[0].u.astype(int), half[1].u.astype(int)
    assert np.abs(u1 - (3 * u0 + np.roll(u0, -1, 1)) / 4).max() <= 1
