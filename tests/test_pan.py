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
