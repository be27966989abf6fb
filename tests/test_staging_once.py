"""GOP-wave staging writes each decoded frame once (ISSUE 43).

`stage_waves` / `stage_luma_waves` fill a wave's host arrays in place —
pad rows and columns by edge replication, tail repeats and pad GOPs
from the slot they repeat — and upload those arrays. Every case here
holds them, byte for byte, to the construction they replaced: pad each
frame (`Frame.padded(16)`: np.pad, mode "edge"), stack a GOP's frames
with its last repeated to F, stack the wave's GOPs with the last
repeated to the device count. The host arrays are reused once their
upload has completed (`dispatch._WAVE_ARRAYS`): a wave written over
whatever an earlier one left must equal a wave written into new
memory, and a staged wave must never change afterwards."""

import numpy as np
import pytest

import jax

from thinvids_tpu.core.types import Frame, GopSpec, SegmentPlan, VideoMeta
from thinvids_tpu.ingest.decode import open_video
from thinvids_tpu.io.y4m import write_y4m
from thinvids_tpu.parallel import dispatch
from thinvids_tpu.parallel.dispatch import GopShardEncoder, default_mesh

#: (width, height): rows alone pad (1080 -> 1088), rows AND columns
#: pad (100x52 -> 112x64), neither pads
SIZES = {"rows": (64, 1080), "rows_and_columns": (100, 52), "none": (64, 48)}


def make_frames(n, w, h, seed=0):
    rng = np.random.default_rng(seed)
    return [Frame(y=rng.integers(0, 256, (h, w), dtype=np.uint8),
                  u=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8),
                  v=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8))
            for _ in range(n)]


def encoder(w, h, n, case):
    """(encoder, frame count) of a plan case."""
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1, num_frames=n)
    one = default_mesh(jax.devices()[:1])
    if case == "balanced_repeat":       # 7 frames as 4 + 3: F 4, one repeat
        return GopShardEncoder(meta, qp=30, mesh=one, gop_frames=4)
    if case == "cut_aligned":           # shots [0, 5) and [5, 11): real < F
        enc = GopShardEncoder(meta, qp=30, mesh=one, gop_frames=4)
        enc.scene_cuts = (5,)
        return enc
    if case == "two_gops_a_device":     # waves of two GOPs on one device
        return GopShardEncoder(meta, qp=30, mesh=one, gop_frames=3,
                               gops_per_wave=2)
    assert case == "pad_gop"            # 3 GOPs (3, 2, 3) on 4 devices
    enc = GopShardEncoder(meta, qp=30, mesh=default_mesh(jax.devices()[:4]),
                          gop_frames=3)
    enc.plan_override = SegmentPlan(
        gops=(GopSpec(0, 0, 3), GopSpec(1, 3, 2), GopSpec(2, 5, 3)),
        num_devices=4, frames_per_gop=3)
    return enc


FRAMES_OF = {"balanced_repeat": 7, "cut_aligned": 11, "two_gops_a_device": 12,
             "pad_gop": 8}


def reference_wave(frames, wave, G, F, plane):
    """Pad, stack, stack: the three-copy construction."""
    gops = []
    for gop in wave:
        arrs = [getattr(frames[i].padded(16), plane)
                for i in range(gop.start_frame, gop.end_frame)]
        gops.append(np.stack(arrs + [arrs[-1]] * (F - len(arrs))))
    return np.stack(gops + [gops[-1]] * (G - len(gops)))


def reference_F(enc, n):
    plan = enc.plan(n)
    F = max(g.num_frames for g in plan.gops)
    return max(F, plan.frames_per_gop) if plan.pin_frames else F


def assert_waves_equal(enc, staged_waves, frames, planes="yuv"):
    F = reference_F(enc, len(frames))
    for wave, *arrays in staged_waves:
        G = arrays[0].shape[0]
        assert G % enc.num_devices == 0 and G >= len(wave)
        for arr, plane in zip(arrays, planes):
            want = reference_wave(frames, wave, G, F, plane)
            assert arr.dtype == np.uint8 and arr.shape == want.shape
            assert np.array_equal(np.asarray(arr), want), plane


@pytest.mark.parametrize("source", ["list", "stream"])
@pytest.mark.parametrize("case", list(FRAMES_OF))
@pytest.mark.parametrize("size", list(SIZES))
def test_staged_waves_equal_pad_stack_stack(tmp_path, size, case, source):
    w, h = SIZES[size]
    n = FRAMES_OF[case]
    frames = make_frames(n, w, h, seed=3)
    enc = encoder(w, h, n, case)
    given = frames
    if source == "stream":
        write_y4m(str(tmp_path / "clip.y4m"), enc.meta, frames)
        given = open_video(str(tmp_path / "clip.y4m"))
    staged = list(enc.stage_waves(given))
    assert [g for wave, *_ in staged for g in wave] == list(enc.plan(n).gops)
    assert_waves_equal(enc, [s[:4] for s in staged], frames)
    for wave, _ys, _us, _vs, qps, *real in staged:
        assert qps.dtype == np.int32 and len(qps) % enc.num_devices == 0
        assert (len(real) == 1) == (case == "cut_aligned")
        if real:        # the GOPs' real lengths ride last, as before
            assert list(np.asarray(real[0])) == [g.num_frames for g in wave]
            assert max(np.asarray(real[0])) < _ys.shape[1]
    # one decoded frame at a time, whatever the wave's length
    assert enc.staging_stats["peak_resident_frames"] == 1


@pytest.mark.parametrize("case", list(FRAMES_OF))
@pytest.mark.parametrize("size", list(SIZES))
def test_luma_waves_equal_pad_stack_stack(size, case):
    w, h = SIZES[size]
    n = FRAMES_OF[case]
    frames = make_frames(n, w, h, seed=4)
    enc = encoder(w, h, n, case)
    staged = list(enc.stage_luma_waves(frames))
    assert all(len(s) == 2 for s in staged)
    assert_waves_equal(enc, staged, frames, planes="y")
    snap = enc.stages.snapshot()
    assert snap["stage_copy_bytes"] == snap["h2d_bytes"] \
        == sum(ys.nbytes for _, ys in staged)


@pytest.mark.parametrize("size", list(SIZES))
def test_a_staged_wave_outlives_later_waves(size):
    """The buffer-lifetime rule, on the backend whose device array may
    BE the host array: a wave still equals its frames after three later
    waves were staged (and its frames' memory written over)."""
    w, h = SIZES[size]
    frames = make_frames(16, w, h, seed=5)
    kept = [Frame(f.y.copy(), f.u.copy(), f.v.copy()) for f in frames]
    meta = VideoMeta(width=w, height=h, num_frames=16)
    enc = GopShardEncoder(meta, qp=30, mesh=default_mesh(jax.devices()[:1]),
                          gop_frames=4)
    waves = enc.stage_waves(frames)
    first = next(waves)
    later = [next(waves) for _ in range(3)]
    for f in frames:                    # the decoder reuses its memory
        f.y[:] = 0
        f.u[:] = 0
        f.v[:] = 0
    assert_waves_equal(enc, [first[:4]] + [s[:4] for s in later], kept)


@pytest.mark.parametrize("size", list(SIZES))
def test_copy_bytes_are_the_uploaded_planes_and_upload_is_part_of_stage(size):
    w, h = SIZES[size]
    enc = encoder(w, h, 7, "balanced_repeat")
    staged = list(enc.stage_waves(make_frames(7, w, h, seed=6)))
    snap = enc.stages.snapshot()
    planes = sum(a.nbytes for _, ys, us, vs, *_ in staged
                 for a in (ys, us, vs))
    small = sum(a.nbytes for _, _ys, _us, _vs, *rest in staged for a in rest)
    assert snap["stage_copy_bytes"] == planes
    assert snap["h2d_bytes"] == planes + small
    assert 0 < snap["upload"] <= snap["stage"]
    assert snap["decode"] > 0


@pytest.mark.parametrize("stage", ["stage_waves", "stage_luma_waves"])
def test_frame_of_another_size_is_refused(stage):
    frames = make_frames(3, 64, 48) + make_frames(1, 64, 80)
    enc = GopShardEncoder(VideoMeta(width=64, height=48, num_frames=4),
                          qp=30, mesh=default_mesh(jax.devices()[:1]),
                          gop_frames=4)
    with pytest.raises(ValueError, match="pads to 64x80"):
        list(getattr(enc, stage)(frames))
    assert enc.stages.snapshot()["h2d_bytes"] == 0


@pytest.mark.parametrize("at", [0, 2])
def test_non_420_raises_before_anything_is_uploaded(at):
    frames = make_frames(4, 64, 48)
    rng = np.random.default_rng(7)
    frames[at] = Frame(y=frames[at].y,
                       u=rng.integers(0, 256, (48, 64), dtype=np.uint8),
                       v=rng.integers(0, 256, (48, 64), dtype=np.uint8))
    enc = GopShardEncoder(VideoMeta(width=64, height=48, num_frames=4),
                          qp=30, mesh=default_mesh(jax.devices()[:1]),
                          gop_frames=4)
    with pytest.raises(ValueError, match="only 4:2:0"):
        list(enc.stage_waves(frames))
    snap = enc.stages.snapshot()
    assert snap["h2d_bytes"] == 0 and snap["upload"] == 0


def test_a_short_source_is_refused():
    enc = GopShardEncoder(VideoMeta(width=64, height=48, num_frames=8),
                          qp=30, mesh=default_mesh(jax.devices()[:1]),
                          gop_frames=4)

    class Short:
        def __len__(self):
            return 8

        def iter_frames(self):
            return iter(make_frames(6, 64, 48))

    with pytest.raises(ValueError, match="ended at 6"):
        list(enc.stage_waves(Short()))


@pytest.fixture
def empty_store(monkeypatch):
    """This test's own store of free wave arrays."""
    store = dispatch._WaveArrays()
    monkeypatch.setattr(dispatch, "_WAVE_ARRAYS", store)
    return store


def wave_shapes(G, F, w, h, planes="yuv"):
    ph, pw = -(-h // 16) * 16, -(-w // 16) * 16
    return [(G, F, ph, pw) if p == "y" else (G, F, ph // 2, pw // 2)
            for p in planes]


@pytest.mark.parametrize("planes", ["yuv", "y"])
@pytest.mark.parametrize("size", list(SIZES))
def test_a_wave_written_over_an_earlier_waves_arrays(empty_store, size,
                                                     planes):
    """Every byte of a reused array is written again: pad rows, pad
    columns, tail repeats and all."""
    w, h = SIZES[size]
    frames = make_frames(7, w, h, seed=8)
    enc = encoder(w, h, 7, "balanced_repeat")       # waves (4), (3 + 1)
    dirty = [np.full(shape, 0xA5, np.uint8)
             for shape in wave_shapes(1, 4, w, h, planes)]
    empty_store.give(dirty)
    stage = enc.stage_waves if planes == "yuv" else enc.stage_luma_waves
    staged = [s[:1 + len(planes)] for s in stage(frames)]
    assert_waves_equal(enc, staged, frames, planes)
    # the first wave went into the arrays the store held
    assert not any((a == 0xA5).all() for a in dirty)


class TestWaveArrays:
    def test_gives_back_what_it_was_given(self, empty_store):
        shapes = wave_shapes(1, 4, 64, 48)
        got = empty_store.take(shapes)
        assert [a.shape for a in got] == shapes
        assert all(a.dtype == np.uint8 for a in got)
        empty_store.give(got)
        again = empty_store.take(shapes)
        assert all(a is b for a, b in zip(got, again))
        assert all(a is not b                       # handed out once
                   for a, b in zip(again, empty_store.take(shapes)))

    def test_other_shapes_get_new_arrays_and_take_the_store_over(
            self, empty_store):
        hd, uhd = wave_shapes(1, 4, 64, 48), wave_shapes(1, 4, 128, 96)
        kept = empty_store.take(hd)
        empty_store.give(kept)
        other = empty_store.take(uhd)               # hd's set stays
        assert [a.shape for a in other] == uhd
        assert empty_store.take(hd)[0] is kept[0]
        empty_store.give(kept)
        empty_store.give(other)                     # the resolution changed
        assert empty_store.take(hd)[0] is not kept[0]
        assert empty_store.take(uhd)[0] is other[0]

    def test_keeps_a_bounded_number_of_sets(self, empty_store):
        KEEP = dispatch._WaveArrays.KEEP
        shapes = wave_shapes(1, 4, 64, 48)
        sets = [empty_store.take(shapes) for _ in range(KEEP + 2)]
        for arrays in sets:
            empty_store.give(arrays)
        back = [empty_store.take(shapes) for _ in range(KEEP + 2)]
        held = {id(a[0]) for a in sets}
        assert sum(id(a[0]) in held for a in back) == KEEP


    def test_no_set_is_handed_to_two_threads_at_once(self, empty_store):
        """More staging threads than cores take, fill, check and give
        back: a set two of them held at once would show the other's
        bytes."""
        import sys
        import threading
        import time

        shapes = wave_shapes(1, 2, 64, 48)
        wrong = []
        deadline = time.monotonic() + 2.0

        def stage(tag):
            rounds = 0
            while time.monotonic() < deadline and rounds < 400:
                arrays = empty_store.take(shapes)
                for a in arrays:
                    a[:] = tag
                if any((a != tag).any() for a in arrays):
                    wrong.append(tag)
                empty_store.give(arrays)
                rounds += 1

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=stage, args=(tag,))
                       for tag in range(1, 25)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not wrong


def test_jobs_of_one_process_share_the_arrays(empty_store):
    """The second job's first wave is written into the first job's
    arrays (a short job has one wave: nothing else could warm it)."""
    w, h = SIZES["rows_and_columns"]
    outs = []
    for seed in (11, 12):
        frames = make_frames(4, w, h, seed=seed)
        enc = GopShardEncoder(VideoMeta(width=w, height=h, num_frames=4),
                              qp=30, mesh=default_mesh(jax.devices()[:1]),
                              gop_frames=4)
        staged = [s[:4] for s in enc.stage_waves(frames)]
        assert_waves_equal(enc, staged, frames)
        outs.append((enc, staged, frames))
    for enc, staged, frames in outs:                # and the first still holds
        assert_waves_equal(enc, staged, frames)
