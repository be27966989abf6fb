"""Scene cuts start closed GOPs (ISSUE 32, the `scenecut` setting).

`parallel/scenecut.py` costs every frame of the source (inter against
intra, on 32x32 luma block sums), `planner.take_cuts` decides which
frames are cuts (x264's rule and ramp), `plan_segments` plans each shot
as a clip of its own, and the executor does all of it in its `segment`
stage. Held here, at small sizes on the CPU:

- the served detector and planner equal the plain rule
  (`tools/scenecut_plain.py`) cut for cut and GOP start for GOP start
  on seeded content, find every generated cut, and find none in a pan
  or in grain that is new on every frame (the calibration prints its
  smallest margins);
- what the planner promises of a plan made on cuts;
- through the coordinator on the XLA mirror: the MP4's `stss` is the
  plan, two decoders agree on it, a clip without cuts keeps its bytes,
  one program shape whatever the shots, and band and live jobs keep
  their fixed grid;
- the same at the serving point (ISSUE 39, `serving-1080p-edited`: the
  cuts with mode decision, P_Skip, the in-loop filter and AQ inside
  the bounded P-frame loop): the cases of `TestThroughTheCoordinator`
  that take an operating point run at the library's and the serving
  one, and there the decode also equals the encoder's own
  reconstruction. Every comparison is for equality: the encoder is
  integer-exact, so each tolerance is zero;
- the benchmark's own copy of the generator gives the same planes.
"""

import contextlib
import functools
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.codecs.h264 import jaxinter
from thinvids_tpu.codecs.h264.layout import unflatten_gop
from thinvids_tpu.codecs.h264.rdo import (RD_OFF, RdConfig,
                                          rd_from_settings)
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.core.config import (DEFAULT_SETTINGS, JOB_SETTING_KEYS,
                                      Settings, overlay_job_settings,
                                      reset_live_settings,
                                      update_live_settings)
from thinvids_tpu.core.status import Status
from thinvids_tpu.core.types import GopSpec, SegmentPlan, VideoMeta
from thinvids_tpu.io.mp4 import read_mp4
from thinvids_tpu.io.y4m import write_y4m
from thinvids_tpu.parallel import dispatch, scenecut
from thinvids_tpu.parallel.planner import (min_gop_frames, plan_encode,
                                           plan_fixed_segments,
                                           plan_segments, suffix_cuts,
                                           take_cuts)
from thinvids_tpu.tools import scenecut_plain
from thinvids_tpu.tools.pan import cut_frames, make_frames

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHOTS = (72, 40, 88, 56)            # the benchmark's, of a 256-frame clip
SEEDS = list(range(1, 11))
#: detector content: 60 blocks a frame
DW, DH, DN, DGOP = 320, 192, 32, 8
#: end-to-end content: 20 blocks a frame, one program shape (1, 8, ...)
W, H, N, GOP = 160, 128, 40, 8


def make_settings(**over):
    return Settings(values=dict(DEFAULT_SETTINGS, heartbeat_throttle_s=0.0,
                                **over))


def _lumas(frames):
    return [f.y for f in frames]


def _ratios(frames):
    """inter / intra of every frame but the first."""
    inter, intra = scenecut.frame_costs(_lumas(frames))
    return [p / i for p, i in zip(inter[1:], intra[1:])]


# ---------------------------------------------------------------------------
# the detector against the plain rule, and its calibration
# ---------------------------------------------------------------------------

class TestDetectorEqualsThePlainRule:
    @pytest.mark.parametrize("seed", SEEDS)
    def test_cuts_content_cut_for_cut(self, seed):
        frames = make_frames(DN, DW, DH, seed=seed, cuts=SHOTS)
        served = scenecut.detect(frames, DGOP, 40)
        plain = scenecut_plain.scene_cuts(_lumas(frames), DGOP, 40)
        assert (list(served[0]), served[1]) == (plain[0], len(plain[1]))
        assert list(served[0]) == cut_frames(DN, SHOTS) == [9, 14, 25]

    @pytest.mark.parametrize("scenecut_", [1, 40, 100])
    @pytest.mark.parametrize("gop", [3, 8, 32])
    def test_close_cuts_ramp_and_suppression(self, gop, scenecut_):
        """Shots of 1 to 5 frames: cuts fall inside the shortest GOP
        and all along the ramp, and the two forms still agree on
        which are taken and how many are suppressed."""
        frames = make_frames(26, 128, 96, seed=gop,
                             cuts=(5, 1, 2, 6, 3, 1, 4, 4))
        served = scenecut.detect(frames, gop, scenecut_)
        plain = scenecut_plain.scene_cuts(_lumas(frames), gop, scenecut_)
        assert (list(served[0]), served[1]) == (plain[0], len(plain[1]))
        lo = min_gop_frames(gop)
        starts = [0, *served[0]]
        assert all(b - a >= lo for a, b in zip(starts, starts[1:]))
        if scenecut_ == 40 and gop == 32:
            assert served[1] >= 1       # 3-frame floor, 1-frame shots

    @pytest.mark.parametrize("w,h", [(96, 64), (100, 70), (31, 33)])
    def test_block_sums_with_ragged_edges(self, w, h):
        y = np.random.default_rng(w).integers(0, 256, (h, w), np.uint8)
        assert scenecut.block_sums(y).tolist() == \
            scenecut_plain.block_sums(y)
        prev = scenecut.block_sums(y[::-1].copy())
        inter, intra = scenecut.frame_costs([y[::-1].copy(), y])
        assert (inter[1], intra[1]) == scenecut_plain.costs(
            scenecut.block_sums(y).tolist(), prev.tolist())

    def test_equal_flat_frames_are_no_cuts(self):
        flat = [np.full((64, 64), 16, np.uint8)] * 6
        assert scenecut.detect([type("F", (), {"y": y}) for y in flat],
                               8, 40) == ((), 0)
        assert scenecut_plain.scene_cuts(flat, 8, 40) == ([], [])

    def test_luma_is_read_by_offset_from_a_y4m(self, tmp_path):
        from thinvids_tpu.ingest.decode import open_video

        frames = make_frames(6, 70, 50, seed=2)
        path = tmp_path / "a.y4m"
        write_y4m(path, VideoMeta(width=70, height=50, num_frames=6),
                  frames)
        with open_video(str(path)) as src:
            planes = list(scenecut.lumas(src))
            assert src.frames_decoded == 0
        assert all(np.array_equal(a, f.y) for a, f in zip(planes, frames))


class TestCalibration:
    """At scenecut 40 the threshold falls to inter >= 0.6 x intra a GOP
    after the last cut and stands at 0.9 x right after one."""

    @pytest.mark.parametrize("seed", SEEDS)
    @pytest.mark.parametrize("content", ["pan", "grain"])
    def test_no_cut_in_a_pan_or_in_grain(self, content, seed):
        frames = make_frames(DN, DW, DH, seed=seed, pan=3,
                             grain=5.0 if content == "grain" else 0.0)
        assert scenecut.detect(frames, DGOP, 40) == ((), 0)
        assert scenecut.detect(frames, 32, 40) == ((), 0)
        assert max(_ratios(frames)) < 0.3

    def test_the_smallest_margins(self):
        quiet, cut, within = 0.0, 1e9, 0.0
        for seed in SEEDS:
            for grain in (0.0, 5.0):
                quiet = max(quiet, *_ratios(make_frames(
                    DN, DW, DH, seed=seed, pan=3, grain=grain)))
            r = _ratios(make_frames(DN, DW, DH, seed=seed, cuts=SHOTS))
            at = [c - 1 for c in cut_frames(DN, SHOTS)]
            cut = min(cut, *(r[i] for i in at))
            within = max(within, *(x for i, x in enumerate(r)
                                   if i not in at))
        print(f"\nscenecut calibration at {DW}x{DH}, ten seeds: "
              f"pan/grain inter/intra <= {quiet:.3f}, inside a shot <= "
              f"{within:.3f}, at a cut >= {cut:.3f}; thresholds 0.6-0.9")
        assert quiet < 0.3 and within < 0.4 and cut > 1.2

    def test_two_seeds_of_the_one_shot_scene_are_no_cut(self):
        """They differ in their noise texture alone: a pan, to a
        detector and to a viewer."""
        a = make_frames(2, DW, DH, seed=1)[0]
        b = make_frames(2, DW, DH, seed=2)[1]
        assert scenecut.detect([a, b], DGOP, 100)[0] == ()


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------

def _starts(plan):
    return [g.start_frame for g in plan.gops]


class TestPlannerOnCuts:
    @pytest.mark.parametrize("n,gop,devices", [
        (256, 32, 1), (256, 32, 4), (100, 32, 4), (7, 8, 8), (250, 32, 2)])
    def test_no_cuts_is_the_uncut_plan(self, n, gop, devices):
        want = plan_segments(n, gop, devices)
        for cuts in ((), None):
            got = plan_segments(n, gop, devices, cuts=cuts)
            assert got.gops == want.gops
            assert got.pin_frames is (cuts is not None)
        assert want.pin_frames is False

    def test_the_cells_plan_is_ten_gops(self):
        plan = plan_segments(256, 32, 1, cuts=(72, 112, 200))
        assert [(g.start_frame, g.num_frames) for g in plan.gops] == [
            (0, 24), (24, 24), (48, 24), (72, 20), (92, 20), (112, 30),
            (142, 29), (171, 29), (200, 28), (228, 28)]
        assert _starts(plan) == scenecut_plain.gop_starts(
            256, 32, (72, 112, 200))
        assert 100.0 * (10 * 32 - 256) / (10 * 32) == 20.0

    @pytest.mark.parametrize("seed", range(8))
    def test_properties_on_random_cuts(self, seed):
        rng = np.random.default_rng(seed)
        n, gop = int(rng.integers(40, 400)), int(rng.choice((4, 8, 32)))
        lo = min_gop_frames(gop)
        cuts, t = [], 0
        while True:
            t += int(rng.integers(lo, 3 * gop))
            if t > n - 1:
                break
            cuts.append(t)
        plan = plan_segments(n, gop, 1, cuts=cuts)
        covered = [f for g in plan.gops
                   for f in range(g.start_frame, g.end_frame)]
        assert covered == list(range(n))
        assert [g.index for g in plan.gops] == list(range(plan.num_gops))
        assert all(g.num_frames <= gop for g in plan.gops)
        assert set(cuts) <= set(_starts(plan))
        assert not any(g.start_frame < c < g.end_frame
                       for g in plan.gops for c in cuts)
        # no GOP start closer than the shortest GOP to the next one
        assert all(g.num_frames >= lo for g in plan.gops)
        assert _starts(plan) == scenecut_plain.gop_starts(n, gop, cuts)
        # the mesh width changes how waves are filled, not the GOPs
        wide = plan_segments(n, gop, 4, cuts=cuts)
        assert wide.gops == plan.gops and wide.num_devices == 4
        assert wide.pin_frames and plan.pin_frames

    def test_suffix_replan_keeps_the_later_cuts(self):
        cuts = (72, 112, 200)
        whole = plan_segments(256, 32, 4, cuts=cuts)
        for done in (3, 5, 7):      # GOPs completed before the replan
            start = whole.gops[done].start_frame
            rest = suffix_cuts(cuts, start)
            assert rest == tuple(c - start for c in cuts if c > start)
            suffix = plan_segments(256 - start, 32, 2, cuts=rest)
            assert [s + start for s in _starts(suffix)] == \
                _starts(whole)[done:]
        assert suffix_cuts(None, 10) is None

    def test_cuts_must_rise_inside_the_clip(self):
        for bad in ((0, 5), (5, 5), (9, 4), (40,), (12, 45)):
            with pytest.raises(ValueError):
                plan_segments(40, 8, 1, cuts=bad)

    def test_over_the_segment_cap_the_cuts_are_dropped(self):
        plan = plan_segments(64, 8, 1, max_segments=8,
                             cuts=(5, 30, 41))      # 1 + 4 + 2 + 3 GOPs
        assert plan.gops == plan_segments(64, 8, 1, max_segments=8).gops
        assert plan.pin_frames
        kept = plan_segments(64, 8, 1, max_segments=10, cuts=(5, 30, 41))
        assert kept.num_gops == 10

    @pytest.mark.parametrize("gop,lo", [(32, 3), (8, 1), (2, 1), (250, 25)])
    def test_suppressed_cuts_are_counted(self, gop, lo):
        """Every frame a cut (inter far above intra): one is taken
        every `min_gop` frames, the ones between are suppressed."""
        assert min_gop_frames(gop) == lo
        n = 4 * lo + 1
        taken, suppressed = take_cuts([9] * n, [1] * n, gop, 40)
        assert taken == tuple(range(lo, n, lo))
        assert suppressed == (n - 1) - len(taken)

    def test_the_ramp(self):
        """Threshold (100 - bias) / 100 x intra: bias is a quarter of
        scenecut at the shortest GOP, all of it from gop_frames on."""
        n = 40

        def first_cut(inter):       # intra 100 everywhere
            return take_cuts([inter] * n, [100] * n, 32, 40)[0][:1]

        assert first_cut(59) == ()              # never reaches 60
        assert first_cut(60) == (32,)           # 100 - 40
        assert first_cut(90) == (3,)            # 100 - 40 / 4
        assert first_cut(89) == (4,)            # one step up the ramp
        assert take_cuts([0] * n, [0] * n, 32, 40) == ((), 0)
        assert take_cuts([59] * n, [100] * n, 32, 100)[0][:1] == (10,)
        assert take_cuts([99] * n, [100] * n, 32, 0) == ((), 0)

    def test_the_record_round_trips_the_cuts(self):
        import json

        snap = make_settings(gop_frames=32)
        plan = plan_encode(256, snap, num_devices=4, cuts=(72, 112, 200))
        rec = json.loads(json.dumps(plan.record()))
        assert rec["shape"] == "gop" and rec["cuts"] == [72, 112, 200]
        again = plan_encode(256, snap, num_devices=4, shape=rec["shape"],
                            cuts=rec["cuts"])
        assert again == plan and again.segments.num_gops == 10
        off = plan_encode(256, snap, num_devices=4)
        assert off.record()["cuts"] is None
        assert plan_encode(256, snap, num_devices=4,
                           cuts=()).record()["cuts"] == []

    def test_the_band_shape_keeps_its_fixed_grid(self):
        snap = make_settings(gop_frames=8, sfe_bands=2)
        plan = plan_encode(40, snap, num_devices=2, total_bands=2,
                           mb_height=8, cuts=(11, 18, 31))
        assert plan.shape == "band" and plan.cuts is None
        assert plan.segments.gops == plan_fixed_segments(40, 8, 2).gops
        assert not plan.segments.pin_frames

    def test_the_setting(self):
        assert DEFAULT_SETTINGS["scenecut"] == 0
        assert "scenecut" in JOB_SETTING_KEYS
        base = make_settings()
        for raw, want in ((40, 40), ("55", 55), (-3, 0), (250, 100)):
            assert overlay_job_settings(
                base, {"scenecut": raw}).scenecut == want


# ---------------------------------------------------------------------------
# through the coordinator, on the XLA mirror
# ---------------------------------------------------------------------------

META = VideoMeta(width=W, height=H, fps_num=30, fps_den=1, num_frames=N)


def _one_chip():
    return dispatch.default_mesh(jax.devices()[:1])


def _rig(tmp_path, name, mesh=None, encoder_factory=None, **settings):
    snap = make_settings(**{"gop_frames": GOP, "qp": 27, **settings})
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / name),
                          sync=True, mesh=mesh or _one_chip(),
                          encoder_factory=encoder_factory)
    coord._launcher = execu.launch
    return coord, execu


def _source(tmp_path, frames, name="clip.y4m"):
    path = tmp_path / name
    write_y4m(path, META, frames)
    return str(path)


#: the operating points of the two edited-footage deployments, as a
#: daemon gets them: `TVT_*` environment = the live settings, which is
#: where an encoder built by `make_shard_encoder` reads its RdConfig
#: (library-1080p-edited: the defaults; serving-1080p-edited: README's
#: serving point, aq_strength 1.0 = aq_q 4)
POINTS = {"library": {},
          "serving": dict(qp=25, mode_decision=True, pskip=True,
                          deblock=True, aq_strength=1.0)}
POINT_RD = {point: rd_from_settings(make_settings(**values))
            for point, values in POINTS.items()}


@contextlib.contextmanager
def _operating_point(point):
    if POINTS[point]:
        update_live_settings(POINTS[point])
    try:
        yield
    finally:
        reset_live_settings()


def _run(tmp_path, name, path, job_settings=None, mesh=None,
         point="library", **settings):
    coord, _ = _rig(tmp_path, name, mesh=mesh,
                    **{**POINTS[point], **settings})
    before = dispatch.stage_snapshot()
    with _operating_point(point):
        job = coord.add_job(path, META, settings=job_settings)
    job = coord.store.get(job.id)
    assert job.status is Status.DONE, job.failure_reason
    after = dispatch.stage_snapshot()
    return coord, job, {k: after[k] - before[k] for k in after}


@pytest.fixture(scope="module")
def edited(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("edited")
    frames = make_frames(N, W, H, seed=4, cuts=SHOTS)
    return tmp, frames, _source(tmp, frames)


@pytest.fixture(scope="module")
def served(edited):
    tmp, _frames, path = edited
    return _run(tmp, "on", path, scenecut=40)


@pytest.fixture(scope="module", params=sorted(POINTS))
def served_at(request, edited):
    """(operating point, `_run`'s result) of the edited clip with
    `scenecut` 40 at each operating point; the library's is `served`."""
    if request.param == "library":
        return request.param, request.getfixturevalue("served")
    tmp, _frames, path = edited
    return request.param, _run(tmp, "on-" + request.param, path,
                               point=request.param, scenecut=40)


class TestThroughTheCoordinator:
    def test_stss_is_the_plain_rules_gop_starts(self, edited, served_at):
        _tmp, frames, _path = edited
        _point, (_coord, job, grew) = served_at
        cuts, suppressed = scenecut_plain.scene_cuts(_lumas(frames),
                                                     GOP, 40)
        assert cuts == cut_frames(N, SHOTS) == [11, 18, 31]
        want = scenecut_plain.gop_starts(N, GOP, cuts)
        assert read_mp4(job.output_path).sync_samples() == want \
            == [0, 6, 11, 18, 25, 31, 36]
        assert job.parts_total == job.parts_done == len(want)
        assert (grew["scene_cuts"], grew["scene_cuts_suppressed"]) \
            == (3, len(suppressed))
        assert grew["scenecut"] > 0 and grew["waves"] == len(want)
        assert grew["wave_frames"] == len(want) * GOP
        assert grew["pad_frames"] == len(want) * GOP - N
        # every wave ran the bounded loop, none left the sparse budgets
        assert grew["pad_frames_skipped"] == grew["pad_frames"]
        assert grew["dense_fallback_waves"] == 0

    def test_the_note_and_the_span(self, edited, monkeypatch):
        from thinvids_tpu.obs import trace as obs_trace

        tmp, _frames, path = edited
        notes = []
        real = Coordinator.heartbeat_job

        def spy(self, job_id, token, stage, host="", note=""):
            notes.append((stage, note))
            return real(self, job_id, token, stage, host=host, note=note)

        monkeypatch.setattr(Coordinator, "heartbeat_job", spy)
        _coord, job, _grew = _run(tmp, "note", path, scenecut=40)
        assert ("segment", "7 GOPs planned, 3 scene cuts") in notes
        spans = obs_trace.TRACE.snapshot(job.id)["spans"]
        names = [s["name"] for s in spans]
        assert names.count("scenecut") == 1
        assert names.index("scenecut") < names.index("stage")

    def test_both_decoders_decode_it_alike(self, edited, served_at):
        """... and both equal the encoder's own reconstruction, sample
        for sample (tolerance zero), first frame after each cut
        included: at the serving point an IDR with AQ's per-MB QP, then
        P frames whose reference the in-loop filter has been over."""
        from thinvids_tpu.codecs.h264.decoder import decode_annexb
        from thinvids_tpu.codecs.h264.encoder import encode_gop
        from thinvids_tpu.tools import oracle
        from thinvids_tpu.tools.metrics import psnr

        _tmp, frames, _path = edited
        point, (_coord, job, _grew) = served_at
        media = read_mp4(job.output_path)
        stream = media.annexb_for(0, media.num_frames)
        # the reconstruction the encoder keeps, GOP by GOP of the plan:
        # the one-GOP program at that GOP's own length (the scan form)
        # gives the bytes the served job wrote for it, and its recon
        starts = media.sync_samples() + [N]
        qp = POINTS[point].get("qp", 27)
        recon = []
        for i, (a, b) in enumerate(zip(starts, starts[1:])):
            gop, planes = encode_gop(frames[a:b], META, qp=qp,
                                     idr_pic_id=i, return_recon=True,
                                     rd=POINT_RD[point])
            assert gop == media.annexb_for(a, b)
            recon += [[np.asarray(p)[k] for p in planes]
                      for k in range(b - a)]
        own = decode_annexb(stream).frames
        assert len(own) == N == len(recon)
        assert min(psnr(f.y, o.y[:H, :W])
                   for f, o in zip(frames, own)) > 30.0
        for o, (ry, ru, rv) in zip(own, recon):
            assert np.array_equal(o.y[:H, :W], ry[:H, :W])
            assert np.array_equal(o.u[:H // 2, :W // 2],
                                  ru[:H // 2, :W // 2])
            assert np.array_equal(o.v[:H // 2, :W // 2],
                                  rv[:H // 2, :W // 2])
        if not oracle.oracle_available():
            pytest.skip("libavcodec is not available")
        theirs = oracle.decode_h264(stream)
        assert len(theirs) == N
        for o, (y, u, v) in zip(own, theirs):
            assert np.array_equal(o.y[:H, :W], y[:H, :W])
            assert np.array_equal(o.u[:H // 2, :W // 2],
                                  u[:H // 2, :W // 2])
            assert np.array_equal(o.v[:H // 2, :W // 2],
                                  v[:H // 2, :W // 2])

    def test_scenecut_off_runs_nothing(self, edited):
        tmp, _frames, path = edited
        _coord, job, grew = _run(tmp, "off", path)
        assert read_mp4(job.output_path).sync_samples() == \
            list(range(0, N, GOP))
        assert grew["scenecut"] == 0 and grew["scene_cuts"] == 0
        assert grew["pad_frames"] == 0 and grew["wave_frames"] == N

    def test_a_per_job_setting_turns_it_on(self, edited, served):
        tmp, _frames, path = edited
        _coord, job, grew = _run(tmp, "perjob", path,
                                 job_settings={"scenecut": 40})
        assert grew["scene_cuts"] == 3
        with open(job.output_path, "rb") as a, \
                open(served[1].output_path, "rb") as b:
            assert a.read() == b.read()

    def test_four_devices_encode_the_same_bytes(self, edited, served_at):
        tmp, _frames, path = edited
        point, served = served_at
        mesh = dispatch.default_mesh(jax.devices()[:4])
        _coord, job, grew = _run(tmp, "wide-" + point, path, mesh=mesh,
                                 point=point, scenecut=40)
        assert grew["waves"] == 2               # 7 GOPs over 4 devices
        assert grew["wave_frames"] == 8 * GOP   # one pad GOP
        assert grew["pad_frames"] == 8 * GOP - N
        # the pad GOP repeats the last one (4 frames), bound and all
        assert grew["pad_frames_skipped"] == 8 * GOP - N - 4
        with open(job.output_path, "rb") as a, \
                open(served[1].output_path, "rb") as b:
            assert a.read() == b.read()

    @pytest.mark.parametrize("point", sorted(POINTS))
    def test_a_clip_without_cuts_keeps_its_bytes(self, tmp_path, point):
        frames = make_frames(N, W, H, seed=5)
        path = _source(tmp_path, frames)
        _c, off, _g = _run(tmp_path, "off", path, point=point)
        _c, on, grew = _run(tmp_path, "on", path, point=point, scenecut=40)
        assert grew["scene_cuts"] == grew["scene_cuts_suppressed"] == 0
        assert grew["scenecut"] > 0 and grew["pad_frames"] == 0
        assert grew["pad_frames_skipped"] == 0      # the scan form ran
        with open(off.output_path, "rb") as a, \
                open(on.output_path, "rb") as b:
            assert a.read() == b.read()

    def test_one_program_shape_whatever_the_shots(self, tmp_path,
                                                  monkeypatch):
        """Every shot shorter than a GOP: the staged waves still hold
        `gop_frames` frames, where the longest GOP of the plan has 5."""
        shapes = []
        real = dispatch.GopShardEncoder.dispatch_wave

        def spy(self, staged):
            shapes.append(tuple(staged[1].shape))
            return real(self, staged)

        monkeypatch.setattr(dispatch.GopShardEncoder, "dispatch_wave", spy)
        frames = make_frames(N, W, H, seed=6, cuts=(5, 5, 5, 5, 5, 5, 5, 5))
        path = _source(tmp_path, frames)
        _c, job, grew = _run(tmp_path, "short", path, scenecut=40)
        assert read_mp4(job.output_path).sync_samples() == \
            list(range(0, N, 5))
        assert set(shapes) == {(1, GOP, H, W)} and len(shapes) == 8
        assert grew["pad_frames"] == 8 * (GOP - 5)
        shapes.clear()
        _run(tmp_path, "shortoff", path, gop_frames=5)
        assert set(shapes) == {(1, 5, H, W)}

    def test_the_elastic_replan_carries_the_cuts(self, edited, served):
        """A wave that exhausts its retries on four devices: the
        suffix is re-planned on fewer, on the cuts that are left, and
        the bytes are the single-device ones."""
        tmp, _frames, path = edited
        state = {"fail": True}

        class Flaky(dispatch.GopShardEncoder):
            def collect_wave(self, pending):
                if state["fail"] and self.num_devices == 4 \
                        and pending[0][0].index >= 4:
                    raise RuntimeError("injected wave failure")
                return super().collect_wave(pending)

        def factory(meta, settings, mesh):
            return Flaky(meta, qp=int(settings.qp), mesh=mesh,
                         gop_frames=int(settings.gop_frames))

        coord, _ = _rig(tmp, "elastic", encoder_factory=factory,
                        mesh=dispatch.default_mesh(jax.devices()[:4]),
                        scenecut=40, part_failure_max_retries=0)
        job = coord.store.get(coord.add_job(path, META).id)
        assert job.status is Status.DONE, job.failure_reason
        assert any("replanning frames 25+" in e["message"]
                   for e in coord.activity.fetch(500))
        assert read_mp4(job.output_path).sync_samples() == \
            [0, 6, 11, 18, 25, 31, 36]
        with open(job.output_path, "rb") as a, \
                open(served[1].output_path, "rb") as b:
            assert a.read() == b.read()


class TestShapesThatKeepTheirGrid:
    def test_a_band_job_looks_for_no_cuts(self, edited, monkeypatch):
        tmp, _frames, path = edited

        def boom(*a, **k):
            raise AssertionError("a band job looked for scene cuts")

        monkeypatch.setattr(scenecut, "detect", boom)
        mesh = dispatch.default_mesh(jax.devices()[:2])
        _c, job, grew = _run(tmp, "band", path, mesh=mesh, scenecut=40,
                             sfe_bands=2)
        assert read_mp4(job.output_path).sync_samples() == \
            list(range(0, N, GOP))
        assert grew["scenecut"] == 0 and grew["sfe_frames"] == N

    def test_a_live_batch_keeps_the_fixed_grid(self, edited, monkeypatch):
        from thinvids_tpu.abr.ladder import plan_ladder

        tmp, frames, _path = edited

        def boom(*a, **k):
            raise AssertionError("a live batch looked for scene cuts")

        monkeypatch.setattr(scenecut, "detect", boom)
        snap = make_settings(gop_frames=GOP, qp=27, scenecut=40,
                             ladder_rungs=str(H))
        _coord, execu = _rig(tmp, "live", scenecut=40)
        rungs = plan_ladder(META, snap)
        enc, sfe_live = execu._live_encoder(META, snap, rungs)
        bundles = execu._live_encode_batch(
            None, "", snap, enc, rungs, frames, 0, 0, 24, GOP, sfe_live)
        assert [b.gop.start_frame for b in bundles] == [0, 8, 16]
        assert enc.scene_cuts is None

    def test_a_ladder_plans_every_rung_on_the_cuts(self, edited):
        from thinvids_tpu.abr.ladder import plan_ladder

        _tmp, frames, _path = edited
        snap = make_settings(gop_frames=GOP, qp=27, scenecut=40,
                             ladder_rungs=f"{H},{H // 2}")
        rungs = plan_ladder(META, snap)
        enc = dispatch.make_shard_encoder(META, snap, _one_chip(),
                                          rungs=rungs)
        plan, note = LocalExecutor(
            Coordinator(registry=WorkerRegistry(),
                        settings_fn=lambda: snap),
            output_dir=str(_tmp / "ladder"), sync=True
        )._plan_on_cuts(enc, frames, snap)
        assert note == ", 3 scene cuts"
        assert _starts(plan) == [0, 6, 11, 18, 25, 31, 36]
        assert all(e.scene_cuts == (11, 18, 31) for e in enc.encoders)
        assert all(_starts(e.plan(N)) == _starts(plan)
                   for e in enc.encoders)


class TestTheFarmPlansOnTheSameCuts:
    def test_remote_plan_record_and_worker_shape(self):
        from thinvids_tpu.cluster import remote

        plan = plan_segments(N, GOP, 2, cuts=(11, 18, 31))
        shard = remote.Shard(
            id="j-0000", key="0000", job_id="j", input_path="x",
            meta=META, gops=plan.gops[2:4], qp=27, gop_frames=GOP,
            pin_frames=plan.pin_frames, timeout_s=1.0)
        desc = shard.descriptor()
        assert desc["pin_frames"] is True
        assert desc["gops"] == [[0, 0, 7], [1, 7, 7]]
        rec = remote.RemoteExecutor._plan_record("sig", plan, [shard],
                                                 (11, 18, 31))
        assert rec["cuts"] == [11, 18, 31] and rec["pin_frames"] is True
        plain = remote.Shard(
            id="j-0001", key="0001", job_id="j", input_path="x",
            meta=META, gops=plan.gops[:1], qp=27, gop_frames=GOP,
            timeout_s=1.0)
        assert "pin_frames" not in plain.descriptor()

    def test_the_farm_encodes_the_local_bytes(self, edited, served,
                                              monkeypatch):
        """RemoteExecutor + two workers claiming off the real board:
        one look for cuts at the coordinator, shards of the cut-aware
        plan, every worker wave staged to `gop_frames`, the stitched
        MP4 byte for byte the local one."""
        import threading
        import time

        from thinvids_tpu.cluster import remote
        from thinvids_tpu.ingest.decode import read_video

        tmp, _frames, path = edited
        shapes = []
        real = dispatch.GopShardEncoder.dispatch_wave

        def spy(self, staged):
            shapes.append(tuple(staged[1].shape))
            return real(self, staged)

        monkeypatch.setattr(dispatch.GopShardEncoder, "dispatch_wave", spy)
        calls = _program_calls(monkeypatch)
        snap = make_settings(gop_frames=GOP, qp=27, scenecut=40,
                             remote_plan_devices=1, remote_shard_gops=2,
                             remote_no_worker_grace_s=10.0)
        reg = WorkerRegistry()
        for i in range(8):          # admission wants four idle
            reg.heartbeat(f"w{i:02d}", metrics={"worker": True})
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        execu = remote.RemoteExecutor(
            coord, output_dir=str(tmp / "farm"), sync=True, poll_s=0.02)
        coord._launcher = execu.launch
        stop = threading.Event()
        clip = read_video(path)[1]
        mesh = _one_chip()

        def worker(host):
            while not stop.is_set():
                desc = execu.board.claim(host)
                if desc is None:
                    time.sleep(0.01)
                    continue
                execu.board.submit_part(
                    desc["id"], host,
                    remote.encode_shard(desc, clip, mesh=mesh))

        for i in range(2):
            threading.Thread(target=worker, args=(f"w{i:02d}",),
                             daemon=True).start()
        before = dispatch.stage_snapshot()
        try:
            job = coord.store.get(coord.add_job(path, META).id)
        finally:
            stop.set()
        assert job.status is Status.DONE, job.failure_reason
        assert job.parts_done == job.parts_total == 7
        grew = dispatch.stage_snapshot()
        assert grew["scene_cuts"] - before["scene_cuts"] == 3
        assert grew["scenecut"] > before["scenecut"]
        assert shapes and {s[1] for s in shapes} == {GOP}
        # the workers' waves run the local job's program: the one that
        # takes the GOPs' real lengths (ISSUE 34)
        assert calls == [("_encode_gop_single", True)] * 7
        assert grew["pad_frames_skipped"] - before["pad_frames_skipped"] \
            == 7 * GOP - N
        with open(job.output_path, "rb") as a, \
                open(served[1].output_path, "rb") as b:
            assert a.read() == b.read()

    def test_the_signature_moves_with_the_threshold(self, edited):
        from thinvids_tpu.cluster import remote
        from thinvids_tpu.cluster.jobs import Job

        _tmp, _frames, path = edited
        job = Job(id="j" * 12, input_path=path)
        sig = remote.RemoteExecutor._plan_signature
        off = sig(job, make_settings(gop_frames=GOP))
        assert off == sig(job, make_settings(gop_frames=GOP, scenecut=0))
        assert len({off, sig(job, make_settings(gop_frames=GOP,
                                                scenecut=40)),
                    sig(job, make_settings(gop_frames=GOP,
                                           scenecut=41))}) == 3


# ---------------------------------------------------------------------------
# a cut-aligned GOP stops at its real length (ISSUE 34)
# ---------------------------------------------------------------------------

SERVING = RdConfig(mode_decision=True, pskip=True, deblock=True, aq_q=8)


def _pinned(lengths, devices=1):
    """A plan as `plan_segments` makes it on cuts: GOPs of `lengths`
    frames, every one staged to GOP."""
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    return SegmentPlan(
        gops=tuple(GopSpec(index=i, start_frame=int(a), num_frames=int(n))
                   for i, (a, n) in enumerate(zip(starts, lengths))),
        num_devices=devices, frames_per_gop=GOP, pin_frames=True)


def _gop_bytes(frames, plan, rd=RD_OFF, devices=1, index=0):
    """(payload per GOP, what the stage counters grew by) of `frames`
    through a GopShardEncoder held to `plan` (None: its own, unpinned,
    one GOP as long as the clip, the scan form at that length, as GOP
    `index` of its clip)."""
    meta = VideoMeta(width=W, height=H, fps_num=30, fps_den=1,
                     num_frames=len(frames))
    enc = dispatch.GopShardEncoder(
        meta, qp=27, mesh=dispatch.default_mesh(jax.devices()[:devices]),
        gop_frames=GOP if plan is not None else len(frames), rd=rd)
    enc.plan_override = plan
    enc.gop_index_offset = index
    segs = enc.encode(frames)
    return [seg.payload for seg in segs], enc.stages.snapshot()


def _program_calls(monkeypatch):
    """Record, per call of a GOP program, whether it was handed the
    GOPs' real lengths."""
    calls = []
    for name in ("_encode_gop_single", "_encode_wave_gop"):
        real = getattr(dispatch, name)

        def spy(*args, _real=real, _name=name, **kwargs):
            calls.append((_name, len(args) == 5))
            return _real(*args, **kwargs)

        monkeypatch.setattr(dispatch, name, spy)
    return calls


class TestACutAlignedGopStopsAtItsLength:
    @pytest.mark.parametrize("rd", [RD_OFF, SERVING], ids=["library", "rd"])
    @pytest.mark.parametrize("n", [1, 2, GOP - 1, GOP])
    def test_the_bytes_of_the_same_frames_as_a_gop_of_that_length(
            self, n, rd):
        """Staged to GOP frames with `n_frames` = n, against the scan
        form over a GOP staged to n: the same NAL bytes."""
        frames = make_frames(n, W, H, seed=11)
        (bounded,), grew = _gop_bytes(frames, _pinned([n]), rd=rd)
        (scanned,), plain = _gop_bytes(frames, None, rd=rd)
        assert bounded == scanned
        assert grew["wave_frames"] == GOP and plain["wave_frames"] == n
        assert grew["pad_frames"] == grew["pad_frames_skipped"] == GOP - n
        assert plain["pad_frames"] == plain["pad_frames_skipped"] == 0

    @pytest.mark.parametrize("rd", [RD_OFF, SERVING], ids=["library", "rd"])
    @pytest.mark.parametrize("n", [1, 2, GOP - 1, GOP])
    def test_what_the_loop_skips_is_zero_and_the_rest_is_the_scans(
            self, n, rd):
        """`encode_gop_planes` on one staged GOP: frames before n come
        out as the scan over all GOP frames gives them, frames from n
        on are zeros, in the layout `unflatten_gop` reads."""
        frames = make_frames(n, W, H, seed=12)
        frames = frames + [frames[-1]] * (GOP - n)
        planes = [jnp.asarray(np.stack([getattr(f.padded(16), p)
                                        for f in frames])) for p in "yuv"]
        run = jax.jit(jaxinter.encode_gop_planes,
                      static_argnames=("mbw", "mbh", "rd"))
        kw = dict(mbw=W // 16, mbh=H // 16, rd=rd)
        mv_s, flat_s = run(*planes, jnp.int32(27), **kw)
        mv_b, flat_b = run(*planes, jnp.int32(27), **kw,
                           n_frames=jnp.int32(n))
        assert mv_b.shape == mv_s.shape and flat_b.shape == flat_s.shape
        assert mv_b.dtype == mv_s.dtype and flat_b.dtype == flat_s.dtype
        intra_s, p_s = unflatten_gop(np.asarray(flat_s), np.asarray(mv_s),
                                     GOP, W // 16, H // 16,
                                     ships_modes=rd.ships_modes)
        intra_b, p_b = unflatten_gop(np.asarray(flat_b), np.asarray(mv_b),
                                     GOP, W // 16, H // 16,
                                     ships_modes=rd.ships_modes)
        for a, b in zip(intra_s, intra_b):
            assert np.array_equal(a, b)
        for a, b in zip(p_s, p_b):
            assert a.shape[0] == GOP - 1
            assert np.array_equal(a[:n - 1], b[:n - 1])
            assert not b[n - 1:].any()

    @pytest.mark.parametrize("rd", [RD_OFF, SERVING], ids=["library", "rd"])
    def test_through_the_dense_fallback(self, rd, monkeypatch):
        """Grain that leaves the sparse budgets: the levels — and with
        the serving tools the per-MB modes and QP deltas beside them —
        that the bounded program left on the device (zeros past n)
        pack to the bytes of the scan form at that length, from the ONE
        program call the wave made."""
        n = 5
        frames = make_frames(n, W, H, seed=13, grain=8.0)
        calls = _program_calls(monkeypatch)
        (bounded,), grew = _gop_bytes(frames, _pinned([n]), rd=rd)
        assert calls == [("_encode_gop_single", True)]
        (scanned,), plain = _gop_bytes(frames, None, rd=rd)
        assert grew["dense_fallback_waves"] == 1 \
            == plain["dense_fallback_waves"]
        assert bounded == scanned
        assert grew["pad_frames_skipped"] == GOP - n

    def test_on_two_devices_each_loop_has_its_own_bound(self, monkeypatch):
        """Three GOPs of 5, 8 and 3 frames over a 2-device mesh: two
        waves, the second with a pad GOP; each GOP's bytes are those of
        the one-device scan form at its length."""
        calls = _program_calls(monkeypatch)
        lengths = [5, GOP, 3]
        frames = make_frames(sum(lengths), W, H, seed=14)
        got, grew = _gop_bytes(frames, _pinned(lengths, 2), devices=2)
        assert calls == [("_encode_wave_gop", True)] * 2
        a = 0
        for i, (n, payload) in enumerate(zip(lengths, got)):
            (want,), _ = _gop_bytes(frames[a:a + n], None, index=i)
            assert payload == want
            a += n
        assert grew["wave_frames"] == 4 * GOP
        assert grew["pad_frames"] == 4 * GOP - sum(lengths)
        # the pad GOP repeats the last one, loop bound and all
        assert grew["pad_frames_skipped"] == 4 * GOP - sum(lengths) - 3

    @pytest.mark.parametrize("program", ["_encode_gop_single",
                                         "_encode_wave_gop"])
    def test_a_plain_plans_program_has_no_traced_bound(self, program):
        """Without `n_frames` the program is the one it was: every loop
        a `scan` of static length (the jaxpr of the parent commit, text
        for text: PERF.md, PR 34). With it, one `while` more and one
        scan fewer, and nothing else of another kind."""
        G, F = 2, 4
        c = (G, F, H // 2, W // 2)
        args = [jax.ShapeDtypeStruct((G, F, H, W), jnp.uint8),
                jax.ShapeDtypeStruct(c, jnp.uint8),
                jax.ShapeDtypeStruct(c, jnp.uint8),
                jax.ShapeDtypeStruct((G,), jnp.int32)]
        kw = dict(mbw=W // 16, mbh=H // 16)
        if program == "_encode_wave_gop":
            kw["mesh"] = dispatch.default_mesh(jax.devices()[:2])
        fn = functools.partial(getattr(dispatch, program), **kw)
        plain = str(jax.make_jaxpr(fn)(*args))
        assert plain == str(jax.make_jaxpr(fn)(*args, None))
        assert "while[" not in plain and plain.count("scan[") > 2
        bounded = str(jax.make_jaxpr(fn)(*args, args[3]))
        assert bounded.count("while[") == 1
        assert bounded.count("scan[") == plain.count("scan[") - 1

    @pytest.mark.parametrize("rd", [RD_OFF, SERVING], ids=["library", "rd"])
    @pytest.mark.parametrize("bounded", [False, True], ids=["scan", "while"])
    def test_either_loop_traces_the_p_frame_step_once(self, monkeypatch,
                                                      rd, bounded):
        """ISSUE 39: the bounded loop sizes its buffers from the ONE
        trace it evaluates as its body. A second trace for the shapes
        alone cost the serving set 11 s of every start on the chip
        (PERF.md §6, PR 39), which is what kept the bounded form away
        from the plans that do not need it (ROADMAP D10)."""
        traces = []
        real = jaxinter._encode_p_plane

        def counting(*args, **kwargs):
            traces.append(1)
            return real(*args, **kwargs)

        monkeypatch.setattr(jaxinter, "_encode_p_plane", counting)
        c = (1, 4, H // 2, W // 2)
        args = [jax.ShapeDtypeStruct((1, 4, H, W), jnp.uint8),
                jax.ShapeDtypeStruct(c, jnp.uint8),
                jax.ShapeDtypeStruct(c, jnp.uint8),
                jax.ShapeDtypeStruct((1,), jnp.int32)]
        fn = functools.partial(dispatch._encode_gop_single.__wrapped__,
                               mbw=W // 16, mbh=H // 16, rd=rd)
        jax.make_jaxpr(fn)(*args, *args[3:] * bounded)
        assert len(traces) == 1

    def test_a_job_runs_one_of_the_two_programs(self, edited, monkeypatch):
        """The plan decides: every wave of a job planned on cuts hands
        its program the real lengths (full GOPs too), and no wave of a
        job planned without does. The repeats skipped are counted, in
        the snapshot and in the registry `/metrics` serves."""
        from thinvids_tpu.obs import metrics as obs_metrics

        tmp, _frames, path = edited
        calls = _program_calls(monkeypatch)
        exported = obs_metrics.STAGE_COUNTER_TOTALS["pad_frames_skipped"]
        before = exported.get()
        _c, _job, grew = _run(tmp, "one-on", path, scenecut=40)
        assert calls == [("_encode_gop_single", True)] * 7
        assert grew["pad_frames_skipped"] == grew["pad_frames"] \
            == 7 * GOP - N == exported.get() - before
        calls.clear()
        _c, _job, grew = _run(tmp, "one-off", path)
        assert calls == [("_encode_gop_single", False)] * 5
        assert grew["pad_frames_skipped"] == grew["pad_frames"] == 0
        assert exported.get() - before == 7 * GOP - N

    def test_every_rung_of_a_ladder_gets_the_lengths(self, edited,
                                                     monkeypatch):
        """The ladder stages once and hands the staged wave to each
        rung, scaled or not: the real lengths ride along."""
        from thinvids_tpu.abr.ladder import plan_ladder

        _tmp, frames, _path = edited
        calls = _program_calls(monkeypatch)
        snap = make_settings(gop_frames=GOP, qp=27,
                             ladder_rungs=f"{H},{H // 2}")
        rungs = plan_ladder(META, snap)
        for cuts, bounded in (((11, 18, 31), True), (None, False)):
            enc = dispatch.make_shard_encoder(META, snap, _one_chip(),
                                              rungs=rungs)
            enc.scene_cuts = cuts
            calls.clear()
            bundles = enc.encode(frames)
            waves = len(bundles)
            assert waves == (7 if cuts else 5)
            assert calls == [("_encode_gop_single", bounded)] * 2 * waves


# ---------------------------------------------------------------------------
# the content
# ---------------------------------------------------------------------------

def _bench_generator(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_gen_{name}",
        os.path.join(ROOT, "benchmark", "generators", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class TestTheHarnessCopyIsTheSameGenerator:
    @pytest.mark.parametrize("n,w,h,seed,pan,shots", [
        (16, 128, 128, 2**31 + 5, 3, SHOTS),
        (256, 64, 48, 7, 3, SHOTS),
        (24, 70, 50, 3, 2, (5, 9, 10)),
        (9, 96, 64, 1, 5, (1, 1, 7)),
    ])
    def test_same_planes_for_the_same_arguments(self, n, w, h, seed, pan,
                                                shots):
        gen = _bench_generator("cuts")
        frames = make_frames(n, w, h, seed=seed, pan=pan, cuts=shots)
        planes = list(gen.planes(n, w, h, seed, pan=pan, shots=shots))
        assert len(frames) == len(planes) == n
        for f, (y, u, v) in zip(frames, planes):
            for mine, theirs in ((f.y, y), (f.u, u), (f.v, v)):
                assert mine.dtype == theirs.dtype == np.uint8
                assert np.array_equal(mine, theirs)
        assert gen.cut_frames(n, shots) == cut_frames(n, shots)
        assert f.y.shape == (h, w) and f.u.shape == (h // 2, w // 2)

    def test_the_cells_cuts_and_the_rehearsals(self):
        gen = _bench_generator("cuts")
        assert gen.SHOTS == SHOTS
        assert gen.cut_frames(256) == [72, 112, 200]
        assert gen.cut_frames(16) == [5, 7, 13]     # first shot >= 2
        assert cut_frames(N, SHOTS) == [11, 18, 31]

    def test_the_shot_lengths_do_not_move_with_the_seed(self):
        for seed in (1, 2):
            frames = make_frames(DN, DW, DH, seed=seed, cuts=SHOTS)
            assert scenecut.detect(frames, DGOP, 40)[0] == (9, 14, 25)
        a = make_frames(DN, DW, DH, seed=1, cuts=SHOTS)
        b = make_frames(DN, DW, DH, seed=2, cuts=SHOTS)
        assert not np.array_equal(a[0].y, b[0].y)

    def test_a_shot_is_a_pan_of_its_own_scene(self):
        frames = make_frames(12, 96, 64, seed=3, pan=2, cuts=(6, 6))
        for a, b in zip(frames[:5], frames[1:6]):
            assert not np.array_equal(a.y, b.y)
            moved = [np.array_equal(b.y[max(0, -dy):64 - max(0, dy),
                                        max(0, -dx):96 - max(0, dx)],
                                    a.y[max(0, dy):64 + min(0, dy),
                                        max(0, dx):96 + min(0, dx)])
                     for dy in (-2, 2) for dx in (-2, 2)]
            assert sum(moved) == 1
        with pytest.raises(ValueError):
            make_frames(12, 96, 64, cuts=(6, 6), grain=3.0)
