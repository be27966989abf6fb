"""Quarter-sample motion vectors (ISSUE 41, the `subpel` setting).

With `subpel="quarter"` the motion search also scores §8.4.2.2.1's
quarter positions (tests/test_jaxme.py holds the kernel to its spec and
the spec to the standard, position by position) and every vector
downstream of it is in quarter-sample units. This file holds what
follows the vector:

- the encoder's reconstruction = the in-repo decoder's = libavcodec's,
  sample for sample, on a hand-held clip (tools/handheld.py) at both
  operating points, with the in-loop filter on and off, through the
  bounded P-frame loop and through the dense fallback;
- quarter vectors buy bits at no lower PSNR on that clip, and
  `subpel="half"` (the default) still writes the parent commit's bytes;
- the filter's bS = 1 test, the packers and the setting's own plumbing;
- through the coordinator: a job under `subpel=quarter` alone, with
  `scenecut`, with `sfe_bands` on a CPU mesh and on the remote backend
  encodes at quarter precision (read from the stream's vectors), and a
  per-job `subpel` the daemon cannot apply is refused at admission.
"""

import dataclasses
import hashlib
import threading
import time

import numpy as np
import pytest

import jax

from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.codecs.h264 import deblock, inter, jaxme, rdo
from thinvids_tpu.codecs.h264.decoder import decode_annexb
from thinvids_tpu.codecs.h264.encoder import encode_gop
from thinvids_tpu.codecs.h264.rdo import RD_OFF, RdConfig, rd_from_settings
from thinvids_tpu.core import config
from thinvids_tpu.core.config import (DEFAULT_SETTINGS, Settings,
                                      overlay_job_settings,
                                      reset_live_settings,
                                      update_live_settings)
from thinvids_tpu.core.status import Status
from thinvids_tpu.core.types import VideoMeta, concat_segments
from thinvids_tpu.io.mp4 import read_mp4
from thinvids_tpu.io.y4m import write_y4m
from thinvids_tpu.parallel import dispatch
from thinvids_tpu.parallel.dispatch import GopShardEncoder, default_mesh
from thinvids_tpu.parallel.planner import plan_segments
from thinvids_tpu.tools import oracle
from thinvids_tpu.tools.deblock_plain import deblock_picture_plain
from thinvids_tpu.tools.handheld import make_frames as handheld_frames
from thinvids_tpu.tools.metrics import psnr
from thinvids_tpu.tools.pan import make_frames as pan_frames

W, H, N, GOP = 160, 96, 8, 8
META = VideoMeta(width=W, height=H, fps_num=30, fps_den=1, num_frames=N)
SERVING = dict(mode_decision=True, pskip=True, deblock=True, aq_q=4)
#: (qp, RdConfig fields) of the two operating points
POINTS = {"library": (27, {}), "serving": (25, SERVING)}


def _rd(point, subpel="quarter", **over):
    return RdConfig(**{**POINTS[point][1], **over}, subpel=subpel)


@pytest.fixture(scope="module")
def clip():
    return handheld_frames(N, W, H, seed=3)


def _odd_share(mvs):
    """Share of P macroblocks whose vector has an odd quarter component."""
    mv = np.concatenate([m.reshape(-1, 2) for m in mvs if m is not None])
    return float((mv & 1).any(axis=1).mean())


def _slice_sizes(stream):
    """Bytes of each picture's slice NAL, in coding order."""
    from thinvids_tpu.io.mp4 import split_annexb

    return [len(nal) for nal in split_annexb(stream)
            if nal and (nal[0] & 0x1F) in (1, 5)]


def _same_planes(frame, planes, h=H, w=W):
    y, u, v = (np.asarray(p) for p in planes)
    return (np.array_equal(frame.y[:h, :w], y[:h, :w])
            and np.array_equal(frame.u[:h // 2, :w // 2],
                               u[:h // 2, :w // 2])
            and np.array_equal(frame.v[:h // 2, :w // 2],
                               v[:h // 2, :w // 2]))


def _decoders_agree(stream, recon, n=N, h=H, w=W):
    """The in-repo decoder and libavcodec against the encoder's own
    reconstruction (`recon`: (ys, us, vs) stacked over frames), sample
    for sample; returns the in-repo decode."""
    own = decode_annexb(stream)
    assert len(own.frames) == n
    for i, frame in enumerate(own.frames):
        assert _same_planes(frame, [p[i] for p in recon], h, w), i
    if oracle.oracle_available():
        theirs = oracle.decode_h264(stream)
        assert len(theirs) == n
        for frame, planes in zip(own.frames, theirs):
            assert _same_planes(frame, planes, h, w)
    return own


# ---------------------------------------------------------------------------
# encoder = in-repo decoder = libavcodec
# ---------------------------------------------------------------------------

class TestReconstructionEqualsBothDecoders:
    @pytest.mark.parametrize("filtered", [False, True])
    @pytest.mark.parametrize("point", sorted(POINTS))
    def test_on_the_handheld_clip(self, clip, point, filtered):
        qp = POINTS[point][0]
        rd = _rd(point, deblock=filtered)
        stream, recon = encode_gop(clip, META, qp=qp, return_recon=True,
                                   rd=rd)
        own = _decoders_agree(stream, recon)
        assert own.mvs[0] is None and _odd_share(own.mvs) > 0.2
        assert min(psnr(f.y, o.y) for f, o in zip(clip, own.frames)) > 30

    @pytest.mark.parametrize("point", sorted(POINTS))
    def test_through_the_bounded_loop(self, clip, point):
        """A plan made on scene cuts (GOPs of 5 and 3 frames staged to
        8, the P-frame loop stopped at each GOP's length) writes what
        the one-GOP program writes for each GOP."""
        qp, rd = POINTS[point][0], _rd(point)
        enc = GopShardEncoder(META, qp=qp, gop_frames=GOP, rd=rd,
                              mesh=default_mesh(jax.devices()[:1]))
        enc.plan_override = plan_segments(N, GOP, 1, cuts=(5,))
        assert enc.plan_override.pin_frames
        segs = enc.encode(clip)
        assert [s.gop.num_frames for s in segs] == [5, 3]
        assert enc.stages.snapshot()["pad_frames_skipped"] == 2 * GOP - N
        recon = [[], [], []]
        for seg in segs:
            a, b = seg.gop.start_frame, seg.gop.end_frame
            stream, planes = encode_gop(clip[a:b], META, qp=qp,
                                        idr_pic_id=seg.gop.index,
                                        return_recon=True, rd=rd)
            assert seg.payload == stream
            for acc, p in zip(recon, planes):
                acc.extend(np.asarray(p))
        own = _decoders_agree(concat_segments(segs), recon)
        assert _odd_share(own.mvs) > 0.1

    @pytest.mark.parametrize("point", sorted(POINTS))
    def test_through_the_dense_fallback(self, clip, point):
        """Grain new on every frame takes the wave out of the sparse
        budgets: its whole levels cross, and the bytes are the plain
        encoder's."""
        rng = np.random.default_rng(11)
        grainy = [dataclasses.replace(
            f, y=np.clip(np.rint(f.y + rng.normal(0, 6.0, f.y.shape)),
                         0, 255).astype(np.uint8)) for f in clip]
        qp, rd = POINTS[point][0], _rd(point)
        enc = GopShardEncoder(META, qp=qp, gop_frames=GOP, rd=rd,
                              mesh=default_mesh(jax.devices()[:1]))
        (seg,) = enc.encode(grainy)
        assert enc.stages.snapshot()["dense_fallback_waves"] == 1
        stream, recon = encode_gop(grainy, META, qp=qp, return_recon=True,
                                   rd=rd)
        assert seg.payload == stream
        _decoders_agree(stream, recon)


class TestWhatQuarterVectorsBuy:
    @pytest.mark.parametrize("point", sorted(POINTS))
    def test_fewer_bits_at_no_lower_psnr(self, clip, point):
        qp = POINTS[point][0]
        size, quality = {}, {}
        for subpel in rdo.SUBPELS:
            stream, recon = encode_gop(clip, META, qp=qp, return_recon=True,
                                       rd=_rd(point, subpel))
            size[subpel] = len(stream)
            quality[subpel] = np.mean([
                psnr(f.y, np.asarray(recon[0][i])[:H, :W].astype(np.uint8))
                for i, f in enumerate(clip)])
        assert size["quarter"] < size["half"]
        assert quality["quarter"] >= quality["half"]

    @pytest.mark.parametrize("path", [
        dict(vx=6.7, vy=0.3, ax=0.9, Tx=19.0, ay=0.5, Ty=27.0),
        dict(vx=-1.3, vy=2.6, ax=0.8, Tx=17.0, ay=1.9, Ty=29.0),
    ], ids=["fast-pan", "diagonal-drift"])
    def test_a_gops_first_p_frame_is_no_worse_than_at_half(self, path):
        """Other camera paths than the benchmark's. A GOP's first P
        frame searches with a temporal median of zero, so the motion is
        the probe's to find; the quarter table keeps the probe's fine
        half-sample classes, so that frame costs what it costs at half
        precision (without them a fast pan's doubled, PERF.md §6), and
        the frames after it cost less."""
        w, h, n = 320, 192, 8
        frames = handheld_frames(n, w, h, seed=3, **path)
        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=n)
        sizes = {}
        for subpel in rdo.SUBPELS:
            stream = encode_gop(frames, meta, qp=25,
                                rd=_rd("serving", subpel))
            sizes[subpel] = _slice_sizes(stream)
        first = {k: v[1] for k, v in sizes.items()}
        assert first["quarter"] <= 1.02 * first["half"]
        assert sum(sizes["quarter"][2:]) < 0.95 * sum(sizes["half"][2:])

    #: sha256 of `encode_gop(pan_frames(6, 96, 64, seed=5), ...)` at the
    #: two points, written by the parent commit of ISSUE 41 (5e3ddce)
    PARENT = {
        "library": "bef89e81e8efa573dd1d3561e6182ef660639b7f9ee1b790c6e35a74aa1dfbfa",
        "serving": "1f88f080e1f43a44e4a2059977ed757331b98d2a9882e6e0dcec95bb5d01a011",
    }

    @pytest.mark.parametrize("point", sorted(POINTS))
    def test_half_still_writes_the_parents_bytes(self, point):
        frames = pan_frames(6, 96, 64, seed=5)
        meta = VideoMeta(width=96, height=64, fps_num=30, fps_den=1,
                         num_frames=6)
        qp, fields = POINTS[point]
        default = encode_gop(frames, meta, qp=qp, rd=RdConfig(**fields))
        assert hashlib.sha256(default).hexdigest() == self.PARENT[point]
        assert default == encode_gop(frames, meta, qp=qp,
                                     rd=_rd(point, "half"))
        own = decode_annexb(default)
        assert _odd_share(own.mvs) == 0.0

    def test_half_is_the_same_static_argument(self):
        """One executable per value: the default IS "half" (equal and
        hash-equal, so a jit keyed on it never retraces), "quarter" is
        another."""
        assert RdConfig() == RdConfig(subpel="half") == RD_OFF
        assert hash(RdConfig()) == hash(RdConfig(subpel="half"))
        assert RdConfig(subpel="quarter") != RD_OFF
        assert (RD_OFF.mv_per_pel, _rd("library").mv_per_pel) == (2, 4)
        with pytest.raises(ValueError, match="subpel"):
            RdConfig(subpel="eighth")


# ---------------------------------------------------------------------------
# downstream of the vector: the filter's bS test, the packers
# ---------------------------------------------------------------------------

class TestDownstreamOfTheVector:
    @pytest.mark.parametrize("per_pel", [2, 4])
    def test_bs1_is_one_whole_sample_in_either_unit(self, per_pel):
        """Uncoded blocks whose vectors differ by one unit less than a
        sample keep their edge (bS 0); by a whole sample it is filtered
        (bS 1) — and the plain §8.7 reference agrees in both units."""
        rng = np.random.default_rng(5)
        mbh, mbw = 2, 3
        y = rng.integers(60, 200, (16 * mbh, 16 * mbw)).astype(np.uint8)
        u = rng.integers(60, 200, (8 * mbh, 8 * mbw)).astype(np.uint8)
        v = rng.integers(60, 200, (8 * mbh, 8 * mbw)).astype(np.uint8)
        qp = np.full((mbh, mbw), 36, np.int32)
        nz4 = np.zeros((4 * mbh, 4 * mbw), bool)
        out = {}
        for step in (per_pel - 1, per_pel):
            mv = np.zeros((mbh, mbw, 2), np.int32)
            mv[:, 1] = (0, step)
            got = deblock.deblock_frame(y, u, v, qp, intra=False, nz4=nz4,
                                        mv=mv, mv_per_pel=per_pel)
            want = deblock_picture_plain(y, u, v, qp, intra=False, nz4=nz4,
                                         mv=mv, mv_per_pel=per_pel)
            for a, b in zip(got, want):
                assert np.array_equal(a, b)
            out[step] = got[0]
        assert np.array_equal(out[per_pel - 1], y)
        assert not np.array_equal(out[per_pel], y)

    @pytest.mark.parametrize("per_pel", [2, 4])
    def test_python_and_native_packers_code_the_same_mvd(self, per_pel):
        from thinvids_tpu import native
        from thinvids_tpu.codecs.h264.headers import PPS, SPS

        if not native.available():
            pytest.skip("native packer not buildable here")
        rng = np.random.default_rng(per_pel)
        mbw, mbh = 4, 3
        n = mbw * mbh
        mv = rng.integers(-9, 10, (n, 2)).astype(np.int32)
        luma = np.zeros((n, 16, 16), np.int32)
        luma[::2, 0, 0] = 3
        cdc = np.zeros((n, 2, 4), np.int32)
        cac = np.zeros((n, 2, 4, 15), np.int32)
        sps = SPS(width=16 * mbw, height=16 * mbh, fps_num=30, fps_den=1)
        args = (mv, luma, cdc, cac, mbw, mbh, sps, PPS(init_qp=27), 27, 1)
        plain = inter.pack_p_slice(*args, native=False, mv_per_pel=per_pel)
        assert plain == inter.pack_p_slice(*args, native=True,
                                           mv_per_pel=per_pel)
        other = inter.pack_p_slice(*args, native=False,
                                   mv_per_pel=6 - per_pel)
        assert other != plain
        # plane layout (the served path's entry): the same slice
        lp = np.zeros((16 * mbh, 16 * mbw), np.int16)
        for mi in range(0, n, 2):
            lp[16 * (mi // mbw), 16 * (mi % mbw)] = 3
        z4 = np.zeros((n, 4), np.int16)
        zc = np.zeros((8 * mbh, 8 * mbw), np.int16)
        for use_native in (False, True):
            assert plain == inter.pack_p_slice_plane(
                mv.astype(np.int8), lp, z4, z4, zc, zc, mbw, mbh, sps,
                PPS(init_qp=27), 27, 1, native=use_native,
                mv_per_pel=per_pel)


class TestTheSetting:
    def teardown_method(self):
        reset_live_settings()

    def test_default_clamp_env_and_job_key(self, monkeypatch):
        assert DEFAULT_SETTINGS["subpel"] == "half"
        assert "subpel" in config.JOB_SETTING_KEYS
        base = Settings(values=dict(DEFAULT_SETTINGS))
        for raw, want in [("quarter", "quarter"), (" Quarter ", "quarter"),
                          ("half", "half"), ("eighth", "half"), (4, "half")]:
            assert overlay_job_settings(
                base, {"subpel": raw}).subpel == want
        assert rd_from_settings(base) == RD_OFF
        assert rd_from_settings(overlay_job_settings(
            base, {"subpel": "quarter"})).subpel == "quarter"
        monkeypatch.setenv("TVT_SUBPEL", "quarter")
        assert config.get_settings(refresh=True).subpel == "quarter"
        meta = VideoMeta(width=64, height=48, num_frames=2)
        one = default_mesh(jax.devices()[:1])
        assert GopShardEncoder(meta, mesh=one).rd.subpel == "quarter"
        # an environment's typo fails the encoder, it does not run at half
        monkeypatch.setenv("TVT_SUBPEL", "quater")
        config.get_settings(refresh=True)
        with pytest.raises(ValueError, match="subpel"):
            GopShardEncoder(meta, mesh=one)
        monkeypatch.delenv("TVT_SUBPEL")
        assert config.get_settings(refresh=True).subpel == "half"
        assert update_live_settings({"subpel": "quarter"}) \
            == {"subpel": "quarter"}
        assert config.get_settings().subpel == "quarter"
        assert update_live_settings({"subpel": "eighth"}) \
            == {"subpel": "half"}

    def test_lambda_is_per_unit(self):
        """The same displacement costs about the same under either
        unit: twice the units at half the price."""
        assert (jaxme.LAMBDA_Q * 2 - jaxme.LAMBDA_H).max() <= 1
        assert (jaxme.LAMBDA_Q * 2 - jaxme.LAMBDA_H).min() >= 0
        assert jaxme.LAMBDA_Q[25] == 6 and jaxme.LAMBDA_H[25] == 11

    def test_the_int8_transfer_holds_every_vector(self):
        from thinvids_tpu.codecs.h264 import jaxinter

        for subpel in rdo.SUBPELS:
            per_pel = rdo.MV_PER_PEL[subpel]
            reach = max(max(abs(qy), abs(qx))
                        for (_c, qy, qx) in jaxme.offset_table(subpel))
            assert per_pel * jaxme._CLIM + reach \
                <= per_pel * jaxme.SEARCH_RANGE <= 127
            jaxinter._check_mv8(RdConfig(subpel=subpel))


# ---------------------------------------------------------------------------
# through the coordinator, on the XLA mirror
# ---------------------------------------------------------------------------

JW, JH, JN, JGOP = 160, 128, 16, 8
JMETA = VideoMeta(width=JW, height=JH, fps_num=30, fps_den=1, num_frames=JN)


def _settings(**over):
    return Settings(values=dict(DEFAULT_SETTINGS, heartbeat_throttle_s=0.0,
                                gop_frames=JGOP, qp=27, **over))


def _run(tmp_path, name, path, job_settings=None, mesh=None, live=None,
         **settings):
    """One job through a coordinator whose settings are `settings`, the
    daemon's LIVE settings (where an encoder reads its RdConfig) being
    `live` (default: the same `subpel`)."""
    snap = _settings(**settings)
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(
        coord, output_dir=str(tmp_path / name), sync=True,
        mesh=mesh or default_mesh(jax.devices()[:1]))
    coord._launcher = execu.launch
    before = dispatch.stage_snapshot()
    update_live_settings(live if live is not None else
                         {"subpel": settings.get("subpel", "half")})
    try:
        job = coord.add_job(path, JMETA, settings=job_settings)
    finally:
        reset_live_settings()
    after = dispatch.stage_snapshot()
    return coord.store.get(job.id), {k: after[k] - before.get(k, 0)
                                     for k in after}


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("handheld")
    frames = handheld_frames(JN, JW, JH, seed=4)
    path = tmp / "clip.y4m"
    write_y4m(path, JMETA, frames)
    return tmp, frames, str(path)


def _vectors(job):
    media = read_mp4(job.output_path)
    return decode_annexb(media.annexb_for(0, media.num_frames))


class TestThroughTheCoordinator:
    def test_quarter_alone(self, source):
        tmp, frames, path = source
        job, grew = _run(tmp, "quarter", path, subpel="quarter")
        assert job.status is Status.DONE, job.failure_reason
        own = _vectors(job)
        assert len(own.frames) == JN and _odd_share(own.mvs) > 0.2
        # the counters saw what the stream holds; the gauge names the
        # executable that ran
        p_mbs = (JN - JN // JGOP) * (JW // 16) * (JH // 16)
        assert grew["mvs_coded"] == p_mbs
        assert grew["mvs_quarter"] == round(_odd_share(own.mvs) * p_mbs)
        assert dispatch.stage_snapshot()["me_candidates"] == 379 \
            == len(jaxme.offset_table("quarter"))
        half, grew = _run(tmp, "half", path)
        assert half.status is Status.DONE
        assert _odd_share(_vectors(half).mvs) == 0.0
        assert (grew["mvs_coded"], grew["mvs_quarter"]) == (p_mbs, 0)
        assert dispatch.stage_snapshot()["me_candidates"] == 227
        with open(job.output_path, "rb") as a, \
                open(half.output_path, "rb") as b:
            assert len(a.read()) < len(b.read())

    def test_with_scenecut(self, source):
        """GOPs planned on cuts run the bounded program; the hand-held
        clip has none, so the plan is the fixed grid's and every wave
        still takes the bounded form's path to the packer."""
        tmp, _frames, path = source
        job, grew = _run(tmp, "cuts", path, subpel="quarter", scenecut=40)
        assert job.status is Status.DONE, job.failure_reason
        assert grew["scenecut"] > 0
        assert _odd_share(_vectors(job).mvs) > 0.2

    def test_with_sfe_bands_on_a_mesh(self, source):
        tmp, _frames, path = source
        mesh = default_mesh(jax.devices()[:2])
        job, grew = _run(tmp, "bands", path, mesh=mesh, subpel="quarter",
                         job_settings={"sfe_bands": 2})
        assert job.status is Status.DONE, job.failure_reason
        assert grew["sfe_frames"] == JN
        own = _vectors(job)
        assert len(own.frames) == JN and _odd_share(own.mvs) > 0.2
        assert grew["mvs_quarter"] > 0

    def test_a_per_job_subpel_the_daemon_cannot_apply_is_refused(
            self, source):
        tmp, _frames, path = source
        job, grew = _run(tmp, "refused", path,
                         job_settings={"subpel": "quarter"})
        assert job.status is Status.REJECTED
        assert "subpel" in job.reject_reason \
            and "daemon-wide" in job.reject_reason
        assert grew["waves"] == 0
        # the daemon's own value, asked again per job, is no override
        job, _grew = _run(tmp, "same", path, subpel="quarter",
                          job_settings={"subpel": "quarter"})
        assert job.status is Status.DONE
        assert _odd_share(_vectors(job).mvs) > 0.2

    def test_on_the_remote_backend(self, source):
        """The plan's signature and every shard's descriptor carry the
        key: workers whose own daemon runs at half-sample precision
        encode the job at the coordinator's."""
        from thinvids_tpu.cluster import remote
        from thinvids_tpu.cluster.jobs import Job
        from thinvids_tpu.ingest.decode import read_video

        tmp, _frames, path = source
        sig = remote.RemoteExecutor._plan_signature
        probe = Job(id="j" * 12, input_path=path)
        assert sig(probe, _settings()) == sig(probe, _settings(subpel="half"))
        assert sig(probe, _settings()) != sig(probe,
                                              _settings(subpel="quarter"))
        snap = _settings(subpel="quarter", remote_plan_devices=1,
                         remote_shard_gops=1, remote_no_worker_grace_s=10.0)
        reg = WorkerRegistry()
        for i in range(8):
            reg.heartbeat(f"w{i:02d}", metrics={"worker": True})
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        execu = remote.RemoteExecutor(
            coord, output_dir=str(tmp / "farm"), sync=True, poll_s=0.02)
        coord._launcher = execu.launch
        stop, descs = threading.Event(), []
        clip = read_video(path)[1]
        mesh = default_mesh(jax.devices()[:1])

        def worker(host):
            while not stop.is_set():
                desc = execu.board.claim(host)
                if desc is None:
                    time.sleep(0.01)
                    continue
                descs.append(desc)
                execu.board.submit_part(
                    desc["id"], host,
                    remote.encode_shard(desc, clip, mesh=mesh))

        for i in range(2):
            threading.Thread(target=worker, args=(f"w{i:02d}",),
                             daemon=True).start()
        try:
            job = coord.store.get(coord.add_job(path, JMETA).id)
        finally:
            stop.set()
        assert job.status is Status.DONE, job.failure_reason
        assert len(descs) == JN // JGOP
        assert all(d["shape"] == "gop/quarter" for d in descs)
        assert _odd_share(_vectors(job).mvs) > 0.2

    def test_a_worker_from_before_the_setting_refuses_the_shard(self):
        """The precision rides in the shard's SHAPE tag, which a worker
        that knows only "gop" and "band" answers `unsupported` (its own
        check, remote.encode_shard's of before this setting, spelt out
        below), so no shard of a quarter plan is encoded at half
        precision under the plan's signature; the board then keeps the
        shard from that host. A half-precision shard's wire form is
        the one it was."""
        from thinvids_tpu.cluster import remote
        from thinvids_tpu.cluster.remote import ShardBoard

        def shard(**more):
            return remote.Shard(
                id="j-0", key="0", job_id="j", input_path="x", meta=JMETA,
                gops=plan_segments(JN, JGOP, 1).gops[:1], qp=27,
                gop_frames=JGOP, timeout_s=1.0, **more)

        def old_worker_takes(desc):
            shape = str(desc.get("shape", "gop") or "gop")
            return shape in ("gop", "band")

        assert "shape" not in shard().descriptor()
        assert "subpel" not in shard(subpel="quarter").descriptor()
        band = dict(shape="band", band_start=0, band_count=1,
                    total_bands=2, halo_rows=32)
        assert shard(**band).descriptor()["shape"] == "band"
        for more, tag in (({}, "gop/quarter"), (band, "band/quarter")):
            desc = shard(subpel="quarter", **more).descriptor()
            assert desc["shape"] == tag and not old_worker_takes(desc)
            assert remote.wire_shape(desc) == (tag.split("/")[0], "quarter")
        assert remote.wire_shape(shard().descriptor()) == ("gop", "half")
        # this worker, handed a precision of a later one
        desc = shard(subpel="quarter").descriptor()
        desc["shape"] = "gop/eighth"
        with pytest.raises(remote.UnsupportedShardShape, match="eighth"):
            remote.encode_shard(desc, [])
        # and what the board does with that answer
        reg = WorkerRegistry()
        for host in ("old", "new"):
            reg.heartbeat(host, metrics={"worker": True})
        board = ShardBoard(Coordinator(registry=reg,
                                       settings_fn=_settings))
        quarter = shard(subpel="quarter")
        board.add_job("j", [quarter], max_attempts=3, backoff_s=5.0,
                      quarantine_after=3)
        assert board.claim("old")["shape"] == "gop/quarter"
        board.report_unsupported("j-0", "old", "shard shape 'gop/quarter' "
                                 "not implemented by this worker")
        assert quarter.attempt == 0 and "old" in quarter.no_hosts
        assert board.claim("old") is None
        assert board.claim("new")["id"] == "j-0"
