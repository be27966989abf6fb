"""ABR ladder subsystem tests (thinvids_tpu/abr/).

Layers: downscaler parity against an independent pure-numpy polyphase
reference (odd/even dims, 4:2:0 chroma), ladder planning (rung dims /
QP model), the decode+H2D-once invariant (`h2d_bytes` must not scale
with rung count) and top-rung byte identity with the single-rendition
path, HLS packaging + playlist conformance lint (positive and
tampered), the executor end-to-end ladder job (watch-folder naming →
DONE → servable master.m3u8 with decodable rungs), the remote-farm
rung×shard path, and the jax-free grep guard on ladder.py/hls.py.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from thinvids_tpu.abr import hls
from thinvids_tpu.abr.ladder import (LadderShardEncoder, plan_ladder,
                                     rung_segments)
from thinvids_tpu.abr.scale import (LANCZOS_A, PlaneScaler,
                                    lanczos_kernel, resample_matrix)
from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
from thinvids_tpu.core.status import Status
from thinvids_tpu.core.types import (Frame, VideoMeta, concat_segments)
from thinvids_tpu.io.y4m import write_y4m
from thinvids_tpu.parallel.dispatch import GopShardEncoder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_settings(**over):
    values = dict(DEFAULT_SETTINGS)
    values.update(over)
    return Settings(values=values)


def textured_frames(w, h, n, seed=0):
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    base = (xx * 1.7 + yy * 0.9) % 256 + 20 * np.sin(xx * 0.2)
    frames = []
    for i in range(n):
        y = np.clip(base + 5 * i + rng.normal(0, 3, (h, w)), 0,
                    255).astype(np.uint8)
        u = np.clip(120 + 30 * np.sin(yy[::2, ::2] * 0.05 + i), 0,
                    255).astype(np.uint8)
        v = np.clip(130 + 30 * np.cos(xx[::2, ::2] * 0.04 + i), 0,
                    255).astype(np.uint8)
        frames.append(Frame(y=y, u=u, v=v))
    return frames


# ---------------------------------------------------------------------------
# downscaler
# ---------------------------------------------------------------------------


def reference_polyphase(plane: np.ndarray, src_valid: int, dst_valid: int,
                        axis: int) -> np.ndarray:
    """Independent pure-numpy polyphase Lanczos-3 along one axis
    (direct per-output-tap convolution — no shared code with
    abr/scale.py's matrix builder)."""
    moved = np.moveaxis(plane.astype(np.float64), axis, 0)
    ratio = src_valid / dst_valid
    support = LANCZOS_A * ratio
    out = np.zeros((dst_valid,) + moved.shape[1:], np.float64)
    for i in range(dst_valid):
        center = (i + 0.5) * ratio - 0.5
        acc = np.zeros(moved.shape[1:], np.float64)
        wsum = 0.0
        j = int(np.floor(center - support)) + 1
        while j < center + support:
            wj = float(lanczos_kernel(
                np.array([(j - center) / ratio]))[0])
            acc += wj * moved[min(max(j, 0), src_valid - 1)]
            wsum += wj
            j += 1
        out[i] = acc / wsum
    return np.moveaxis(out, 0, axis)


class TestScale:
    def test_matrix_rows_normalized_and_edge_clamped(self):
        m = resample_matrix(64, 32, src_valid=50, dst_valid=24)
        np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-5)
        # taps never sample the padding beyond the valid source range
        assert np.all(m[:, 50:] == 0.0)
        # padded output rows repeat the last valid row
        np.testing.assert_array_equal(m[24], m[23])
        np.testing.assert_array_equal(m[31], m[23])

    @pytest.mark.parametrize("src,dst", [
        ((64, 48), (32, 24)),        # clean power-of-two, mb-aligned
        ((62, 50), (36, 24)),        # even, not mb-aligned
        ((61, 37), (24, 16)),        # odd luma dims (odd chroma too)
    ])
    def test_device_scale_matches_numpy_polyphase_reference(self, src,
                                                            dst):
        w, h = src
        dw, dh = dst
        rng = np.random.default_rng(7)
        frame = Frame(
            y=rng.integers(0, 256, (h, w), np.uint8),
            u=rng.integers(0, 256, ((h + 1) // 2, (w + 1) // 2),
                           np.uint8),
            v=rng.integers(0, 256, ((h + 1) // 2, (w + 1) // 2),
                           np.uint8)).padded(16)
        sc = PlaneScaler(w, h, dw, dh)
        dy, du, dv = sc.scale_wave(jnp.asarray(frame.y[None]),
                                   jnp.asarray(frame.u[None]),
                                   jnp.asarray(frame.v[None]))
        # reference works on the VALID region with its own edge clamp
        ref_y = reference_polyphase(
            reference_polyphase(frame.y, h, dh, axis=0), w, dw, axis=1)
        ref_y = np.clip(np.floor(ref_y + 0.5), 0, 255).astype(np.uint8)
        got_y = np.asarray(dy[0])[:dh, :dw]
        diff = np.abs(got_y.astype(int) - ref_y.astype(int))
        # ≤1 LSB from float summation order; overwhelmingly exact
        assert diff.max() <= 1
        assert (diff == 0).mean() > 0.95
        for plane, dev in (("u", du), ("v", dv)):
            p = getattr(frame, plane)
            ch, cw = (h + 1) // 2, (w + 1) // 2
            ref = reference_polyphase(
                reference_polyphase(p, ch, dh // 2, axis=0),
                cw, dw // 2, axis=1)
            ref = np.clip(np.floor(ref + 0.5), 0, 255).astype(np.uint8)
            got = np.asarray(dev[0])[:dh // 2, :dw // 2]
            assert np.abs(got.astype(int) - ref.astype(int)).max() <= 1

    def test_psnr_floor_on_real_decoded_frame(self, tmp_path):
        """Scale a frame decoded from a REAL encoded stream and pin a
        PSNR floor against an independent resampler (cv2 INTER_AREA):
        the device scaler must produce the picture, not just match its
        own reference."""
        import cv2

        from thinvids_tpu.io.mp4 import write_mp4

        w, h, n = 128, 96, 4
        frames = textured_frames(w, h, n)
        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=n)
        enc = GopShardEncoder(meta, qp=24, gop_frames=n)
        stream = concat_segments(enc.encode(frames))
        path = str(tmp_path / "clip.mp4")
        write_mp4(path, stream, meta)
        cap = cv2.VideoCapture(path)
        ok, img = cap.read()
        cap.release()
        assert ok
        decoded_y = cv2.cvtColor(img, cv2.COLOR_BGR2YUV)[:, :, 0]

        dw, dh = 64, 48
        frame = Frame(y=decoded_y,
                      u=np.full((h // 2, w // 2), 128, np.uint8),
                      v=np.full((h // 2, w // 2), 128, np.uint8)
                      ).padded(16)
        sc = PlaneScaler(w, h, dw, dh)
        dy, _du, _dv = sc.scale_wave(jnp.asarray(frame.y[None]),
                                     jnp.asarray(frame.u[None]),
                                     jnp.asarray(frame.v[None]))
        got = np.asarray(dy[0])[:dh, :dw].astype(np.float64)
        want = cv2.resize(decoded_y, (dw, dh),
                          interpolation=cv2.INTER_AREA).astype(np.float64)
        mse = np.mean((got - want) ** 2)
        psnr = 10 * np.log10(255.0 ** 2 / max(mse, 1e-9))
        assert psnr >= 30.0, f"downscale PSNR {psnr:.1f} dB below floor"


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


class TestLadderPlan:
    def test_default_ladder_from_1080p(self):
        meta = VideoMeta(width=1920, height=1080)
        rungs = plan_ladder(meta, make_settings(qp=27))
        assert [(r.width, r.height) for r in rungs] == [
            (1920, 1080), (1280, 720), (854, 480), (640, 360)]
        assert rungs[0].top and not any(r.top for r in rungs[1:])
        # top rung keeps the base QP exactly (byte-identity anchor);
        # lower rungs encode finer under the octave ladder model
        assert rungs[0].qp == 27
        qps = [r.qp for r in rungs]
        assert qps == sorted(qps, reverse=True)
        assert all(r.qp <= 27 for r in rungs)
        assert all(r.width % 2 == 0 and r.height % 2 == 0 for r in rungs)

    def test_rungs_at_or_above_source_collapse(self):
        meta = VideoMeta(width=1280, height=720)
        rungs = plan_ladder(meta, make_settings(qp=30))
        assert [(r.width, r.height) for r in rungs] == [
            (1280, 720), (854, 480), (640, 360)]

    def test_junk_and_custom_spec(self):
        meta = VideoMeta(width=640, height=480)
        rungs = plan_ladder(
            meta, make_settings(qp=30, ladder_rungs="360p, nope, 240,"))
        assert [(r.height) for r in rungs] == [480, 360, 240]

    def test_filename_convention_is_stem_suffix_only(self):
        """`name.ladder.ext` opts in; derived names (stamped copies)
        must NOT inherit the ladder type."""
        snap = make_settings(auto_start_jobs=False)
        coord = Coordinator(registry=WorkerRegistry(),
                            settings_fn=lambda: snap)
        meta = VideoMeta(width=64, height=48, num_frames=4)
        assert coord.add_job("/w/a.ladder.y4m", meta).job_type \
            == "ladder"
        assert coord.add_job("/w/a.ladder.stamped.y4m", meta).job_type \
            == "transcode"
        assert coord.add_job("/w/plain.y4m", meta).job_type \
            == "transcode"
        assert coord.add_job("/w/plain2.y4m", meta,
                             job_type="ladder").job_type == "ladder"

    def test_live_setting_clamp_uses_canonical_parser(self):
        from thinvids_tpu.core.config import _validate_setting

        assert _validate_setting("ladder_rungs",
                                 "360p; junk, 720 ,720") == "720,360"
        assert _validate_setting("ladder_rungs", "nope") \
            == "1080,720,480,360"


# ---------------------------------------------------------------------------
# ladder encode: identity + upload invariant
# ---------------------------------------------------------------------------


class TestLadderEncode:
    W, H, N, GOP = 64, 48, 16, 4

    def _meta(self):
        return VideoMeta(width=self.W, height=self.H, fps_num=30,
                         fps_den=1, num_frames=self.N)

    def test_top_rung_byte_identical_and_h2d_once(self):
        frames = textured_frames(self.W, self.H, self.N)
        meta = self._meta()
        snap = make_settings(qp=30, ladder_rungs="32,24")
        rungs = plan_ladder(meta, snap)
        assert len(rungs) == 3

        ladder = LadderShardEncoder(meta, rungs, gop_frames=self.GOP)
        bundles = ladder.encode(frames)
        single = GopShardEncoder(meta, qp=30, gop_frames=self.GOP)
        ref = concat_segments(single.encode(frames))

        top = concat_segments(rung_segments(bundles, rungs[0].name))
        assert top == ref                      # byte-identical top rung

        snap_ladder = ladder.stages.snapshot()
        h2d_single = single.stages.snapshot()["h2d_bytes"]
        assert h2d_single > 0
        # decode + H2D once per wave: a 3-rung ladder uploads EXACTLY
        # what the single-rendition encode uploads
        assert snap_ladder["h2d_bytes"] == h2d_single
        # the aggregated profile carries the scaled rungs' host work
        # (pack/dispatch), not just the stager's, plus the scale stage
        assert snap_ladder["pack"] > 0 and snap_ladder["scale"] > 0

        # every rung shares the GOP plan (count + frame ranges)
        for rung in rungs[1:]:
            segs = rung_segments(bundles, rung.name)
            assert [(s.gop.index, s.gop.start_frame, s.gop.num_frames)
                    for s in segs] == \
                   [(s.gop.index, s.gop.start_frame, s.gop.num_frames)
                    for s in rung_segments(bundles, rungs[0].name)]

    def test_h2d_does_not_scale_with_rung_count(self):
        frames = textured_frames(self.W, self.H, 8)
        meta = VideoMeta(width=self.W, height=self.H, fps_num=30,
                         fps_den=1, num_frames=8)
        totals = []
        for spec in ("32", "32,24"):
            rungs = plan_ladder(meta, make_settings(qp=30,
                                                    ladder_rungs=spec))
            enc = LadderShardEncoder(meta, rungs, gop_frames=4)
            enc.encode(frames)
            totals.append(enc.stages.snapshot()["h2d_bytes"])
        assert totals[0] == totals[1] > 0

    def test_rung_streams_decode_at_rung_dims(self):
        """Every rung's bitstream decodes cleanly at its own dims
        (cv2/ffmpeg as the independent decoder)."""
        import cv2

        from thinvids_tpu.io.mp4 import write_mp4

        frames = textured_frames(self.W, self.H, 8)
        meta = VideoMeta(width=self.W, height=self.H, fps_num=30,
                         fps_den=1, num_frames=8)
        rungs = plan_ladder(meta, make_settings(qp=30,
                                                ladder_rungs="32,24"))
        bundles = LadderShardEncoder(meta, rungs,
                                     gop_frames=4).encode(frames)
        import tempfile

        for rung in rungs:
            stream = concat_segments(rung_segments(bundles, rung.name))
            rmeta = VideoMeta(width=rung.width, height=rung.height,
                              fps_num=30, fps_den=1, num_frames=8)
            with tempfile.NamedTemporaryFile(suffix=".mp4") as fp:
                write_mp4(fp.name, stream, rmeta)
                cap = cv2.VideoCapture(fp.name)
                count = 0
                while True:
                    ok, img = cap.read()
                    if not ok:
                        break
                    assert img.shape[:2] == (rung.height, rung.width)
                    count += 1
                cap.release()
            assert count == 8, f"rung {rung.name} decoded {count}/8"


# ---------------------------------------------------------------------------
# HLS packaging + conformance lint
# ---------------------------------------------------------------------------


def packaged_ladder(tmp_path, segment_s=0.25, n=16):
    w, h = 64, 48
    frames = textured_frames(w, h, n)
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=n)
    rungs = plan_ladder(meta, make_settings(qp=30, ladder_rungs="32,24"))
    bundles = LadderShardEncoder(meta, rungs, gop_frames=4).encode(frames)
    out = str(tmp_path / "out.hls")
    streams = [hls.RungStream(r.name, r.width, r.height,
                              rung_segments(bundles, r.name))
               for r in rungs]
    master = hls.package_ladder(out, streams, 30, 1,
                                segment_s=segment_s)
    return out, master, rungs, n


class TestHlsPackaging:
    def test_lint_passes_and_boundaries_align(self, tmp_path):
        out, master, rungs, n = packaged_ladder(tmp_path)
        info = hls.lint_ladder(out, expected_duration_s=n / 30)
        assert info["rungs"] == len(rungs) == 3
        assert info["segments"] > 1            # actually segmented
        # EXTINF sums match the stream duration exactly (lint arg) and
        # BANDWIDTH is monotonic (lint raises otherwise)
        assert info["bandwidths"] == sorted(info["bandwidths"])

    def test_master_attributes(self, tmp_path):
        out, master, rungs, _n = packaged_ladder(tmp_path)
        text = open(master).read()
        for rung in rungs:
            assert f"RESOLUTION={rung.width}x{rung.height}" in text
            assert f"{rung.name}/media.m3u8" in text
        assert 'CODECS="avc1.42C0' in text
        assert "FRAME-RATE=30.000" in text

    def test_segments_open_on_idr_and_samples_read_back(self, tmp_path):
        out, _master, rungs, n = packaged_ladder(tmp_path)
        for rung in rungs:
            rung_dir = os.path.join(out, rung.name)
            init = open(os.path.join(rung_dir, hls.INIT_NAME),
                        "rb").read()
            entry = hls.init_video_entry(init)
            assert entry[4:8] == b"avc1"
            total = 0
            for name in sorted(os.listdir(rung_dir)):
                if not name.endswith(".m4s"):
                    continue
                seg = open(os.path.join(rung_dir, name), "rb").read()
                samples = hls.segment_track_samples(seg, track_id=1)
                assert samples, f"{rung.name}/{name} has no samples"
                # first sample of every segment is an IDR NAL
                nal_type = samples[0][4] & 0x1F
                assert nal_type == 5, f"segment opens on NAL {nal_type}"
                total += len(samples)
            assert total == n

    def test_lint_rejects_extinf_over_target_duration(self, tmp_path):
        out, _master, rungs, _n = packaged_ladder(tmp_path)
        mp = os.path.join(out, rungs[0].name, hls.MEDIA_PLAYLIST)
        text = open(mp).read().replace("#EXTINF:0.26667,",
                                       "#EXTINF:5.00000,", 1)
        open(mp, "w").write(text)
        with pytest.raises(ValueError, match="TARGETDURATION"):
            hls.lint_ladder(out)

    def test_lint_rejects_non_monotonic_bandwidth(self, tmp_path):
        out, master, _rungs, _n = packaged_ladder(tmp_path)
        lines = open(master).read().splitlines()
        # swap the first variant's BANDWIDTH to a huge value
        for i, line in enumerate(lines):
            if line.startswith("#EXT-X-STREAM-INF:"):
                lines[i] = line.replace("BANDWIDTH=",
                                        "BANDWIDTH=9999999990", 1)
                break
        open(master, "w").write("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="monotonic"):
            hls.lint_ladder(out)

    def test_lint_rejects_boundary_mismatch(self, tmp_path):
        out, _master, rungs, _n = packaged_ladder(tmp_path)
        mp = os.path.join(out, rungs[1].name, hls.MEDIA_PLAYLIST)
        text = open(mp).read().replace("#EXTINF:0.26667,",
                                      "#EXTINF:0.40000,", 1)
        open(mp, "w").write(text)
        with pytest.raises(ValueError, match="differ|sum"):
            hls.lint_ladder(out)

    def test_package_rejects_misaligned_rung_plans(self, tmp_path):
        out, _master, _rungs, _n = packaged_ladder(tmp_path)
        # reuse one rung's real segments, drop one from the other rung
        frames = textured_frames(64, 48, 8)
        meta = VideoMeta(width=64, height=48, fps_num=30, fps_den=1,
                         num_frames=8)
        rungs = plan_ladder(meta, make_settings(qp=30,
                                                ladder_rungs="24"))
        bundles = LadderShardEncoder(meta, rungs,
                                     gop_frames=4).encode(frames)
        top = rung_segments(bundles, rungs[0].name)
        low = rung_segments(bundles, rungs[1].name)[:-1]
        with pytest.raises(ValueError, match="align"):
            hls.package_ladder(
                str(tmp_path / "bad.hls"),
                [hls.RungStream("48p", 64, 48, top),
                 hls.RungStream("24p", 32, 24, low)], 30, 1)


# ---------------------------------------------------------------------------
# executor end-to-end (local + watch-folder naming + serving)
# ---------------------------------------------------------------------------


def make_rig(tmp_path, snap):
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / "library"),
                          sync=True)
    coord._launcher = execu.launch
    return coord, execu


class TestLadderJobEndToEnd:
    def test_watch_named_ladder_job_to_served_master(self, tmp_path):
        w, h, n = 64, 48, 16
        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=n)
        clip = tmp_path / "clip.ladder.y4m"     # watch-folder naming
        write_y4m(str(clip), meta, textured_frames(w, h, n))
        snap = make_settings(qp=30, gop_frames=4, segment_s=0.25,
                             ladder_rungs="32,24",
                             heartbeat_throttle_s=0.0)
        coord, _execu = make_rig(tmp_path, snap)
        job = coord.add_job(str(clip), meta)
        job = coord.store.get(job.id)
        assert job.job_type == "ladder"          # from the filename
        assert job.status is Status.DONE, job.failure_reason
        assert job.output_path.endswith("master.m3u8")
        assert os.path.exists(job.output_path)
        out_dir = os.path.dirname(job.output_path)
        info = hls.lint_ladder(out_dir, expected_duration_s=n / 30)
        assert info["rungs"] == 3
        assert job.parts_done == job.parts_total > 0
        assert job.output_bytes > 0

        # the API serves the tree at /hls/<job>/...
        from thinvids_tpu.api.server import ApiServer, _FileResponse

        api = ApiServer(coord)
        status, payload = api.route("GET", f"/hls/{job.id}/master.m3u8",
                                    {}, {})
        assert status == 200 and isinstance(payload, _FileResponse)
        assert payload.content_type == "application/vnd.apple.mpegurl"
        status, payload = api.route(
            "GET", f"/hls/{job.id}/32p/media.m3u8", {}, {})
        assert status == 200
        status, payload = api.route(
            "GET", f"/hls/{job.id}/32p/init.mp4", {}, {})
        assert status == 200 and payload.content_type == "video/mp4"
        # traversal + junk rejected
        from thinvids_tpu.api.server import ApiError

        with pytest.raises(ApiError):
            api.route("GET", f"/hls/{job.id}/../../etc/passwd", {}, {})
        with pytest.raises(ApiError):
            api.route("GET", f"/hls/{job.id}/32p/evil.sh", {}, {})
        # /preview must not hand a playlist out labelled video/mp4
        with pytest.raises(ApiError, match="master.m3u8"):
            api.route("GET", f"/preview/{job.id}", {}, {})

    def test_audio_passthrough_fragment_track(self, tmp_path):
        """A RungStream with audio carries it bit-exact as a second
        fragment track (second trak in init + second traf per segment,
        audio codec in that variant's CODECS); audio=None stays
        video-only. (The executor attaches audio to every rung — this
        pins the per-stream plumbing underneath.)"""
        from thinvids_tpu.io.mp4 import Mp4Track, _box

        w, h, n = 64, 48, 8
        frames = textured_frames(w, h, n)
        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=n)
        # fabricate a passthrough-able audio track (opaque sample entry)
        entry = _box(b"mp4a", b"\x00" * 28)
        audio = Mp4Track(handler="soun", stsd_entry=entry,
                         timescale=48000,
                         stts=[(4, 12000)],
                         samples=[bytes([i] * 8) for i in range(4)])
        rungs = plan_ladder(meta, make_settings(qp=30,
                                                ladder_rungs="24"))
        bundles = LadderShardEncoder(meta, rungs,
                                     gop_frames=4).encode(frames)
        out = str(tmp_path / "a.hls")
        streams = [hls.RungStream(r.name, r.width, r.height,
                                  rung_segments(bundles, r.name),
                                  audio=audio if r.top else None)
                   for r in rungs]
        master = hls.package_ladder(out, streams, 30, 1, segment_s=0.15)
        hls.lint_ladder(out)
        # the muxed variant must declare BOTH codecs (RFC 8216
        # §4.3.4.2) or players never bring up the audio decoder
        text = open(master).read()
        top_inf = [l for l in text.splitlines()
                   if l.startswith("#EXT-X-STREAM-INF") and
                   f"RESOLUTION={rungs[0].width}x{rungs[0].height}"
                   in l][0]
        assert "mp4a.40.2" in top_inf
        low_inf = [l for l in text.splitlines()
                   if l.startswith("#EXT-X-STREAM-INF") and
                   f"RESOLUTION={rungs[1].width}x{rungs[1].height}"
                   in l][0]
        assert "mp4a" not in low_inf
        top_dir = os.path.join(out, rungs[0].name)
        init = open(os.path.join(top_dir, hls.INIT_NAME), "rb").read()
        assert init.count(b"trak") >= 2 and b"mp4a" in init
        got_audio = []
        for name in sorted(os.listdir(top_dir)):
            if name.endswith(".m4s"):
                seg = open(os.path.join(top_dir, name), "rb").read()
                got_audio.extend(hls.segment_track_samples(seg,
                                                           track_id=2))
        assert got_audio == audio.samples       # bit-exact passthrough
        low_dir = os.path.join(out, rungs[1].name)
        low_init = open(os.path.join(low_dir, hls.INIT_NAME),
                        "rb").read()
        assert b"mp4a" not in low_init


# ---------------------------------------------------------------------------
# remote farm: rungs × shards
# ---------------------------------------------------------------------------


def board_worker(board, host, stop):
    """Fake worker thread claiming straight off the board with the real
    shard encoder (the test_remote harness pattern)."""
    from thinvids_tpu.cluster.remote import encode_shard
    from thinvids_tpu.ingest.decode import read_video

    cache = {}

    def loop():
        while not stop.is_set():
            desc = board.claim(host)
            if desc is None:
                time.sleep(0.01)
                continue
            path = desc["input_path"]
            if path not in cache:
                cache[path] = read_video(path)[1]
            segs = encode_shard(desc, cache[path])
            board.submit_part(desc["id"], host, segs)

    t = threading.Thread(target=loop, daemon=True,
                         name=f"fake-worker-{host}")
    t.start()
    return t


class TestRemoteLadder:
    def test_rung_shard_encodes_bit_identical_to_local_ladder(
            self, tmp_path):
        """A worker's scaled-rung shard (device downscale on ITS mesh)
        reproduces the coordinator-local ladder encode bit for bit."""
        from thinvids_tpu.cluster.remote import Shard, encode_shard

        w, h, n = 64, 48, 8
        frames = textured_frames(w, h, n)
        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=n)
        rungs = plan_ladder(meta, make_settings(qp=30,
                                                ladder_rungs="24"))
        ladder = LadderShardEncoder(meta, rungs, gop_frames=4)
        bundles = ladder.encode(frames)
        want = rung_segments(bundles, rungs[1].name)

        plan = ladder.plan(n)
        shard = Shard(
            id="j-24p-0000", job_id="j", input_path="x.y4m", meta=meta,
            gops=plan.gops, qp=rungs[1].qp, gop_frames=4,
            timeout_s=60.0, rung=rungs[1].name,
            rung_width=rungs[1].width, rung_height=rungs[1].height)
        got = encode_shard(shard.descriptor(), frames)
        assert [s.payload for s in got] == [s.payload for s in want]

    def test_remote_ladder_job_end_to_end(self, tmp_path):
        from thinvids_tpu.cluster.remote import RemoteExecutor

        w, h, n = 64, 48, 16
        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=n)
        clip = tmp_path / "farm.ladder.y4m"
        write_y4m(str(clip), meta, textured_frames(w, h, n))
        snap = make_settings(
            qp=30, gop_frames=2, segment_s=0.25, ladder_rungs="32,24",
            heartbeat_throttle_s=0.0, remote_plan_devices=8,
            remote_shard_gops=2, remote_no_worker_grace_s=10.0)
        reg = WorkerRegistry()
        for i in range(8):
            reg.heartbeat(f"w{i:02d}", metrics={"worker": True})
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        execu = RemoteExecutor(coord, output_dir=str(tmp_path / "lib"),
                               sync=True, poll_s=0.02)
        coord._launcher = execu.launch
        stop = threading.Event()
        for i in range(2):
            board_worker(execu.board, f"w{i:02d}", stop)
        try:
            job = coord.add_job(str(clip), meta)
        finally:
            stop.set()
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        # rungs × GOPs parts accounting: 8 GOPs × 3 rungs
        assert job.parts_total == 24 and job.parts_done == 24
        assert job.output_path.endswith("master.m3u8")
        info = hls.lint_ladder(os.path.dirname(job.output_path),
                               expected_duration_s=n / 30)
        assert info["rungs"] == 3


# ---------------------------------------------------------------------------
# jax-free guard
# ---------------------------------------------------------------------------


def test_ladder_and_hls_are_manifested_jax_free(analysis_ctx):
    """Packaging and planning must run on jax-free worker/sidecar
    processes. Migrated from a
    subprocess import probe to the analyzer's import-graph proof: the
    manifest must keep declaring both modules jax-free, and the
    confinement pass (which walks the TRANSITIVE module-scope import
    closure, package __init__ chains included) must be clean on HEAD.
    Tree-wide enforcement rides `cli.py check` in tier-1."""
    from thinvids_tpu.analysis import imports
    from thinvids_tpu.analysis.astutil import matches_any

    m, tree = analysis_ctx
    for mod in ("thinvids_tpu.abr.ladder", "thinvids_tpu.abr.hls"):
        assert matches_any(mod, m.jax_free), (
            f"manifest no longer declares {mod} jax-free")
    open_ = [f for f in imports.check_jax_confinement(tree, m)
             if f.key not in m.waivers and f.module.startswith(
                 "thinvids_tpu.abr")]
    assert not open_, "\n".join(f.format() for f in open_)
