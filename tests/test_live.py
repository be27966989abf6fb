"""Live LL-HLS subsystem tests (thinvids_tpu/live/ + ingest/tail.py).

Layers: tail-ingest edge cases (mid-frame partial append, writer
stall-then-resume, stall-timeout / .eos end-of-stream, header-late
open), live playlist rendering + conformance lint (positive and
tampered: MEDIA-SEQUENCE monotonicity, part-duration bound, ENDLIST
contradictions), the watcher's live-name fast path, the settings-key
hygiene gate (every config key must have a reader — VERDICT Weak #3),
the LL-HLS blocking-reload gate, and the end-to-end live job: a
background writer appends y4m while a reader polls the playlist and
fetches segments BEFORE the job finishes; when the writer closes the
stream the final tree gains EXT-X-ENDLIST and passes the existing VOD
conformance lint. A DVR-window variant proves MEDIA-SEQUENCE advance
plus on-disk GC.
"""

import io
import os
import threading
import time

import numpy as np
import pytest

from thinvids_tpu.abr import hls
from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
from thinvids_tpu.core.status import Status
from thinvids_tpu.core.types import Frame, VideoMeta
from thinvids_tpu.ingest.decode import DecodeError
from thinvids_tpu.ingest.tail import (EOS_SUFFIX, TailFrameSource,
                                      is_live_name, spool_stream)
from thinvids_tpu.io.y4m import Y4MWriter

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


def frame_records(meta, frames):
    """(header bytes, [one record per frame]) for incremental writes."""
    buf = io.BytesIO()
    writer = Y4MWriter(buf, meta)
    header = buf.getvalue()
    records = []
    for frame in frames:
        buf.seek(0)
        buf.truncate()
        writer.write(frame)
        records.append(buf.getvalue())
    return header, records


W, H = 64, 48
META = VideoMeta(width=W, height=H, fps_num=30, fps_den=1)


# ---------------------------------------------------------------------------
# tail ingest
# ---------------------------------------------------------------------------


class TestTailIngest:
    def test_mid_frame_partial_append_not_counted(self, tmp_path):
        frames = textured_frames(W, H, 3)
        header, recs = frame_records(META, frames)
        path = str(tmp_path / "grow.live.y4m")
        with open(path, "wb") as fp:
            fp.write(header + recs[0] + recs[1][: len(recs[1]) // 2])
        tail = TailFrameSource(path, stall_timeout_s=1.0, poll_s=0.01)
        assert tail.available() == 1          # torn record excluded
        got = list(tail.iter_frames())
        assert len(got) == 1
        assert np.array_equal(got[0].y, frames[0].y)
        # completing the torn record makes frame 2 visible
        with open(path, "ab") as fp:
            fp.write(recs[1][len(recs[1]) // 2:])
        assert tail.available() == 2
        assert not tail.ended

    def test_writer_stall_then_resume(self, tmp_path):
        frames = textured_frames(W, H, 4)
        header, recs = frame_records(META, frames)
        path = str(tmp_path / "grow.live.y4m")
        with open(path, "wb") as fp:
            fp.write(header + recs[0])
        tail = TailFrameSource(path, stall_timeout_s=5.0, poll_s=0.005)

        def resume():
            time.sleep(0.15)                  # a stall SHORTER than the
            with open(path, "ab") as fp:      # budget, then more frames
                fp.write(recs[1] + recs[2])
        t = threading.Thread(target=resume)
        t.start()
        n = tail.wait_frames(3)
        t.join()
        assert n == 3 and not tail.ended
        assert [f.pts for f in tail.iter_frames(1, 3)] == [1, 2]

    def test_stall_timeout_is_clean_end_of_stream(self, tmp_path):
        header, recs = frame_records(META, textured_frames(W, H, 2))
        path = str(tmp_path / "grow.live.y4m")
        with open(path, "wb") as fp:
            fp.write(header + recs[0] + recs[1])
        tail = TailFrameSource(path, stall_timeout_s=0.5, poll_s=0.01)
        t0 = time.monotonic()
        n = tail.wait_frames(10)              # never arrives
        assert tail.ended and n == 2
        assert time.monotonic() - t0 >= 0.4

    def test_eos_marker_ends_without_waiting_out_the_stall(self, tmp_path):
        header, recs = frame_records(META, textured_frames(W, H, 1))
        path = str(tmp_path / "grow.live.y4m")
        with open(path, "wb") as fp:
            fp.write(header + recs[0])
        with open(path + EOS_SUFFIX, "wb"):
            pass
        tail = TailFrameSource(path, stall_timeout_s=30.0, poll_s=0.01)
        t0 = time.monotonic()
        n = tail.wait_frames(5)
        assert tail.ended and n == 1
        assert time.monotonic() - t0 < 5.0

    def test_header_arriving_late_is_waited_for(self, tmp_path):
        header, recs = frame_records(META, textured_frames(W, H, 1))
        path = str(tmp_path / "grow.live.y4m")
        with open(path, "wb"):
            pass                              # file exists, empty

        def write_header():
            time.sleep(0.1)
            with open(path, "ab") as fp:
                fp.write(header + recs[0])
        t = threading.Thread(target=write_header)
        t.start()
        tail = TailFrameSource(path, stall_timeout_s=5.0, poll_s=0.01)
        t.join()
        assert tail.wait_frames(1) == 1

    def test_header_never_arriving_raises_decode_error(self, tmp_path):
        path = str(tmp_path / "never.live.y4m")
        with pytest.raises(DecodeError):
            TailFrameSource(path, stall_timeout_s=0.3, poll_s=0.01)

    def test_stop_check_aborts_wait_early(self, tmp_path):
        header, recs = frame_records(META, textured_frames(W, H, 1))
        path = str(tmp_path / "grow.live.y4m")
        with open(path, "wb") as fp:
            fp.write(header + recs[0])
        tail = TailFrameSource(path, stall_timeout_s=30.0, poll_s=0.005)
        t0 = time.monotonic()
        tail.wait_frames(5, stop_check=lambda: True)
        assert time.monotonic() - t0 < 1.0
        assert not tail.ended                 # aborted, not ended

    def test_spool_stream_reproduces_file_and_marks_eos(self, tmp_path):
        header, recs = frame_records(META, textured_frames(W, H, 3))
        data = header + b"".join(recs)
        path = str(tmp_path / "sock.live.y4m")
        n = spool_stream(io.BytesIO(data), path, chunk_bytes=64)
        assert n == len(data)
        assert open(path, "rb").read() == data
        assert os.path.exists(path + EOS_SUFFIX)
        tail = TailFrameSource(path, stall_timeout_s=5.0)
        assert tail.wait_frames(99) == 3 and tail.ended

    def test_live_name_convention_is_stem_suffix_only(self):
        assert is_live_name("cam1.live.y4m")
        assert is_live_name("/a/b/Show.LIVE.Y4M")
        assert not is_live_name("clip.y4m")
        assert not is_live_name("clip.live.stamped.y4m")
        assert not is_live_name("alive.y4m")


# ---------------------------------------------------------------------------
# live playlist rendering + lint
# ---------------------------------------------------------------------------


def _snapshot(tmp_path, segments, open_parts, **kw):
    kw.setdefault("media_sequence", 0)
    kw.setdefault("target_s", 1.0)
    kw.setdefault("part_target_s", 0.2)
    text = hls.render_live_media_playlist(segments, open_parts, **kw)
    path = str(tmp_path / "media.m3u8")
    with open(path, "w", encoding="utf-8") as fp:
        fp.write(text)
    return path, text


def _seg(i, parts=2, part_s=0.2):
    plist = [hls.LivePart(uri=hls.PART_PATTERN % (i, p),
                          duration_s=part_s) for p in range(parts)]
    return hls.LiveSegmentRef(uri=hls.SEGMENT_PATTERN % i,
                              duration_s=parts * part_s, parts=plist)


class TestLivePlaylistLint:
    def test_open_snapshot_then_advance_is_monotonic(self, tmp_path):
        open_parts = [hls.LivePart(uri=hls.PART_PATTERN % (2, 0),
                                   duration_s=0.2)]
        path, text = _snapshot(tmp_path, [_seg(0), _seg(1)], open_parts,
                               preload_uri=hls.PART_PATTERN % (2, 1))
        assert "#EXT-X-ENDLIST" not in text
        assert 'PRELOAD-HINT:TYPE=PART' in text
        st = hls.lint_live_media_playlist(path)
        assert (st["next_msn"], st["next_part"]) == (2, 1)
        # edge advances: one more part announced
        open_parts.append(hls.LivePart(uri=hls.PART_PATTERN % (2, 1),
                                       duration_s=0.2))
        path, _ = _snapshot(tmp_path, [_seg(0), _seg(1)], open_parts,
                            preload_uri=hls.PART_PATTERN % (2, 2))
        st2 = hls.lint_live_media_playlist(path, prev=st)
        assert (st2["next_msn"], st2["next_part"]) == (2, 2)
        # stream closes: parts/hints gone, ENDLIST present, still
        # monotonic vs the last open snapshot
        path, text = _snapshot(
            tmp_path, [_seg(0), _seg(1), _seg(2)], [], ended=True)
        assert "#EXT-X-ENDLIST" in text and "PRELOAD" not in text
        st3 = hls.lint_live_media_playlist(path, prev=st2)
        assert st3["ended"]

    def test_dvr_window_advances_media_sequence(self, tmp_path):
        st = hls.lint_live_media_playlist(_snapshot(
            tmp_path, [_seg(0), _seg(1)], [], media_sequence=0)[0])
        st2 = hls.lint_live_media_playlist(_snapshot(
            tmp_path, [_seg(1), _seg(2)], [], media_sequence=1)[0],
            prev=st)
        assert st2["media_sequence"] == 1

    def test_tampered_media_sequence_regression_rejected(self, tmp_path):
        st = hls.lint_live_media_playlist(_snapshot(
            tmp_path, [_seg(1), _seg(2)], [], media_sequence=1)[0])
        path, _ = _snapshot(tmp_path, [_seg(0), _seg(1)], [],
                            media_sequence=0)
        with pytest.raises(ValueError, match="MEDIA-SEQUENCE"):
            hls.lint_live_media_playlist(path, prev=st)

    def test_tampered_edge_retreat_rejected(self, tmp_path):
        open_parts = [hls.LivePart(uri=hls.PART_PATTERN % (1, 0),
                                   duration_s=0.2)]
        st = hls.lint_live_media_playlist(_snapshot(
            tmp_path, [_seg(0)], open_parts)[0])
        path, _ = _snapshot(tmp_path, [_seg(0)], [])
        with pytest.raises(ValueError, match="retreated"):
            hls.lint_live_media_playlist(path, prev=st)

    def test_tampered_part_duration_over_part_target(self, tmp_path):
        bad = [hls.LivePart(uri=hls.PART_PATTERN % (0, 0),
                            duration_s=0.5)]    # > PART-TARGET 0.2
        path, _ = _snapshot(tmp_path, [], bad)
        with pytest.raises(ValueError, match="PART-TARGET"):
            hls.lint_live_media_playlist(path)

    def test_tampered_extinf_over_target(self, tmp_path):
        seg = hls.LiveSegmentRef(uri="seg_00000.m4s", duration_s=3.0)
        path, _ = _snapshot(tmp_path, [seg], [], target_s=1.0)
        with pytest.raises(ValueError, match="TARGETDURATION"):
            hls.lint_live_media_playlist(path)

    def test_tampered_endlist_while_open_rejected(self, tmp_path):
        """An ENDLIST pasted onto a live snapshot that still promises
        a preload hint is a contradiction the lint must catch."""
        open_parts = [hls.LivePart(uri=hls.PART_PATTERN % (0, 0),
                                   duration_s=0.2)]
        path, text = _snapshot(tmp_path, [], open_parts,
                               preload_uri=hls.PART_PATTERN % (0, 1))
        with open(path, "a", encoding="utf-8") as fp:
            fp.write("#EXT-X-ENDLIST\n")
        with pytest.raises(ValueError, match="preload"):
            hls.lint_live_media_playlist(path)

    def test_ended_stream_reopening_rejected(self, tmp_path):
        st = hls.lint_live_media_playlist(_snapshot(
            tmp_path, [_seg(0)], [], ended=True)[0])
        path, _ = _snapshot(tmp_path, [_seg(0)], [])
        with pytest.raises(ValueError, match="reopened"):
            hls.lint_live_media_playlist(path, prev=st)

    def test_open_playlist_requires_part_inf_and_server_control(
            self, tmp_path):
        path = str(tmp_path / "media.m3u8")
        with open(path, "w", encoding="utf-8") as fp:
            fp.write("#EXTM3U\n#EXT-X-TARGETDURATION:1\n"
                     '#EXT-X-MAP:URI="init.mp4"\n'
                     "#EXTINF:0.4,\nseg_00000.m4s\n")
        with pytest.raises(ValueError, match="PART-INF"):
            hls.lint_live_media_playlist(path)


# ---------------------------------------------------------------------------
# watcher live fast path
# ---------------------------------------------------------------------------


class TestWatcherLive:
    def test_live_name_submits_on_first_sighting(self, tmp_path):
        from thinvids_tpu.ingest.watcher import FileLedger, WatchIngester

        header, recs = frame_records(META, textured_frames(W, H, 2))
        watch = tmp_path / "watch"
        watch.mkdir()
        (watch / "cam.live.y4m").write_bytes(header + recs[0])
        (watch / "batch.y4m").write_bytes(header + recs[0] + recs[1])
        calls = []
        ing = WatchIngester(str(watch),
                            FileLedger(str(tmp_path / "ledger")),
                            submit=lambda p, s: calls.append(p) or True,
                            stable_checks=3)
        submitted = ing.scan_once()
        # the live stream skipped stabilization; the batch file waits
        assert submitted == ["cam.live.y4m"]
        assert calls and calls[0].endswith("cam.live.y4m")

    def test_growing_live_source_does_not_supersede_its_job(
            self, tmp_path):
        from thinvids_tpu.ingest.watcher import coordinator_submitter

        header, recs = frame_records(META, textured_frames(W, H, 2))
        path = str(tmp_path / "cam.live.y4m")
        with open(path, "wb") as fp:
            fp.write(header + recs[0])
        coord = Coordinator(settings_fn=lambda: make_settings(
            auto_start_jobs=False))
        submit = coordinator_submitter(coord)
        assert submit(path, "missing") is True
        jobs = coord.store.list()
        assert len(jobs) == 1 and jobs[0].job_type == "live"
        # the file grows; the next sighting is expected growth, not a
        # re-drop: no second job, no stop of the running one
        with open(path, "ab") as fp:
            fp.write(recs[1])
        assert submit(path, "changed") is True
        jobs = coord.store.list()
        assert len(jobs) == 1
        assert jobs[0].status is not Status.STOPPED

    def test_live_probe_failure_is_retried_not_blacklisted(
            self, tmp_path):
        from thinvids_tpu.ingest.watcher import coordinator_submitter

        path = str(tmp_path / "cam.live.y4m")
        with open(path, "wb"):
            pass                              # no header on disk yet
        coord = Coordinator(settings_fn=lambda: make_settings())
        submit = coordinator_submitter(coord)
        assert submit(path, "missing") is False   # retry next scan
        assert len(coord.store.list()) == 0


# ---------------------------------------------------------------------------
# settings hygiene (VERDICT Weak #3)
# ---------------------------------------------------------------------------


def test_every_settings_key_has_a_reader_outside_config(analysis_ctx):
    """Dead config lies to operators: every DEFAULT_SETTINGS key must
    be referenced somewhere outside core/config.py (executor, planner,
    API, dashboard, ...). Promoted from a source-blob grep into
    the analyzer's config-discipline pass (TVT-C001), which this test
    now drives directly."""
    from thinvids_tpu.analysis.configcheck import check_dead_keys

    m, tree = analysis_ctx
    dead = [f for f in check_dead_keys(tree, m)
            if f.key not in m.waivers]
    assert not dead, "\n".join(f.format() for f in dead) + \
        " — delete them or wire them up"


def test_dead_keys_stay_deleted():
    for key in ("target_segment_frames", "software_fallback",
                "active_window_s", "target_height"):
        assert key not in DEFAULT_SETTINGS


# ---------------------------------------------------------------------------
# blocking playlist reload
# ---------------------------------------------------------------------------


class TestBlockingReload:
    def _server(self):
        from thinvids_tpu.api.server import ApiServer

        return ApiServer(Coordinator(settings_fn=make_settings))

    def test_returns_immediately_when_edge_already_reached(
            self, tmp_path):
        api = self._server()
        path, _ = _snapshot(tmp_path, [_seg(0), _seg(1)], [])
        t0 = time.monotonic()
        api._block_for_playlist_edge(path, {"_HLS_msn": "0"}, True)
        assert time.monotonic() - t0 < 0.5

    def test_blocks_until_edge_advances(self, tmp_path):
        api = self._server()
        open_parts = [hls.LivePart(uri=hls.PART_PATTERN % (1, 0),
                                   duration_s=0.2)]
        path, _ = _snapshot(tmp_path, [_seg(0)], open_parts)

        def advance():
            time.sleep(0.2)
            _snapshot(tmp_path, [_seg(0), _seg(1)], [])
        t = threading.Thread(target=advance)
        t.start()
        t0 = time.monotonic()
        # wants part 1 of msn 1 — only satisfied once segment 1 closes
        api._block_for_playlist_edge(
            path, {"_HLS_msn": "1", "_HLS_part": "1"}, True)
        took = time.monotonic() - t0
        t.join()
        assert 0.15 <= took < 5.0

    def test_bad_params_are_rejected(self, tmp_path):
        from thinvids_tpu.api.server import ApiError

        api = self._server()
        path, _ = _snapshot(tmp_path, [_seg(0)], [])
        with pytest.raises(ApiError):
            api._block_for_playlist_edge(path, {"_HLS_msn": "x"}, True)


# ---------------------------------------------------------------------------
# end-to-end live job
# ---------------------------------------------------------------------------


def make_rig(tmp_path, snap, sync=False):
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / "library"),
                          sync=sync)
    coord._launcher = execu.launch
    return coord, execu


class TestLiveJobEndToEnd:
    def test_serve_during_ingest_then_endlist_and_vod_lint(
            self, tmp_path):
        """The acceptance flow: while the source file is still growing
        a client fetches master.m3u8 and an already-announced segment;
        after the writer closes, the final playlist gains
        EXT-X-ENDLIST and the tree passes the batch VOD lint."""
        from thinvids_tpu.api.server import ApiServer, _FileResponse

        n, gop = 16, 4
        frames = textured_frames(W, H, n)
        header, recs = frame_records(META, frames)
        path = str(tmp_path / "cam.live.y4m")
        # How long the poll below waits for mid-stream output. What it
        # waits on is the job's first compile: 16 s of this test's 17
        # alone, 30-36 s beside nine other compiling processes. The
        # writer HOLDS the tail open (gate) for all of it: a writer
        # that let go earlier (it used to, after 20 s) hands the job
        # its last frames and `.eos` while it still compiles, and the
        # job then goes from its first output to DONE in 10 ms, between
        # two polls. The hold must read as "writer still alive", not
        # EOS, so the stall budget outlasts it — the explicit .eos
        # marker ends the stream without the wait
        poll_s = 180.0
        snap = make_settings(qp=30, gop_frames=gop, segment_s=0.25,
                             ladder_rungs="24", live_stall_s=2 * poll_s,
                             heartbeat_throttle_s=0.0)
        coord, execu = make_rig(tmp_path, snap)
        api = ApiServer(coord)

        gate = threading.Event()              # writer holds the tail
                                              # until ingest is proven

        def writer():
            with open(path, "wb") as out:
                out.write(header)
                out.flush()
                for i, rec in enumerate(recs):
                    if i == len(recs) - 2:
                        # hold the live edge open until the test has
                        # fetched output mid-stream (it sets the gate
                        # when its poll ends, however it ends)
                        gate.wait(poll_s + 30.0)
                    out.write(rec)
                    out.flush()
                    time.sleep(0.01)
            with open(path + EOS_SUFFIX, "wb"):
                pass

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        meta = VideoMeta(width=W, height=H, fps_num=30, fps_den=1,
                         num_frames=n)
        job = coord.add_job(path, meta)
        assert coord.store.get(job.id).job_type == "live"

        # poll until output is served WHILE the job is still running
        served_master = served_segment = None
        lint_state = None
        deadline = time.time() + poll_s
        while time.time() < deadline and served_segment is None:
            st = coord.store.get(job.id)
            assert st.status is not Status.FAILED, st.failure_reason
            if st.output_path and os.path.exists(st.output_path) \
                    and st.status is Status.RUNNING:
                code, payload = api.route(
                    "GET", f"/hls/{job.id}/master.m3u8", {}, {})
                assert code == 200 and isinstance(payload,
                                                  _FileResponse)
                # live playlists must be uncacheable
                assert payload.headers["Cache-Control"] == "no-cache"
                served_master = payload
                media = os.path.join(os.path.dirname(st.output_path),
                                     "24p", "media.m3u8")
                if os.path.exists(media):
                    lint_state = hls.lint_live_media_playlist(
                        media, prev=lint_state)
                    # wait for a CLOSED segment (bare URI) — parts
                    # alone announce earlier but aren't listed as
                    # whole-segment URIs yet
                    if lint_state["segments"]:
                        # fetch an already-announced resource NOW,
                        # before the job finishes
                        with open(media, encoding="utf-8") as fp:
                            text = fp.read()
                        uri = next(l for l in text.splitlines()
                                   if l.endswith(".m4s")
                                   and not l.startswith("#"))
                        code, seg = api.route(
                            "GET", f"/hls/{job.id}/24p/{uri}", {}, {})
                        assert code == 200
                        assert "immutable" in \
                            seg.headers["Cache-Control"]
                        served_segment = uri
                        gate.set()            # let the writer finish
            if st.status is Status.DONE:
                break
            time.sleep(0.01)
        gate.set()
        wt.join(20)
        execu.join(30)
        st = coord.store.get(job.id)
        assert st.status is Status.DONE, st.failure_reason
        assert served_master is not None, "master never served mid-run"
        assert served_segment is not None, "no segment fetched mid-run"
        assert st.parts_done == st.parts_total > 0

        # final tree: ENDLIST + full VOD conformance
        out_dir = os.path.dirname(st.output_path)
        media = os.path.join(out_dir, "24p", "media.m3u8")
        final = hls.lint_live_media_playlist(media, prev=lint_state)
        assert final["ended"]
        info = hls.lint_ladder(out_dir, expected_duration_s=n / 30)
        assert info["rungs"] == 2
        # a DONE live playlist is cacheable (briefly)
        code, payload = api.route(
            "GET", f"/hls/{job.id}/master.m3u8", {}, {})
        assert payload.headers["Cache-Control"].startswith("public")

    def test_stream_close_mid_gop_emits_short_tail(self, tmp_path):
        """A writer that dies mid-GOP (6 frames into a 4-frame grid =
        1.5 GOPs) still produces a valid closed stream: the tail
        partial GOP becomes a short final part/segment."""
        n, gop = 6, 4
        frames = textured_frames(W, H, n)
        header, recs = frame_records(META, frames)
        path = str(tmp_path / "cut.live.y4m")
        with open(path, "wb") as fp:
            fp.write(header + b"".join(recs))
        with open(path + EOS_SUFFIX, "wb"):
            pass
        snap = make_settings(qp=30, gop_frames=gop, segment_s=10.0,
                             ladder_rungs="24", live_stall_s=5.0,
                             heartbeat_throttle_s=0.0)
        coord, _execu = make_rig(tmp_path, snap, sync=True)
        meta = VideoMeta(width=W, height=H, fps_num=30, fps_den=1,
                         num_frames=n)
        job = coord.add_job(path, meta)
        st = coord.store.get(job.id)
        assert st.status is Status.DONE, st.failure_reason
        out_dir = os.path.dirname(st.output_path)
        info = hls.lint_ladder(out_dir, expected_duration_s=n / 30)
        assert info["segments"] == 1          # one short segment
        assert abs(info["duration_s"] - n / 30) < 1e-3

    def test_dvr_window_gc_advances_media_sequence_and_deletes(
            self, tmp_path):
        n, gop = 32, 4                        # 8 GOPs → 4 segments
        frames = textured_frames(W, H, n)
        header, recs = frame_records(META, frames)
        path = str(tmp_path / "dvr.live.y4m")
        with open(path, "wb") as fp:
            fp.write(header + b"".join(recs))
        with open(path + EOS_SUFFIX, "wb"):
            pass
        snap = make_settings(qp=30, gop_frames=gop, segment_s=0.25,
                             ladder_rungs="24", live_stall_s=5.0,
                             dvr_window_s=0.5,
                             heartbeat_throttle_s=0.0)
        coord, _execu = make_rig(tmp_path, snap, sync=True)
        meta = VideoMeta(width=W, height=H, fps_num=30, fps_den=1,
                         num_frames=n)
        job = coord.add_job(path, meta)
        st = coord.store.get(job.id)
        assert st.status is Status.DONE, st.failure_reason
        out_dir = os.path.dirname(st.output_path)
        media = os.path.join(out_dir, "24p", "media.m3u8")
        final = hls.lint_live_media_playlist(media)
        assert final["ended"]
        # the window slid: MEDIA-SEQUENCE advanced and the earliest
        # segment left both the playlist and the disk
        assert final["media_sequence"] > 0
        assert final["segments"] < 4
        rung_dir = os.path.join(out_dir, "24p")
        assert not os.path.exists(
            os.path.join(rung_dir, hls.SEGMENT_PATTERN % 0))
        with open(media, encoding="utf-8") as fp:
            assert hls.SEGMENT_PATTERN % 0 not in fp.read()


def test_tail_and_packager_are_manifested_jax_free(analysis_ctx):
    """ingest/tail.py and live/packager.py are control-plane modules:
    importable (and usable for lint/serving) in a process that never
    loads a device backend. Migrated from a stubbed-import probe to
    the analyzer's import-graph proof (manifest declaration + clean
    confinement pass over the transitive module-scope closure);
    tree-wide enforcement rides `cli.py check` in tier-1."""
    from thinvids_tpu.analysis import imports
    from thinvids_tpu.analysis.astutil import matches_any

    m, tree = analysis_ctx
    for mod in ("thinvids_tpu.ingest.tail",
                "thinvids_tpu.live.packager"):
        assert matches_any(mod, m.jax_free), (
            f"manifest no longer declares {mod} jax-free")
    open_ = [f for f in imports.check_jax_confinement(tree, m)
             if f.key not in m.waivers and f.module in (
                 "thinvids_tpu.ingest.tail",
                 "thinvids_tpu.live.packager")]
    assert not open_, "\n".join(f.format() for f in open_)
