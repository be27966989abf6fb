"""Executor: turns a reserved Job into sharded encode waves + a muxed file.

The data-plane half the coordinator was missing: the reference's worker
task chain `transcode → split → encode×N → stitch`
(/root/reference/worker/tasks.py:810-833, 1354, 1741) collapsed onto a
device mesh — "split" is the GOP plan, "encode×N" is the shard_map wave
fan-out, "stitch" is the ordered concat + MP4 mux. Progress, heartbeats
and completion flow back through the coordinator's token-fenced
callbacks; a stale token halts the run between waves (the reference's
halt checks at every stage, worker/tasks.py:1611-1651).

Wave-level fault handling replaces the reference's part-level retry
(worker/tasks.py:1385-1464): a wave that raises is re-dispatched up to
`part_failure_max_retries` times before the job fails with stage/host
attribution.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from typing import Callable

from ..core.status import Status
from ..ingest.decode import open_video
from ..io.mp4 import mux_mp4
from ..core.types import concat_segments
from ..obs import flight as obs_flight
from ..obs import trace as obs_trace
from .coordinator import Coordinator
from .jobs import Job


class HaltedError(RuntimeError):
    """Run token went stale mid-run (stop/restart/watchdog revocation)."""


def _live_batch_plan(num_frames: int, gop_frames: int,
                     num_devices: int):
    """Fixed GOP grid for one live batch: exactly `gop_frames` per GOP
    (short tail at end of stream), indices local to the batch. The
    default planner's wave balancing would split GOPs differently per
    batch size / mesh width, making live part boundaries
    nondeterministic. (Shared with the SFE encoder's GOP walk —
    parallel/planner.plan_fixed_segments.)"""
    from ..parallel.planner import plan_fixed_segments

    return plan_fixed_segments(num_frames, gop_frames, num_devices)


class _WaveExhausted(RuntimeError):
    """One wave burned its whole retry budget; carries the segments the
    failing range completed so an elastic replan can resume after them."""

    def __init__(self, reason: str, completed: list) -> None:
        super().__init__(reason)
        self.reason = reason
        self.completed = completed


class LocalExecutor:
    """Runs reserved jobs on the local process's device mesh.

    Plugs into :class:`Coordinator` as its launcher: `launch()` spawns a
    worker thread per job (pass ``sync=True`` for deterministic tests).
    """

    def __init__(self, coordinator: Coordinator, output_dir: str,
                 mesh=None, host: str = "local", sync: bool = False,
                 encoder_factory: Callable | None = None) -> None:
        self.coordinator = coordinator
        self.output_dir = output_dir
        self.mesh = mesh
        self.host = host
        self.sync = sync
        #: test seam: (meta, settings, mesh) -> GopShardEncoder-like
        self._encoder_factory = encoder_factory or self._default_encoder
        self._threads: list[threading.Thread] = []
        # flight-recorder artifacts (<job>.trace.json) land next to the
        # output tree this executor writes (obs/flight.py)
        obs_flight.configure(output_dir)

    # -- coordinator launcher interface --------------------------------

    def launch(self, job: Job) -> None:
        if self.sync:
            self.run(job)
            return
        t = threading.Thread(target=self.run, args=(job,), daemon=True,
                             name=f"tvt-exec-{job.id[:8]}")
        self._threads.append(t)
        t.start()

    def join(self, timeout: float | None = None) -> None:
        for t in self._threads:
            t.join(timeout)

    # -- pipeline ------------------------------------------------------

    @staticmethod
    def _default_encoder(meta, settings, mesh):
        """Plan-driven encoder resolution (parallel/dispatch.
        make_shard_encoder): `sfe_bands > 0` selects the split-frame
        band shape (one frame sharded across the mesh as MB-row band
        slices — the single-stream latency path; 0 keeps current
        behavior byte-identical), else GOP waves. The remote backend
        resolves through the SAME seam — its band shape additionally
        spans hosts (cluster/remote.py band shards + halo relay)."""
        from ..parallel.dispatch import make_shard_encoder

        return make_shard_encoder(meta, settings, mesh)

    def run(self, job: Job) -> None:
        token = job.run_token
        # bind the job's trace context to this thread: spans record
        # through the encoder's StageProfile + the wave loop below, and
        # the structured JSON log mode stamps (job_id, trace_id) onto
        # every line emitted while the run owns this thread
        with obs_trace.bind(job.id, obs_trace.TRACE.trace_id(job.id)):
            self._run_traced(job, token)

    def _run_traced(self, job: Job, token: str) -> None:
        co = self.coordinator
        # one-element list: the encode hook advances the stage marker in
        # place so failure attribution survives the subclass seam
        stage = ["probe"]
        source = None
        try:
            settings = co.job_settings(job)
            co.heartbeat_job(job.id, token, stage[0], host=self.host)
            if getattr(job, "job_type", "transcode") == "live":
                # live LL-HLS: the source is still GROWING — tail it
                # GOP-by-GOP and serve viewers during ingest (live/).
                # Always encoded on this process's mesh, even under the
                # remote backend: farming one GOP at a time would put a
                # worker round-trip inside the glass-to-playlist path.
                with self._maybe_trace(settings, job):
                    self._run_live(job, token, settings, stage)
                return
            # streaming ingest: open (header parse / container demux)
            # WITHOUT decoding — frames decode wave-by-wave during the
            # encode, so the clip never materializes in host RAM and
            # time-to-first-wave is one wave's decode
            source = open_video(job.input_path)
            meta, audio = source.meta, source.audio
            if not len(source):
                raise ValueError(f"no frames in {job.input_path}")
            if not co.mark_running(job.id, token):
                raise HaltedError("fenced before start")

            if getattr(job, "job_type", "transcode") == "ladder":
                # ABR ladder: rungs encode from ONE staged wave stream
                # (lower rungs derive on device) and the output is a
                # served HLS directory, not a single MP4 (abr/).
                with self._maybe_trace(settings, job):
                    rungs, rung_segs = self._encode_ladder(
                        job, token, source, settings, meta, stage)
                self._package_ladder(job, token, rungs, rung_segs, meta,
                                     audio, settings, len(source), stage)
                return

            with self._maybe_trace(settings, job):
                segments = self._encode_job(job, token, source, settings,
                                            meta, stage)

            from ..parallel.dispatch import job_clock

            stage[0] = "stitch"
            co.heartbeat_job(job.id, token, stage[0], host=self.host)
            with job_clock("job_stitch"):
                stream = concat_segments(segments)
            base = os.path.splitext(os.path.basename(job.input_path))[0]
            out_path = os.path.join(self.output_dir, base + ".mp4")
            os.makedirs(self.output_dir, exist_ok=True)
            with job_clock("job_mux"):
                data = mux_mp4(stream, meta, audio=audio)
            tmp = f"{out_path}.{job.id}.tmp"    # job-unique: no clobber
                                                # across same-name jobs
            with job_clock("job_write"):
                with open(tmp, "wb") as fp:
                    fp.write(data)
                os.replace(tmp, out_path)   # atomic commit (ref: tasks.py:769)
            with job_clock("job_commit"):   # the journal's fsyncs
                co.update_progress(job.id, token, combine_progress=100.0)
                co.complete_job(job.id, token, out_path, len(data))
        except HaltedError:
            pass                            # fenced: a newer run owns the job
        except Exception as exc:            # noqa: BLE001 - attribute & fail
            co.fail_job(job.id, token, stage=stage[0], host=self.host,
                        reason=f"{type(exc).__name__}: {exc}")
        finally:
            if source is not None:
                source.close()

    def _encode_job(self, job: Job, token: str, frames, settings, meta,
                    stage: list) -> list:
        """segment + encode stages → ordered EncodedSegments. The seam
        the remote backend overrides (cluster/remote.py dispatches GOP
        shards to worker daemons here); this implementation runs on the
        local process's device mesh. `frames` is a lazy FrameSource
        (len + slicing + iteration; ingest/decode.py) — treat it as a
        sequence, never materialize it wholesale. `stage` is a
        one-element list the hook mutates for failure attribution."""
        from ..parallel.dispatch import job_clock

        co = self.coordinator
        stage[0] = "segment"
        with job_clock("job_build"):
            enc = self._encoder_factory(meta, settings, self.mesh)
        self._bind_trace(job, enc)
        with job_clock("job_plan"):
            plan, cut_note = self._plan_on_cuts(enc, frames, settings)
        co.update_progress(job.id, token, parts_total=plan.num_gops,
                           segment_progress=100.0)
        co.heartbeat_job(job.id, token, stage[0], host=self.host,
                         note=f"{plan.num_gops} GOPs planned{cut_note}")

        stage[0] = "encode"
        target_kbps = float(settings.get("target_bitrate_kbps", 0.0))
        if str(settings.rc_mode) == "vbr2pass" and target_kbps > 0:
            segments = self._encode_vbr2pass(job, token, enc, frames,
                                             settings, meta, target_kbps)
        else:
            segments = self._encode_with_retry(job, token, enc, frames,
                                               settings)
        self._emit_stage_breakdown(job, enc)
        return segments

    @staticmethod
    def _scene_cuts(frames, settings, stages):
        """The `scenecut` setting's look at the source, in the
        `segment` stage: (cuts taken, cuts suppressed) from one read of
        its luma under the stage clock and span `scenecut` of `stages`
        (a StageProfile), or None where the setting is off or the
        job's shape keeps a fixed GOP grid (planner.plan_encode says
        which). The device idles meanwhile: the plan needs the whole
        list before the first wave is staged."""
        from ..parallel.planner import plan_shape

        threshold = int(settings.get("scenecut", 0) or 0)
        if threshold <= 0 or plan_shape(settings) != "gop":
            return None
        from ..parallel import scenecut

        with stages.stage("scenecut"):
            return scenecut.detect(frames, int(settings.gop_frames),
                                   threshold)

    def _plan_on_cuts(self, enc, frames, settings):
        """(plan, heartbeat note) of a GOP-shape job: hand the encoder
        the source's scene cuts, ask it for the plan, and count what
        became of them (`scene_cuts`: cuts that start a GOP of the
        plan; `scene_cuts_suppressed`: the rest, too close to the last
        one or over the segment cap)."""
        stages = getattr(enc, "stages", None)
        found = self._scene_cuts(frames, settings, stages) \
            if stages is not None else None
        if found is None:
            return enc.plan(len(frames)), ""
        enc.scene_cuts = found[0]
        plan = enc.plan(len(frames))
        return plan, self._count_cuts(stages, plan, *found)

    @staticmethod
    def _count_cuts(stages, plan, cuts, suppressed: int) -> str:
        """Bump the two cut counters for a plan made on `cuts` and
        return the heartbeat's words for it."""
        starts = {g.start_frame for g in plan.gops}
        taken = sum(1 for c in cuts if c in starts)
        stages.bump("scene_cuts", taken)
        stages.bump("scene_cuts_suppressed",
                    suppressed + len(cuts) - taken)
        return f", {taken} scene cuts"

    def _encode_ladder(self, job: Job, token: str, frames, settings,
                       meta, stage: list):
        """Ladder encode stage: one LadderShardEncoder fans every wave
        across the rung set on the local mesh (decode + H2D once; lower
        rungs scale on device). Returns (rungs, {rung name → ordered
        EncodedSegments}). The seam the remote backend overrides to
        farm rung×shard work instead (cluster/remote.py)."""
        from ..abr.ladder import plan_ladder, rung_segments
        from ..parallel.dispatch import make_shard_encoder

        co = self.coordinator
        if str(settings.rc_mode) == "vbr2pass":
            # the two-pass QP solver has no multi-rendition form yet;
            # say so instead of silently dropping the bitrate target
            co.activity.emit(
                "encode", "ladder jobs use the octave-model per-rung "
                "QPs; rc_mode=vbr2pass / target_bitrate_kbps ignored",
                job_id=job.id, host=self.host)
        stage[0] = "segment"
        rungs = plan_ladder(meta, settings)
        enc = make_shard_encoder(meta, settings, self.mesh, rungs=rungs)
        self._bind_trace(job, enc)
        plan, cut_note = self._plan_on_cuts(enc, frames, settings)
        co.update_progress(job.id, token, parts_total=plan.num_gops,
                           segment_progress=100.0)
        co.heartbeat_job(
            job.id, token, stage[0], host=self.host,
            note=f"{plan.num_gops} GOPs x {len(rungs)} rungs{cut_note}")

        stage[0] = "encode"
        # no elastic replan for ladders: a mesh change mid-job would
        # re-plan GOP boundaries and break cross-rung segment alignment
        bundles = self._encode_with_retry(job, token, enc, frames,
                                          settings, allow_replan=False)
        self._emit_stage_breakdown(job, enc)
        return rungs, {r.name: rung_segments(bundles, r.name)
                       for r in rungs}

    def _package_ladder(self, job: Job, token: str, rungs, rung_segs,
                        meta, audio, settings, num_frames: int,
                        stage: list) -> None:
        """Package stage: rungs → fMP4 segments + playlists under
        `<output_dir>/<base>.hls/`, lint-checked, committed with an
        atomic directory rename; the job completes pointing at the
        master playlist (served via /hls/<job>/master.m3u8)."""
        import shutil

        from ..abr import hls

        co = self.coordinator
        stage[0] = "package"
        co.heartbeat_job(job.id, token, stage[0], host=self.host,
                         note=f"{len(rungs)} rungs → HLS")
        # audio passes through bit-exact on EVERY rung: variants must
        # share one codec set or an adaptive down-switch at a segment
        # edge drops the sound track (players handle codec-set changes
        # across variants poorly); the duplicated compressed audio is
        # noise next to any rung's video bytes
        streams = [hls.RungStream(
            name=r.name, width=r.width, height=r.height,
            segments=rung_segs[r.name], audio=audio) for r in rungs]
        base = os.path.splitext(os.path.basename(job.input_path))[0]
        out_dir = os.path.join(self.output_dir, base + ".hls")
        tmp = f"{out_dir}.{job.id}.tmp"     # job-unique staging dir
        shutil.rmtree(tmp, ignore_errors=True)
        try:
            hls.package_ladder(
                tmp, streams, meta.fps_num, meta.fps_den,
                segment_s=float(settings.get("segment_s", 6.0)))
            fps = meta.fps_num / max(1, meta.fps_den)
            hls.lint_ladder(tmp, expected_duration_s=num_frames / fps)
            shutil.rmtree(out_dir, ignore_errors=True)
            os.rename(tmp, out_dir)         # atomic commit
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        total = 0
        for root, _dirs, files in os.walk(out_dir):
            total += sum(os.path.getsize(os.path.join(root, f))
                         for f in files)
        master = os.path.join(out_dir, hls.MASTER_PLAYLIST)
        co.update_progress(job.id, token, combine_progress=100.0)
        co.complete_job(job.id, token, master, total)

    def _run_live(self, job: Job, token: str, settings,
                  stage: list) -> None:
        """Live LL-HLS pipeline: tail the growing source, encode each
        completed GOP through the ladder encoders wave-by-wave, and
        hand every finished GOP bundle to the incremental packager —
        output availability is decoupled from job completion (the
        master playlist is published, and /hls serves it, after the
        FIRST GOP clears all rungs).

        Latency model: at the live edge one GOP encodes at a time
        (glass-to-playlist ≈ GOP duration + one wave's encode+package);
        during backlog/catch-up, up to LIVE_CATCHUP_WAVES waves of GOPs
        batch per dispatch. End-of-stream is the tail source's stall
        timeout (`live_stall_s`) or `.eos` marker; the packager then finalizes
        with EXT-X-ENDLIST and — when nothing was GC'd out of the DVR
        window — the tree passes the full VOD conformance lint. Waves
        do not retry or replan here: a live edge cannot rewind, so a
        wave failure fails the job with attribution."""
        import shutil

        from ..abr import hls
        from ..abr.ladder import plan_ladder
        from ..ingest.tail import TailFrameSource
        from ..live.packager import LiveLadderPackager

        co = self.coordinator
        stage[0] = "tail"
        stall = float(settings.get("live_stall_s", 10.0))
        tail = TailFrameSource(job.input_path, stall_timeout_s=stall)
        meta = tail.meta                    # header facts; num_frames grows
        if not co.mark_running(job.id, token):
            raise HaltedError("fenced before start")
        gop_n = int(settings.gop_frames)
        rungs = plan_ladder(meta, settings)
        enc, sfe_live = self._live_encoder(meta, settings, rungs)
        self._bind_trace(job, enc)
        base = os.path.splitext(os.path.basename(job.input_path))[0]
        out_dir = os.path.join(self.output_dir, base + ".hls")
        os.makedirs(self.output_dir, exist_ok=True)
        # a restarted live job re-tails from frame 0: the previous
        # attempt's tree is stale output, not resumable state
        shutil.rmtree(out_dir, ignore_errors=True)
        packager = LiveLadderPackager(
            out_dir, rungs, meta.fps_num, meta.fps_den,
            segment_s=float(settings.get("segment_s", 6.0)),
            gop_frames=gop_n,
            dvr_window_s=float(settings.get("dvr_window_s", 0.0)))
        co.heartbeat_job(
            job.id, token, stage[0], host=self.host,
            note=f"tailing x{len(rungs)} rungs (stall {stall:.0f}s)")

        def fenced() -> bool:
            return not co.token_is_current(job.id, token)

        stage[0] = "encode"
        # Prime the jit cache for the live-edge wave shape NOW, while
        # the source is still filling its first GOP: the first part's
        # glass-to-playlist latency must not pay the compile (tens of
        # seconds on a real TPU). One dummy wave, output discarded.
        self._warm_live_shapes(enc, meta, gop_n)
        # QoS deadline: a live batch slower than this budget preempts
        # batch work on the cluster until the edge recovers
        # (cluster/qos.py). 0 = auto: 2x the stream's segment duration.
        part_budget = float(settings.get("live_part_budget_s", 0.0)) \
            or 2.0 * float(settings.get("segment_s", 6.0))
        wave_cap = self._live_backlog_cap(job, settings, enc)
        frames_done = gops_done = 0
        published = False
        while True:
            avail = tail.wait_frames(frames_done + gop_n,
                                     stop_check=fenced)
            batch_t0 = time.monotonic()
            if fenced():
                raise HaltedError("stale run token")
            if avail <= frames_done and tail.ended:
                break
            if tail.ended:
                # drain wave-by-wave (the final partial GOP rides the
                # last batch) — never one giant batch, a fast writer
                # can leave an arbitrarily deep backlog at EOS
                count = min(avail - frames_done, wave_cap * gop_n)
            else:
                whole = (avail - frames_done) // gop_n
                # at the live edge whole==1 (lowest latency); during
                # catch-up batch up to the backlog cap per dispatch
                # (a few local waves — or the whole farm's width when
                # the remote backend fans catch-up GOPs out)
                count = min(whole, wave_cap) * gop_n
            bundles = self._live_encode_batch(
                job, token, settings, enc, rungs, tail, frames_done,
                gops_done, count, gop_n, sfe_live)
            for bundle in bundles:
                packager.add_gop(bundle)
            if not published:
                # the served tree now exists: announce it while the
                # job keeps RUNNING — viewers join during ingest
                co.publish_output(job.id, token, packager.master_path)
                published = True
            gops_done += len(bundles)
            frames_done += count
            # deadline report: wall-clock from the batch's frames being
            # available to its parts being fetchable — over budget,
            # the coordinator preempts batch shards (cluster/qos.py)
            co.note_live_part(job.id, token,
                              time.monotonic() - batch_t0, part_budget)
            co.update_progress(job.id, token, parts_total=gops_done,
                               parts_done=gops_done,
                               segment_progress=100.0)
            co.heartbeat_job(
                job.id, token, stage[0], host=self.host,
                note=f"live edge: {gops_done} GOPs, "
                     f"{packager.segments_announced} segments, "
                     f"{packager.segments_gced} GC'd")
        if gops_done == 0:
            raise ValueError(
                f"live source {job.input_path} ended with no frames")

        stage[0] = "finalize"
        co.heartbeat_job(job.id, token, stage[0], host=self.host,
                         note="end of stream; writing ENDLIST")
        packager.close()
        fps = meta.fps_num / max(1, meta.fps_den)
        if packager.segments_gced == 0:
            # nothing left the DVR window: the closed tree is a full
            # VOD and must pass the batch conformance gate unchanged
            hls.lint_ladder(out_dir,
                            expected_duration_s=frames_done / fps)
        else:
            for r in rungs:
                hls.lint_live_media_playlist(os.path.join(
                    out_dir, r.name, hls.MEDIA_PLAYLIST))
        self._emit_stage_breakdown(job, enc)
        co.update_progress(job.id, token, encode_progress=100.0,
                           combine_progress=100.0)
        co.complete_job(job.id, token, packager.master_path,
                        packager.total_bytes())

    def _live_encoder(self, meta, settings, rungs):
        """Live-edge encoder selection (plan-driven, like every other
        path): the ladder stack by default; a SINGLE-rung stream with
        `sfe_bands > 0` runs the split-frame encoder at the live edge
        instead — every frame sharded across the mesh as band slices,
        so glass-to-playlist latency rides the per-frame SFE pipeline
        rather than whole-GOP waves. Returns (encoder, sfe_mode)."""
        from ..parallel.dispatch import make_shard_encoder

        sfe_bands = int(settings.get("sfe_bands", 0) or 0)
        if sfe_bands > 0 and len(rungs) == 1:
            return make_shard_encoder(meta, settings, self.mesh,
                                      shape="band"), True
        return make_shard_encoder(meta, settings, self.mesh,
                                  rungs=rungs), False

    #: waves one live catch-up batch may hold. A batch's parts reach the
    #: packager when the whole batch is encoded, so this bounds what a
    #: viewer waits for during catch-up; within the batch the waves
    #: pipeline (one GOP per device each), which a one-wave batch could
    #: not: it would pay a bare lead-in and tail per GOP and recover
    #: slower than the source runs.
    LIVE_CATCHUP_WAVES = 4

    def _live_backlog_cap(self, job, settings, enc) -> int:
        """Whole GOPs one catch-up dispatch may batch:
        LIVE_CATCHUP_WAVES local waves. The remote backend widens this
        to the farm (its override fans the backlog across workers) —
        but only when the fan-out will actually engage, so a disabled
        knob keeps the pre-farm local batch bound."""
        return enc.num_devices * self.LIVE_CATCHUP_WAVES

    def _live_encode_batch(self, job, token, settings, enc, rungs,
                           tail, frames_done: int, gops_done: int,
                           count: int, gop_n: int, sfe_live: bool):
        """Encode one live batch (the seam the remote backend overrides
        to fan catch-up GOPs across the farm). GOP indices / frame
        ranges continue the global stream (same offset contract the
        elastic replan uses), and the batch's GOP boundaries are
        pinned EXPLICITLY: the local planner balances GOP lengths to
        the mesh width, which would make part boundaries depend on
        arrival timing and device count — a live stream's GOP grid
        must be a pure function of the frame index (gop_frames-sized,
        like the remote backend's shard plan_override contract)."""
        enc.gop_index_offset = gops_done
        enc.frame_offset = frames_done
        enc.plan_override = _live_batch_plan(count, gop_n,
                                             enc.num_devices)
        # lazy window, not a materialized list: the staging thread
        # decodes the batch wave-by-wave (bounded residency, same
        # contract as batch ingest)
        out = enc.encode(tail[frames_done:frames_done + count])
        if not sfe_live:
            return out
        # SFE live edge: plain EncodedSegments wrap into single-rung
        # bundles so the incremental packager consumes them unchanged
        from ..abr.ladder import LadderGopBundle

        return [LadderGopBundle(gop=s.gop,
                                renditions={rungs[0].name: s})
                for s in out]

    @staticmethod
    def _warm_live_shapes(enc, meta, gop_n: int) -> None:
        """Compile the live-edge wave program (one gop_n-frame GOP,
        padded to the mesh width like every live batch) on synthetic
        frames before real ones arrive — overlap jit compile with the
        source's first-GOP fill instead of serializing it into the
        first part's latency."""
        import numpy as np

        from ..core.types import Frame

        h, w = meta.height, meta.width
        dummy = [Frame(y=np.zeros((h, w), np.uint8),
                       u=np.full((h // 2, w // 2), 128, np.uint8),
                       v=np.full((h // 2, w // 2), 128, np.uint8))
                 for _ in range(gop_n)]
        enc.plan_override = _live_batch_plan(gop_n, gop_n,
                                             enc.num_devices)
        try:
            enc.encode(dummy)
        except Exception:       # noqa: BLE001 - warm is best-effort;
            pass                # a real defect fails the REAL first
                                # wave with proper attribution

    def _bind_trace(self, job: Job, enc) -> None:
        """Bind the job's span recorder to the encoder's stage profile:
        every timed stage (decode/stage/dispatch/device_wait/fetch/
        pack/concat, SFE per-frame) then records a span into the job's
        distributed trace. Inert when the job was sampled out
        (trace_sample) or the encoder is a test double without a
        profile."""
        stages = getattr(enc, "stages", None)
        set_tracer = getattr(stages, "set_tracer", None)
        if set_tracer is not None:
            set_tracer(obs_trace.TRACE.recorder(job.id, host=self.host))

    def _emit_stage_breakdown(self, job: Job, enc) -> None:
        """Record the encoder's host-stage wall-clock breakdown (wave
        dispatch / device wait / D2H fetch / sparse unpack / unflatten /
        CAVLC pack / concat) in the job's activity feed — the per-job
        counterpart of /metrics_snapshot's live aggregate."""
        stages = getattr(enc, "stages", None)
        if stages is None:
            return
        import json

        self.coordinator.activity.emit(
            "encode", "stage_ms " + json.dumps(stages.snapshot()),
            job_id=job.id, host=self.host)

    @staticmethod
    @contextlib.contextmanager
    def _maybe_trace(settings, job: Job):
        """jax.profiler trace of the encode stage when `profile_dir` is
        set (SURVEY §5.1: the reference had activity timers only; here
        per-kernel device timelines land beside the job's events).

        The profile names its own parts: the clocks `profile_start` and
        `profile_stop` time the profiler's start and its stop (the
        collection of the trace: tens of seconds for a long job), and
        the annotation `tvt:encode_stage` marks, on the profiler's
        clock, where the encode stage begins and ends between them.
        While it is live every span of the program is an annotation
        `tvt:<name>` too (obs/trace.annotation), so the `.xplane.pb`
        says what the host did while the device ran nothing."""
        profile_dir = str(settings.get("profile_dir", "") or "")
        if not profile_dir:
            yield
            return
        import jax

        from ..parallel.dispatch import job_clock

        # no Python-function events: nothing reads them, and collecting
        # them stretched the split-frame job's encode stage by an eighth
        # (1.878 s against 1.658 s, PERF.md §6 PR 35). The host tracer
        # stays at its default: the annotations are its events.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with job_clock("profile_start"):
            jax.profiler.start_trace(
                os.path.join(profile_dir, f"job-{job.id[:8]}"),
                profiler_options=options)
        obs_trace.set_annotation_factory(jax.profiler.TraceAnnotation)
        try:
            with jax.profiler.TraceAnnotation("tvt:encode_stage"):
                yield
        finally:
            obs_trace.set_annotation_factory(None)
            with job_clock("profile_stop"):
                jax.profiler.stop_trace()

    def _encode_vbr2pass(self, job: Job, token: str, enc, frames,
                         settings, meta, target_kbps: float) -> list:
        """Two-pass VBR via rc.encode_vbr2pass's single solve/refine
        loop, with every pass riding this executor's retry/halt/progress
        wrapper and heartbeating its pass number."""
        from ..parallel import rc

        co = self.coordinator

        def on_pass(pass_no, gop_qps):
            note = ("vbr pass 1 (analysis)" if gop_qps is None else
                    f"vbr pass {pass_no} (qp {gop_qps.min()}"
                    f"-{gop_qps.max()})")
            co.heartbeat_job(job.id, token, "encode", host=self.host,
                             note=note)

        segments, _stats = rc.encode_vbr2pass(
            frames, meta, target_kbps, base_qp=int(settings.qp), enc=enc,
            encode_fn=lambda e: self._encode_with_retry(
                job, token, e, frames, settings, allow_replan=False),
            on_pass=on_pass,
            aq_strength=float(settings.get("aq_strength", 0.0) or 0.0))
        return segments

    def _encode_with_retry(self, job: Job, token: str, enc, frames,
                           settings, allow_replan: bool = True) -> list:
        """Wave loop with per-wave retry, halt checks, and elastic
        replan: when a wave exhausts its retry budget on a multi-device
        mesh, the remaining frames are re-planned on a SHRUNKEN mesh and
        encoding continues — the TPU analog of the reference's elastic
        worker set (parts re-placed on healthy nodes,
        worker/tasks.py:1845-2029; SURVEY §2.9 "Elastic DP"). A
        single-device failure has nowhere left to shrink and fails the
        job with attribution.

        `allow_replan=False` (the vbr2pass passes) fails instead of
        replanning: a mesh change mid-pass would change the GOP count
        under the QP solver and orphan the per-GOP QP map.
        """
        from ..parallel.planner import suffix_cuts

        co = self.coordinator
        total_gops = enc.plan(len(frames)).num_gops
        cuts = getattr(enc, "scene_cuts", None)     # of the whole clip
        segments: list = []
        start_frame = 0
        shrink_attempt = 0
        while True:
            try:
                segments.extend(self._encode_range(
                    job, token, enc, frames, start_frame, settings,
                    total_gops, len(segments)))
                segments.sort(key=lambda s: s.gop.index)
                return segments
            except _WaveExhausted as exc:
                segments.extend(exc.completed)
                shrink_attempt += 1
                shrunk = (self._shrink_encoder(enc, settings,
                                               shrink_attempt)
                          if allow_replan else None)
                if shrunk is None:
                    raise RuntimeError(exc.reason) from exc
                # completed waves are a contiguous frame prefix (waves
                # collect in order); resume after it on the new mesh
                start_frame = max(
                    (s.gop.end_frame for s in segments), default=0)
                if cuts is not None:
                    # the later cuts go with the suffix: no second look
                    shrunk.scene_cuts = suffix_cuts(cuts, start_frame)
                # the suffix re-plans with a different device count, so
                # the GOP total changes — keep progress honest
                total_gops = len(segments) + shrunk.plan(
                    len(frames) - start_frame).num_gops
                co.update_progress(job.id, token, parts_total=total_gops)
                co.activity.emit(
                    "encode", f"wave retries exhausted; replanning "
                    f"frames {start_frame}+ on {shrunk.num_devices} "
                    f"devices (was {enc.num_devices})",
                    job_id=job.id, host=self.host)
                enc = shrunk

    def _qos_pause(self, job: Job, token: str, settings) -> None:
        """Hold a BATCH-class job's wave loop while the QoS controller
        has batch work preempted for a struggling live edge
        (cluster/qos.py): in-flight waves drain, no new wave
        dispatches, heartbeats keep the watchdog off. Ladder and live
        jobs never pause; re-raises HaltedError if fenced mid-pause."""
        from .qos import BATCH_RANK, job_rank

        co = self.coordinator
        qos = getattr(co, "qos", None)
        if qos is None or qos.batch_allowed():
            return
        override = str(settings.get("job_priority", "auto") or "auto")
        if job_rank(getattr(job, "job_type", "transcode"),
                    override) < BATCH_RANK:
            return
        co.activity.emit("qos", "batch waves paused: live QoS "
                         "preemption", job_id=job.id, host=self.host)
        while not qos.wait_batch_allowed(0.1):
            if not co.token_is_current(job.id, token):
                raise HaltedError("stale run token")
            co.heartbeat_job(job.id, token, "encode", host=self.host,
                             note="paused: live QoS preemption")

    def _shrink_encoder(self, enc, settings, attempt: int):
        """Encoder over a shrunken copy of enc's mesh, or None when it
        cannot shrink further (or the encoder exposes no mesh).

        A Python-level wave failure carries no device attribution, so
        the shrink is blind — it drops devices from the tail, doubling
        the count each consecutive attempt (1, 2, 4, ...) so a bad
        device at a low index is excluded in O(log n) rounds rather
        than n full retry budgets."""
        mesh = getattr(enc, "mesh", None)
        meta = getattr(enc, "meta", None)
        if mesh is None or meta is None:
            return None
        devices = list(mesh.devices.flat)
        if len(devices) <= 1:
            return None
        drop = min(len(devices) - 1, 2 ** (attempt - 1))
        import numpy as np
        from jax.sharding import Mesh

        return self._encoder_factory(
            meta, settings, Mesh(np.array(devices[:-drop]), ("gop",)))

    def _encode_range(self, job: Job, token: str, enc, frames,
                      start_frame: int, settings, total_gops: int,
                      done0: int) -> list:
        """Pipelined wave loop over frames[start_frame:].

        A wave is one GOP per device (parallel/dispatch), and the loop's
        order is: start wave n's fetch (`enc.start_fetch`: its counts
        are in, its payload slice is enqueued), THEN dispatch wave n+1,
        then unpack and pack wave n under wave n+1's program. The
        payload slice is a program on the device's compute queue;
        dispatched after wave n+1 it would wait for all of it, and
        wave n's host work would land behind it with the device idle.
        An encoder without the step (the ladder, test doubles) or with
        an empty one (split-frame) keeps the old order: dispatch n+1,
        collect n.

        The staging chain (decode → one write of each frame into the
        wave's host arrays → H2D) runs on a background staging thread
        (`decode_ahead` waves ahead of the dispatch window —
        parallel/dispatch.background_stage), so ingest overlaps device
        compute instead of serializing ahead of it and wave n+1's
        inputs are on the device when wave n ends.
        Staging stays bounded, not free: input residency is the 2
        in-flight waves PLUS up to `decode_ahead` staged-but-undispatched
        waves (+1 blocked in the queue put) of HBM-resident YUV arrays —
        size `decode_ahead` against the device's HBM headroom, not just
        source latency. A retried wave re-dispatches from its retained
        staged tuple.
        Raises _WaveExhausted (carrying the range's completed segments)
        when one wave fails `part_failure_max_retries` times.
        """
        from ..parallel.dispatch import GopShardEncoder, background_stage

        co = self.coordinator
        max_retries = int(settings.part_failure_max_retries)
        if start_frame:
            # GOP indices / frame ranges restart at 0 for the subrange;
            # offset emitted segments so ordering + idr_pic_id stay
            # globally consistent with already-completed ones
            enc.gop_index_offset = done0
            enc.frame_offset = start_frame
        # the encoder already resolved the `decode_ahead` setting in
        # its constructor (like pack_workers/pipeline_window), so honor
        # its knob — incl. explicit constructor overrides; the class
        # default only covers test doubles that lack the attribute
        decode_ahead = int(getattr(enc, "decode_ahead", 0) or 0) \
            or GopShardEncoder.DECODE_AHEAD
        feed = background_stage(
            enc.stage_waves(frames[start_frame:] if start_frame
                            else frames),
            decode_ahead)
        staged_iter = enumerate(feed)
        segments: list = []
        done = done0
        pending: deque = deque()        # (idx, staged, handle)
        attempts: dict[int, int] = {}
        # per-wave spans in the job's distributed trace (inert when
        # the job was sampled out — trace_sample)
        rec = obs_trace.TRACE.recorder(job.id, host=self.host)

        def halt_check() -> None:
            if not co.token_is_current(job.id, token):
                raise HaltedError("stale run token")

        start_fetch = getattr(enc, "start_fetch", None)

        def dispatch_next() -> None:
            try:
                i, staged = next(staged_iter)
            except StopIteration:
                return
            if pending and start_fetch is not None:
                # the order rule. A failure here is the wave's own:
                # collect_wave repeats the step and owns the retry
                with contextlib.suppress(Exception):
                    start_fetch(pending[-1][2])
            with rec.span("wave_dispatch", wave=i):
                pending.append((i, staged, enc.dispatch_wave(staged)))

        try:
            dispatch_next()
            while pending:
                halt_check()
                self._qos_pause(job, token, settings)
                if len(pending) < 2:
                    dispatch_next()     # overlap: depth-2 window, no more
                i, staged, handle = pending.popleft()
                try:
                    with rec.span("wave_collect", wave=i):
                        segs = enc.collect_wave(handle)
                except HaltedError:
                    raise
                except Exception as exc:  # noqa: BLE001 - wave retry budget
                    n = attempts.get(i, 0) + 1
                    attempts[i] = n
                    if n > max_retries:
                        raise _WaveExhausted(
                            f"wave {i} failed after {n - 1} retries: "
                            f"{type(exc).__name__}: {exc}", segments) \
                            from exc
                    co.activity.emit(
                        "encode", f"wave {i} attempt {n} failed, "
                        f"retrying: {exc}", job_id=job.id, host=self.host)
                    # staged[0] is the wave's GOP list (GopShardEncoder)
                    # or a single GopSpec (SfeShardEncoder: one GOP per
                    # wave, frames sharded as bands within it)
                    wave_gops = (len(staged[0])
                                 if hasattr(staged[0], "__len__") else 1)
                    retried = co.store.get(job.id).parts_retried \
                        + wave_gops
                    co.update_progress(job.id, token, parts_retried=retried)
                    halt_check()
                    pending.appendleft((i, staged,
                                        enc.dispatch_wave(staged)))
                    continue
                segments.extend(segs)
                done += len(segs)
                co.update_progress(
                    job.id, token, parts_done=done,
                    encode_progress=100.0 * done / max(1, total_gops))
                co.heartbeat_job(job.id, token, "encode", host=self.host,
                                 note=f"{done}/{total_gops} GOPs")
            return segments
        finally:
            feed.close()                # stop the staging thread
                                        # (halt / replan / exhaustion)
