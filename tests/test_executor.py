"""End-to-end executor tests: Job → plan → sharded encode → MP4 → DONE.

The round-3 gap: the coordinator's launcher was only ever a test
list-append; these tests drive the real data plane behind it
(cluster/executor.py), matching the reference's task chain
transcode → split → encode×N → stitch
(/root/reference/worker/tasks.py:810-833, 1354, 1741).
"""

import numpy as np
import pytest

from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
from thinvids_tpu.core.status import Status
from thinvids_tpu.core.types import Frame, VideoMeta
from thinvids_tpu.io.y4m import write_y4m


def make_settings(**over):
    values = dict(DEFAULT_SETTINGS)
    values.update(over)
    return Settings(values=values)


def clip_frames(w=64, h=48, n=12):
    yy, xx = np.mgrid[0:h, 0:w]
    return [Frame(
        y=((xx * 2 + yy + 7 * i) % 256).astype(np.uint8),
        u=np.full((h // 2, w // 2), 108, np.uint8),
        v=np.full((h // 2, w // 2), 148, np.uint8),
    ) for i in range(n)]


@pytest.fixture
def clip_y4m(tmp_path):
    w, h, n = 64, 48, 12
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1, num_frames=n)
    path = tmp_path / "clip.y4m"
    write_y4m(path, meta, clip_frames(w, h, n))
    return str(path)


def make_rig(tmp_path, settings=None, **executor_kw):
    snap = settings or make_settings(gop_frames=4, qp=30,
                                     heartbeat_throttle_s=0.0)
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / "library"),
                          sync=True, **executor_kw)
    coord._launcher = execu.launch
    return coord, execu


class TestEndToEnd:
    def test_add_job_to_done_with_decodable_mp4(self, tmp_path, clip_y4m):
        import cv2

        coord, _ = make_rig(tmp_path)
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        # 12 frames / gop 4 wave-rounded onto the 8-device test mesh
        assert job.parts_total == 8 and job.parts_done == 8
        assert job.segment_progress == 100.0
        assert job.encode_progress == 100.0
        assert job.combine_progress == 100.0
        assert job.output_path.endswith("clip.mp4")
        assert job.output_bytes > 0
        cap = cv2.VideoCapture(job.output_path)
        count = 0
        while True:
            ok, img = cap.read()
            if not ok:
                break
            assert img.shape[:2] == (48, 64)
            count += 1
        assert count == 12

    def test_wave_retry_then_success(self, tmp_path, clip_y4m):
        flaky = {"fails_left": 2, "calls": 0}

        class FlakyEncoder:
            def __init__(self, meta, settings, mesh):
                from thinvids_tpu.parallel.dispatch import GopShardEncoder

                self.inner = LocalExecutor._default_encoder(
                    meta, settings, mesh)

            def plan(self, n):
                return self.inner.plan(n)

            def stage_waves(self, frames):
                return self.inner.stage_waves(frames)

            def dispatch_wave(self, staged):
                return self.inner.dispatch_wave(staged)

            def collect_wave(self, pending):
                flaky["calls"] += 1
                if flaky["fails_left"] > 0:
                    flaky["fails_left"] -= 1
                    raise RuntimeError("injected wave failure")
                return self.inner.collect_wave(pending)

        coord, _ = make_rig(
            tmp_path, encoder_factory=lambda m, s, mesh: FlakyEncoder(
                m, s, mesh))
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        assert flaky["calls"] >= 3      # 2 failures + successful retries

    def test_retry_budget_exhausted_fails_with_attribution(
            self, tmp_path, clip_y4m):
        class DeadEncoder:
            def __init__(self, meta, settings, mesh):
                self.inner = LocalExecutor._default_encoder(
                    meta, settings, mesh)

            def plan(self, n):
                return self.inner.plan(n)

            def stage_waves(self, frames):
                return self.inner.stage_waves(frames)

            def dispatch_wave(self, staged):
                return self.inner.dispatch_wave(staged)

            def collect_wave(self, pending):
                raise RuntimeError("device on fire")

        snap = make_settings(gop_frames=4, qp=30, part_failure_max_retries=1,
                             heartbeat_throttle_s=0.0)
        coord, _ = make_rig(
            tmp_path, settings=snap,
            encoder_factory=lambda m, s, mesh: DeadEncoder(m, s, mesh))
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.FAILED
        assert job.failure_stage == "encode"
        assert job.failure_host == "local"
        assert "1 retries" in job.failure_reason
        assert "device on fire" in job.failure_reason

    def test_profile_dir_emits_device_trace(self, tmp_path, clip_y4m):
        import os

        trace_dir = tmp_path / "traces"
        snap = make_settings(gop_frames=4, qp=30,
                             heartbeat_throttle_s=0.0,
                             profile_dir=str(trace_dir))
        coord, _ = make_rig(tmp_path, settings=snap)
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        files = [os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
                 for f in fs]
        assert files, "profiler trace directory is empty"

    def test_elastic_replan_on_shrunken_mesh(self, tmp_path, clip_y4m):
        """A wave that keeps failing on the full mesh exhausts its
        budget; the executor re-plans the remaining frames on a smaller
        mesh and the job still completes with every frame (SURVEY §2.9
        Elastic DP)."""
        from thinvids_tpu.tools import oracle

        mesh_sizes = []

        class DyingMeshEncoder:
            """Collect always fails while the mesh has 8 devices."""

            def __init__(self, meta, settings, mesh):
                self.inner = LocalExecutor._default_encoder(
                    meta, settings, mesh)
                mesh_sizes.append(self.inner.num_devices)

            def __getattr__(self, name):      # mesh/meta/offsets delegate
                return getattr(self.inner, name)

            def __setattr__(self, name, value):
                if name == "inner":
                    object.__setattr__(self, name, value)
                else:
                    setattr(self.inner, name, value)

            def collect_wave(self, pending):
                if self.inner.num_devices == 8:
                    raise RuntimeError("slice lost a chip")
                return self.inner.collect_wave(pending)

        snap = make_settings(gop_frames=4, qp=30,
                             part_failure_max_retries=1,
                             heartbeat_throttle_s=0.0)
        coord, _ = make_rig(
            tmp_path, settings=snap,
            encoder_factory=lambda m, s, mesh: DyingMeshEncoder(m, s, mesh))
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        assert mesh_sizes == [8, 7]           # one shrink step sufficed
        # the suffix re-plan changes the GOP total; progress must track it
        assert job.parts_total == job.parts_done
        assert job.encode_progress == 100.0
        assert any("replanning" in line
                   for line in coord.activity.fetch_job(job.id))
        if oracle.oracle_available():
            with open(job.output_path, "rb") as fp:
                from thinvids_tpu.io.mp4 import demux_mp4

                media = demux_mp4(fp.read())
            assert len(oracle.decode_h264(media.annexb)) == 12

    def test_single_device_mesh_cannot_replan_fails(self, tmp_path,
                                                    clip_y4m):
        class DeadEncoder:
            def __init__(self, meta, settings, mesh):
                import numpy as np
                import jax
                from jax.sharding import Mesh

                self.inner = LocalExecutor._default_encoder(
                    meta, settings,
                    Mesh(np.array(jax.devices()[:1]), ("gop",)))

            def __getattr__(self, name):
                return getattr(self.inner, name)

            def collect_wave(self, pending):
                raise RuntimeError("single chip on fire")

        snap = make_settings(gop_frames=4, qp=30,
                             part_failure_max_retries=0,
                             heartbeat_throttle_s=0.0)
        coord, _ = make_rig(
            tmp_path, settings=snap,
            encoder_factory=lambda m, s, mesh: DeadEncoder(m, s, mesh))
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.FAILED
        assert "single chip on fire" in job.failure_reason

    def test_stopped_job_halts_between_waves(self, tmp_path, clip_y4m):
        coord_holder = {}

        class StoppingEncoder:
            """Stops the job after the first collected wave."""

            def __init__(self, meta, settings, mesh):
                self.inner = LocalExecutor._default_encoder(
                    meta, settings, mesh)
                self.collected = 0

            def plan(self, n):
                return self.inner.plan(n)

            def stage_waves(self, frames):
                # one GOP per wave so the halt check between waves fires
                for staged in self.inner.stage_waves(frames):
                    yield staged

            def dispatch_wave(self, staged):
                return self.inner.dispatch_wave(staged)

            def collect_wave(self, pending):
                out = self.inner.collect_wave(pending)
                self.collected += 1
                coord_holder["coord"].stop_job(coord_holder["job_id"])
                return out

        # mesh of 1 virtual device → several waves for 3 GOPs
        import jax

        mesh1 = None
        from thinvids_tpu.parallel.dispatch import default_mesh

        mesh1 = default_mesh(jax.devices()[:1])
        enc_holder = {}

        def factory(m, s, mesh):
            enc = StoppingEncoder(m, s, mesh1)
            enc_holder["enc"] = enc
            return enc

        coord, _ = make_rig(tmp_path, encoder_factory=factory)
        coord_holder["coord"] = coord
        # add_job dispatches synchronously; capture id via launcher wrap
        orig_launch = coord._launcher

        def launch(job):
            coord_holder["job_id"] = job.id
            orig_launch(job)
        coord._launcher = launch
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.STOPPED
        assert enc_holder["enc"].collected == 1     # halted before wave 2
        assert job.output_path == ""


class TestProgressHistory:
    def test_monotonic_progress_and_heartbeats(self, tmp_path, clip_y4m):
        progress = []

        class SpyCoordinator(Coordinator):
            def update_progress(self, job_id, token, **fields):
                progress.append(dict(fields))
                return super().update_progress(job_id, token, **fields)

        snap = make_settings(gop_frames=4, qp=30, heartbeat_throttle_s=0.0)
        reg = WorkerRegistry()
        for i in range(8):
            reg.heartbeat(f"w{i:02d}")
        coord = SpyCoordinator(registry=reg, settings_fn=lambda: snap)
        execu = LocalExecutor(coord, output_dir=str(tmp_path / "lib"),
                              sync=True)
        coord._launcher = execu.launch
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.DONE
        encs = [p["encode_progress"] for p in progress
                if "encode_progress" in p]
        assert encs == sorted(encs) and encs[-1] == 100.0
        dones = [p["parts_done"] for p in progress if "parts_done" in p]
        assert dones == sorted(dones) and dones[-1] == 8
        assert job.heartbeat_stage in ("encode", "stitch")


class TestWaveOrder:
    """The executor's wave loop (PR 29): wave n's fetch is started
    before wave n+1's program is dispatched, and wave n is collected
    under wave n+1's program; encoders without the step keep the old
    order."""

    def _rig(self, tmp_path, factory, **settings):
        import jax

        from thinvids_tpu.parallel.dispatch import default_mesh

        snap = make_settings(gop_frames=4, qp=30,
                             heartbeat_throttle_s=0.0, **settings)
        return make_rig(tmp_path, settings=snap,
                        mesh=default_mesh(jax.devices()[:1]),
                        encoder_factory=factory)

    def _run(self, coord, clip_y4m):
        job = coord.add_job(clip_y4m, VideoMeta(width=64, height=48,
                                                num_frames=12))
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        assert job.parts_done == 3          # 12 frames, GOP 4, 1 device
        return job

    def test_fetch_of_wave_n_starts_before_dispatch_of_wave_n_plus_1(
            self, tmp_path, clip_y4m):
        from thinvids_tpu.parallel.dispatch import GopShardEncoder

        log = []

        class Recording(GopShardEncoder):
            def dispatch_wave(self, staged):
                log.append(("dispatch", staged[0][0].index))
                return super().dispatch_wave(staged)

            def start_fetch(self, pending):
                if pending[8].tiny is None:     # not started yet
                    log.append(("fetch", pending[0][0].index))
                super().start_fetch(pending)

            def collect_wave(self, pending):
                log.append(("collect", pending[0][0].index))
                return super().collect_wave(pending)

        def factory(meta, settings, mesh):
            return Recording(meta, qp=int(settings.qp), mesh=mesh,
                             gop_frames=int(settings.gop_frames))

        coord, _ = self._rig(tmp_path, factory)
        job = self._run(coord, clip_y4m)
        # one GOP per wave: three waves for three GOPs
        assert [e for e in log if e[0] == "dispatch"] \
            == [("dispatch", i) for i in range(3)]
        assert log == [
            ("dispatch", 0),
            ("fetch", 0), ("dispatch", 1), ("collect", 0),
            ("fetch", 1), ("dispatch", 2), ("collect", 1),
            ("collect", 2), ("fetch", 2),   # last wave: collect starts it
        ], log
        # the handoff has a span of its own in the job's trace
        from thinvids_tpu.obs import trace as obs_trace

        doc = obs_trace.TRACE.export_chrome(job.id)
        names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
        assert names.count("wave_fetch_start") == 3
        assert names.count("wave_dispatch") == 3
        assert names.count("wave_collect") == 3

    def test_encoder_without_the_step_keeps_the_old_order(
            self, tmp_path, clip_y4m):
        """A double with no start_fetch (the ladder encoder has none
        either): dispatch n+1, then collect n, as before."""
        log = []

        class Plain:
            def __init__(self, meta, settings, mesh):
                self.inner = LocalExecutor._default_encoder(
                    meta, settings, mesh)

            def plan(self, n):
                return self.inner.plan(n)

            def stage_waves(self, frames):
                return self.inner.stage_waves(frames)

            def dispatch_wave(self, staged):
                log.append(("dispatch", staged[0][0].index))
                return self.inner.dispatch_wave(staged)

            def collect_wave(self, pending):
                log.append(("collect", pending[0][0].index))
                return self.inner.collect_wave(pending)

        coord, _ = self._rig(tmp_path, Plain)
        self._run(coord, clip_y4m)
        assert log == [("dispatch", 0), ("dispatch", 1), ("collect", 0),
                       ("dispatch", 2), ("collect", 1), ("collect", 2)]

    def test_ladder_call_order_unchanged(self, monkeypatch):
        """The ladder dispatches a wave's rungs together and collects
        them together, two waves deep; each rung's fetch is started
        inside its own collect_wave, never ahead of a dispatch."""
        from thinvids_tpu.abr.ladder import LadderShardEncoder, plan_ladder
        from thinvids_tpu.parallel.dispatch import GopShardEncoder

        log = []
        depth = {"collect": 0}

        class Rung(GopShardEncoder):
            def dispatch_wave(self, staged):
                log.append(("dispatch", staged[0][0].index))
                return super().dispatch_wave(staged)

            def start_fetch(self, pending):
                if pending[8].tiny is None:     # not started yet
                    assert depth["collect"] == 1, "fetch ahead of collect"
                super().start_fetch(pending)

            def collect_wave(self, pending):
                log.append(("collect", pending[0][0].index))
                depth["collect"] += 1
                try:
                    return super().collect_wave(pending)
                finally:
                    depth["collect"] -= 1

        import jax

        from thinvids_tpu.parallel import dispatch as dispatch_mod

        meta = VideoMeta(width=64, height=48, fps_num=30, fps_den=1,
                         num_frames=12)
        rungs = plan_ladder(meta, make_settings(qp=30, ladder_rungs="32"))
        assert len(rungs) == 2
        monkeypatch.setattr(dispatch_mod, "GopShardEncoder", Rung)
        ladder = LadderShardEncoder(
            meta, rungs, gop_frames=4,
            mesh=dispatch_mod.default_mesh(jax.devices()[:1]))
        assert not hasattr(ladder, "start_fetch")
        bundles = ladder.encode(clip_frames(n=12))
        assert len(bundles) == 3
        assert log == [("dispatch", 0)] * 2 + [("dispatch", 1)] * 2 \
            + [("collect", 0)] * 2 + [("dispatch", 2)] * 2 \
            + [("collect", 1)] * 2 + [("collect", 2)] * 2, log
