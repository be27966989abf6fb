"""One clock for host and device in the profiled job (ISSUE 35).

A job with the setting `profile_dir` runs under the executor's profile
context: clocks `profile_start` / `profile_stop` round the profiler's
start and stop, the annotation `tvt:encode_stage` round the encode
stage, and every span of the program as an annotation `tvt:<name>` in
the `.xplane.pb`, on the profiler's clock. Every job's phases outside
the wave pipeline are clocked (`job_build` … `job_commit`) in the
process totals — and stay OUT of `GET /trace/<job>`, whose first and
last span the benchmark takes for the extent of the wave pipeline.

ISSUE 39: so is the set-up of an executable. The clock `program_build`
runs round the first call of each GOP / step program of the process
(form, RdConfig, shape) and `programs_built` counts them; a second job
of that shape starts neither, and neither is a span of a job's ring.
"""

import os
import types

import pytest

from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
from thinvids_tpu.core.status import Status
from thinvids_tpu.core.types import VideoMeta
from thinvids_tpu.io.y4m import write_y4m
from thinvids_tpu.obs import trace
from thinvids_tpu.tools.pan import make_frames

#: the "X" events of a local GOP-shape job's GET /trace/<job> at the
#: parent of this PR: `benchmark/tvtbench/evidence.pipeline_extent`
#: takes min and max over ALL of them, so a span outside the wave
#: pipeline would turn `job_fixed_ms` and `mux_ms_per_job` to ~0.
#: (Since PR 48 a job inside the sparse budgets has no `unflatten`:
#: its slice thunks build their views on the levels they unpack; the
#: span is the dense fallback's and a library-less host's.)
PIPELINE_SPANS = {"decode", "stage", "upload", "dispatch", "device_wait",
                  "fetch", "sparse_unpack", "pack", "concat",
                  "wave_dispatch", "wave_collect", "wave_fetch_start"}
JOB_CLOCKS = ("job_build", "job_plan", "job_stitch", "job_mux",
              "job_write", "job_commit")


def run_job(tmp_path, name, encoder_factory=None, width=64, **settings):
    """(coordinator, finished job) of one 8-frame 64x48 local job in
    four GOPs, run in the calling thread."""
    meta = VideoMeta(width=width, height=48, fps_num=30, fps_den=1,
                     num_frames=8)
    clip = tmp_path / f"{name}.y4m"
    write_y4m(str(clip), meta, make_frames(8, width, 48))
    snap = Settings(values=dict(
        DEFAULT_SETTINGS, gop_frames=2, qp=30, heartbeat_throttle_s=0.0,
        **settings))
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / "lib"),
                          sync=True, encoder_factory=encoder_factory)
    coord._launcher = execu.launch
    job = coord.add_job(str(clip), meta)
    return coord, coord.store.get(job.id)


def stage_ms():
    from thinvids_tpu.parallel.dispatch import stage_snapshot

    return stage_snapshot()


def grew(before, after, key):
    return float(after.get(key, 0)) - float(before.get(key, 0))


def host_annotations(profile_dir):
    """[(thread line, name, start_ns, end_ns)] of the `tvt:*` events of
    the one `.xplane.pb` under `profile_dir`."""
    from jax.profiler import ProfileData

    found = [os.path.join(d, f) for d, _s, fs in os.walk(profile_dir)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(found) == 1, found
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            out += [(i, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name.startswith("tvt:")]
    return out


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    """One unprofiled and one profiled job of the same clip, with the
    process totals before, between and after."""
    tmp = tmp_path_factory.mktemp("profile_clock")
    s0 = stage_ms()
    coord_a, plain = run_job(tmp, "plain")
    s1 = stage_ms()
    coord_b, profiled = run_job(tmp, "profiled",
                                profile_dir=str(tmp / "profiles"))
    s2 = stage_ms()
    assert plain.status is Status.DONE, plain.failure_reason
    assert profiled.status is Status.DONE, profiled.failure_reason
    return types.SimpleNamespace(
        tmp=tmp, plain=plain, profiled=profiled, coords=(coord_a, coord_b),
        snaps=(s0, s1, s2))


class TestProfileContext:
    def test_encode_stage_encloses_the_pipeline_annotations(self, pair):
        notes = host_annotations(pair.tmp / "profiles")
        stage = [n for n in notes if n[1] == "tvt:encode_stage"]
        assert len(stage) == 1
        _line, _name, lo, hi = stage[0]
        names = {n[1] for n in notes}
        for want in ("decode", "stage", "dispatch", "device_wait",
                     "pack", "wave_dispatch", "wave_collect",
                     "job_build", "job_plan"):
            assert f"tvt:{want}" in names, f"no tvt:{want} in the profile"
        for _l, name, start, end in notes:
            assert lo <= start and end <= hi, \
                f"{name} [{start}, {end}] outside the stage [{lo}, {hi}]"
        # what follows the stage is clocked, and not annotated: the
        # profile has stopped
        assert not names & {"tvt:job_stitch", "tvt:job_mux",
                            "tvt:profile_start", "tvt:profile_stop"}

    def test_factory_is_cleared_after_the_job(self, pair):
        assert trace._ANNOTATE is None
        assert trace.annotation("decode") is trace._NO_ANNOTATION

    def test_factory_is_cleared_when_the_job_fails(self, tmp_path):
        def broken_factory(_meta, _settings, _mesh):
            assert trace._ANNOTATE is not None    # the profile is live
            raise RuntimeError("no encoder today")

        _coord, job = run_job(tmp_path, "broken",
                              encoder_factory=broken_factory,
                              profile_dir=str(tmp_path / "profiles"))
        assert job.status is Status.FAILED
        assert "no encoder today" in job.failure_reason
        assert trace._ANNOTATE is None
        # and the profiler was stopped: the next profile can start
        _coord, again = run_job(tmp_path, "again",
                                profile_dir=str(tmp_path / "profiles2"))
        assert again.status is Status.DONE, again.failure_reason

    def test_profiled_output_equals_the_unprofiled(self, pair):
        with open(pair.plain.output_path, "rb") as fp:
            plain = fp.read()
        with open(pair.profiled.output_path, "rb") as fp:
            profiled = fp.read()
        assert plain and plain == profiled

    def test_profile_clocks_grow_for_the_profiled_job_alone(self, pair):
        s0, s1, s2 = pair.snaps
        for key in ("profile_start", "profile_stop"):
            assert grew(s0, s1, key) == 0, key
            assert grew(s1, s2, key) > 0, key


class TestJobClocks:
    def test_each_clock_runs_once_a_job(self, tmp_path, monkeypatch):
        from thinvids_tpu.parallel import dispatch

        seen = []
        real = dispatch.job_clock

        def counting(name):
            seen.append(name)
            return real(name)

        monkeypatch.setattr(dispatch, "job_clock", counting)
        _coord, job = run_job(tmp_path, "counted")
        assert job.status is Status.DONE, job.failure_reason
        assert sorted(seen) == sorted(JOB_CLOCKS)
        assert set(JOB_CLOCKS) <= set(dispatch.JOB_CLOCKS)

    def test_clocks_grow_and_fit_inside_the_run(self, pair):
        s0, s1, _s2 = pair.snaps
        each = [grew(s0, s1, key) for key in JOB_CLOCKS]
        # (the snapshot rounds to 10 us: joining four tiny segments
        # may read 0)
        assert all(ms >= 0 for ms in each), dict(zip(JOB_CLOCKS, each))
        for key in ("job_build", "job_mux", "job_write", "job_commit"):
            assert grew(s0, s1, key) > 0, key
        run_ms = (pair.plain.finished_at - pair.plain.started_at) * 1e3
        assert sum(each) < run_ms

    @pytest.mark.parametrize("which", ["plain", "profiled"])
    def test_trace_holds_the_pipeline_spans_and_no_other(self, pair,
                                                         which):
        from thinvids_tpu.api.server import ApiServer

        coord = pair.coords[which == "profiled"]
        job = getattr(pair, which)
        status, doc = ApiServer(coord).route(
            "GET", f"/trace/{job.id}", {}, {})
        assert status == 200
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert names == PIPELINE_SPANS


@pytest.fixture
def no_program_yet(monkeypatch):
    """This process as if it had called no step program: what other
    tests of the worker built is forgotten for the test (the jit cache
    may still hold the executable: the first call is clocked either
    way, a cache load being one way to set a program up)."""
    from thinvids_tpu.parallel import dispatch

    monkeypatch.setattr(dispatch, "_PROGRAMS_BUILT", set())
    return dispatch


def with_rd(rd):
    def factory(meta, settings, mesh):
        from thinvids_tpu.parallel.dispatch import GopShardEncoder

        return GopShardEncoder(meta, qp=int(settings.qp), mesh=mesh,
                               gop_frames=int(settings.gop_frames), rd=rd)
    return factory


class TestProgramBuild:
    #: what the second job differs in -> executables it sets up
    SECOND = {"nothing": 0, "shape": 1, "rd": 1}

    @pytest.mark.parametrize("differs", sorted(SECOND))
    def test_counted_once_per_form_rd_and_shape(self, tmp_path,
                                                no_program_yet, differs):
        from thinvids_tpu.codecs.h264.rdo import RD_OFF, RdConfig

        s0 = stage_ms()
        _c, first = run_job(tmp_path, "first", with_rd(RD_OFF))
        s1 = stage_ms()
        assert first.status is Status.DONE, first.failure_reason
        # however many waves the job had, ONE executable: the first
        # call's
        assert grew(s0, s1, "programs_built") == 1
        assert grew(s0, s1, "program_build") > 0
        assert grew(s0, s1, "program_build") <= grew(s0, s1, "dispatch")
        _c, second = run_job(
            tmp_path, "second",
            with_rd(RdConfig(pskip=True) if differs == "rd" else RD_OFF),
            width=80 if differs == "shape" else 64)
        s2 = stage_ms()
        assert second.status is Status.DONE, second.failure_reason
        assert grew(s1, s2, "programs_built") == self.SECOND[differs]
        assert (grew(s1, s2, "program_build") > 0) == \
            bool(self.SECOND[differs])

    def test_the_log_names_the_executable_once(self, tmp_path,
                                               no_program_yet, caplog):
        import logging

        with caplog.at_level(logging.INFO,
                             logger="thinvids_tpu.parallel.dispatch"):
            run_job(tmp_path, "a")
            run_job(tmp_path, "b")
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("program built")]
        assert len(lines) == 1
        assert "form=scan" in lines[0] and "RdConfig(" in lines[0]
        assert "shape=(" in lines[0] and ", 48, 64)" in lines[0]

    def test_a_live_profile_holds_it_and_no_ring_does(self, tmp_path,
                                                      no_program_yet):
        """`tvt:program_build` inside `tvt:dispatch` in the profiled
        job's `.xplane.pb`; the job's ring keeps the pipeline's spans
        alone, so `job_fixed_ms` and `mux_ms_per_job` read what they
        read."""
        from thinvids_tpu.api.server import ApiServer

        coord, job = run_job(tmp_path, "live",
                             profile_dir=str(tmp_path / "profiles"))
        assert job.status is Status.DONE, job.failure_reason
        notes = host_annotations(tmp_path / "profiles")
        built = [n for n in notes if n[1] == "tvt:program_build"]
        assert len(built) == 1
        line, _name, lo, hi = built[0]
        assert any(n[0] == line and n[1] == "tvt:dispatch"
                   and n[2] <= lo and hi <= n[3] for n in notes)
        status, doc = ApiServer(coord).route(
            "GET", f"/trace/{job.id}", {}, {})
        assert status == 200
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert names == PIPELINE_SPANS

    def test_the_first_snapshot_has_both_keys(self):
        """A reader takes growth between two snapshots, and the
        benchmark's `program_build_s` the first one's value."""
        snap = stage_ms()
        assert snap["program_build"] >= 0
        assert isinstance(snap["programs_built"], int)

