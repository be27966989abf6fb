"""Observability layer (thinvids_tpu/obs/): metrics registry +
Prometheus exposition, distributed tracing, flight recorder.

Covers the ISSUE 10 acceptance surface:

- ``GET /metrics`` serves VALID Prometheus text exposition (asserted
  by the strict parser below) covering stage, origin, QoS and
  shard-board metrics;
- ``GET /trace/<job>`` exports valid Chrome trace-event JSON whose
  spans nest correctly for a local e2e job, and — for a 2-worker
  remote e2e job over the real HTTP /work protocol — yields ONE trace
  whose coordinator and worker spans share the job's trace id
  (X-Tvt-Trace propagation);
- the flight recorder dumps ``<job>.trace.json`` on an injected shard
  failure (worker quarantine) and on job failure;
- tracing enabled changes no output bytes and its overhead is bounded.
"""

import contextlib
import json
import os
import re
import threading
import time

import numpy as np
import pytest

from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.core.config import (DEFAULT_SETTINGS, Settings,
                                      reset_live_settings,
                                      update_live_settings)
from thinvids_tpu.core.status import Status
from thinvids_tpu.core.types import VideoMeta
from thinvids_tpu.io.y4m import write_y4m
from thinvids_tpu.obs import flight, trace
from thinvids_tpu.obs.metrics import MetricsRegistry, REGISTRY
from thinvids_tpu.tools.pan import make_frames


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def make_settings(**over):
    return Settings(values=dict(DEFAULT_SETTINGS, **over))


def clip_frames(w=64, h=48, n=8):
    return make_frames(n, w, h)


def write_clip(path, w=64, h=48, n=8):
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=n)
    write_y4m(str(path), meta, clip_frames(w, h, n))
    return meta


_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(value: str) -> str:
    return (value.replace(r"\"", '"').replace(r"\n", "\n")
            .replace("\\\\", "\\"))


def _value(text: str) -> float:
    if text == "+Inf":
        return float("inf")
    return float(text)


def parse_prometheus(text):
    """Strict text-exposition parser: every sample line must belong to
    a family announced by # HELP + # TYPE, labels must parse, values
    must be numbers. Returns {family: {"type", "help", "samples":
    [(name, {label: value}, float)]}}."""
    families = {}
    owner = {}
    for line in text.rstrip("\n").split("\n"):
        assert line.strip() == line and line, f"bad line {line!r}"
        if line.startswith("# HELP "):
            _h, name, help_text = line[2:].split(" ", 2)
            families[name] = {"help": help_text, "type": None,
                              "samples": []}
            owner[name] = name
        elif line.startswith("# TYPE "):
            _t, name, kind = line[2:].split(" ", 2)
            assert name in families, f"TYPE before HELP for {name}"
            families[name]["type"] = kind
            if kind == "histogram":
                for suffix in ("_bucket", "_sum", "_count"):
                    owner[name + suffix] = name
        elif line.startswith("#"):
            continue
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"unparseable sample line {line!r}"
            name, raw_labels, raw_value = m.groups()
            fam = owner.get(name)
            assert fam is not None, f"sample {name} for unknown family"
            labels = {}
            if raw_labels:
                consumed = 0
                for lm in _LABEL_RE.finditer(raw_labels):
                    labels[lm.group(1)] = _unescape(lm.group(2))
                    consumed = lm.end()
                rest = raw_labels[consumed:].strip(", ")
                assert not rest, f"unparsed labels {rest!r} in {line!r}"
            families[fam]["samples"].append(
                (name, labels, _value(raw_value)))
    for name, fam in families.items():
        assert fam["type"] in ("counter", "gauge", "histogram"), name
    return families


def local_rig(tmp_path, snap, workers=8, **executor_kw):
    reg = WorkerRegistry()
    for i in range(workers):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / "lib"),
                          sync=True, **executor_kw)
    coord._launcher = execu.launch
    return coord, execu


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_histogram_render_and_parse(self):
        reg = MetricsRegistry()
        c = reg.counter("t_requests_total", "requests", labels=("route",))
        c.labels("hls").inc()
        c.labels("hls").inc(2)
        g = reg.gauge("t_sessions", "sessions")
        g.set(7)
        h = reg.histogram("t_latency_seconds", "latency",
                          buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 5.0, 50.0):
            h.observe(v)
        fams = parse_prometheus(reg.render())
        assert fams["t_requests_total"]["type"] == "counter"
        assert ("t_requests_total", {"route": "hls"}, 3.0) \
            in fams["t_requests_total"]["samples"]
        assert ("t_sessions", {}, 7.0) in fams["t_sessions"]["samples"]
        assert fams["t_latency_seconds"]["type"] == "histogram"

    def test_histogram_buckets_monotone_and_inf_equals_count(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_h_seconds", "h", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.005, 0.05, 0.5, 2.0, 9.0):
            h.observe(v)
        fams = parse_prometheus(reg.render())
        samples = fams["t_h_seconds"]["samples"]
        buckets = [(labels["le"], v) for name, labels, v in samples
                   if name.endswith("_bucket")]
        counts = [v for _le, v in buckets]
        assert counts == sorted(counts), "bucket counts must be cumulative"
        count = next(v for name, _l, v in samples
                     if name.endswith("_count"))
        total = next(v for name, _l, v in samples
                     if name.endswith("_sum"))
        assert buckets[-1][0] == "+Inf" and buckets[-1][1] == count == 6
        assert total == pytest.approx(11.56)

    def test_label_escaping_roundtrips(self):
        reg = MetricsRegistry()
        g = reg.gauge("t_esc", "esc", labels=("path",))
        nasty = 'a"b\\c\nd'
        g.labels(nasty).set(1)
        fams = parse_prometheus(reg.render())
        (_name, labels, value), = fams["t_esc"]["samples"]
        assert labels["path"] == nasty and value == 1.0

    def test_conflicting_redeclaration_raises(self):
        reg = MetricsRegistry()
        reg.counter("t_x_total", "x")
        assert reg.counter("t_x_total", "x") is reg.get("t_x_total")
        with pytest.raises(ValueError):
            reg.gauge("t_x_total", "x")
        with pytest.raises(ValueError):
            reg.counter("t_x_total", "x", labels=("a",))


# ---------------------------------------------------------------------------
# trace store
# ---------------------------------------------------------------------------


class TestTraceStore:
    def test_ring_bound_honors_trace_ring_spans(self):
        update_live_settings({"trace_ring_spans": 256})
        try:
            store = trace.TraceStore()
            store.start("jring")
            for i in range(300):
                store.record_span("jring", "s", t0=float(i), dur_s=0.01)
            snap = store.snapshot("jring")
            assert len(snap["spans"]) == 256
            # oldest evicted, newest kept
            assert snap["spans"][-1]["t0"] == 299.0
        finally:
            reset_live_settings()

    def test_trace_sample_zero_records_nothing(self):
        update_live_settings({"trace_sample": 0.0})
        try:
            store = trace.TraceStore()
            assert store.start("joff") == ""
            rec = store.recorder("joff")
            assert not rec.enabled
            with rec.span("anything"):
                pass
            assert store.snapshot("joff")["spans"] == []
        finally:
            reset_live_settings()

    def test_ingest_drops_stale_trace_id(self):
        store = trace.TraceStore()
        tid = store.start("jr")
        wire = [{"name": "w", "t0": 1.0, "dur_s": 0.5,
                 "tags": {"k": 1}}]
        assert store.ingest("jr", "not-the-trace", wire) == 0
        assert store.ingest("jr", tid, wire, host="w00") == 1
        span = store.snapshot("jr")["spans"][0]
        assert span["host"] == "w00" and span["tags"] == {"k": 1}

    def test_export_chrome_shape(self):
        store = trace.TraceStore()
        tid = store.start("jx")
        rec = store.recorder("jx", host="h1")
        with rec.span("outer", wave=0):
            with rec.span("inner"):
                pass
        doc = store.export_chrome("jx")
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in events} == {"outer", "inner"}
        for e in events:
            assert isinstance(e["ts"], int) and e["dur"] >= 1
            assert e["args"]["trace_id"] == tid
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert any(e["name"] == "process_name"
                   and e["args"]["name"] == "h1" for e in metas)
        assert doc["otherData"]["trace_id"] == tid

    def test_eviction_is_lru_by_activity_not_start_order(self):
        """A long-running job that keeps recording must survive 64+
        later dispatches; the idle completed jobs age out instead."""
        store = trace.TraceStore()
        store.start("long-runner")
        for i in range(trace.MAX_JOBS - 1):
            store.start(f"short-{i}")
            # the long job records between other dispatches (activity)
            store.record_span("long-runner", "wave", t0=float(i),
                              dur_s=0.1)
        store.start("one-more")        # evicts the LRU entry
        assert store.snapshot("long-runner") is not None
        assert store.snapshot("short-0") is None

    def test_restart_gets_fresh_trace_and_drops_straggler_spans(self):
        store = trace.TraceStore()
        old = store.start("j2")
        new = store.start("j2")
        assert old != new
        assert store.ingest(
            "j2", old, [{"name": "stale", "t0": 1.0, "dur_s": 1.0}]) == 0
        assert store.trace_id("j2") == new

    def test_bind_exposes_ids_to_current_thread(self):
        assert trace.current_ids() is None
        with trace.bind("jobX", "traceY"):
            assert trace.current_ids() == ("jobX", "traceY")
        assert trace.current_ids() is None


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------


class _FakeAnnotation:
    """Stands in for `jax.profiler.TraceAnnotation`: notes every name
    it is entered and left with."""

    def __init__(self):
        self.log = []

    @contextlib.contextmanager
    def __call__(self, name):
        self.log.append(("enter", name))
        try:
            yield
        finally:
            self.log.append(("exit", name))


class TestProfilerAnnotations:
    """While a device profile is live the executor hands obs/trace a
    factory of annotations; every span then opens `tvt:<name>`."""

    @pytest.fixture
    def fake(self):
        fake = _FakeAnnotation()
        trace.set_annotation_factory(fake)
        yield fake
        trace.set_annotation_factory(None)

    @staticmethod
    def _recorders():
        trace.TRACE.start("annotated-job")
        yield trace.TRACE.recorder("annotated-job")
        trace.TRACE.drop("annotated-job")
        yield trace.NULL_RECORDER       # a job sampled out

    def test_span_recorder_enters_and_leaves_it(self, fake):
        for rec in self._recorders():
            fake.log.clear()
            with rec.span("wave_dispatch", wave=0):
                assert fake.log == [("enter", "tvt:wave_dispatch")]
            assert fake.log == [("enter", "tvt:wave_dispatch"),
                                ("exit", "tvt:wave_dispatch")]

    def test_stage_profile_enters_and_leaves_it(self, fake):
        from thinvids_tpu.parallel.dispatch import StageProfile

        prof = StageProfile()
        with pytest.raises(ValueError):
            with prof.stage("decode"):
                with prof.stage("pack", part_of="sfe"):
                    raise ValueError("mid-stage")
        assert fake.log == [("enter", "tvt:decode"), ("enter", "tvt:pack"),
                            ("exit", "tvt:pack"), ("exit", "tvt:decode")]
        snap = prof.snapshot()
        assert snap["decode"] >= snap["pack"] == snap["sfe"] > 0

    def test_untouched_when_no_profile_is_live(self, fake):
        from thinvids_tpu.parallel.dispatch import StageProfile

        trace.set_annotation_factory(None)
        assert trace.annotation("decode") is trace.annotation("pack")
        with StageProfile().stage("decode"):
            pass
        for rec in self._recorders():
            with rec.span("wave_collect"):
                pass
        assert fake.log == []

    def test_trace_module_imports_and_annotates_without_jax(self):
        import subprocess
        import sys

        code = ("import sys\n"
                "sys.modules['jax'] = None\n"
                "from thinvids_tpu.obs import trace\n"
                "with trace.annotation('decode'):\n"
                "    pass\n"
                "with trace.NULL_RECORDER.span('wave_collect'):\n"
                "    pass\n"
                "print('ok')\n")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, PYTHONPATH=repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0 and "ok" in out.stdout, out.stderr


class TestSnapshotDuringImport:
    def test_metrics_snapshot_is_whole_while_dispatch_imports(
            self, monkeypatch):
        """A module sits in sys.modules from the moment its import
        starts: while the first job's thread is still importing
        parallel/dispatch (and jaxme) the snapshot answers 200 with the
        parts it cannot fill empty, not 500."""
        import sys
        import types

        from thinvids_tpu.api.server import ApiServer

        for name in ("thinvids_tpu.parallel.dispatch",
                     "thinvids_tpu.codecs.h264.jaxme"):
            monkeypatch.setitem(sys.modules, name, types.ModuleType(name))
        api = ApiServer(Coordinator(settings_fn=lambda: make_settings()))
        status, out = api.route("GET", "/metrics_snapshot", {}, {})
        assert status == 200
        assert out["stage_ms"] == {} and out["sfe_latency_ms"] == {}
        assert out["motion_search"] is None


class TestKnobs:
    def test_clamps(self):
        try:
            applied = update_live_settings({
                "trace_ring_spans": 1, "trace_sample": 7.5,
                "metrics_enabled": "0", "flight_record": "no"})
            assert applied["trace_ring_spans"] == 256
            assert applied["trace_sample"] == 1.0
            assert applied["metrics_enabled"] is False
            assert applied["flight_record"] is False
            applied = update_live_settings({"trace_ring_spans": 10 ** 9})
            assert applied["trace_ring_spans"] == 65536
        finally:
            reset_live_settings()

    def test_metrics_endpoint_gated_by_metrics_enabled(self):
        from thinvids_tpu.api.server import ApiError, ApiServer

        coord = Coordinator(
            settings_fn=lambda: make_settings(metrics_enabled=False))
        api = ApiServer(coord)
        with pytest.raises(ApiError) as ei:
            api.route("GET", "/metrics", {}, {})
        assert ei.value.status == 404


# ---------------------------------------------------------------------------
# JSON log mode
# ---------------------------------------------------------------------------


class TestJsonLogs:
    def _record(self, msg="hello"):
        import logging

        return logging.LogRecord("thinvids_tpu.test", logging.INFO,
                                 __file__, 1, msg, None, None)

    def test_json_formatter_emits_one_object_with_trace_ids(self):
        from thinvids_tpu.core.log import JsonFormatter

        fmt = JsonFormatter("hostA")
        doc = json.loads(fmt.format(self._record()))
        assert doc["msg"] == "hello" and doc["host"] == "hostA"
        assert doc["level"] == "INFO" and "job_id" not in doc
        with trace.bind("jobJ", "traceT"):
            doc = json.loads(fmt.format(self._record("in job")))
        assert doc["job_id"] == "jobJ" and doc["trace_id"] == "traceT"

    def test_env_selects_json_formatter(self, monkeypatch):
        from thinvids_tpu.core.log import JsonFormatter, _make_formatter

        monkeypatch.setenv("TVT_LOG_FORMAT", "json")
        assert isinstance(_make_formatter("h"), JsonFormatter)
        monkeypatch.delenv("TVT_LOG_FORMAT")
        assert not isinstance(_make_formatter("h"), JsonFormatter)


# ---------------------------------------------------------------------------
# local e2e: trace + metrics through the production pipeline
# ---------------------------------------------------------------------------


def _assert_spans_nest(doc):
    """Chrome events on one (pid, tid) must nest by containment (a
    child never straddles its parent's end) — 1 ms tolerance for the
    independent float→µs truncations of start and duration."""
    by_thread = {}
    for e in doc["traceEvents"]:
        if e["ph"] != "X":
            continue
        by_thread.setdefault((e["pid"], e["tid"]), []).append(e)
    tol = 1000
    for events in by_thread.values():
        events.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in events:
            while stack and e["ts"] >= stack[-1]["ts"] \
                    + stack[-1]["dur"] - tol:
                stack.pop()
            if stack:
                parent = stack[-1]
                assert e["ts"] + e["dur"] <= parent["ts"] \
                    + parent["dur"] + tol, \
                    (f"span {e['name']} straddles "
                     f"{parent['name']}'s end")
            stack.append(e)


class TestLocalE2E:
    def test_local_job_yields_one_nested_trace_and_metrics(self, tmp_path):
        from thinvids_tpu.api.server import ApiServer

        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=8)
        snap = make_settings(gop_frames=2, qp=30,
                             heartbeat_throttle_s=0.0)
        coord, _execu = local_rig(tmp_path, snap)
        job = coord.add_job(str(clip), meta)
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason

        api = ApiServer(coord)
        status, doc = api.route("GET", f"/trace/{job.id}", {}, {})
        assert status == 200
        json.dumps(doc)                       # valid JSON document
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert events, "local job recorded no spans"
        names = {e["name"] for e in events}
        # the pipeline stages + per-wave spans all landed
        for want in ("decode", "stage", "dispatch", "device_wait",
                     "fetch", "pack", "concat", "wave_collect",
                     "wave_dispatch"):
            assert want in names, f"missing span {want}"
        # ONE trace id across every span
        assert {e["args"]["trace_id"] for e in events} \
            == {doc["otherData"]["trace_id"]}
        _assert_spans_nest(doc)

        # /metrics: valid exposition covering stage, origin, QoS and
        # shard-board families (the parser is strict)
        status, text = api.route("GET", "/metrics", {}, {})
        assert status == 200
        fams = parse_prometheus(text.body.decode("utf-8"))
        stage = fams["tvt_stage_seconds_total"]
        assert stage["type"] == "counter"
        stages_seen = {labels["stage"]
                       for _n, labels, v in stage["samples"] if v > 0}
        assert {"dispatch", "device_wait", "pack"} <= stages_seen
        # the set-up of this process's executables: a stage clock of
        # the process totals and a counter beside it (ISSUE 39)
        assert "program_build" in {
            labels["stage"] for _n, labels, _v in stage["samples"]}
        built = fams["tvt_programs_built_total"]
        assert built["type"] == "counter"
        assert all(v >= 0 for _n, _labels, v in built["samples"])
        assert fams["tvt_origin_requests_total"]["type"] == "counter"
        assert fams["tvt_qos_breaches_total"]["type"] == "counter"
        assert fams["tvt_qos_preempting"]["type"] == "gauge"
        board = fams["tvt_shard_board_shards"]
        assert {labels["state"] for _n, labels, _v
                in board["samples"]} >= {"pending", "assigned", "done"}
        jobs = {labels["status"]: v
                for _n, labels, v in fams["tvt_jobs"]["samples"]}
        assert jobs["done"] >= 1
        hist = fams["tvt_sfe_frame_latency_seconds"]
        assert hist["type"] == "histogram"

    def test_unsampled_job_returns_404_trace(self, tmp_path):
        from thinvids_tpu.api.server import ApiError, ApiServer

        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=4)
        update_live_settings({"trace_sample": 0.0})
        try:
            snap = make_settings(gop_frames=2, qp=30,
                                 heartbeat_throttle_s=0.0)
            coord, _execu = local_rig(tmp_path, snap)
            job = coord.add_job(str(clip), meta)
            job = coord.store.get(job.id)
            assert job.status is Status.DONE, job.failure_reason
            api = ApiServer(coord)
            with pytest.raises(ApiError) as ei:
                api.route("GET", f"/trace/{job.id}", {}, {})
            assert ei.value.status == 404
        finally:
            reset_live_settings()


# ---------------------------------------------------------------------------
# remote e2e: 2 workers over the real HTTP /work protocol
# ---------------------------------------------------------------------------


class TestRemoteTrace:
    def test_two_worker_farm_job_yields_one_coherent_trace(self, tmp_path):
        from thinvids_tpu.api.server import ApiServer
        from thinvids_tpu.cluster.remote import RemoteExecutor, WorkerDaemon

        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=16)
        snap = make_settings(gop_frames=2, qp=30,
                             heartbeat_throttle_s=0.0,
                             remote_plan_devices=8, remote_shard_gops=2,
                             remote_no_worker_grace_s=30.0,
                             min_idle_workers=0)
        reg = WorkerRegistry()
        hosts = ("tw00", "tw01")
        for host in hosts:
            reg.heartbeat(host, metrics={"worker": True})
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        execu = RemoteExecutor(coord, output_dir=str(tmp_path / "lib"),
                               sync=True, poll_s=0.02)
        coord._launcher = execu.launch
        api = ApiServer(coord, work=execu.board).start()
        stop = threading.Event()
        daemons = [WorkerDaemon(api.url, host=host, poll_s=0.02)
                   for host in hosts]
        threads = [threading.Thread(target=d.run_forever, args=(stop,),
                                    daemon=True) for d in daemons]
        for t in threads:
            t.start()
        try:
            job = coord.add_job(str(clip), meta)
            job = coord.store.get(job.id)
            assert job.status is Status.DONE, job.failure_reason
            # worker span uploads are best-effort async after the last
            # part lands — wait for both hosts' spans to arrive
            deadline = time.time() + 20
            while time.time() < deadline:
                snap_t = trace.TRACE.snapshot(job.id)
                span_hosts = {s["host"] for s in snap_t["spans"]}
                if set(hosts) <= span_hosts:
                    break
                time.sleep(0.05)
            status, doc = api.route("GET", f"/trace/{job.id}", {}, {})
        finally:
            stop.set()
            for t in threads:
                t.join(5)
            api.stop()
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        trace_id = doc["otherData"]["trace_id"]
        # ONE trace id on every span, coordinator and workers alike
        assert {e["args"]["trace_id"] for e in events} == {trace_id}
        names = {e["name"] for e in events}
        assert "shard" in names, "coordinator-side shard spans missing"
        assert "worker_shard" in names and "upload_part" in names, \
            "worker-side spans missing"
        # worker stage clocks (encode internals) rode along too
        assert "pack" in names and "device_wait" in names
        pid_names = {e["args"]["name"]
                     for e in doc["traceEvents"]
                     if e["ph"] == "M" and e["name"] == "process_name"}
        assert set(hosts) <= pid_names


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_artifact_on_injected_shard_failure_quarantine(self, tmp_path):
        """Two injected consecutive shard failures quarantine the
        worker — the job's flight record must land as
        <job>.trace.json with the shard errors inside."""
        from thinvids_tpu.cluster.remote import Shard, ShardBoard
        from thinvids_tpu.core.types import GopSpec

        snap = make_settings(pipeline_worker_count=0, min_idle_workers=0)
        reg = WorkerRegistry()
        reg.heartbeat("bad-worker", metrics={"worker": True})
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        flight.configure(str(tmp_path))
        board = ShardBoard(coord)
        trace.TRACE.start("jobq")
        meta = VideoMeta(width=64, height=48, fps_num=30, fps_den=1,
                         num_frames=4)
        shard = Shard(id="jobq-0000", job_id="jobq", input_path="x.y4m",
                      meta=meta,
                      gops=(GopSpec(index=0, start_frame=0,
                                    num_frames=2),),
                      qp=30, gop_frames=2, timeout_s=100.0)
        board.add_job("jobq", [shard], max_attempts=5, backoff_s=0.0,
                      quarantine_after=2)
        for _ in range(2):
            desc = board.claim("bad-worker")
            assert desc is not None
            board.report_failure(desc["id"], "bad-worker", "injected")
        assert coord.registry.all()[0].disabled
        path = tmp_path / "jobq.trace.json"
        assert path.exists(), "flight record not written on quarantine"
        doc = json.loads(path.read_text())
        other = doc["otherData"]
        assert "quarantined" in other["reason"]
        assert any("injected" in e["message"] for e in other["errors"])
        assert "settings" in other and "traceEvents" in doc

    def test_artifact_on_job_failure_with_settings_and_errors(
            self, tmp_path):
        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=4)
        snap = make_settings(gop_frames=2, qp=30,
                             heartbeat_throttle_s=0.0)

        def broken_factory(_meta, _settings, _mesh):
            raise RuntimeError("injected encoder failure")

        coord, _execu = local_rig(tmp_path, snap,
                                  encoder_factory=broken_factory)
        job = coord.add_job(str(clip), meta)
        job = coord.store.get(job.id)
        assert job.status is Status.FAILED
        path = tmp_path / "lib" / f"{job.id}.trace.json"
        assert path.exists(), "flight record not written on job failure"
        doc = json.loads(path.read_text())
        other = doc["otherData"]
        assert "injected encoder failure" in other["reason"]
        assert any("injected encoder failure" in e["message"]
                   for e in other["errors"])
        assert other["settings"]["gop_frames"] == 2

    def test_unsampled_job_still_dumps_errors_and_settings(self, tmp_path):
        """flight_record is an independent gate from trace_sample: a
        sampled-out job's postmortem still dumps (error ring +
        settings, empty traceEvents)."""
        flight.configure(str(tmp_path))
        update_live_settings({"trace_sample": 0.0})
        try:
            assert trace.TRACE.start("junsamp") == ""
            trace.TRACE.record_error("junsamp", "it broke")
            path = flight.record("junsamp", reason="failure",
                                 settings=make_settings(qp=33))
        finally:
            reset_live_settings()
        assert path and os.path.exists(path)
        doc = json.loads(open(path).read())
        assert [e for e in doc["traceEvents"] if e.get("ph") == "X"] \
            == []
        assert any("it broke" in e["message"]
                   for e in doc["otherData"]["errors"])
        assert doc["otherData"]["settings"]["qp"] == 33

    def test_flight_record_gate_off_writes_nothing(self, tmp_path):
        flight.configure(str(tmp_path))
        trace.TRACE.start("jgate")
        update_live_settings({"flight_record": False})
        try:
            assert flight.record("jgate", reason="x") is None
        finally:
            reset_live_settings()
        assert not (tmp_path / "jgate.trace.json").exists()


# ---------------------------------------------------------------------------
# parity + overhead
# ---------------------------------------------------------------------------


class TestTracingParity:
    @staticmethod
    def _encoder(w=64, h=48):
        from thinvids_tpu.parallel.dispatch import GopShardEncoder

        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=8)
        return GopShardEncoder(meta, qp=30, gop_frames=2)

    @staticmethod
    def _encode_traced(enc, frames, job):
        """(stream, spans) of one encode with `job`'s recorder bound."""
        from thinvids_tpu.core.types import concat_segments

        trace.TRACE.start(job)
        enc.stages.set_tracer(trace.TRACE.recorder(job))
        try:
            stream = concat_segments(enc.encode(frames))
        finally:
            enc.stages.set_tracer(None)
        spans = trace.TRACE.snapshot(job)["spans"]
        trace.TRACE.drop(job)
        return stream, spans

    def test_tracing_changes_no_output_bytes(self):
        from thinvids_tpu.core.types import concat_segments

        frames = clip_frames(n=8)
        enc = self._encoder()
        baseline = concat_segments(enc.encode(frames))
        traced, spans = self._encode_traced(enc, frames, "parity-job")
        assert traced == baseline
        assert spans, "tracer was bound but recorded nothing"

    def test_span_count_does_not_grow_with_the_picture(self):
        """Spans belong to stages, waves, GOPs and frames, never to
        macroblocks: the same 8 frames in the same GOPs and waves
        record the same spans at four times the macroblocks. (What
        the spans cost is inside every `frames_per_s` of the
        benchmark, whose cells trace every job.)"""
        import collections

        def span_names(w, h):
            _, spans = self._encode_traced(
                self._encoder(w, h), clip_frames(w, h, 8),
                f"span-count-{w}x{h}")
            return collections.Counter(s["name"] for s in spans)

        small, large = span_names(64, 48), span_names(128, 96)
        assert small and small == large


# ---------------------------------------------------------------------------
# snapshot percentiles (the SFE per-frame latency ring's p50/p99)
# ---------------------------------------------------------------------------


class TestSfeLatencyPercentiles:
    def test_metrics_snapshot_carries_sfe_percentiles(self, tmp_path):
        from thinvids_tpu.api.server import ApiServer
        from thinvids_tpu.core.types import concat_segments
        from thinvids_tpu.parallel.dispatch import SfeShardEncoder

        meta = VideoMeta(width=64, height=96, fps_num=30, fps_den=1,
                         num_frames=6)
        enc = SfeShardEncoder(meta, qp=30, gop_frames=3, bands=2)
        concat_segments(enc.encode(clip_frames(64, 96, 6)))
        assert enc.stages.snapshot()["sfe_frames"] == 6
        coord = Coordinator(settings_fn=lambda: make_settings())
        api = ApiServer(coord)
        _status, out = api.route("GET", "/metrics_snapshot", {}, {})
        pct = out["sfe_latency_ms"]
        assert pct["count"] >= 4
        assert pct["p99_ms"] >= pct["p50_ms"] > 0
