"""Remote worker execution backend tests (cluster/remote.py).

Three layers, increasingly integrated:

- `TestWireFormat` / `TestShardBoard`: deterministic unit tests of the
  part framing and the board's lease state machine on a fake clock —
  claim gating by role/quarantine, timeout + stale-worker requeue with
  backoff, attempt budgets, quarantine after consecutive failures.
- `TestRemoteExecutorInProcess`: a real RemoteExecutor with fake worker
  THREADS claiming straight off the board — byte-identity with
  LocalExecutor, worker death mid-shard, all-workers-dead failure,
  vbr2pass local fallback.
- `TestWorkApi` + `test_farm_end_to_end_with_worker_kill`: the HTTP
  layer, the latter the hermetic acceptance test — coordinator + 2
  worker daemon SUBPROCESSES on localhost, stitched bitstream
  byte-identical to a single-process LocalExecutor encode, and the job
  surviving a SIGKILL of one worker mid-encode.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.cluster.remote import (
    RemoteExecutor,
    Shard,
    ShardBoard,
    WorkerClient,
    encode_shard,
    pack_parts,
    unpack_parts,
)
from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
from thinvids_tpu.core.status import ShardState, Status
from thinvids_tpu.core.types import EncodedSegment, Frame, GopSpec, VideoMeta
from thinvids_tpu.io.y4m import write_y4m

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_settings(**over):
    values = dict(DEFAULT_SETTINGS)
    values.update(over)
    return Settings(values=values)


def clip_frames(w=64, h=48, n=16):
    yy, xx = np.mgrid[0:h, 0:w]
    return [Frame(
        y=((xx * 2 + yy + 7 * i) % 256).astype(np.uint8),
        u=np.full((h // 2, w // 2), 108, np.uint8),
        v=np.full((h // 2, w // 2), 148, np.uint8),
    ) for i in range(n)]


def write_clip(path, w=64, h=48, n=16):
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1, num_frames=n)
    write_y4m(str(path), meta, clip_frames(w, h, n))
    return meta


def fake_segment(index, start_frame=0, num_frames=2, payload=b"\0\0\1x"):
    return EncodedSegment(
        gop=GopSpec(index=index, start_frame=start_frame,
                    num_frames=num_frames),
        payload=payload, frame_sizes=(len(payload),))


def make_shard(sid="j0-0000", job_id="j0", gop0=0, ngops=2,
               timeout_s=60.0):
    gops = tuple(GopSpec(index=gop0 + i, start_frame=2 * (gop0 + i),
                         num_frames=2) for i in range(ngops))
    return Shard(id=sid, job_id=job_id, input_path="/in/a.y4m",
                 meta=VideoMeta(width=64, height=48), gops=gops, qp=30,
                 gop_frames=2, timeout_s=timeout_s)


class TestWireFormat:
    def test_roundtrip(self):
        segs = [fake_segment(3, 6, 2, b"\0\0\1abc"),
                fake_segment(4, 8, 1, b"\0\0\1d" * 5)]
        out = unpack_parts(pack_parts(segs))
        assert len(out) == 2
        for a, b in zip(segs, out):
            assert a.gop == b.gop
            assert a.payload == b.payload
            assert a.frame_sizes == b.frame_sizes

    def test_truncated_payload_raises(self):
        data = pack_parts([fake_segment(0)])
        with pytest.raises(ValueError):
            unpack_parts(data[:-1])

    def test_trailing_garbage_raises(self):
        data = pack_parts([fake_segment(0)])
        with pytest.raises(ValueError):
            unpack_parts(data + b"!")


def make_board(clock=None, workers=("w1", "w2", "w3"), pipeline_count=1,
               worker_metrics=True, **over):
    """Coordinator + board with `workers` heartbeated as claim-capable
    daemons; pipeline_count=1 puts the naturally-first host on the
    pipeline role and the rest on encode."""
    clock = clock or FakeClock()
    snap = make_settings(pipeline_worker_count=pipeline_count, **over)
    reg = WorkerRegistry(clock=clock)
    for hostname in workers:
        reg.heartbeat(hostname,
                      metrics={"worker": True} if worker_metrics else None,
                      now=clock())
    coord = Coordinator(registry=reg, clock=clock,
                        settings_fn=lambda: snap)
    return ShardBoard(coord, clock=clock), coord, clock


class TestShardBoard:
    def test_claim_respects_role_split(self):
        board, coord, _ = make_board()
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3)
        # w1 is the pipeline-role host; encode workers exist → denied
        assert board.claim("w1") is None
        desc = board.claim("w2")
        assert desc is not None and desc["id"] == "j0-0000"
        assert desc["gops"] == [[0, 0, 2], [1, 2, 2]]   # shard-local
        assert board.claim("w3") is None                # queue drained

    def test_pipeline_worker_claims_when_no_encode_workers(self):
        board, coord, _ = make_board(workers=("w1",), pipeline_count=8)
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3)
        assert board.claim("w1") is not None

    def test_pipeline_worker_takes_overflow(self):
        """Reserved pipeline-role workers absorb pending work the
        encode workers can't start on — the reserve must not idle a
        farm with a deep queue."""
        board, coord, _ = make_board()      # w1 pipeline, w2/w3 encode
        shards = [make_shard(sid=f"j0-{i:04d}", gop0=2 * i)
                  for i in range(5)]
        board.add_job("j0", shards, max_attempts=3, backoff_s=0.0,
                      quarantine_after=3)
        # 5 pending > 2 encode workers → overflow opens to w1
        assert board.claim("w1") is not None
        assert board.claim("w2") is not None
        assert board.claim("w3") is not None
        # 2 pending, 2 encode workers → reserve closes again
        assert board.claim("w1") is None

    def test_quarantined_worker_denied(self):
        board, coord, _ = make_board()
        coord.registry.set_disabled("w2", True, reason="flaky")
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3)
        assert board.claim("w2") is None
        assert board.claim("w3") is not None

    def test_submit_part_completes_job(self):
        board, coord, _ = make_board()
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3)
        desc = board.claim("w2")
        segs = [fake_segment(0, 0, 2), fake_segment(1, 2, 2)]
        assert board.submit_part(desc["id"], "w2", segs)
        done, total, retried, failed, _h = board.job_progress("j0")
        assert (done, total, retried, failed) == (2, 2, 0, "")
        got = board.take_segments("j0")
        assert [s.gop.index for s in got] == [0, 1]
        # lifetime counters feed /metrics_snapshot
        w2 = {w.host: w for w in coord.registry.all()}["w2"]
        assert w2.shards_done == 1

    def test_wrong_gop_coverage_rejected(self):
        board, coord, _ = make_board()
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3)
        desc = board.claim("w2")
        with pytest.raises(ValueError):
            board.submit_part(desc["id"], "w2", [fake_segment(0, 0, 2)])

    def test_lease_timeout_requeues_with_backoff(self):
        board, coord, clock = make_board()
        board.add_job("j0", [make_shard(timeout_s=60.0)], max_attempts=3,
                      backoff_s=2.0, quarantine_after=5)
        board.claim("w2")
        clock.advance(61.0)
        # keep w3 alive so the requeued shard has somewhere to go
        coord.registry.heartbeat("w3", now=clock())
        assert board.requeue_expired() == ["j0-0000"]
        _d, _t, retried, failed, _h = board.job_progress("j0")
        assert retried == 2 and failed == ""
        # backoff gates the re-claim...
        assert board.claim("w3") is None
        clock.advance(2.1)
        desc = board.claim("w3")
        assert desc is not None and desc["attempt"] == 1
        # ...and the failure counted against the lease holder
        w2 = {w.host: w for w in coord.registry.all()}["w2"]
        assert w2.shards_failed == 1 and w2.consecutive_failures == 1

    def test_stale_worker_requeues_before_deadline(self):
        """SIGKILLed worker: its heartbeat TTL expires long before the
        lease deadline; the sweep must not wait for the lease."""
        board, coord, clock = make_board()
        board.add_job("j0", [make_shard(timeout_s=3600.0)], max_attempts=3,
                      backoff_s=0.0, quarantine_after=5)
        board.claim("w2")
        clock.advance(20.0)                  # > metrics_ttl_s (15), << lease
        assert board.requeue_expired() == ["j0-0000"]
        coord.registry.heartbeat("w3", now=clock())
        assert board.claim("w3") is not None

    def test_attempt_budget_fails_job(self):
        board, coord, clock = make_board()
        board.add_job("j0", [make_shard()], max_attempts=1, backoff_s=0.0,
                      quarantine_after=99)
        for _ in range(2):
            desc = board.claim("w2")
            assert desc is not None
            board.report_failure(desc["id"], "w2", "encoder exploded")
        _d, _t, _r, failed, failed_host = board.job_progress("j0")
        assert "after 2 attempts" in failed
        assert "encoder exploded" in failed
        assert failed_host == "w2"

    def test_quarantine_after_consecutive_failures(self):
        board, coord, clock = make_board()
        shards = [make_shard(sid=f"j0-{i:04d}", gop0=2 * i)
                  for i in range(4)]
        board.add_job("j0", shards, max_attempts=5, backoff_s=0.0,
                      quarantine_after=3)
        for _ in range(3):
            desc = board.claim("w2")
            board.report_failure(desc["id"], "w2", "boom")
        w2 = {w.host: w for w in coord.registry.all()}["w2"]
        assert w2.disabled and "quarantined" in w2.quarantine_reason
        assert board.claim("w2") is None     # no more work for w2
        assert any(e["stage"] == "quarantine"
                   for e in coord.activity.fetch())

    def test_stale_failure_report_ignored_after_requeue(self):
        """An evicted worker's failure report lands after the shard was
        requeued and re-leased: it must not touch the current holder's
        lease or burn an attempt."""
        board, coord, clock = make_board()
        board.add_job("j0", [make_shard(timeout_s=10.0)], max_attempts=2,
                      backoff_s=0.0, quarantine_after=99)
        board.claim("w2")
        clock.advance(11.0)
        coord.registry.heartbeat("w3", now=clock())
        board.requeue_expired()                     # attempt 1, w2 blamed
        desc2 = board.claim("w3")
        assert desc2 is not None
        board.report_failure("j0-0000", "w2", "late crash report")
        shard = board._find_locked("j0-0000")
        assert shard.state is ShardState.ASSIGNED   # w3's lease intact
        assert shard.assigned_host == "w3"
        assert shard.attempt == 1                   # no extra attempt

    def test_late_part_from_expired_lease_accepted_once(self):
        """First result wins: the original worker's part lands after a
        requeue — the encode is deterministic, so accept it and let the
        second worker's duplicate drop."""
        board, coord, clock = make_board()
        board.add_job("j0", [make_shard(timeout_s=10.0)], max_attempts=5,
                      backoff_s=0.0, quarantine_after=99)
        board.claim("w2")
        clock.advance(11.0)
        coord.registry.heartbeat("w3", now=clock())
        board.requeue_expired()
        desc2 = board.claim("w3")
        segs = [fake_segment(0, 0, 2), fake_segment(1, 2, 2)]
        assert board.submit_part("j0-0000", "w2", segs)      # late winner
        assert not board.submit_part(desc2["id"], "w3", segs)  # duplicate
        done, total, _r, _f, _h = board.job_progress("j0")
        assert done == total == 2

    def test_restart_race_cancel_is_token_fenced(self):
        """A halted run waking after /restart_job must not cancel the
        new run's board entry; the new add_job also supersedes the old
        entry's queue slots."""
        board, coord, _ = make_board()
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3, token="run-old")
        # restart: new run installs its shards before the old run's
        # cleanup fires
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3, token="run-new")
        board.cancel_job("j0", token="run-old")     # stale: no-op
        desc = board.claim("w2")
        assert desc is not None                     # new entry intact
        assert desc["id"] == "j0-0000"
        board.cancel_job("j0", token="run-new")     # owner: removes
        _d, _t, _r, failed, _h = board.job_progress("j0")
        assert failed == "cancelled"

    def test_part_from_superseded_run_is_dropped(self):
        """Shard ids are RUN-SCOPED (the run token rides in the id), so
        a part still in flight from a superseded run resolves to NO
        shard in the restarted run's entry — the old run may have
        encoded under different job settings, and its bytes must not
        land in the new run's output. This is the TVT-M002 model's
        `cross-run-part` invariant (mutation `shared_ids` reproduces
        the pre-fix hole)."""
        board, coord, _ = make_board()
        old = make_shard(sid="j0-runAAA-0000")
        board.add_job("j0", [old], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3, token="run-old")
        desc = board.claim("w2")
        assert desc["id"] == "j0-runAAA-0000"
        # restart: fresh plan under the new token → new run-scoped ids
        board.add_job("j0", [make_shard(sid="j0-runBBB-0000")],
                      max_attempts=3, backoff_s=0.0,
                      quarantine_after=3, token="run-new")
        accepted = board.submit_part(
            desc["id"], "w2", [fake_segment(0, 0, 2),
                               fake_segment(1, 2, 2)])
        assert not accepted
        done, total, *_rest = board.job_progress("j0")
        assert (done, total) == (0, 2)

    def test_shard_ids_embed_the_run_token(self, tmp_path):
        """RemoteExecutor._shards_for scopes every shard id to the run
        token that planned it (restart ⇒ disjoint id namespaces)."""
        from thinvids_tpu.cluster.jobs import Job

        settings = make_settings()
        coord, execu = make_remote_rig(tmp_path, settings)
        job = Job(id="deadbeefdeadbeef", input_path="/in/a.y4m")
        vm = VideoMeta(width=64, height=48, num_frames=16)
        plan = execu._plan_remote(16, settings)
        ids_a = [s.id for s in execu._shards_for(
            job, vm, plan, settings, qp=30, token="aaaa1111")]
        ids_b = [s.id for s in execu._shards_for(
            job, vm, plan, settings, qp=30, token="bbbb2222")]
        assert all("aaaa11" in sid for sid in ids_a)
        assert all("bbbb22" in sid for sid in ids_b)
        assert not set(ids_a) & set(ids_b)

    def test_stale_worker_claim_denied_until_real_heartbeat(self):
        """Regression (ISSUE 12 satellite): a worker whose heartbeat
        TTL lapsed used to revive itself through claim()'s
        unconditional pre-check heartbeat and win a shard — racing
        requeue_expired's pre-lock active-set snapshot, which then
        swept the fresh lease and burned an attempt. Liveness is now
        re-checked under the lock from the registry's current state,
        and only a GRANTED claim refreshes it: a stale worker's poll
        returns None until its agent actually heartbeats again."""
        board, coord, clock = make_board()
        shards = [make_shard(sid=f"j0-{i:04d}", gop0=2 * i)
                  for i in range(2)]
        board.add_job("j0", shards, max_attempts=3, backoff_s=0.0,
                      quarantine_after=9)
        assert board.claim("w2") is not None      # fresh: wins
        clock.advance(20.0)                       # > metrics_ttl_s 15
        # stale worker asks for more work: denied, NOT revived
        assert board.claim("w2") is None
        workers = {w.host: w for w in coord.registry.all()}
        assert clock() - workers["w2"].last_seen > 15.0
        # the sweep judges the stale lease without interference
        assert board.requeue_expired() == ["j0-0000"]
        # a real agent heartbeat restores eligibility
        coord.registry.heartbeat("w2", now=clock())
        assert board.claim("w2") is not None

    def test_snapshot_carries_timings(self):
        board, coord, clock = make_board()
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3)
        desc = board.claim("w2")
        clock.advance(1.5)
        board.submit_part(desc["id"], "w2",
                          [fake_segment(0, 0, 2), fake_segment(1, 2, 2)])
        snap = board.snapshot()
        assert snap["shards"]["done"] == 1
        assert snap["workers"]["w2"]["shards_done"] == 1
        assert snap["workers"]["w2"]["last_shard_s"] == 1.5
        assert snap["recent"][-1]["host"] == "w2"


# ---------------------------------------------------------------------------
# in-process executor tests (fake worker threads on the real board)
# ---------------------------------------------------------------------------


def make_remote_rig(tmp_path, settings, workers=8):
    reg = WorkerRegistry()
    for i in range(workers):
        reg.heartbeat(f"w{i:02d}", metrics={"worker": True})
    coord = Coordinator(registry=reg, settings_fn=lambda: settings)
    execu = RemoteExecutor(coord, output_dir=str(tmp_path / "lib_remote"),
                           sync=True, poll_s=0.02)
    coord._launcher = execu.launch
    return coord, execu


def board_worker(board, host, stop, die_holding=False):
    """Fake worker thread: claims straight off the board (no HTTP) and
    encodes with the real shard encoder. `die_holding=True` makes it
    vanish with its first claimed lease unfinished (SIGKILL analog)."""
    from thinvids_tpu.ingest.decode import read_video

    cache = {}

    def loop():
        while not stop.is_set():
            desc = board.claim(host)
            if desc is None:
                time.sleep(0.01)
                continue
            if die_holding:
                return                       # lease dies with us
            path = desc["input_path"]
            if path not in cache:
                cache[path] = read_video(path)[1]
            segs = encode_shard(desc, cache[path])
            board.submit_part(desc["id"], host, segs)

    t = threading.Thread(target=loop, daemon=True,
                         name=f"fake-worker-{host}")
    t.start()
    return t


def local_reference_bytes(tmp_path, clip, meta, settings):
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: settings)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / "lib_local"),
                          sync=True)
    coord._launcher = execu.launch
    job = coord.add_job(str(clip), meta)
    job = coord.store.get(job.id)
    assert job.status is Status.DONE, job.failure_reason
    with open(job.output_path, "rb") as fp:
        return fp.read()


class TestRemoteExecutorInProcess:
    def test_remote_matches_local_bit_identical(self, tmp_path):
        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=16)
        # plan width pinned to the local mesh's 8 devices so both
        # backends derive the identical GOP plan
        snap = make_settings(gop_frames=2, qp=30, heartbeat_throttle_s=0.0,
                             remote_plan_devices=8, remote_shard_gops=2,
                             remote_no_worker_grace_s=10.0)
        want = local_reference_bytes(tmp_path, clip, meta, snap)

        coord, execu = make_remote_rig(tmp_path, snap)
        stop = threading.Event()
        for i in range(2):
            board_worker(execu.board, f"w{i:02d}", stop)
        try:
            job = coord.add_job(str(clip), meta)
        finally:
            stop.set()
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        assert job.parts_done == job.parts_total == 8
        assert job.encode_progress == 100.0
        with open(job.output_path, "rb") as fp:
            assert fp.read() == want

    def test_worker_death_mid_shard_requeues_and_completes(self, tmp_path):
        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=24)
        # short liveness TTL: the dead worker's lease is swept as soon
        # as its heartbeat goes stale, long before the 1h lease
        snap = make_settings(gop_frames=2, qp=30, heartbeat_throttle_s=0.0,
                             remote_plan_devices=8, remote_shard_gops=1,
                             metrics_ttl_s=0.5, remote_shard_timeout_s=3600.0,
                             remote_retry_backoff_s=0.0,
                             remote_no_worker_grace_s=30.0,
                             min_idle_workers=0)
        want = local_reference_bytes(
            tmp_path, clip, meta,
            make_settings(gop_frames=2, qp=30, heartbeat_throttle_s=0.0))

        coord, execu = make_remote_rig(tmp_path, snap, workers=2)
        stop = threading.Event()
        board_worker(execu.board, "w00", stop, die_holding=True)
        live = {"started": False}

        def start_survivor():
            # let the dying worker grab its lease first
            time.sleep(0.2)
            board_worker(execu.board, "w01", stop)
            live["started"] = True

        threading.Thread(target=start_survivor, daemon=True).start()
        # keep the survivor's heartbeat fresh under the tiny TTL
        beat = threading.Event()

        def heartbeat_survivor():
            while not beat.is_set():
                if live["started"]:
                    coord.registry.heartbeat("w01",
                                             metrics={"worker": True})
                time.sleep(0.1)

        threading.Thread(target=heartbeat_survivor, daemon=True).start()
        try:
            job = coord.add_job(str(clip), meta)
        finally:
            stop.set()
            beat.set()
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        assert job.parts_retried >= 1          # the orphaned shard
        assert any("w00" in (e.get("host") or "") and "failed" in e["message"]
                   for e in coord.activity.fetch(200))
        with open(job.output_path, "rb") as fp:
            assert fp.read() == want

    def test_all_workers_dead_fails_with_attribution(self, tmp_path):
        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=8)
        snap = make_settings(gop_frames=2, qp=30, heartbeat_throttle_s=0.0,
                             metrics_ttl_s=0.3, min_idle_workers=0,
                             remote_no_worker_grace_s=0.3)
        coord, execu = make_remote_rig(tmp_path, snap, workers=2)
        # the coordinator's own agent keeps heartbeating (no worker
        # flag): it must NOT suppress the all-dead detection
        beat = threading.Event()

        def coordinator_agent():
            while not beat.is_set():
                coord.registry.heartbeat("coord-host")
                time.sleep(0.05)

        threading.Thread(target=coordinator_agent, daemon=True).start()
        deadline = time.time() + 30
        try:
            job = coord.add_job(str(clip), meta)   # sync: returns failed
        finally:
            beat.set()
        job = coord.store.get(job.id)
        assert time.time() < deadline, "all-dead detection hung"
        assert job.status is Status.FAILED
        assert "no live encode workers" in job.failure_reason
        assert job.failure_stage == "encode"
        events = coord.activity.fetch(200)
        assert any(e["label"] == "ERROR"
                   and "no live encode workers" in e["message"]
                   for e in events)

    def test_vbr2pass_falls_back_to_local_mesh(self, tmp_path):
        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=16)
        snap = make_settings(gop_frames=4, qp=30, heartbeat_throttle_s=0.0,
                             rc_mode="vbr2pass", target_bitrate_kbps=300.0)
        coord, execu = make_remote_rig(tmp_path, snap)
        job = coord.add_job(str(clip), meta)   # no workers needed
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        assert any("coordinator mesh" in e["message"]
                   for e in coord.activity.fetch(200))

    def test_direct_mode_job_encodes_on_coordinator_mesh(self, tmp_path):
        """The admission policy's processing_mode finally has teeth:
        a direct-mode job (here: oversize under
        large_file_behavior="direct") encodes whole on the coordinator
        mesh — it completes with NO worker ever claiming."""
        import os

        clip = tmp_path / "big.y4m"
        write_clip(clip, n=8)
        meta = VideoMeta(width=64, height=48, fps_num=30, fps_den=1,
                         num_frames=8,
                         size_bytes=os.path.getsize(str(clip)))
        snap = make_settings(gop_frames=2, qp=30, heartbeat_throttle_s=0.0,
                             large_file_gb=1e-9,
                             large_file_behavior="direct",
                             min_idle_workers=0)
        coord, execu = make_remote_rig(tmp_path, snap)   # nobody claims
        job = coord.add_job(str(clip), meta)
        job = coord.store.get(job.id)
        assert job.processing_mode == "direct"
        assert job.status is Status.DONE, job.failure_reason
        assert any("direct mode" in e["message"]
                   for e in coord.activity.fetch(200))
        # nothing ever hit the farm board
        assert execu.board.snapshot()["shards"]["done"] == 0

    def test_recovered_job_defers_planning_until_workers_heartbeat(
            self, tmp_path):
        """The coordinator-restart scenario (ROADMAP open item): the
        job launches while only non-claim-capable agents are registered
        (the coordinator's own device pseudo-hosts). Shard planning
        must wait for the first worker heartbeats instead of
        degenerating to 2 giant shards against an empty farm."""
        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=16)
        snap = make_settings(gop_frames=2, qp=30, heartbeat_throttle_s=0.0,
                             remote_plan_devices=8,
                             remote_no_worker_grace_s=10.0,
                             min_idle_workers=0)
        coord, execu = make_remote_rig(tmp_path, snap, workers=0)
        # metrics-only agents satisfy admission but can't take shards
        for i in range(8):
            coord.registry.heartbeat(f"dev{i}")
        stop = threading.Event()

        def late_farm():
            time.sleep(0.15)
            for i in range(4):
                coord.registry.heartbeat(f"w{i:02d}",
                                         metrics={"worker": True})
                time.sleep(0.3)     # STAGGERED re-heartbeats, like a
                                    # real farm restart — the settle
                                    # window must count the farm whole,
                                    # not plan on worker #1 alone
            for i in range(2):
                board_worker(execu.board, f"w{i:02d}", stop)

        threading.Thread(target=late_farm, daemon=True).start()
        try:
            job = coord.add_job(str(clip), meta)
        finally:
            stop.set()
        job = coord.store.get(job.id)
        assert job.status is Status.DONE, job.failure_reason
        events = [e["message"] for e in coord.activity.fetch(400)]
        # 8 GOPs over the 4 late workers -> auto ~2 shards/worker ->
        # 8 single-GOP shards; the empty-registry degenerate plan
        # would have been "as 2 shards"
        assert any("as 8 shards" in m for m in events), events
        assert job.parts_total == 8


# ---------------------------------------------------------------------------
# HTTP layer
# ---------------------------------------------------------------------------


class TestWorkApi:
    def test_claim_part_status_over_http(self, tmp_path):
        from thinvids_tpu.api.server import ApiServer

        board, coord, _ = make_board(clock=None)
        board.add_job("j0", [make_shard()], max_attempts=3, backoff_s=0.0,
                      quarantine_after=3)
        api = ApiServer(coord, work=board).start()
        try:
            client = WorkerClient(api.url, timeout_s=5.0)
            assert client.claim("w1") is None          # pipeline-role
            desc = client.claim("w2")
            assert desc["id"] == "j0-0000"
            segs = [fake_segment(0, 0, 2), fake_segment(1, 2, 2)]
            assert client.upload_part(desc["id"], "w2", segs)
            done, total, _r, _f, _h = board.job_progress("j0")
            assert done == total == 2
            # /metrics_snapshot carries the farm stats
            with urllib.request.urlopen(
                    api.url + "/metrics_snapshot", timeout=5) as resp:
                out = json.loads(resp.read())
            assert out["work"]["shards"]["done"] == 1
            # failure report path
            board.add_job("j1", [make_shard(sid="j1-0000", job_id="j1")],
                          max_attempts=3, backoff_s=0.0, quarantine_after=3)
            desc = client.claim("w3")
            client.report_failure(desc["id"], "w3", "synthetic")
            _d, _t, retried, _f, _h = board.job_progress("j1")
            assert retried == 2
        finally:
            api.stop()

    def test_work_routes_503_without_backend(self):
        from thinvids_tpu.api.server import ApiServer

        coord = Coordinator(settings_fn=lambda: make_settings())
        api = ApiServer(coord)      # no work board attached
        with pytest.raises(Exception) as ei:
            api.route("POST", "/work/claim", {}, {"host": "w1"})
        assert getattr(ei.value, "status", None) == 503


class TestWorkerResilience:
    """ISSUE 13 satellite: jittered-backoff retries on the worker's
    HTTP surface — the coordinator's restart window (refused
    connections, 5xx) must neither fail shards nor quarantine healthy
    workers, and an integrity-rejected upload must heal by re-sending
    the idempotent part, not by re-encoding."""

    def _rig(self, tmp_path):
        snap = make_settings(gop_frames=2, qp=30,
                             pipeline_worker_count=0,
                             heartbeat_throttle_s=0.0)
        reg = WorkerRegistry()
        reg.heartbeat("w-res", metrics={"worker": True})
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        board = ShardBoard(coord, spool_dir=str(tmp_path / "spool"))
        return coord, board

    def _real_shard(self, clip, meta, sid="jres-0000"):
        gops = tuple(GopSpec(index=i, start_frame=2 * i, num_frames=2)
                     for i in range(2))
        return Shard(id=sid, key="0000", job_id="jres",
                     input_path=str(clip), meta=meta, gops=gops,
                     qp=30, gop_frames=2, timeout_s=120.0)

    def test_claim_loop_survives_api_bounce(self, tmp_path):
        from thinvids_tpu.api.server import ApiServer
        from thinvids_tpu.cluster.remote import WorkerDaemon

        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=4)
        coord, board = self._rig(tmp_path)
        api = ApiServer(coord, work=board).start()
        port = api.port
        client = WorkerClient(api.url, timeout_s=5.0, retries=40,
                              backoff_s=0.05)
        daemon = WorkerDaemon(api.url, host="w-res", poll_s=0.05,
                              client=client)
        stop = threading.Event()
        threading.Thread(target=daemon.run_forever, args=(stop,),
                         daemon=True).start()
        try:
            time.sleep(0.3)             # daemon is mid-claim-loop
            api.stop()                  # bounce: restart window begins
            time.sleep(0.5)
            api = ApiServer(coord, host="127.0.0.1", port=port,
                            work=board).start()
            # work posted AFTER the bounce: the retrying claim loop
            # must find it without ever surfacing a shard failure
            board.add_job("jres", [self._real_shard(clip, meta)],
                          max_attempts=3, backoff_s=0.0,
                          quarantine_after=3)
            deadline = time.time() + 60
            while time.time() < deadline:
                done, total, *_rest = board.job_progress("jres")
                if total and done >= total:
                    break
                coord.registry.heartbeat("w-res",
                                         metrics={"worker": True})
                time.sleep(0.1)
            done, total, retried, failed, _h = board.job_progress("jres")
            assert (done, total, failed) == (2, 2, "")
            assert retried == 0
            assert daemon.shards_failed == 0
            assert daemon.shards_done == 1
        finally:
            stop.set()
            api.stop()

    def test_upload_retries_through_integrity_reject(self, tmp_path):
        """An upload corrupted in transit: ingest rejects on digest,
        the lease comes straight back, and the worker's retry of the
        same (idempotent) upload lands — no attempt burned, no
        quarantine accounting, no re-encode."""
        from thinvids_tpu.api.server import ApiServer
        from thinvids_tpu.cluster.remote import WorkerDaemon

        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=4)
        coord, board = self._rig(tmp_path)
        api = ApiServer(coord, work=board).start()
        try:
            board.add_job("jres", [self._real_shard(clip, meta)],
                          max_attempts=3, backoff_s=0.0,
                          quarantine_after=3)
            api.corrupt_parts(1)        # chaos: flip a bit in the next
            client = WorkerClient(      # upload body before unpack
                api.url, timeout_s=5.0, retries=5, backoff_s=0.05)
            daemon = WorkerDaemon(api.url, host="w-res", poll_s=0.05,
                                  client=client)
            assert daemon.step()        # one claim → encode → upload
            done, total, retried, failed, _h = board.job_progress("jres")
            assert (done, total, failed) == (2, 2, "")
            assert retried == 0                      # no attempt burn
            assert daemon.shards_done == 1
            assert daemon.shards_failed == 0
            snap = board.snapshot()
            assert snap["integrity_rejects"] == 1
            w = {x.host: x for x in coord.registry.all()}["w-res"]
            assert w.consecutive_failures == 0
        finally:
            api.stop()

    def test_upload_gives_up_after_retry_budget(self, tmp_path):
        """Every retry rejected (persistent corruption): upload_part
        returns False instead of looping forever."""
        from thinvids_tpu.api.server import ApiServer

        clip = tmp_path / "clip.y4m"
        meta = write_clip(clip, n=4)
        coord, board = self._rig(tmp_path)
        api = ApiServer(coord, work=board).start()
        try:
            board.add_job("jres", [self._real_shard(clip, meta)],
                          max_attempts=5, backoff_s=0.0,
                          quarantine_after=9)
            client = WorkerClient(api.url, timeout_s=5.0, retries=2,
                                  backoff_s=0.01)
            desc = board.claim("w-res")
            api.corrupt_parts(10)       # poison every retry
            segs = encode_shard(desc, read_video_frames(str(clip)))
            assert client.upload_part(desc["id"], "w-res", segs) is False
            assert board.snapshot()["integrity_rejects"] == 3
        finally:
            api.stop()


def read_video_frames(path):
    from thinvids_tpu.ingest.decode import read_video

    return read_video(path)[1]


# ---------------------------------------------------------------------------
# hermetic multi-process farm (the acceptance test)
# ---------------------------------------------------------------------------


def _call(base, path, method="GET", body=None, timeout=10):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(base + path, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _wait(predicate, deadline_s, interval=0.25, what="condition"):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        out = predicate()
        if out:
            return out
        time.sleep(interval)
    raise TimeoutError(f"timed out waiting for {what}")


def _farm_env(tmp_path):
    return dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
        TVT_EXECUTION_BACKEND="remote",
        TVT_MIN_IDLE_WORKERS="0", TVT_PIPELINE_WORKER_COUNT="2",
        TVT_REMOTE_PLAN_DEVICES="8", TVT_REMOTE_SHARD_GOPS="1",
        TVT_METRICS_TTL_S="3", TVT_REMOTE_RETRY_BACKOFF_S="0.2",
        TVT_GOP_FRAMES="2", TVT_QP="30", TVT_SCHEDULER_POLL_S="0.5")


def _spawn_worker(base, name, env):
    return subprocess.Popen(
        [sys.executable, "-m", "thinvids_tpu.cli", "worker",
         "--coordinator", base, "--node-name", name,
         "--interval", "0.3", "--poll", "0.2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


def test_farm_end_to_end_with_worker_kill(tmp_path):
    """Acceptance: coordinator + 2 localhost worker daemons encode a
    clip whose stitched MP4 is BYTE-identical to the single-process
    LocalExecutor output; a second job still completes after one worker
    daemon is SIGKILLed mid-encode."""
    import socket as socket_mod

    clip1 = tmp_path / "clip1.y4m"
    meta1 = write_clip(clip1, n=16)
    clip2 = tmp_path / "clip2.y4m"
    meta2 = write_clip(clip2, n=36)
    # in-process references on the 8-device test mesh (same plan width
    # as TVT_REMOTE_PLAN_DEVICES pins farm-side)
    ref_settings = make_settings(gop_frames=2, qp=30,
                                 heartbeat_throttle_s=0.0)
    want1 = local_reference_bytes(tmp_path / "r1", clip1, meta1,
                                  ref_settings)
    want2 = local_reference_bytes(tmp_path / "r2", clip2, meta2,
                                  ref_settings)

    with socket_mod.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        port = sk.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    env = _farm_env(tmp_path)
    coord = subprocess.Popen(
        [sys.executable, "-m", "thinvids_tpu.cli", "coordinator",
         "--host", "127.0.0.1", "--port", str(port),
         "--state-dir", str(tmp_path / "state"),
         "--output-dir", str(tmp_path / "library")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    workers = []
    try:
        _wait(lambda: _try_health(base), 45, what="coordinator API")
        workers = [_spawn_worker(base, f"farm-w{i}", env)
                   for i in range(2)]
        _wait(lambda: len([n for n in _call(base, "/nodes_data")["nodes"]
                           if n["host"].startswith("farm-w")]) == 2,
              30, what="both workers registered")

        # ---- job 1: byte-identity ------------------------------------
        job1 = _call(base, "/add_job", "POST",
                     {"input_path": str(clip1)})
        done1 = _wait(lambda: _job_if_terminal(base, job1["id"]), 180,
                      what="job1 terminal")
        assert done1["status"] == "done", done1
        with open(done1["output_path"], "rb") as fp:
            assert fp.read() == want1

        # ---- job 2: SIGKILL one worker mid-encode --------------------
        job2 = _call(base, "/add_job", "POST",
                     {"input_path": str(clip2)})

        def victim_busy():
            m = _call(base, "/metrics_snapshot")["metrics"]
            return m.get("farm-w0", {}).get("worker_busy") or None

        try:
            _wait(victim_busy, 60, interval=0.1,
                  what="farm-w0 busy on a shard")
        except TimeoutError:
            pass        # job may already be draining; kill regardless
        workers[0].kill()                      # SIGKILL, no goodbye
        workers[0].wait(timeout=10)
        done2 = _wait(lambda: _job_if_terminal(base, job2["id"]), 240,
                      what="job2 terminal after worker kill")
        assert done2["status"] == "done", done2
        with open(done2["output_path"], "rb") as fp:
            assert fp.read() == want2
        # the farm stats made it to the metrics surface
        snap = _call(base, "/metrics_snapshot")
        assert snap.get("work", {}).get("workers"), snap.get("work")
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.wait(timeout=10)
        coord.send_signal(signal.SIGTERM)
        try:
            coord.wait(timeout=15)
        except subprocess.TimeoutExpired:
            coord.kill()


def test_coordinator_crash_resume_end_to_end(tmp_path):
    """Acceptance (ISSUE 13): the coordinator is SIGKILLed mid-farm-job
    and restarted over the same state dir. The job must land DONE with
    output BYTE-identical to an uninterrupted run, with >= 1 shard
    rehydrated from the durable part spool (the reuse counter) instead
    of re-encoded — and a spool corruption injected while the
    coordinator was down must be rejected at resume, never stitched."""
    import socket as socket_mod

    clip = tmp_path / "clip.y4m"
    meta = write_clip(clip, n=28)       # 14 GOPs → 14 1-GOP shards
    ref_settings = make_settings(gop_frames=2, qp=30,
                                 heartbeat_throttle_s=0.0)
    want = local_reference_bytes(tmp_path / "ref", clip, meta,
                                 ref_settings)

    with socket_mod.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        port = sk.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    env = dict(_farm_env(tmp_path),
               TVT_REMOTE_HTTP_RETRIES="12",
               TVT_REMOTE_HTTP_BACKOFF_S="0.2")
    state_dir = str(tmp_path / "state")

    def spawn_coordinator():
        return subprocess.Popen(
            [sys.executable, "-m", "thinvids_tpu.cli", "coordinator",
             "--host", "127.0.0.1", "--port", str(port),
             "--state-dir", state_dir,
             "--output-dir", str(tmp_path / "library")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    coord = spawn_coordinator()
    workers = []
    try:
        _wait(lambda: _try_health(base), 45, what="coordinator API")
        workers = [_spawn_worker(base, f"crash-w{i}", env)
                   for i in range(2)]
        _wait(lambda: len([n for n in _call(base, "/nodes_data")["nodes"]
                           if n["host"].startswith("crash-w")]) == 2,
              30, what="both workers registered")
        job = _call(base, "/add_job", "POST", {"input_path": str(clip)})

        def partially_done():
            try:
                done = _call(base, "/work/board")["shards"]["done"]
            except Exception:   # noqa: BLE001 - board not up yet
                return None
            return done if done >= 4 else None

        _wait(partially_done, 120, interval=0.1,
              what="4+ shards spooled before the crash")
        coord.kill()                    # SIGKILL, no journal goodbye
        coord.wait(timeout=10)

        # chaos: one spooled part rots while the coordinator is down
        from thinvids_tpu.tools.loadgen import corrupt_spooled_part

        spool_dir = os.path.join(state_dir, "part-spool", job["id"])
        assert corrupt_spooled_part(
            os.path.join(state_dir, "part-spool"), job["id"]) is not None

        coord = spawn_coordinator()     # restart over the same state
        _wait(lambda: _try_health(base), 45,
              what="coordinator API after restart")
        done = _wait(lambda: _job_if_terminal(base, job["id"]), 240,
                     what="job terminal after coordinator restart")
        assert done["status"] == "done", done
        with open(done["output_path"], "rb") as fp:
            assert fp.read() == want    # byte-identical despite the
                                        # crash AND the corruption
        snap = _call(base, "/metrics_snapshot")["work"]
        assert snap["resumed"] >= 1, snap       # spool reuse, not a
                                                # full re-encode
        assert snap["integrity_rejects"] >= 1, snap  # the flipped part
                                                # was caught at resume
        # the finished job released its checkpoint + spool
        assert not os.path.exists(spool_dir)
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
                w.wait(timeout=10)
        if coord.poll() is None:
            coord.send_signal(signal.SIGTERM)
            try:
                coord.wait(timeout=15)
            except subprocess.TimeoutExpired:
                coord.kill()


def _try_health(base):
    try:
        return _call(base, "/health", timeout=3)
    except (urllib.error.URLError, ConnectionError, OSError):
        return None


def _job_if_terminal(base, job_id):
    job = _call(base, f"/job_properties/{job_id}")["job"]
    return job if job["status"] in ("done", "failed", "stopped") else None
