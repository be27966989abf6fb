"""Host spans and device ops on one clock (`tvtbench/host_reduce.py`):
the arithmetic on hand-made lists, the wire-level reader against the
recorded profile beside this file, and the seven readers where there is
nothing to read."""

import json
import os

import pytest

from tvtbench import host_reduce as hr
from tvtbench.spec import load_module
from trim_host_xplane import field

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_READERS = ("profile_window_excess_ms", "idle_in_stage_pct",
                  "idle_unnamed_ms_per_frame", "lead_in_ms_per_job",
                  "tail_ms_per_job")
CLOCK_READERS = ("job_build_ms", "job_finish_ms")
STAGE = hr.STAGE


def test_the_shortest_covering_annotation_wins():
    """`wave_collect` (10..90) holds `device_wait` (20..40); another
    thread's `decode` (30..35) lies inside both and `stage` (80..120)
    outlasts the collect."""
    notes = [(10, 90, "tvt:wave_collect"), (20, 40, "tvt:device_wait"),
             (30, 35, "tvt:decode"), (80, 120, "tvt:stage")]
    assert hr.innermost(notes) == [
        (10, 20, "tvt:wave_collect"), (20, 30, "tvt:device_wait"),
        (30, 35, "tvt:decode"), (35, 40, "tvt:device_wait"),
        (40, 80, "tvt:wave_collect"), (80, 120, "tvt:stage")]
    # equally long: the one that started last is the inner one
    assert hr.innermost([(0, 10, "tvt:a"), (5, 15, "tvt:b")]) == [
        (0, 5, "tvt:a"), (5, 15, "tvt:b")]
    assert hr.innermost([]) == []


def test_stage_extent_excess_lead_in_and_tail():
    """A window of 1000 with the stage at 100..900 and two device
    programs at 250..500 and 600..850 (the first holds a nested op)."""
    notes = [(100, 900, STAGE), (120, 250, "tvt:stage"),
             (500, 560, "tvt:device_wait"), (850, 880, "tvt:pack")]
    ops = [(250, 500), (300, 400), (600, 850)]
    got = hr.reduce_host(notes, ops, 1000)
    assert got["stage_ps"] == (100, 900)
    assert got["excess_ps"] == 100 + 100
    assert got["busy_ps"] == 500
    assert got["idle_ps"] == 300
    assert got["lead_in_ps"] == 150 and got["tail_ps"] == 50
    assert got["idle_by"] == {"stage": 130, "device_wait": 60, "pack": 30,
                              hr.UNNAMED: 20 + 40 + 20}
    # named + unnamed = idle inside the stage, exactly
    assert sum(got["idle_by"].values()) == got["idle_ps"]


def test_ops_outside_the_stage_count_for_nothing():
    """A device op that starts before the stage (the job before) and one
    that ends after it are cut at its edges."""
    notes = [(100, 200, STAGE)]
    got = hr.reduce_host(notes, [(50, 120), (180, 260)], 400)
    assert got["busy_ps"] == 40 and got["idle_ps"] == 60
    assert got["lead_in_ps"] == 0 and got["tail_ps"] == 0
    assert got["idle_by"] == {hr.UNNAMED: 60}
    assert got["excess_ps"] == 100 + 200


def test_annotations_under_device_time_name_no_idle():
    notes = [(0, 100, STAGE), (10, 55, "tvt:pack"), (40, 90, "tvt:decode")]
    got = hr.reduce_host(notes, [(20, 50), (70, 100)], 100)
    # idle: 0..20 and 50..70; pack (the shorter) has 10..20 and 50..55,
    # decode 55..70
    assert got["idle_by"] == {"pack": 15, "decode": 15, hr.UNNAMED: 10}
    assert sum(got["idle_by"].values()) == got["idle_ps"] == 40


@pytest.mark.parametrize("notes, ops, window", [
    ([(10, 60, "tvt:pack")], [(20, 50)], 100),      # no encode stage
    ([(0, 100, STAGE)], [], 100),                   # no device op
    ([(0, 100, STAGE)], [(20, 50)], None),          # no window
])
def test_nothing_to_read_is_not_measured(notes, ops, window):
    assert hr.reduce_host(notes, ops, window) is None


# -- the wire-level reader: every line has a time base of its own ---------

def xline(name, base_ns, events):
    """An XLine: `events` are (metadata id, offset_ps, duration_ps)."""
    return field(2, name.encode()) + field(3, base_ns) + b"".join(
        field(4, field(1, m) + field(2, off) + field(3, dur))
        for m, off, dur in events)


def xplane(name, lines, names=(), stats=b""):
    meta = b"".join(field(4, field(1, k) + field(2, field(1, k)
                                                   + field(2, n.encode())))
                    for k, n in names)
    return field(2, name.encode()) + meta + stats + b"".join(
        field(3, line) for line in lines)


def test_lines_are_set_on_one_clock(tmp_path):
    """The host plane's two thread lines and the device's op line each
    count their events from a base of their own; a second device plane
    and the Python tracer's events are passed over."""
    host = xplane(hr.HOST_PLANE, [
        xline("exec", 5, [(1, 1_000, 90_000), (2, 11_000, 20_000)]),
        xline("stager", 7, [(3, 0, 4_000), (9, 0, 500)])],
        names=[(1, STAGE), (2, "tvt:device_wait"), (3, "tvt:decode"),
               (9, "$threading.py:1001 run")])
    device = xplane("/device:TPU:0", [
        xline("Steps", 0, [(4, 0, 99_000)]),
        xline("XLA Ops", 20, [(4, 0, 10_000), (5, 2_000, 3_000)])])
    other = xplane("/device:TPU:1", [xline("XLA Ops", 0, [(4, 0, 1)])])
    env = xplane(
        hr.ENV_PLANE, [],
        stats=field(5, field(1, 1) + field(2, field(1, 1) + field(
            2, b"profile_start_time")))
        + field(5, field(1, 2) + field(2, field(1, 2) + field(
            2, b"profile_stop_time")))
        + field(6, field(1, 1) + field(3, 1_790_000_000_000_000_000))
        + field(6, field(1, 2) + field(3, 1_790_000_000_000_000_100)))
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(b"".join(field(1, p)
                              for p in (host, device, other, env)))
    got = hr.read_host(str(path))
    assert sorted(got["annotations"]) == [
        (6_000, 96_000, STAGE), (7_000, 11_000, "tvt:decode"),
        (16_000, 36_000, "tvt:device_wait")]
    assert got["ops"] == [(20_000, 30_000), (22_000, 25_000)]
    assert got["window_ps"] == 100_000
    red = hr.reduce_host(**got)
    assert red["excess_ps"] == 6_000 + 4_000
    assert red["lead_in_ps"] == 14_000 and red["tail_ps"] == 66_000
    assert red["idle_by"] == {"decode": 4_000, "device_wait": 4_000 + 6_000,
                              hr.UNNAMED: 80_000 - 14_000}


# -- the recorded profile ----------------------------------------------------

def test_recorded_profile_gives_the_pinned_values(monkeypatch):
    """`recorded_host.xplane.pb`: a 32-frame `hd-shorts` job traced on
    the chip (how it was made and cut: the `how` of the expected file),
    with the `stage_ms` snapshots of its run; the seven readers on it."""
    with open(os.path.join(HERE, "recorded_host.expected.json"),
              encoding="utf-8") as fp:
        want = json.load(fp)
    path = os.path.join(HERE, "recorded_host.xplane.pb")
    got = hr.read_host(path)
    assert len(got["ops"]) == want["device_ops"]
    assert len(got["annotations"]) == want["annotations"]
    red = hr.reduce_host(**got)
    assert sum(red["idle_by"].values()) == red["idle_ps"]
    assert {k: v for k, v in red.items() if k != "stage_ps"} \
        == want["reduced"]
    monkeypatch.setattr(hr.sr, "traced_profile", lambda cell: path)
    hr._CACHE.clear()
    ev = evidence_of(want["snapshot"]["before"], want["snapshot"]["after"],
                     done=want["jobs_done"],
                     profile={"busy_s": 1.0, "window_s": 2.0})
    values = {name: load_module("layer_metrics", name).read(ev)
              for name in DEVICE_READERS + CLOCK_READERS}
    hr._CACHE.clear()
    assert values == {k: pytest.approx(v, rel=1e-12)
                      for k, v in want["metrics"].items()}
    assert set(values) == set(want["metrics"])


# -- the seven readers ------------------------------------------------------

def evidence_of(before, after, done=2, profile=None):
    jobs = [{"name": f"j{i}", "frames": 32,
             "record": {"status": "done"}} for i in range(done)]
    return {"cell": "no-such-cell", "profile": profile, "jobs": jobs,
            "traced_job": "j0",
            "snapshot": {"before": before, "after": after}}


def test_the_clock_readers_divide_growth_by_jobs_done():
    before = {"job_build": 10.0, "job_plan": 1.0, "scenecut": 0.5,
              "job_stitch": 1.0, "job_mux": 2.0, "job_write": 3.0,
              "job_commit": 4.0}
    after = {"job_build": 16.0, "job_plan": 9.0, "scenecut": 6.5,
             "job_stitch": 1.5, "job_mux": 4.5, "job_write": 6.0,
             "job_commit": 12.0, "profile_start": 300.0}
    ev = evidence_of(before, after)
    assert load_module("layer_metrics", "job_build_ms").read(ev) \
        == pytest.approx((6.0 + 8.0 - 6.0) / 2)
    assert load_module("layer_metrics", "job_finish_ms").read(ev) \
        == pytest.approx((0.5 + 2.5 + 3.0 + 8.0) / 2)


def test_a_program_without_the_clocks_or_the_stage_is_not_measured():
    """The parent of PR 35: `stage_ms` has no `job_*` key and the
    profile no `tvt:encode_stage`; no job done divides by nothing."""
    old = evidence_of({"decode": 1.0}, {"decode": 9.0},
                      profile={"busy_s": 1.0, "window_s": 2.0})
    for name in DEVICE_READERS + CLOCK_READERS:
        assert load_module("layer_metrics", name).read(old) is None, name
    none_done = evidence_of({"job_build": 1.0}, {"job_build": 2.0}, done=0)
    for name in CLOCK_READERS:
        assert load_module("layer_metrics", name).read(none_done) is None


def test_the_rehearsal_measures_the_two_clock_metrics(tmp_path):
    """`--rehearse-cpu --trace 1`: the executor's clocks reach
    `/metrics_snapshot` on any platform; the five device metrics need a
    device plane and stay out."""
    import subprocess
    import sys

    from conftest import ROOT

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "hd-backlog", "--seed", "3500000007", "--seconds",
         "3", "--trace", "1", "--rehearse-cpu"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=600)
    assert proc.returncode == 0, proc.stderr.decode()[-3000:]
    line = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"] == {}
    assert set(CLOCK_READERS) <= set(line["measured"])
    assert not set(DEVICE_READERS) & set(line["measured"])
    # and the two that take the same stretches from outside still read
    assert {"job_fixed_ms",
            "encode_stage_share_pct"} <= set(line["measured"])
