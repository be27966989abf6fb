"""The trace reducer: its arithmetic on hand-made events, and the whole
reduction against a small recorded .xplane.pb kept beside this file."""

import json
import os
import subprocess
import sys

import pytest

from tvtbench import profile_reduce as pr

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)


def test_union_merges_nested_and_touching():
    assert pr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 6]]
    assert pr.total(pr.union([(0, 10), (2, 3), (4, 12)])) == 12


def test_subtract_leaves_what_is_not_covered():
    a = pr.union([(0, 10), (20, 30)])
    b = pr.union([(2, 4), (8, 22), (29, 40)])
    # left of A: [0,2) [4,8) [22,29)
    assert pr.subtract(a, b) == pytest.approx(2 + 4 + 7)
    assert pr.subtract(a, []) == 20


def test_self_time_names_leaves_not_loops():
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "fusion"),
              (4.0, 9.0, "kernel"), (5.0, 6.0, "inner"),
              (12.0, 13.0, "fusion")]
    got = pr.self_times(events)
    assert got["while"] == [pytest.approx(2.0), 1]
    assert got["fusion"] == [pytest.approx(4.0), 2]
    assert got["kernel"] == [pytest.approx(4.0), 1]
    assert got["inner"] == [pytest.approx(1.0), 1]


def test_reduce_planes_busy_gaps_me_and_collectives():
    ev = [
        (0.000, 0.010, "while.1", "while.1"),
        (0.000, 0.004, "_me_pallas.3", "%_me_pallas.3 = custom-call(...)"),
        (0.000, 0.001, "fusion.7", "%fusion.7 jit(_me_pallas)/pad"),
        (0.004, 0.0041, "collective-permute-start.1", "..."),
        (0.0059, 0.006, "collective-permute-done.1", "..."),
        (0.005, 0.0059, "fusion.2", "fusion.2"),
        (0.006, 0.007, "all-reduce.4", "%all-reduce.4 = ..."),
        (0.020, 0.030, "fusion.2", "fusion.2"),
    ]
    # the permute is in flight from its start to its done
    flight = [(0.004, 0.006, "collective-permute-start.1")]
    out = pr.reduce_planes([{"name": "/device:TPU:0", "events": ev,
                             "async_events": flight},
                            {"name": "/device:TPU:1", "events": ev,
                             "async_events": flight}],
                           window_s=0.040, t0_epoch_s=100.0)
    assert out["busy_s"] == pytest.approx(0.020)
    # the pad fusion traced from the same jit is not the kernel
    assert out["me"] == {"seconds": pytest.approx(0.004), "events": 2}
    # 2 ms of permute in flight + 1 ms of all-reduce
    assert out["collectives"]["seconds"] == pytest.approx(0.003)
    assert out["collectives"]["events"] == 4
    # fusion.2 hides 0.9 ms of the permute; its start and done halves
    # on the op line are not compute
    assert out["collectives"]["exposed_seconds"] == pytest.approx(0.0021)
    # between the ops, and after the last one to the window's end
    assert sorted(out["gaps"]) == [[pytest.approx(0.010), pytest.approx(0.010)],
                                   [pytest.approx(0.030), pytest.approx(0.010)]]
    names = [name for name, _s, _n in out["ops"]]
    assert names[0] == "fusion.2"           # 10.9 ms of self time
    assert dict((n, s) for n, s, _c in out["ops"])["while.1"] == \
        pytest.approx(0.0039)               # 10 ms less its children


def test_no_device_plane_gives_no_busy_time():
    out = pr.reduce_planes([], window_s=1.0)
    assert out["device_planes"] == [] and out["busy_s"] == 0.0


RECORDED = os.path.join(HERE, "recorded.xplane.pb")
EXPECTED = os.path.join(HERE, "recorded.expected.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the test")
def test_recorded_trace_reduces_to_the_recorded_numbers(tmp_path):
    """A TPU v5e trace, trimmed (recorded.expected.json says how, with
    trim_xplane.py): busy union, idle share, op ranking and the
    motion-search calls as first computed and looked at by hand."""
    out = tmp_path / "reduced.json"
    subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "tvtbench",
                                      "profile_reduce.py"),
         RECORDED, str(out)],
        check=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    got = json.loads(out.read_text())
    want = json.load(open(EXPECTED))
    assert [p["name"] for p in got["device_planes"]] == want["planes"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 1 - got["busy_s"] / got["window_s"] == \
        pytest.approx(want["idle_share"], rel=1e-9)
    assert [n for n, _s, _c in got["ops"][:5]] == want["top_ops"]
    assert got["me"]["events"] == want["me"]["events"]
    assert got["me"]["seconds"] == pytest.approx(want["me"]["seconds"])
    # the union, again, by a different route: a sweep over sorted edges
    planes, _w, _t0 = pr.read_xplane(RECORDED)
    for plane, summary in zip(planes, got["device_planes"]):
        edges = sorted([(s, 1) for s, _e, _n, _t in plane["events"]]
                       + [(e, -1) for _s, e, _n, _t in plane["events"]],
                       key=lambda x: (x[0], -x[1]))
        depth, busy, since = 0, 0.0, None
        for t, d in edges:
            if depth == 0 and d == 1:
                since = t
            depth += d
            if depth == 0:
                busy += t - since
        assert busy == pytest.approx(summary["busy_s"], rel=1e-9)
