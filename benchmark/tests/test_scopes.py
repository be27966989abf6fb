"""Device time per stage (`tvtbench/scope_reduce.py`): the arithmetic on
hand-made events, the wire-level reader against the two recorded traces
beside this file, and the six readers where there is nothing to read."""

import json
import os

import pytest

from tvtbench import scope_reduce as sr
from tvtbench.spec import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("dev_intra_ms_per_frame", "dev_me_prep_ms_per_frame",
           "dev_residual_ms_per_frame", "dev_pack_ms_per_frame",
           "dev_halo_ms_per_frame", "dev_unscoped_pct")

GOP = "jit(_encode_gop_single)/tvt.layout/while/body/closed_call/"


def plane(events, meta, name="/device:TPU:0"):
    return {"name": name, "events": events,
            "meta": {k: (v[0], v[1], "") for k, v in meta.items()}}


def test_scope_is_the_last_component_of_the_path():
    assert sr.scope_of(GOP + "tvt.pack/scatter:") == "tvt.pack"
    assert sr.scope_of(GOP + "tvt.layout/while/body/closed_call/"
                       "tvt.me_search/jit(_me_pallas)/pallas_call:") \
        == "tvt.me_search"
    assert sr.scope_of("jit(f)/tvt.layout/while:") == "tvt.layout"
    assert sr.scope_of("jit(f)/while/body/add:") is None
    assert sr.scope_of("reduce_window_sum:") is None
    assert sr.scope_of("") is None and sr.scope_of(None) is None
    # a name that only starts like one is not a stage
    assert sr.scope_of("jit(f)/mytvt.pack/add:") is None


def test_a_loop_keeps_what_its_children_do_not_cover():
    """The GOP loop (10 ms) holds an intra fusion (3 ms), the P loop
    (5 ms) with a kernel call (4 ms) inside, and 2 ms of its own; an op
    with no path runs after it."""
    meta = {1: ("while.1", "jit(_encode_gop_single)/tvt.layout/while:"),
            2: ("fusion.7", GOP + "tvt.intra/while/body/closed_call/add:"),
            3: ("while.2", GOP + "tvt.layout/while:"),
            4: ("_me_pallas.3", GOP + "tvt.layout/while/body/closed_call/"
                "tvt.me_search/jit(_me_pallas)/pallas_call:"),
            5: ("copy.9", "")}
    events = [(0.000, 0.010, 1), (0.001, 0.004, 2), (0.004, 0.009, 3),
              (0.0045, 0.0085, 4), (0.012, 0.013, 5)]
    got = sr.reduce_scopes([plane(events, meta)])
    assert got["stale"] is None
    assert got["busy_s"] == pytest.approx(0.011)
    assert got["scopes"] == {"tvt.layout": pytest.approx(0.003),
                             "tvt.intra": pytest.approx(0.003),
                             "tvt.me_search": pytest.approx(0.004)}
    assert got["unscoped_s"] == pytest.approx(0.001)
    # the stages and the rest partition the busy time
    assert sum(got["scopes"].values()) + got["unscoped_s"] == \
        pytest.approx(got["busy_s"])
    assert got["ops"][0][:3] == [pytest.approx(0.004), "tvt.me_search",
                                 "_me_pallas.3"]


def test_a_loop_without_a_path_inherits_what_its_children_share():
    """The profiler gives a `while` no `tf_op`. The loop over GOPs
    holds an intra op, the loop over P frames (whose condition and body
    share `.../tvt.layout/while`), and a loop the compiler made, whose
    ops have no path either: that one stays without a stage."""
    meta = {1: ("while.1", ""),
            2: ("fusion.7", GOP + "tvt.intra/while/body/closed_call/add:"),
            3: ("while.2", ""),
            4: ("compare.1", GOP + "tvt.layout/while/cond/lt:"),
            5: ("fusion.9", GOP + "tvt.layout/while/body/closed_call/"
                "tvt.residual/sub:"),
            6: ("while.3", ""),
            7: ("dynamic-update-slice.2", "")}
    events = [(0.000, 0.020, 1), (0.001, 0.004, 2),
              (0.004, 0.010, 3), (0.004, 0.005, 4), (0.005, 0.008, 5),
              (0.012, 0.018, 6), (0.013, 0.015, 7),
              # the P loop again, in the next GOP: every occurrence counts
              (0.030, 0.040, 1), (0.031, 0.036, 3), (0.032, 0.035, 5)]
    assert sr.inherited_paths(events, plane(events, meta)["meta"]) == {
        1: GOP.rstrip("/"),
        3: GOP + "tvt.layout/while"}
    got = sr.reduce_scopes([plane(events, meta)])
    # GOP loop: 20 - 3 - 6 - 6 and 10 - 5; P loop: 6 - 1 - 3 and 5 - 3
    assert got["scopes"]["tvt.layout"] == pytest.approx(
        0.005 + 0.005 + 0.001 + 0.002 + 0.002)
    assert got["scopes"]["tvt.intra"] == pytest.approx(0.003)
    assert got["scopes"]["tvt.residual"] == pytest.approx(0.006)
    assert got["unscoped_s"] == pytest.approx(0.006)
    assert got["stale"] is None


def test_one_instruction_name_in_two_programs_is_two_ops():
    """`fusion.17` of the IDR step is intra work, `fusion.17` of the P
    step is residual work: the metadata id tells them apart, the name
    does not. Times are the mean over the device planes."""
    meta = {11: ("fusion.17", "jit(_sfe_intra_step)/shard_map/tvt.intra/"
                              "while/body/closed_call/mul:"),
            22: ("fusion.17", "jit(_sfe_p_step)/shard_map/tvt.residual/"
                              "sub:"),
            23: ("fusion.3", "jit(_sfe_p_step)/shard_map/tvt.halo/"
                             "concatenate:")}
    one = [(0.0, 0.002, 11), (0.010, 0.013, 22), (0.013, 0.014, 23)]
    two = [(0.0, 0.004, 11), (0.010, 0.015, 22), (0.015, 0.016, 23)]
    got = sr.reduce_scopes([plane(one, meta, "/device:TPU:0"),
                            plane(two, meta, "/device:TPU:1")])
    assert got["scopes"] == {"tvt.intra": pytest.approx(0.003),
                             "tvt.residual": pytest.approx(0.004),
                             "tvt.halo": pytest.approx(0.001)}
    assert got["unscoped_s"] == 0.0
    assert got["busy_s"] == pytest.approx(0.008)


def test_executables_without_the_names_are_stale_not_zero():
    bare = {1: ("fusion.1", "jit(_encode_gop_single)/while/body/add:"),
            2: ("copy.2", "")}
    got = sr.reduce_scopes([plane([(0.0, 0.005, 1), (0.005, 0.006, 2)],
                                  bare)])
    assert got["scopes"] == {} and got["stale"] == \
        "no op carries a tvt.* stage"
    # half stale: the IDR step came from an older tree's cache, the P
    # step was compiled by this one
    mixed = {1: ("fusion.1", "jit(_sfe_intra_step)/shard_map/mul:"),
             2: ("fusion.1", "jit(_sfe_p_step)/shard_map/tvt.residual/"
                             "sub:"),
             3: ("dynamic-slice.1", "jit(dynamic_slice)/dynamic_slice:")}
    events = [(0.0, 0.002, 1), (0.002, 0.00995, 2), (0.00995, 0.01, 3)]
    got = sr.reduce_scopes([plane(events, mixed)])
    assert got["stale"] == \
        "no op of jit(_sfe_intra_step) carries a tvt.* stage"
    # a program too small to matter is not evidence of anything
    got = sr.reduce_scopes([plane(events[1:], mixed)])
    assert got["stale"] is None
    assert got["unscoped_s"] == pytest.approx(0.00005)
    assert sr.reduce_scopes([])["stale"] == "no op carries a tvt.* stage"


# -- the wire-level reader ------------------------------------------------

RECORDED = os.path.join(HERE, "recorded.xplane.pb")
SCOPED = os.path.join(HERE, "recorded_scopes.xplane.pb")


def test_wire_reader_agrees_with_the_profiledata_reader():
    """PR 22's trace, read without jax: the motion-search calls found by
    their path sum to what `profile_reduce` found by instruction name,
    and the busy union is the same."""
    with open(os.path.join(HERE, "recorded.expected.json")) as fp:
        want = json.load(fp)
    planes = sr.read_xplane(RECORDED)
    assert [p["name"] for p in planes] == want["planes"]
    only = planes[0]
    me = [e - s for s, e, m in only["events"]
          if only["meta"][m][1].endswith("jit(_me_pallas)/pallas_call:")]
    assert len(me) == want["me"]["events"]
    assert sum(me) == pytest.approx(want["me"]["seconds"], rel=1e-6)
    got = sr.reduce_scopes(planes)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    # its one `while` has no path: it is filed with the ops inside it
    loops = [k for k, v in only["meta"].items() if v[0].startswith("while")]
    assert [only["meta"][k][1] for k in loops] == [""]
    assert sr.inherited_paths(only["events"], only["meta"]) == {
        loops[0]: "jit(_encode_gop_single)/while/body/closed_call/while"}
    # compiled before the stages had names
    assert got["stale"] == "no op carries a tvt.* stage"
    assert got["unscoped_s"] == pytest.approx(got["busy_s"])


def evidence_for(cell, busy_s, frames=32):
    return {"cell": cell, "traced_job": "w0003",
            "jobs": [{"name": "w0003", "frames": frames}],
            "profile": {"busy_s": busy_s}}


def read_all(ev):
    return {name: load_module("layer_metrics", name).read(ev)
            for name in READERS}


def test_readers_say_not_measured_and_why(monkeypatch, capsys):
    monkeypatch.setattr(sr, "traced_profile", lambda cell: RECORDED)
    ev = evidence_for("hd-shorts", 0.8010132940000003)
    assert set(read_all(ev).values()) == {None}
    err = capsys.readouterr().err
    # said once, not six times: the readers share one parse
    assert err.count("no op carries a tvt.* stage") == 1
    assert "compile cache" in err and "not measured" in err
    # no device profile (the CPU rehearsal): nothing is even looked for
    monkeypatch.setattr(sr, "traced_profile", lambda cell: 1 / 0)
    assert set(read_all(dict(ev, profile=None)).values()) == {None}
    # no file
    monkeypatch.setattr(sr, "traced_profile", lambda cell: None)
    assert set(read_all(ev).values()) == {None}
    assert "no .xplane.pb" in capsys.readouterr().err


def test_the_file_is_found_where_run_py_left_it(monkeypatch, tmp_path):
    deep = tmp_path / ".smoke_work/benchmark/hd-shorts/profiles/x/plugins"
    deep.mkdir(parents=True)
    (deep / "vm.xplane.pb").write_bytes(b"")
    (deep / "vm.trace.json.gz").write_bytes(b"")
    monkeypatch.setattr(sr, "ROOT", str(tmp_path))
    assert sr.traced_profile("hd-shorts") == str(deep / "vm.xplane.pb")
    assert sr.traced_profile("hd-backlog") is None


@pytest.mark.skipif(not os.path.exists(SCOPED),
                    reason="no recorded trace with stage names")
def test_recorded_trace_with_stages(monkeypatch):
    """A trace of this tree's `hd-shorts` (recorded_scopes.expected.json
    says how it was cut): the stage sums as first computed and looked
    at by hand, through the readers."""
    with open(os.path.join(HERE, "recorded_scopes.expected.json")) as fp:
        want = json.load(fp)
    got = sr.reduce_scopes(sr.read_xplane(SCOPED))
    assert got["stale"] is None
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["unscoped_s"] == pytest.approx(want["unscoped_s"], rel=1e-9)
    assert set(got["scopes"]) == set(want["scopes"])
    for scope, sec in want["scopes"].items():
        assert got["scopes"][scope] == pytest.approx(sec, rel=1e-9), scope
    assert sum(got["scopes"].values()) + got["unscoped_s"] == \
        pytest.approx(got["busy_s"], rel=1e-9)

    monkeypatch.setattr(sr, "traced_profile", lambda cell: SCOPED)
    frames = want["frames"]
    values = read_all(evidence_for("hd-shorts", want["busy_s"], frames))
    ms = {k: 1e3 * v / frames for k, v in want["scopes"].items()}
    assert values["dev_intra_ms_per_frame"] == pytest.approx(ms["tvt.intra"])
    assert values["dev_me_prep_ms_per_frame"] == pytest.approx(
        ms["tvt.me_prep"] + ms["tvt.me_median"])
    assert values["dev_residual_ms_per_frame"] == pytest.approx(
        ms["tvt.residual"])
    assert values["dev_pack_ms_per_frame"] == pytest.approx(
        ms["tvt.pack"] + ms["tvt.compact"])
    assert values["dev_halo_ms_per_frame"] == 0.0   # no band in this cell
    assert values["dev_unscoped_pct"] == pytest.approx(
        100 * want["unscoped_s"] / want["busy_s"])


def test_rehearsal_measures_none_of_the_stage_metrics(tmp_path):
    """No device plane in a CPU profile: every `dev_*` metric is "not
    measured" in a traced rehearsal, and the others still are."""
    from conftest import ROOT
    from test_rehearse import check_line, rehearse

    line = rehearse(ROOT, "hd-shorts", 1, tmp_path)
    check_line(line, 1)
    assert "job_fixed_ms" in line["measured"]
    assert not set(READERS) & set(line["measured"])
