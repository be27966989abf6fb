"""The tenth cell, `hd-serving-crossing` (PR 45): `serving-1080p` with
intra macroblocks in P pictures (`p_intra`) on footage whose objects
cross and uncover each other. Its two readers on canned evidence —
among it a program without the counters and without the stage, as the
parent is — the generator's prefix property, what the cell is made of,
and a CPU rehearsal in which the p_intra executable must serve every
job and code intra macroblocks in its P pictures."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT
from tvtbench import scope_reduce
from tvtbench.spec import Cell, load_module


def ev_of(before, after, frames=256, **more):
    return dict({"jobs": [{"name": "w0003", "frames": frames,
                           "record": {"status": "done"}}],
                 "traced_job": "w0003",
                 "snapshot": {"before": before, "after": after}}, **more)


def read(name, ev):
    return load_module("layer_metrics", name).read(ev)


def test_intra_share_is_intra_over_coded_of_the_window():
    before = {"p_mbs_coded": 1000, "p_mbs_intra": 400}
    after = {"p_mbs_coded": 1000 + 8000, "p_mbs_intra": 400 + 1300}
    assert read("p_intra_mb_pct", ev_of(before, after)) \
        == pytest.approx(16.25)
    # the counters with no intra macroblock chosen: 0, not "not measured"
    after = {"p_mbs_coded": 9000, "p_mbs_intra": 400}
    assert read("p_intra_mb_pct", ev_of(before, after)) == 0.0


@pytest.mark.parametrize("before,after", [
    ({"stage": 1.0}, {"stage": 9.0}),                   # the parent
    ({"p_mbs_coded": 5}, {"p_mbs_coded": 9}),           # one counter alone
    ({"p_mbs_coded": 7, "p_mbs_intra": 2},
     {"p_mbs_coded": 7, "p_mbs_intra": 2}),             # p_intra off
])
def test_intra_share_is_not_measured_without_its_counters(before, after):
    assert read("p_intra_mb_pct", ev_of(before, after)) is None


def test_stage_time_reads_the_p_intra_scope_alone(monkeypatch):
    ev = ev_of({}, {}, profile={"frames": 256})
    scopes = {"tvt.p_intra": 0.512, "tvt.intra": 0.07, "tvt.residual": 0.075}
    monkeypatch.setattr(scope_reduce, "scopes_of",
                        lambda ev: {"scopes": scopes, "busy_s": 3.0,
                                    "unscoped_s": 0.1, "stale": None})
    monkeypatch.setattr(
        scope_reduce.evidence, "profile_per_frame",
        lambda ev, seconds: 1e3 * seconds / ev["profile"]["frames"])
    assert read("dev_p_intra_ms_per_frame", ev) == pytest.approx(2.0)
    # a program without the stage (the parent; the setting off): not
    # measured, and not 0
    del scopes["tvt.p_intra"]
    assert read("dev_p_intra_ms_per_frame", ev) is None
    monkeypatch.setattr(scope_reduce, "scopes_of", lambda ev: None)
    assert read("dev_p_intra_ms_per_frame", ev) is None


def test_generator_prefix_seed_and_params():
    gen = load_module("generators", "crossing")
    long = list(gen.planes(6, 192, 128, 2**31 + 9))
    short = list(gen.planes(2, 192, 128, 2**31 + 9))
    for a, b in zip(short, long):
        assert all(np.array_equal(p, q) and p.dtype == np.uint8
                   for p, q in zip(a, b))
    assert long[0][0].shape == (128, 192) and long[0][1].shape == (64, 96)
    other = next(iter(gen.planes(1, 192, 128, 1)))
    assert not np.array_equal(other[0], long[0][0])     # the grain
    assert np.array_equal(other[1], long[0][1])         # not the scene
    # the traffic file names the generator's two numbers and no other
    params = Cell("hd-serving-crossing", ROOT).traffic["generator_params"]
    assert set(params) == {"pan", "sprites"} and params["pan"] == 3
    assert params["sprites"] % 4 == 0 and 12 <= params["sprites"] <= 36
    with pytest.raises(TypeError):
        next(iter(gen.planes(1, 192, 128, 1, grain=5.0)))
    # at 1920 wide the even sprites outrun the search's +-4 pixels
    # round its centres, the odd ones do not; none is a multiple of 16
    for k in range(params["sprites"]):
        body, _cu, _cv, _start, (vx, vy) = gen.sprite(k, 1920, 0)
        assert body.shape[0] % 16 and body.shape[1] % 16
        assert (7 <= abs(vx) <= 13) if k % 2 == 0 else (abs(vx) <= 4)
    # the 128x128 rehearsal clip still holds sprites that move
    small = list(gen.planes(3, 128, 128, 5, **params))
    bare = list(gen.planes(3, 128, 128, 5, pan=3, sprites=0))
    assert all(not np.array_equal(a[0], b[0]) for a, b in zip(small, bare))


def test_the_cell_is_the_serving_cell_plus_p_intra_and_content():
    cell = Cell("hd-serving-crossing", ROOT)
    serving = Cell("hd-serving-rd", ROOT)
    assert cell.chips == 1
    same = ("resolution", "reduced", "psnr_floor_db")
    assert all(cell.config[k] == serving.config[k] for k in same)
    assert cell.config["rehearse_cpu"] == dict(
        serving.config["rehearse_cpu"], gop_frames=4)
    assert cell.config["expect_settings"] == dict(
        serving.config["expect_settings"], p_intra=True)
    for chips in ("1", "4"):
        assert cell.config["env_by_chips"][chips] == dict(
            serving.config["env_by_chips"][chips], TVT_P_INTRA="1")
    assert cell.config["guarantees"][:-1] == serving.config["guarantees"]
    assert "intra in a P slice" in cell.config["guarantees"][-1]
    for key in ("content", "sprites", "decision", "adjacency", "fps"):
        assert key in cell.config["assumed"]
    assert cell.config["assumed"]["architecture"] is None
    assert len(cell.bench["configs"][-1]["source"]) <= 200
    mine, theirs = cell.traffic, serving.traffic
    for key in ("frames_per_clip", "outstanding", "submit", "job_settings",
                "traced_frames"):
        assert mine[key] == theirs[key]
    # the warm-up runs the whole clip: a dense GOP anywhere in it is a
    # program (the levels' re-wording) the window would build
    assert mine["warmup_frames"] == mine["frames_per_clip"]
    assert mine["generator"] == "crossing"
    names = {m["name"] for m in cell.per_layer}
    new = {"p_intra_mb_pct", "dev_p_intra_ms_per_frame"}
    assert new <= names
    assert {m["name"] for m in serving.per_layer} | new \
        | {"dense_retry_ms_per_frame"} == names
    assert {m["name"] for m in cell.end_to_end} == {
        "frames_per_s", "kbit_per_frame", "psnr_y_db", "setup_s"}
    # appended after PR 41's entries (by name, not "the last": the
    # next PR appends after these)
    bench = cell.bench

    def place(entries, name):
        return [e["name"] for e in entries].index(name)

    assert place(bench["workloads"], "hd-serving-crossing") \
        == place(bench["workloads"], "hd-serving-handheld") + 1
    assert place(bench["configs"], "serving-1080p-action") \
        == place(bench["configs"], "serving-1080p-camera") + 1
    at = place(bench["per_layer"], "p_intra_mb_pct")
    assert at > place(bench["per_layer"], "stage_copy_bytes_per_frame")
    assert bench["per_layer"][at + 1]["name"] == "dev_p_intra_ms_per_frame"
    assert all(m["workloads"] == ["hd-serving-crossing"]
               and m["moves"] == "frames_per_s"
               and m["layer"] == "device program"
               for m in bench["per_layer"][at:at + 2])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_rehearse_crossing_cell_traced(tmp_path):
    """The control flow of the cell on the CPU, and from the kept
    evidence (a rehearsal prints no value): `p_intra` is live, one
    executable serves the window, and the jobs' P pictures hold intra
    macroblocks."""
    keep = tmp_path / "keep"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    env.pop("XLA_FLAGS", None)      # (a session that forced CPU devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "hd-serving-crossing", "--seed", str(2**31 + 45),
         "--seconds", "3", "--trace", "1", "--rehearse-cpu", "--keep",
         str(keep)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=600)
    assert proc.returncode == 0, proc.stderr.decode()[-3000:]
    line = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3           # the traced job is the third
    assert line["device"]["platform"] == "cpu"
    assert {"p_intra_mb_pct", "stage_ms_per_frame",
            "sparse_budget_fill_pct", "dense_fallback_waves"} \
        <= set(line["measured"])
    ev = json.loads((keep / "evidence.json").read_text())
    after, before = ev["snapshot"]["after"], ev["snapshot"]["before"]
    assert after["p_mbs_coded"] > before["p_mbs_coded"]
    assert after["p_mbs_intra"] > before["p_mbs_intra"]
    assert 0.0 < read("p_intra_mb_pct", ev) < 100.0
    # nothing was set up inside the window
    assert after["programs_built"] == before["programs_built"] >= 1
