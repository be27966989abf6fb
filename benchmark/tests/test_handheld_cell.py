"""The ninth cell, `hd-serving-handheld` (PR 41): `serving-1080p` with
quarter-sample vectors on a hand-held shot. Its two readers on canned
evidence — among it a program without the counters, as the parent is
— the generator's prefix property, what the cell is made of, and a CPU
rehearsal in which the quarter executable must serve every job."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT
from tvtbench.spec import Cell, load_module


def ev_of(before, after, frames=256, **more):
    return dict({"jobs": [{"name": "w0003", "frames": frames,
                           "record": {"status": "done"}}],
                 "traced_job": "w0003",
                 "snapshot": {"before": before, "after": after}}, **more)


def read(name, ev):
    return load_module("layer_metrics", name).read(ev)


def test_quarter_share_is_odd_vectors_over_vectors_of_the_window():
    before = {"mvs_coded": 1000, "mvs_quarter": 400}
    after = {"mvs_coded": 1000 + 8000, "mvs_quarter": 400 + 5000}
    assert read("qpel_mv_pct", ev_of(before, after)) == pytest.approx(62.5)
    # a half-sample encoder with the counters: 0, not "not measured"
    after = {"mvs_coded": 9000, "mvs_quarter": 400}
    assert read("qpel_mv_pct", ev_of(before, after)) == 0.0


@pytest.mark.parametrize("before,after", [
    ({"stage": 1.0}, {"stage": 9.0}),                   # the parent
    ({"mvs_coded": 5}, {"mvs_coded": 9}),               # one counter alone
    ({"mvs_coded": 7, "mvs_quarter": 2},
     {"mvs_coded": 7, "mvs_quarter": 2}),               # no P macroblock
])
def test_quarter_share_is_not_measured_without_its_counters(before, after):
    assert read("qpel_mv_pct", ev_of(before, after)) is None


def test_candidates_are_the_last_snapshots_gauge():
    ev = ev_of({"me_candidates": 227}, {"me_candidates": 379})
    assert read("me_candidates_per_mb", ev) == 379.0
    assert read("me_candidates_per_mb",
                ev_of({"stage": 1.0}, {"stage": 2.0})) is None


def test_generator_prefix_seed_and_path():
    gen = load_module("generators", "handheld")
    long = list(gen.planes(6, 96, 64, 2**31 + 9))
    short = list(gen.planes(2, 96, 64, 2**31 + 9))
    for a, b in zip(short, long):
        assert all(np.array_equal(p, q) and p.dtype == np.uint8
                   for p, q in zip(a, b))
    assert long[0][0].shape == (64, 96) and long[0][1].shape == (32, 48)
    other = next(iter(gen.planes(1, 96, 64, 1)))
    assert not np.array_equal(other[0], long[0][0])     # the grain
    assert np.array_equal(other[1], long[0][1])         # not the scene
    # the traffic file's path is the generator's default, and it names
    # nothing else: the scene (pan.py's, grain and all) is not a
    # parameter
    path = Cell("hd-serving-handheld", ROOT).traffic["generator_params"]
    assert {k: float(v) for k, v in path.items()} == gen.PATH
    with pytest.raises(TypeError):
        next(iter(gen.planes(1, 96, 64, 1, grain=5.0)))
    x, y = gen.position(np.arange(257), **path)
    d = np.stack([np.diff(x), np.diff(y)], axis=1)
    assert np.abs(d[:, 0]).max() <= 2.77 and np.abs(d[:, 1]).max() <= 1.13
    assert (np.abs(d * 4 - np.rint(d * 4)) / 4).min() > 1e-3


def test_the_cell_is_the_serving_cell_plus_precision_and_content():
    cell = Cell("hd-serving-handheld", ROOT)
    serving = Cell("hd-serving-rd", ROOT)
    assert cell.chips == 1
    same = ("resolution", "reduced", "psnr_floor_db")
    assert all(cell.config[k] == serving.config[k] for k in same)
    # the rehearsal's GOPs are 4 frames for the serving cell's 2: a
    # GOP's first P frame searches round a median of zero, so only
    # later ones can show the quarter rows at work
    assert cell.config["rehearse_cpu"] == dict(
        serving.config["rehearse_cpu"], gop_frames=4)
    assert cell.config["expect_settings"] == dict(
        serving.config["expect_settings"], subpel="quarter")
    for chips in ("1", "4"):
        assert cell.config["env_by_chips"][chips] == dict(
            serving.config["env_by_chips"][chips], TVT_SUBPEL="quarter")
    assert cell.config["guarantees"][:-1] == serving.config["guarantees"]
    assert "quarter-sample vector" in cell.config["guarantees"][-1]
    for key in ("camera path", "quarter window", "lambda", "fps", "content"):
        assert key in cell.config["assumed"]
    mine, theirs = cell.traffic, serving.traffic
    for key in ("frames_per_clip", "outstanding", "submit", "job_settings",
                "traced_frames"):
        assert mine[key] == theirs[key]
    # the warm-up runs the whole clip: a dense GOP in its second half
    # is a program (the levels' re-wording) the window would build
    assert mine["warmup_frames"] == mine["frames_per_clip"]
    assert mine["generator"] == "handheld"
    names = {m["name"] for m in cell.per_layer}
    new = {"qpel_mv_pct", "me_candidates_per_mb"}
    assert new <= names
    # and, as hd-grain, what its dense GOPs cost the host (static
    # grain under real-valued motion: PERF.md)
    assert {m["name"] for m in serving.per_layer} | new \
        | {"dense_retry_ms_per_frame"} == names
    assert {m["name"] for m in cell.end_to_end} == {
        "frames_per_s", "kbit_per_frame", "psnr_y_db", "setup_s"}
    bench = cell.bench
    assert bench["workloads"][-1]["name"] == "hd-serving-handheld"
    assert bench["configs"][-1]["name"] == "serving-1080p-camera"
    assert [m["name"] for m in bench["per_layer"][-2:]] == sorted(
        new, reverse=True)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_rehearse_handheld_cell_traced(tmp_path):
    """The control flow of the cell on the CPU, and from the kept
    evidence (a rehearsal prints no value): `subpel` quarter is live,
    the executable that served the window scores the quarter table,
    and the jobs' vectors use it."""
    keep = tmp_path / "keep"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    env.pop("XLA_FLAGS", None)      # (a session that forced CPU devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "hd-serving-handheld", "--seed", str(2**31 + 41),
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
    assert {"qpel_mv_pct", "me_candidates_per_mb", "stage_ms_per_frame",
            "sparse_budget_fill_pct"} <= set(line["measured"])
    ev = json.loads((keep / "evidence.json").read_text())
    after, before = ev["snapshot"]["after"], ev["snapshot"]["before"]
    assert after["me_candidates"] == before["me_candidates"] == 379
    assert after["mvs_coded"] > before["mvs_coded"]
    assert after["mvs_quarter"] > before["mvs_quarter"]
    assert 0.0 < read("qpel_mv_pct", ev) <= 100.0
    assert read("me_candidates_per_mb", ev) == 379.0
    # nothing was set up inside the window
    assert after["programs_built"] == before["programs_built"] >= 1
