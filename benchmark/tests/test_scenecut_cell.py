"""The seventh cell, `hd-scenecut` (PR 32): its two readers on canned
snapshots — among them a program that has neither the `scenecut` stage
nor the pad counters, as the parent of PR 32 has not — and a CPU
rehearsal of the cell, in which every job must plan its GOPs on the
clip's three cuts."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT
from tvtbench.spec import Cell, load_module


def ev_of(before, after, frames=256):
    return {"jobs": [{"frames": frames, "record": {"status": "done"}}],
            "snapshot": {"before": before, "after": after}}


def read(name, ev):
    return load_module("layer_metrics", name).read(ev)


def test_pad_share_is_pad_over_staged():
    before = {"pad_frames": 64, "wave_frames": 320}
    after = {"pad_frames": 64 + 128, "wave_frames": 320 + 640}
    assert read("pad_frames_pct", ev_of(before, after)) == \
        pytest.approx(20.0)
    after = {"pad_frames": 64, "wave_frames": 320 + 256}    # hd-backlog
    assert read("pad_frames_pct", ev_of(before, after)) == 0.0


@pytest.mark.parametrize("before,after", [
    ({"stage": 1.0}, {"stage": 9.0}),           # a program without them
    ({"pad_frames": 7, "wave_frames": 9},
     {"pad_frames": 7, "wave_frames": 9}),      # neither moved
])
def test_pad_share_is_not_measured_without_counters(before, after):
    assert read("pad_frames_pct", ev_of(before, after)) is None


def test_scenecut_is_per_done_frame_and_silent_on_the_parent():
    ev = ev_of({"scenecut": 100.0}, {"scenecut": 100.0 + 256.0})
    assert read("scenecut_ms_per_frame", ev) == pytest.approx(1.0)
    assert read("scenecut_ms_per_frame",
                ev_of({"scenecut": 0.0}, {"scenecut": 0.0})) == 0.0
    assert read("scenecut_ms_per_frame",
                ev_of({"stage": 1.0}, {"stage": 9.0})) is None


def test_the_cell_is_library_1080p_but_for_scenecut_and_content():
    cut, backlog = Cell("hd-scenecut", ROOT), Cell("hd-backlog", ROOT)
    same = ("resolution", "reduced", "rehearse_cpu", "psnr_floor_db")
    assert all(cut.config[k] == backlog.config[k] for k in same)
    assert cut.config["expect_settings"] == dict(
        backlog.config["expect_settings"], scenecut=40)
    assert cut.config["env_by_chips"]["1"] == dict(
        backlog.config["env_by_chips"]["1"], TVT_SCENECUT="40")
    assert cut.config["guarantees"][:len(backlog.config["guarantees"])] \
        == backlog.config["guarantees"]
    assert len(cut.config["guarantees"]) == \
        len(backlog.config["guarantees"]) + 2
    assert cut.traffic["generator"] == "cuts"
    assert cut.traffic["generator_params"] == {
        "pan": 3, "shots": [72, 40, 88, 56]}
    assert (cut.traffic["frames_per_clip"], cut.traffic["outstanding"],
            cut.traffic["warmup_frames"], cut.traffic["traced_frames"],
            cut.traffic["job_settings"]) == (256, 2, 256, 256, {})
    assert cut.generator.cut_frames(256) == [72, 112, 200]
    names = {m["name"] for m in cut.per_layer}
    assert {"scenecut_ms_per_frame", "pad_frames_pct",
            "me_kernel_roofline", "sparse_budget_fill_pct"} <= names
    assert not {"scenecut_ms_per_frame", "pad_frames_pct"} & \
        {m["name"] for m in backlog.per_layer}
    assert {m["name"] for m in cut.end_to_end} == {
        "frames_per_s", "kbit_per_frame", "psnr_y_db", "setup_s"}


def test_rehearse_scenecut_cell_traced(tmp_path):
    """The control flow of the cell on the CPU, and from the kept
    evidence (a rehearsal prints no value): three cuts a job became GOP
    starts, the span is there, and both readers give a number."""
    keep = tmp_path / "keep"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    env.pop("XLA_FLAGS", None)      # (a session that forced CPU devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "hd-scenecut", "--seed", str(2**31 + 7),
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
    assert {"scenecut_ms_per_frame", "pad_frames_pct",
            "stage_ms_per_frame", "h2d_bytes_per_frame",
            "job_fixed_ms"} <= set(line["measured"])
    ev = json.loads((keep / "evidence.json").read_text())
    jobs = len(ev["jobs"])
    after, before = ev["snapshot"]["after"], ev["snapshot"]["before"]
    grew = {k: after[k] - before[k] for k in
            ("scenecut", "scene_cuts", "scene_cuts_suppressed",
             "pad_frames", "wave_frames", "waves")}
    # 16-frame clips, 2-frame GOPs: shots of 5, 2, 6 and 3 frames are
    # 3 + 1 + 3 + 2 GOPs, each staged to 2 frames
    assert grew["scene_cuts"] == 3 * jobs
    assert grew["scene_cuts_suppressed"] == 0
    assert grew["waves"] == 9 * jobs
    assert grew["wave_frames"] == 18 * jobs
    assert grew["pad_frames"] == 2 * jobs
    assert grew["scenecut"] > 0
    assert read("pad_frames_pct", ev) == pytest.approx(100.0 * 2 / 18)
    assert read("scenecut_ms_per_frame", ev) > 0
    traced = [j for j in ev["jobs"] if j.get("trace")]
    assert traced and all(
        [s["name"] for s in j["trace"]["spans"]].count("scenecut") == 1
        for j in traced)
