"""The eighth cell, `hd-serving-scenecut` (PR 39): `serving-1080p` and
`library-1080p-edited` in one deployment. Its four readers on canned
evidence — among it a program without the clock `program_build` or the
counter `pad_frames_skipped`, as older trees are — and a CPU rehearsal
of the cell in which every wave of every job must run the bounded
P-frame loop with the serving settings live."""

import json
import os
import subprocess
import sys

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


def test_skipped_share_is_skipped_over_staged_repeats():
    before = {"pad_frames": 64, "pad_frames_skipped": 64}
    after = {"pad_frames": 64 + 128, "pad_frames_skipped": 64 + 128}
    assert read("pad_frames_skipped_pct", ev_of(before, after)) == \
        pytest.approx(100.0)
    after = {"pad_frames": 64 + 128, "pad_frames_skipped": 64 + 32}
    assert read("pad_frames_skipped_pct", ev_of(before, after)) == \
        pytest.approx(25.0)
    # the repeats encoded and dropped (a program before PR 34's loop
    # with the counter registered): 0, not "not measured"
    after = {"pad_frames": 64 + 128, "pad_frames_skipped": 64}
    assert read("pad_frames_skipped_pct", ev_of(before, after)) == 0.0


@pytest.mark.parametrize("before,after", [
    ({"pad_frames": 0}, {"pad_frames": 128}),   # no such counter
    ({"pad_frames": 7, "pad_frames_skipped": 7},
     {"pad_frames": 7, "pad_frames_skipped": 7}),       # no repeat staged
    ({"stage": 1.0}, {"stage": 9.0}),           # a program without either
])
def test_skipped_share_is_not_measured_without_its_counters(before, after):
    assert read("pad_frames_skipped_pct", ev_of(before, after)) is None


def test_program_build_is_the_first_snapshots_clock_in_seconds():
    ev = ev_of({"program_build": 41250.0, "programs_built": 1},
               {"program_build": 41250.0, "programs_built": 1})
    assert read("program_build_s", ev) == pytest.approx(41.25)
    # the window's growth is not it: a program built inside the window
    # would be a fault `correct` reports, not set-up
    ev = ev_of({"program_build": 1000.0}, {"program_build": 9000.0})
    assert read("program_build_s", ev) == pytest.approx(1.0)
    # a tree without the clock: not measured, never 0
    assert read("program_build_s",
                ev_of({"dispatch": 5.0}, {"dispatch": 9.0})) is None


@pytest.mark.parametrize("name", ["bounded_dev_deblock_ms_per_frame",
                                  "bounded_deblock_kernel_roofline"])
def test_bounded_readers_are_silent_without_a_bound_or_a_profile(name):
    grew = ({"pad_frames_skipped": 0}, {"pad_frames_skipped": 64})
    still = ({"pad_frames_skipped": 64}, {"pad_frames_skipped": 64})
    # no wave of the window had a bound: not the filter inside a `while`
    assert read(name, ev_of(*still, profile={"busy_s": 1.0})) is None
    assert read(name, ev_of({"stage": 1.0}, {"stage": 2.0},
                            profile={"busy_s": 1.0})) is None
    # a bound, and no device profile (an untraced run, the rehearsal)
    assert read(name, ev_of(*grew, profile=None)) is None


def test_bounded_readers_call_the_accepted_ones(monkeypatch):
    """Neither holds a second copy of the stage sum or of the byte
    count: what the accepted reader gives is what they give."""
    from tvtbench import roofline_deblock, scope_reduce

    ev = ev_of({"pad_frames_skipped": 0}, {"pad_frames_skipped": 64},
               profile={"busy_s": 1.0}, cell="hd-serving-scenecut",
               width=1920, height=1080, device={"kind": "TPU v5 lite"})
    kernel = "jit(_encode_gop_single)/tvt.layout/while/body/" \
             "tvt.deblock/tvt_deblock_wavefront"
    got = {"scopes": {"tvt.deblock": 0.384, "tvt.residual": 1.0},
           "ops": [(0.0768, 256, kernel), (0.5, 9, "fusion.1")],
           "busy_s": 5.0, "unscoped_s": 0.0}
    monkeypatch.setattr(scope_reduce, "scopes_of", lambda _ev: got)
    assert read("bounded_dev_deblock_ms_per_frame", ev) == \
        read("dev_deblock_ms_per_frame", ev) == pytest.approx(1.5)
    least = 256 * roofline_deblock.deblock_bytes(1080, 1920) / 819e9
    assert read("bounded_deblock_kernel_roofline", ev) == \
        read("deblock_kernel_roofline", ev) == \
        pytest.approx(100.0 * least / 0.0768)


def test_the_cell_is_its_two_parents_and_nothing_else():
    cell = Cell("hd-serving-scenecut", ROOT)
    serving = Cell("hd-serving-rd", ROOT)
    cut = Cell("hd-scenecut", ROOT)
    assert cell.chips == 1 and cell.traffic == cut.traffic
    same = ("resolution", "reduced", "rehearse_cpu")
    assert all(cell.config[k] == serving.config[k] for k in same)
    assert cell.config["expect_settings"] == dict(
        serving.config["expect_settings"], scenecut=40)
    for chips in ("1", "4"):
        assert cell.config["env_by_chips"][chips] == dict(
            serving.config["env_by_chips"][chips], TVT_SCENECUT="40")
    assert cell.config["guarantees"] == serving.config["guarantees"] \
        + cut.config["guarantees"][-2:]
    assert cell.config["reduced_detail"]["content"] == \
        cut.config["reduced_detail"]["content"]
    assert cell.config["psnr_floor_db"] >= serving.config["psnr_floor_db"]
    assert "psnr_floor_measured" in cell.config
    names = {m["name"] for m in cell.per_layer}
    new = {"pad_frames_skipped_pct", "program_build_s",
           "bounded_dev_deblock_ms_per_frame",
           "bounded_deblock_kernel_roofline"}
    assert new <= names
    # the parents' own rows stay theirs
    assert not {"scenecut_ms_per_frame", "pad_frames_pct",
                "dev_deblock_ms_per_frame",
                "deblock_kernel_roofline"} & names
    assert {m["name"] for m in serving.per_layer} & new == \
        {"program_build_s"}
    assert {m["name"] for m in cut.per_layer} & new == \
        {"program_build_s", "pad_frames_skipped_pct"}
    assert {m["name"] for m in cell.end_to_end} == {
        "frames_per_s", "kbit_per_frame", "psnr_y_db", "setup_s"}
    bench = cell.bench
    assert len(bench["workloads"]) == 8 and len(bench["configs"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_rehearse_serving_scenecut_cell_traced(tmp_path):
    """The control flow of the cell on the CPU, and from the kept
    evidence (a rehearsal prints no value): the serving settings are
    live, three cuts a job became GOP starts, every staged repeat was
    skipped, and the set-up clock had run before the window opened."""
    keep = tmp_path / "keep"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    env.pop("XLA_FLAGS", None)      # (a session that forced CPU devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "hd-serving-scenecut", "--seed", str(2**31 + 39),
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
    assert {"pad_frames_skipped_pct", "program_build_s",
            "stage_ms_per_frame", "job_fixed_ms"} <= set(line["measured"])
    # no device plane in a CPU profile: device_trace metrics stay out
    assert not {"bounded_dev_deblock_ms_per_frame",
                "bounded_deblock_kernel_roofline"} & set(line["measured"])
    ev = json.loads((keep / "evidence.json").read_text())
    jobs = len(ev["jobs"])
    after, before = ev["snapshot"]["after"], ev["snapshot"]["before"]
    grew = {k: after[k] - before[k] for k in
            ("scene_cuts", "pad_frames", "pad_frames_skipped", "waves",
             "programs_built", "program_build")}
    # 16-frame clips, 2-frame GOPs: 3 + 1 + 3 + 2 GOPs, each staged to
    # 2 frames (at 128x128 and QP 25 some of them leave the sparse
    # budgets, which a 1080p GOP of this content does not: PERF.md)
    assert grew["scene_cuts"] == 3 * jobs and grew["waves"] == 9 * jobs
    assert grew["pad_frames_skipped"] == grew["pad_frames"] == 2 * jobs
    # nothing was set up inside the window; the warm-up job's
    # executables were, before it (the bounded serving GOP program,
    # and at this size the dense fallback's re-wording)
    assert grew["programs_built"] == 0 and grew["program_build"] == 0
    assert before["programs_built"] >= 1 and before["program_build"] > 0
    assert read("pad_frames_skipped_pct", ev) == pytest.approx(100.0)
    assert read("program_build_s", ev) == \
        pytest.approx(before["program_build"] / 1e3)
