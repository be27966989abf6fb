"""The eleventh cell, `hd-screen` (PR 49): `serving-1080p` with Intra4x4
macroblocks in IDR pictures (`intra4x4`) on screen content. Its two
readers on canned evidence — among it a program without the counters
and without the stage, as the parent is — the generator's prefix, seed
and parameter rules, what the cell is made of, and a CPU rehearsal in
which the intra4x4 executable must serve every job and code Intra4x4
macroblocks in its IDR pictures."""

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


def test_intra4x4_share_is_4x4_over_coded_of_the_window():
    before = {"i_mbs_coded": 8160, "i_mbs_4x4": 3000}
    after = {"i_mbs_coded": 8160 * 9, "i_mbs_4x4": 3000 + 8 * 2937.6}
    assert read("intra4x4_mb_pct", ev_of(before, after)) \
        == pytest.approx(36.0)
    # the counters with no Intra4x4 macroblock chosen: 0, not "not
    # measured"
    after = {"i_mbs_coded": 9000, "i_mbs_4x4": 3000}
    assert read("intra4x4_mb_pct", ev_of(before, after)) == 0.0


@pytest.mark.parametrize("before,after", [
    ({"stage": 1.0}, {"stage": 9.0}),                   # the parent
    ({"i_mbs_coded": 5}, {"i_mbs_coded": 9}),           # one counter alone
    ({"i_mbs_coded": 7, "i_mbs_4x4": 2},
     {"i_mbs_coded": 7, "i_mbs_4x4": 2}),               # intra4x4 off
])
def test_intra4x4_share_is_not_measured_without_its_counters(before, after):
    assert read("intra4x4_mb_pct", ev_of(before, after)) is None


def test_stage_time_reads_the_intra4x4_scope_alone(monkeypatch):
    ev = ev_of({}, {}, profile={"frames": 256})
    scopes = {"tvt.intra4x4": 0.512, "tvt.intra": 0.07, "tvt.residual": 0.075}
    monkeypatch.setattr(scope_reduce, "scopes_of",
                        lambda ev: {"scopes": scopes, "busy_s": 3.0,
                                    "unscoped_s": 0.1, "stale": None})
    monkeypatch.setattr(
        scope_reduce.evidence, "profile_per_frame",
        lambda ev, seconds: 1e3 * seconds / ev["profile"]["frames"])
    assert read("dev_intra4x4_ms_per_frame", ev) == pytest.approx(2.0)
    # a program without the stage (the parent; the setting off): not
    # measured, and not 0
    del scopes["tvt.intra4x4"]
    assert read("dev_intra4x4_ms_per_frame", ev) is None
    monkeypatch.setattr(scope_reduce, "scopes_of", lambda ev: None)
    assert read("dev_intra4x4_ms_per_frame", ev) is None


def test_generator_prefix_seed_and_params():
    gen = load_module("generators", "screen")
    long = list(gen.planes(6, 320, 192, 2**31 + 9))
    short = list(gen.planes(2, 320, 192, 2**31 + 9))
    for a, b in zip(short, long):
        assert all(np.array_equal(p, q) and p.dtype == np.uint8
                   for p, q in zip(a, b))
    assert long[0][0].shape == (192, 320) and long[0][1].shape == (96, 160)
    other = next(iter(gen.planes(1, 320, 192, 1)))
    assert not np.array_equal(other[0], long[0][0])     # which glyphs
    assert np.array_equal(other[1], long[0][1])         # not the scene
    assert (other[0] != long[0][0]).mean() < 0.25
    # the traffic file names the generator's four numbers (with the
    # windows: five parameters in all, the issue's, not tuned)
    params = Cell("hd-screen", ROOT).traffic["generator_params"]
    assert params == {"windows": 5, "pane_pan": 2, "type_every": 2,
                      "scroll_px": 4}
    with pytest.raises(TypeError):
        next(iter(gen.planes(1, 320, 192, 1, grain=5.0)))
    # at 1920 wide: windows of the stated sizes off the macroblock grid,
    # glyphs and pitch no multiple of 4, the scroll inside the search
    for k in range(params["windows"]):
        x0, y0, w, h, bar = gen.window(k, 1920, 1080)
        assert x0 % 16 and y0 % 16 and bar == 28
        assert 480 <= w <= 1100 and 300 <= h <= 700
    assert gen.PITCH_X % 4 and gen.PITCH_Y % 4
    assert (gen.GLYPH_W, gen.GLYPH_H) == (7, 11) and len(gen.FONT) == 96
    assert 0.38 < gen.FONT.mean() < 0.46
    assert params["scroll_px"] <= 4 and params["pane_pan"] <= 4
    assert gen.scrolled(57, params["scroll_px"]) == 4 * gen.PITCH_Y
    # the 128x128 rehearsal clip still holds a window with text, and
    # something moves in it
    small = list(gen.planes(3, 128, 128, 5, **params))
    bare = list(gen.planes(3, 128, 128, 5, **dict(params, windows=0)))
    assert all(not np.array_equal(a[0], b[0]) for a, b in zip(small, bare))
    assert len(np.unique(small[0][0])) > 60
    assert not np.array_equal(small[0][0], small[2][0])


def test_the_cell_is_the_serving_cell_plus_intra4x4_and_content():
    cell = Cell("hd-screen", ROOT)
    serving = Cell("hd-serving-rd", ROOT)
    assert cell.chips == 1
    same = ("resolution", "reduced", "psnr_floor_db", "rehearse_cpu")
    assert all(cell.config[k] == serving.config[k] for k in same)
    assert cell.config["expect_settings"] == dict(
        serving.config["expect_settings"], intra4x4=True)
    for chips in ("1", "4"):
        assert cell.config["env_by_chips"][chips] == dict(
            serving.config["env_by_chips"][chips], TVT_INTRA4X4="1")
    assert cell.config["guarantees"][:-1] == serving.config["guarantees"]
    assert "Intra4x4 block" in cell.config["guarantees"][-1]
    for key in ("content", "generator_params", "decision", "searched_modes",
                "schedule", "fps"):
        assert key in cell.config["assumed"]
    assert cell.config["assumed"]["architecture"] is None
    config = [c for c in cell.bench["configs"]
              if c["name"] == "serving-1080p-screen"][0]
    assert len(config["source"]) <= 200
    assert config["source"] == cell.config["source"]
    mine, theirs = cell.traffic, serving.traffic
    for key in ("frames_per_clip", "outstanding", "submit", "job_settings",
                "traced_frames"):
        assert mine[key] == theirs[key]
    assert mine["warmup_frames"] == mine["frames_per_clip"]
    assert mine["generator"] == "screen"
    names = {m["name"] for m in cell.per_layer}
    new = {"intra4x4_mb_pct", "dev_intra4x4_ms_per_frame"}
    assert new <= names
    assert {m["name"] for m in serving.per_layer} | new == names
    assert {m["name"] for m in cell.end_to_end} == {
        "frames_per_s", "kbit_per_frame", "psnr_y_db", "setup_s"}
    # appended after PR 45's entries (by name, not "the last": the
    # next PR appends after these)
    bench = cell.bench

    def place(entries, name):
        return [e["name"] for e in entries].index(name)

    assert place(bench["workloads"], "hd-screen") \
        == place(bench["workloads"], "hd-serving-crossing") + 1
    assert place(bench["configs"], "serving-1080p-screen") \
        == place(bench["configs"], "serving-1080p-action") + 1
    at = place(bench["per_layer"], "intra4x4_mb_pct")
    assert at == place(bench["per_layer"], "dev_p_intra_ms_per_frame") + 1
    assert bench["per_layer"][at + 1]["name"] == "dev_intra4x4_ms_per_frame"
    assert all(m["workloads"] == ["hd-screen"]
               and m["layer"] == "device program"
               for m in bench["per_layer"][at:at + 2])
    assert [m["moves"] for m in bench["per_layer"][at:at + 2]] \
        == ["kbit_per_frame", "frames_per_s"]
    for name in ("dev_deblock_ms_per_frame", "deblock_kernel_roofline",
                 "upload_ms_per_frame", "stage_copy_bytes_per_frame"):
        lists = bench["per_layer"][place(bench["per_layer"], name)]
        assert lists["workloads"][-1] == "hd-screen"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_rehearse_screen_cell_traced(tmp_path):
    """The control flow of the cell on the CPU, and from the kept
    evidence (a rehearsal prints no value): `intra4x4` is live, one
    executable serves the window, and the jobs' IDR pictures hold
    Intra4x4 macroblocks."""
    keep = tmp_path / "keep"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    env.pop("XLA_FLAGS", None)      # (a session that forced CPU devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "hd-screen", "--seed", str(2**31 + 49),
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
    assert {"intra4x4_mb_pct", "stage_ms_per_frame",
            "sparse_budget_fill_pct", "dense_fallback_waves"} \
        <= set(line["measured"])
    ev = json.loads((keep / "evidence.json").read_text())
    after, before = ev["snapshot"]["after"], ev["snapshot"]["before"]
    assert after["i_mbs_coded"] > before["i_mbs_coded"]
    assert after["i_mbs_4x4"] > before["i_mbs_4x4"]
    assert 0.0 < read("intra4x4_mb_pct", ev) <= 100.0
    # nothing was set up inside the window
    assert after["programs_built"] == before["programs_built"] >= 1
