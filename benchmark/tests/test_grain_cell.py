"""The sixth cell, `hd-grain` (PR 30): its two readers on canned
snapshots — among them a program that has no `sparse_*` counters, as
the parent of PR 30 has none — and a CPU rehearsal of the cell beside
the others, in which every GOP must leave the sparse budgets."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT
from tvtbench.spec import Cell, load_module


def ev_of(before, after, frames=128):
    return {"jobs": [{"frames": frames, "record": {"status": "done"}}],
            "snapshot": {"before": before, "after": after}}


def read(name, ev):
    return load_module("layer_metrics", name).read(ev)


def test_fill_is_the_fuller_budget_over_the_window():
    before = {"sparse_blocks_used": 10, "sparse_blocks_budget": 100,
              "sparse_values_used": 5, "sparse_values_budget": 100}
    after = {"sparse_blocks_used": 10 + 390, "sparse_blocks_budget": 300,
             "sparse_values_used": 5 + 160, "sparse_values_budget": 300}
    assert read("sparse_budget_fill_pct", ev_of(before, after)) == \
        pytest.approx(195.0)
    after["sparse_values_used"] = 5 + 500       # the values the fuller
    assert read("sparse_budget_fill_pct", ev_of(before, after)) == \
        pytest.approx(250.0)


@pytest.mark.parametrize("before,after", [
    ({"fetch": 1.0}, {"fetch": 9.0}),           # a program without them
    ({"sparse_blocks_used": 7, "sparse_blocks_budget": 9},
     {"sparse_blocks_used": 7, "sparse_blocks_budget": 9}),   # none moved
])
def test_fill_is_not_measured_without_counters(before, after):
    assert read("sparse_budget_fill_pct", ev_of(before, after)) is None


def test_dense_retry_is_per_done_frame():
    ev = ev_of({"dense_retry": 100.0}, {"dense_retry": 100.0 + 6400.0})
    assert read("dense_retry_ms_per_frame", ev) == pytest.approx(50.0)
    assert read("dense_retry_ms_per_frame", ev_of({}, {})) == 0.0


def test_the_cell_is_library_1080p_but_for_its_content():
    grain, backlog = Cell("hd-grain", ROOT), Cell("hd-backlog", ROOT)
    same = ("resolution", "expect_settings", "env_by_chips", "reduced",
            "rehearse_cpu")
    assert all(grain.config[k] == backlog.config[k] for k in same)
    assert grain.config["guarantees"][:len(backlog.config["guarantees"])] \
        == backlog.config["guarantees"]
    assert grain.traffic["generator"] == "grain"
    assert grain.traffic["generator_params"] == {"pan": 3, "sigma": 5.0}
    assert (grain.traffic["frames_per_clip"], grain.traffic["outstanding"],
            grain.traffic["warmup_frames"], grain.traffic["traced_frames"]) \
        == (128, 2, 128, 128)
    names = {m["name"] for m in grain.per_layer}
    assert {"dense_retry_ms_per_frame", "sparse_budget_fill_pct",
            "dense_fallback_waves", "me_kernel_roofline"} <= names
    assert "dense_retry_ms_per_frame" not in \
        {m["name"] for m in backlog.per_layer}
    assert "sparse_budget_fill_pct" in {m["name"] for m in backlog.per_layer}


def test_rehearse_grain_cell_traced(tmp_path):
    """The control flow of the cell on the CPU, and from the kept
    evidence (a rehearsal prints no value): every wave went dense, and
    the record has the halves of the retry."""
    keep = tmp_path / "keep"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    env.pop("XLA_FLAGS", None)      # (a session that forced CPU devices)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "hd-grain", "--seed", str(2**31 + 5), "--seconds",
         "3", "--trace", "1", "--rehearse-cpu", "--keep", str(keep)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=600)
    assert proc.returncode == 0, proc.stderr.decode()[-3000:]
    line = json.loads(proc.stdout.decode().strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["metrics"] == {}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3           # the traced job is the third
    assert line["device"]["platform"] == "cpu"
    assert {"dense_retry_ms_per_frame", "sparse_budget_fill_pct",
            "dense_fallback_waves", "d2h_bytes_per_frame",
            "pack_ms_per_frame", "job_fixed_ms"} <= set(line["measured"])
    ev = json.loads((keep / "evidence.json").read_text())
    assert read("dense_fallback_waves", ev) > 0
    assert read("sparse_budget_fill_pct", ev) > 100.0
    after, before = ev["snapshot"]["after"], ev["snapshot"]["before"]
    grew = {k: after[k] - before[k] for k in
            ("dense_retry", "dense_reencode", "dense_fetch", "waves",
             "dense_fallback_waves")}
    assert grew["waves"] == grew["dense_fallback_waves"] > 0
    assert grew["dense_reencode"] > 0 and grew["dense_fetch"] > 0
    assert grew["dense_retry"] == pytest.approx(
        grew["dense_reencode"] + grew["dense_fetch"], abs=0.05)
    spans = {s["name"] for j in ev["jobs"] if j.get("trace")
             for s in j["trace"]["spans"]}
    assert {"dense_reencode", "dense_fetch"} <= spans
