"""`--rehearse-cpu`: the whole control flow without a chip, on one
one-chip cell and on the four-chip cell (four virtual CPU devices); and
a configuration, a traffic mix, a generator and a per-layer metric
added as new files in a copy, with no file that was there edited."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT


def rehearse(root, cell, trace, tmp_path, seconds=3):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse-cpu"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=600)
    assert proc.returncode == 0, proc.stderr.decode()[-3000:]
    return json.loads(proc.stdout.decode().strip().splitlines()[-1])


def check_line(line, chips):
    assert line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    # no number under a metric's name from a CPU run
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "busy_s" not in line["device"]


def test_rehearse_one_chip_cell_end_to_end(tmp_path):
    line = rehearse(ROOT, "hd-shorts", 0, tmp_path)
    check_line(line, 1)
    assert line["measured"] == ["frames_per_s", "job_p50_s",
                                "kbit_per_frame", "psnr_y_db", "setup_s"]


def test_rehearse_four_chip_cell_traced(tmp_path):
    line = rehearse(ROOT, "uhd-sfe4-stream", 1, tmp_path)
    check_line(line, 4)
    assert {"job_fixed_ms", "pack_ms_per_frame", "fetch_shards_per_frame",
            "encode_stage_share_pct"} <= set(line["measured"])
    # nothing of the device from a CPU profile
    assert not {"device_idle_pct", "device_busy_ms_per_frame",
                "me_kernel_roofline"} & set(line["measured"])


def test_parent_never_imports_jax():
    code = ("import sys; sys.argv=['run.py','--help']; "
            "import runpy\n"
            "try:\n runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit: pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'"
            % os.path.join(BENCH_DIR, "run.py"))
    subprocess.run([sys.executable, "-c", code], check=True,
                   stdout=subprocess.DEVNULL, timeout=60)


def test_no_program_beside_the_benchmark_is_a_failure(tmp_path):
    """A directory that holds only BENCHMARK.json and `benchmark/`:
    non-zero exit, nothing on stdout."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", "hd-shorts", "--seed", "1", "--seconds", "2",
         "--trace", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == b""


def test_new_cell_from_new_files_only(tmp_path):
    """A later PR's move: new files + BENCHMARK.json entries."""
    root = tmp_path / "copy"
    root.mkdir()
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "thinvids_tpu"), root / "thinvids_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}

    config = json.load(open(os.path.join(
        BENCH_DIR, "configs", "library-1080p.json")))
    config["name"] = "library-720p"
    config["resolution"] = {"width": 1280, "height": 720, "fps": 30}
    config["rehearse_cpu"] = {"width": 64, "height": 64, "gop_frames": 2}
    (root / "benchmark/configs/library-720p.json").write_text(
        json.dumps(config))
    (root / "benchmark/traffic/pairs-2gop.json").write_text(json.dumps({
        "name": "pairs-2gop", "what": "two-GOP clips of flat grey",
        "generator": "flat", "generator_params": {"level": 90},
        "frames_per_clip": 64, "outstanding": 1, "submit": "add_job",
        "job_settings": {}, "warmup_frames": 64, "traced_frames": 64}))
    (root / "benchmark/generators/flat.py").write_text(
        "import numpy as np\n\n\n"
        "def planes(n, width, height, seed, level=128):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    y = np.full((height, width), level, np.uint8)\n"
        "    y[::7, ::5] = rng.integers(0, 255)\n"
        "    c = np.full((height // 2, width // 2), 128, np.uint8)\n"
        "    for _ in range(n):\n"
        "        yield y, c, c\n")
    (root / "benchmark/layer_metrics/waves_per_job.py").write_text(
        '"""executor: waves counted / jobs done."""\n\n'
        "from tvtbench import evidence\n\n\n"
        "def read(ev):\n"
        "    return evidence.stage_delta(ev, 'waves') "
        "/ len(evidence.done_jobs(ev))\n")
    bench["configs"].append({
        "name": "library-720p", "source": config["source"],
        "file": "benchmark/configs/library-720p.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "sd-pairs", "config": "library-720p",
        "traffic": "pairs-2gop", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "waves_per_job", "unit": "waves", "better": "lower",
        "source": "program_counter", "layer": "executor",
        "moves": "frames_per_s", "workloads": ["sd-pairs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    traced = rehearse(str(root), "sd-pairs", 1, tmp_path)
    check_line(traced, 1)
    assert "waves_per_job" in traced["measured"]
    assert "queue_wait_ms" not in traced["measured"]    # hd-shorts only
    plain = rehearse(str(root), "sd-pairs", 0, tmp_path)
    assert "frames_per_s" in plain["measured"]
    assert "job_p50_s" not in plain["measured"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "a file that was there was edited"
