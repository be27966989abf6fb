"""chip_smoke.py, rehearsed on the CPU.

The smoke proper needs a TPU (the driver runs it on one after the
tests). Tier-1 runs its explicit `--platform cpu --tiny` rehearsal —
the same control flow through the real `cli coordinator` daemon and the
CPU reference leg, at toy sizes — and checks that without that flag,
and without a TPU, it refuses to produce a result.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path):
    # one CPU device (conftest's 8-device XLA_FLAGS would add the
    # split-frame job and its compiles); the compile cache goes where
    # the environment says, not into the checkout
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=300)


def test_tiny_cpu_rehearsal_passes_with_the_contract_lines(tmp_path):
    proc = _run(["--platform", "cpu", "--tiny"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()

    # last line: exactly the driver's contract object
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert json.loads(lines[-1]) == {"ok": True, "device": device}

    # the line before: the summary, platform first
    summary = json.loads(lines[-2])
    assert next(iter(summary)) == "platform" and summary["platform"] == "cpu"
    assert summary["ok"] is True and summary["tiny"] is True
    assert summary["device"] == device
    assert summary["motion_search"] == "xla"
    assert summary["parent_imported_jax"] is False
    assert summary["daemon_exit"] == 0
    assert {"jax", "jaxlib", "numpy", "python"} <= set(summary["versions"])
    assert [j["name"] for j in summary["jobs"]] == ["hd_a", "hd_b", "uhd"]
    for job in summary["jobs"]:
        assert job["status"] == "done" and job["parts_retried"] == 0
        assert job["gops"] >= 2 and job["psnr_y"] > 25
        assert {"dense_fallback_waves", "fetch_shards", "d2h_bytes",
                "h2d_bytes"} <= set(job["counters"])
    assert [c["job"] for c in summary["chip_vs_cpu"]] == ["hd_a", "uhd"]
    assert all(c["identical"] for c in summary["chip_vs_cpu"])
    # the cache went where JAX_COMPILATION_CACHE_DIR said, and filled
    cache = summary["compile_cache"]
    assert cache["dir"] == str(tmp_path / "cache")
    assert cache["entries_before"] == 0 < cache["entries_after"]
    assert summary["native_packer"]["artifact"].endswith(
        summary["native_packer"]["source_sha256_16"] + ".so")
    assert not os.path.exists(os.path.join(REPO, ".smoke_work"))


def test_without_a_tpu_it_fails_and_prints_no_result(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 'tpu'" in proc.stderr
    assert not os.path.exists(os.path.join(REPO, ".smoke_work"))
