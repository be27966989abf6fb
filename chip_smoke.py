"""Chip smoke: the transcode path through the real daemon, on the TPU.

    python chip_smoke.py [--seed N]

Drives the system's main path once, the way an operator would: source
files dropped in the watch folder of `python -m thinvids_tpu.cli
coordinator` (watcher -> coordinator -> LocalExecutor -> GopShardEncoder
on the local device mesh -> native CAVLC pack -> MP4 in the library),
with default encode settings (GOP 32, QP 27, CQP) and the two admission
settings a small host needs (TVT_MIN_IDLE_WORKERS=0,
TVT_PIPELINE_WORKER_COUNT=2 — with the defaults a one-chip host has 2
scheduler slots against `min_idle_workers` 4 and never admits a job).

Jobs, content generated from `--seed` (tools/pan.make_frames' diagonal pan):
two 1920x1080 160-frame clips (the second re-uses every compiled
shape), one 3840x2160 64-frame clip and, on a host with more than one
chip, the 2160p clip again through `POST /add_job` with `sfe_bands` =
the chip count (the split-frame path, whose collectives cross chips).

Every job must end `done` with `parts_retried == 0`, decode in
cv2.VideoCapture to the source's frame count, and hold a PSNR-Y floor.
The evidence is taken from the serving process: platform, device kind
and count from the daemon's own heartbeat (`/nodes_data`), the stage
counters and the motion search that ran (`pallas`) from
`/metrics_snapshot`, and no give-way message (retry, replan, packer or
transfer degrade) in the daemon's log or activity feed. After the
daemon has exited, a second child under JAX_PLATFORMS=cpu encodes the
first GOP of each compared job with the same settings and the bytes
must equal the chip's — the design's claim that kernel, XLA mirror and
host packer are integer-exact.

This process never imports jax: a chip belongs to one process, and the
daemon is it. With no TPU the smoke FAILS. The only other mode is
`--platform cpu --tiny` (small frames, 2-frame GOPs, the XLA mirror
accepted), a rehearsal of the control flow for tier-1 and for the
builder before chip time is spent.

The last two lines of stdout are JSON: the summary (its first field is
the platform), then `{"ok": true, "device": {...}}`. On any failed
requirement nothing is printed to stdout, the reasons go to stderr and
the exit code is 1. Wall times in the summary are information for the
next issue, not metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".smoke_work")

#: PSNR-Y floor (dB) at the default QP 27, fixed from the first good
#: runs (measured values are in CHANGES.md, PR 21)
PSNR_FLOOR = 34.0

#: any of these in the daemon's log or activity feed means the path
#: looked healthy by giving way somewhere
GIVE_WAY = [re.compile(p) for p in (
    r"copy_to_host_async rejected",
    r"falling back to threaded pack",
    r"pack sidecar pool broke",
    r"native packer unavailable",
    r"replanning frames",
    r"attempt \d+ failed, retrying",
    r"device metrics unavailable",
    r"cannot count devices",
)]

COUNTERS = ("waves", "dense_fallback_waves", "fetch_shards", "d2h_bytes",
            "h2d_bytes", "sfe_frames")


class SmokeFailure(Exception):
    """A requirement of the smoke did not hold."""


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# CPU reference leg (child process, JAX_PLATFORMS=cpu)
# ---------------------------------------------------------------------------

def cpu_leg(spec_path: str) -> int:
    """Encode GOP 0 of each compared job on the CPU with the job's own
    settings and compare its NAL units with the chip's MP4. Prints one
    JSON list on stdout."""
    with open(spec_path, encoding="utf-8") as fp:
        spec = json.load(fp)

    from thinvids_tpu.core.devices import (configure_compile_cache,
                                           force_cpu_devices)

    force_cpu_devices(max(1, max(int(c["sfe_bands"])
                                 for c in spec["compare"])))
    configure_compile_cache()

    import jax

    from thinvids_tpu.core.config import get_settings, overlay_job_settings
    from thinvids_tpu.core.types import SegmentPlan, concat_segments
    from thinvids_tpu.ingest.decode import open_video
    from thinvids_tpu.io.mp4 import read_mp4, split_annexb
    from thinvids_tpu.parallel.dispatch import (default_mesh,
                                                make_shard_encoder)
    from thinvids_tpu.parallel.planner import plan_encode

    assert jax.default_backend() == "cpu", jax.default_backend()
    out = []
    for c in spec["compare"]:
        bands = int(c["sfe_bands"])
        settings = overlay_job_settings(
            get_settings(), {"sfe_bands": bands} if bands else {})
        with open_video(c["source"]) as source:
            meta = source.meta
            # the chip's GOP grid (it depends on the chip count), one
            # GOP of it; a mesh no wider than that GOP needs
            gop0 = plan_encode(
                len(source), settings, num_devices=int(spec["devices"]),
                mb_height=meta.mb_height).segments.gops[0]
            mesh = default_mesh(jax.devices()[:max(1, bands)])
            enc = make_shard_encoder(meta, settings, mesh)
            enc.plan_override = SegmentPlan(
                gops=(gop0,), num_devices=enc.num_devices,
                frames_per_gop=int(settings.gop_frames))
            t0 = time.time()
            cpu_nals = split_annexb(concat_segments(
                enc.encode(source[0:gop0.num_frames])))
        chip_nals = split_annexb(
            read_mp4(c["output"]).annexb_for(0, gop0.num_frames))
        diff = next((i for i, (a, b) in enumerate(zip(cpu_nals, chip_nals))
                     if a != b), None)
        if diff is None and len(cpu_nals) != len(chip_nals):
            diff = min(len(cpu_nals), len(chip_nals))
        out.append({
            "job": c["job"], "frames_compared": gop0.num_frames,
            "nals": len(chip_nals),
            "bytes": sum(len(n) for n in chip_nals),
            "identical": diff is None, "first_differing_nal": diff,
            "cpu_encode_s": round(time.time() - t0, 1),
        })
    print(json.dumps(out), flush=True)
    return 0


# ---------------------------------------------------------------------------
# the jax-free parent
# ---------------------------------------------------------------------------

def call(base: str, path: str, body: dict | None = None, timeout: float = 10):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(base + path, data=data,
                                 method="POST" if data else "GET")
    if data:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def versions() -> dict:
    from importlib import metadata

    out = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy", "opencv-python-headless",
                "opencv-python"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            pass
    return out


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except FileNotFoundError:
        return 0


def write_clip(path: str, frames, w: int, h: int) -> None:
    from thinvids_tpu.core.types import VideoMeta
    from thinvids_tpu.io.y4m import write_y4m

    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=len(frames))
    write_y4m(path, meta, frames)


def check_output(path: str, frames) -> tuple[int, float]:
    """(frames cv2.VideoCapture decodes, mean PSNR-Y over the frames
    both sides have — tools.metrics' definition). The capture hands
    back the decoder's own planes (CONVERT_RGB off): a BGR round trip
    would rescale luma by the colour range it assumes."""
    import cv2

    from thinvids_tpu.tools.metrics import psnr

    # (raw mode warns "yuv420p ... treated as 8UC1" once per frame)
    cv2.utils.logging.setLogLevel(cv2.utils.logging.LOG_LEVEL_ERROR)
    h, w = frames[0].height, frames[0].width
    cap = cv2.VideoCapture(path)
    cap.set(cv2.CAP_PROP_CONVERT_RGB, 0)
    n, per_frame = 0, []
    try:
        while True:
            ok, raw = cap.read()
            if not ok:
                break
            if n < len(frames):     # luma leads the planes
                per_frame.append(psnr(frames[n].y, raw.reshape(-1, w)[:h]))
            n += 1
    finally:
        cap.release()
    return n, round(sum(per_frame) / max(1, len(per_frame)), 2)


def give_way_lines(lines) -> list[str]:
    return [ln.strip()[:300] for ln in lines
            if any(p.search(ln) for p in GIVE_WAY)]


class Daemon:
    """The one chip-holding child: `cli coordinator` with the default
    encode settings, its output captured to a file."""

    def __init__(self, env: dict, log_path: str) -> None:
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            port = sk.getsockname()[1]
        self.base = f"http://127.0.0.1:{port}"
        self.log_path = log_path
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "thinvids_tpu.cli", "coordinator",
             "--host", "127.0.0.1", "--port", str(port),
             "--state-dir", os.path.join(WORK, "state"),
             "--watch-dir", os.path.join(WORK, "watch"),
             "--output-dir", os.path.join(WORK, "library"),
             "--scan-interval", "0.5"],
            cwd=REPO, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    def alive(self) -> None:
        if self.proc.poll() is not None:
            raise SmokeFailure(
                f"daemon exited with code {self.proc.returncode}:\n"
                + self.log_tail())

    def log_lines(self) -> list[str]:
        with open(self.log_path, encoding="utf-8", errors="replace") as fp:
            return fp.readlines()

    def log_tail(self, n: int = 40) -> str:
        return "".join(ln[:400].rstrip("\n") + "\n"
                       for ln in self.log_lines()[-n:])

    def wait_device(self, deadline: float) -> dict:
        """The daemon's own agent row once it has sampled its devices:
        {"platform", "kind", "count"} as jax reported them there."""
        while time.time() < deadline:
            self.alive()
            try:
                nodes = call(self.base, "/nodes_data")["nodes"]
            except (urllib.error.URLError, ConnectionError, OSError):
                nodes = []
            for node in nodes:
                if node.get("devices", 0) >= 1:
                    return {"platform": node["platform"],
                            "kind": node["device_kind"],
                            "count": node["devices"]}
            time.sleep(0.5)
        raise SmokeFailure("daemon never reported a device:\n"
                           + self.log_tail())

    def wait_job(self, name: str, deadline: float) -> dict:
        job = None
        while time.time() < deadline:
            self.alive()
            jobs = [j for j in
                    call(self.base, f"/jobs?search={name}")["jobs"]
                    if os.path.basename(j["input_path"]) == f"{name}.y4m"]
            job = jobs[0] if jobs else None
            if job and job["status"] in ("done", "failed", "rejected",
                                         "stopped"):
                return job
            time.sleep(0.5)
        snap = call(self.base, "/metrics_snapshot")
        raise SmokeFailure(
            f"job {name} not done by its deadline: status "
            f"{job['status'] if job else 'never submitted'}, stage "
            f"{job.get('heartbeat_stage') if job else '-'} "
            f"({job.get('heartbeat_note') if job else '-'}); scheduler "
            f"wait reason: {snap['scheduler']['wait_reason']!r}\n"
            + self.log_tail())

    def stop(self) -> int | None:
        """SIGTERM, then the exit code (None if it had to be killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        code = self.proc.poll()
        self.kill()
        return code

    def kill(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self._log.close()


def run(args) -> dict:
    """The whole smoke. A job that cannot finish, or a daemon that
    dies, stops it at once (SmokeFailure); every other requirement
    that does not hold is collected, so one run on the chip reports
    all of them — and raised together at the end."""
    from thinvids_tpu import native as native_mod
    from thinvids_tpu.core.devices import DEFAULT_COMPILE_CACHE
    from thinvids_tpu.tools.pan import make_frames

    t_start = time.time()
    t_limit = t_start + args.time_limit
    tiny = args.tiny
    problems: list[str] = []

    def require(ok: bool, problem: str) -> None:
        if not ok:
            log(f"PROBLEM: {problem}")
            problems.append(problem)

    # (name, width, height, frames): 160 frames = 5 GOPs, five
    # one-GOP waves on one chip; hd_b re-uses hd_a's compiled shape
    clips = [("hd_a", 1920, 1080, 160), ("hd_b", 1920, 1080, 160),
             ("uhd", 3840, 2160, 64)]
    if tiny:    # one frame size: the rehearsal's cost is its compiles
        clips = [("hd_a", 128, 64, 6), ("hd_b", 128, 64, 6),
                 ("uhd", 128, 64, 4)]

    shutil.rmtree(WORK, ignore_errors=True)
    for sub in ("src", "watch", "library", "state"):
        os.makedirs(os.path.join(WORK, sub))
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_COMPILE_CACHE
    cache = {"dir": cache_dir, "entries_before": cache_entries(cache_dir)}
    so = native_mod._SO     # named by the source's content hash
    native = {"source_sha256_16": native_mod.source_hash(),
              "artifact": os.path.relpath(so, REPO),
              "built_this_run": not os.path.exists(so)}

    env = dict(os.environ, PYTHONPATH=REPO, TVT_MIN_IDLE_WORKERS="0",
               TVT_PIPELINE_WORKER_COUNT="2")
    if args.platform == "cpu":
        env.update(JAX_PLATFORMS="cpu", TVT_GOP_FRAMES="2")
    daemon = Daemon(env, os.path.join(WORK, "daemon.log"))
    try:
        device = daemon.wait_device(min(t_limit, time.time() + 180))
        log(f"daemon reports {device}")
        if device["platform"] != args.platform:
            raise SmokeFailure(
                f"the daemon runs on {device['platform']!r} "
                f"({device['kind']} x{device['count']}), the smoke needs "
                f"{args.platform!r}: no accelerator, no result")
        devices = int(device["count"])

        sources: dict[str, tuple[str, list]] = {}
        jobs: list[dict] = []
        before = {k: 0 for k in COUNTERS}

        def finish(name: str, bands: int, frames, t0: float) -> None:
            nonlocal before
            job = daemon.wait_job(name, min(t_limit, t0 + 900))
            wall = time.time() - t0
            if job["status"] != "done":
                raise SmokeFailure(
                    f"job {name} ended {job['status']}: "
                    f"{job.get('failure_stage')}: "
                    f"{job.get('failure_reason') or job.get('reject_reason')}"
                    f"\n" + daemon.log_tail())
            snap = call(daemon.base, "/metrics_snapshot")
            now = {k: int(snap["stage_ms"].get(k, 0)) for k in COUNTERS}
            decoded, psnr = check_output(job["output_path"], frames)
            rec = {
                "name": name, "shape": [frames[0].width, frames[0].height],
                "frames": len(frames), "gops": job["parts_total"],
                "sfe_bands": bands, "status": job["status"],
                "parts_retried": job["parts_retried"],
                "wall_s": round(wall, 1),
                "output_bytes": job["output_bytes"],
                "decoded_frames": decoded, "psnr_y": psnr,
                "counters": {k: now[k] - before[k] for k in COUNTERS},
                "output": job["output_path"],
            }
            before = now
            jobs.append(rec)
            log(f"job {rec}")
            require(job["parts_retried"] == 0,
                    f"job {name} retried {job['parts_retried']} parts")
            gave = give_way_lines(call(
                daemon.base, f"/job_activity/{job['id']}")["lines"])
            require(not gave, f"job {name} gave way: {gave}")
            require(decoded == len(frames),
                    f"job {name}: cv2 decoded {decoded} frames, the "
                    f"source has {len(frames)}")
            require(psnr >= PSNR_FLOOR,
                    f"job {name}: PSNR-Y {psnr} dB is under the "
                    f"{PSNR_FLOOR} dB floor")

        # the watch-folder jobs, one at a time (so each wall time is
        # one job's): write outside the watch dir, rename in
        for i, (name, w, h, n) in enumerate(clips):
            frames = make_frames(n, w, h, seed=args.seed + i)
            src = os.path.join(WORK, "src", f"{name}.y4m")
            write_clip(src, frames, w, h)
            dropped = os.path.join(WORK, "watch", f"{name}.y4m")
            os.replace(src, dropped)
            sources[name] = (dropped, frames)
            finish(name, 0, frames, time.time())
        os.unlink(sources["hd_b"][0])       # not compared below

        snap = call(daemon.base, "/metrics_snapshot")
        if devices > 1:
            # the per-shard fetch must have engaged on the GOP waves
            shards = int(snap["stage_ms"].get("fetch_shards", 0))
            require(shards >= devices, f"fetch_shards {shards} < {devices} "
                                       f"devices: the mesh was not used")
            # split-frame job: one frame sharded over every chip — the
            # only path whose ppermute/psum collectives cross chips
            dropped, frames = sources["uhd"]
            sfe_src = os.path.join(WORK, "src", "uhd_sfe.y4m")
            os.link(dropped, sfe_src)
            sources["uhd_sfe"] = (sfe_src, frames)
            t0 = time.time()
            call(daemon.base, "/add_job",
                 {"input_path": sfe_src,
                  "settings": {"sfe_bands": devices}})
            finish("uhd_sfe", devices, frames, t0)
            require(jobs[-1]["counters"]["sfe_frames"] == len(frames),
                    "the sfe_bands job did not take the split-frame path")
            snap = call(daemon.base, "/metrics_snapshot")

        search = snap.get("motion_search")
        want = "pallas" if args.platform == "tpu" else "xla"
        require(search == want, f"motion search ran as {search!r}, "
                                f"expected {want!r}")
        events = [e["message"] for e in
                  call(daemon.base, "/activity?limit=2000")["events"]]
        cache["entries_after_daemon"] = cache_entries(cache_dir)
        code = daemon.stop()
        require(code == 0, f"daemon exit code after SIGTERM: {code}\n"
                           + daemon.log_tail())
        gave = give_way_lines(daemon.log_lines() + events)
        require(not gave, f"the daemon gave way: {gave}")
        require(os.path.exists(so), f"no native packer at {so}")
    finally:
        daemon.kill()

    # the chip is free again: CPU leg, first GOP of the first 1080p
    # clip, of the 2160p clip and of the split-frame job
    outputs = {j["name"]: j.pop("output") for j in jobs}
    spec = {"devices": devices, "compare": [
        {"job": j["name"], "source": sources[j["name"]][0],
         "output": outputs[j["name"]], "sfe_bands": j["sfe_bands"]}
        for j in jobs if j["name"] != "hd_b"]}
    spec_path = os.path.join(WORK, "cpu_leg.json")
    with open(spec_path, "w", encoding="utf-8") as fp:
        json.dump(spec, fp)
    leg = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--cpu-leg", spec_path],
        cwd=REPO, env=dict(env, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=sys.stderr,
        timeout=max(60.0, t_limit - time.time()))
    if leg.returncode != 0:
        raise SmokeFailure(f"CPU leg exited with {leg.returncode}")
    chip_vs_cpu = json.loads(leg.stdout.decode().strip().splitlines()[-1])
    log(f"chip vs cpu {chip_vs_cpu}")
    for c in chip_vs_cpu:
        require(c["identical"],
                f"job {c['job']}: the chip's first GOP differs from the "
                f"CPU's at NAL {c['first_differing_nal']} — the encode is "
                f"not bit-exact across backends")
    cache["entries_after"] = cache_entries(cache_dir)

    total = time.time() - t_start
    require(total <= args.time_limit, f"took {total:.0f} s, over the "
                                      f"{args.time_limit:.0f} s limit")
    summary = {
        "platform": device["platform"], "ok": not problems, "tiny": tiny,
        "seed": args.seed, "versions": versions(), "device": device,
        "motion_search": search, "jobs": jobs,
        "wall_s": {"first_job_including_compile": jobs[0]["wall_s"],
                   "warm_same_shape": jobs[1]["wall_s"],
                   "total": round(total, 1)},
        "chip_vs_cpu": chip_vs_cpu, "compile_cache": cache,
        "native_packer": native, "daemon_exit": code,
        "parent_imported_jax": "jax" in sys.modules,
    }
    if problems:
        log("summary of the failed run: " + json.dumps(summary))
        raise SmokeFailure(f"{len(problems)} requirement(s) failed:\n- "
                           + "\n- ".join(problems))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="content seed (default 0)")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="the platform the daemon must report; cpu is "
                         "accepted only with --tiny")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal: small frames, 2-frame GOPs")
    ap.add_argument("--time-limit", type=float, default=1200.0,
                    help="fail when the whole run takes longer (s)")
    ap.add_argument("--keep-work", action="store_true",
                    help="leave .smoke_work/ (sources, outputs, "
                         "daemon.log) behind for a post-mortem")
    ap.add_argument("--cpu-leg", metavar="SPEC", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.cpu_leg:
        return cpu_leg(args.cpu_leg)
    if (args.platform == "cpu") != args.tiny:
        ap.error("--platform cpu and --tiny go together: the smoke "
                 "proper needs a TPU")
    try:
        summary = run(args)
    except SmokeFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    except ImportError as exc:
        log(f"FAILED: chip_smoke.py drives the repo it sits in, and "
            f"that is not here ({exc})")
        return 1
    finally:
        if not args.keep_work:
            shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
