"""One run of one cell of the benchmark: the served path on the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A jax-free parent starts `python -m thinvids_tpu.cli coordinator` as its
one chip-holding child with the cell's configuration as `TVT_*`
environment, submits jobs as a client (`POST /add_job`), watches them
from the client side, checks the outputs and prints one JSON line:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown`
when traced). `--trace 0` gives the cell's end-to-end metrics, `--trace
1` its per-layer metrics. Everything a cell is made of is found by name
(tvtbench/spec.py); PERF.md says what each piece is for.

With no TPU the run fails and prints nothing. `--rehearse-cpu` (tiny
frames, 2-frame GOPs, the XLA mirror, virtual devices for a four-chip
cell) rehearses the control flow on a machine without a chip; it
prints which metrics it could compute and none of their values.
"""

import time

T_PROCESS_START = time.time()

import argparse         # noqa: E402
import json             # noqa: E402
import os               # noqa: E402
import shutil           # noqa: E402
import subprocess       # noqa: E402
import sys              # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

from tvtbench import (checks, evidence, mp4box, reference,  # noqa: E402
                      sources)
from tvtbench.daemon import BenchFailure, Daemon            # noqa: E402
from tvtbench.loadgen import Uploader                       # noqa: E402
from tvtbench.spec import Cell, load_json, load_module      # noqa: E402


#: give up when a run takes longer: the contract allows a first run,
#: which compiles, 1200 s
TIME_LIMIT_S = 1150.0


def log(msg):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def cache_entries(path):
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except FileNotFoundError:
        return 0


class Run:
    """State of one run, in the order things happen."""

    def __init__(self, args):
        self.args = args
        self.cell = Cell(args.workload, ROOT)
        self.rehearsal = args.rehearse_cpu
        cfg, mix = self.cell.config, self.cell.traffic
        res = dict(cfg["resolution"])
        self.gop = int(cfg["expect_settings"]["gop_frames"])
        scale = 1.0
        if self.rehearsal:
            tiny = cfg["rehearse_cpu"]
            res.update(width=tiny["width"], height=tiny["height"])
            scale = tiny["gop_frames"] / self.gop
            self.gop = int(tiny["gop_frames"])
        self.width, self.height = int(res["width"]), int(res["height"])
        # the same GOP counts at the rehearsal's GOP length
        self.frames = {k: max(self.gop, int(mix[k] * scale)) for k in
                       ("frames_per_clip", "warmup_frames", "traced_frames")}
        self.work = os.path.join(ROOT, ".smoke_work", "benchmark",
                                 self.cell.name)
        self.cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or os.path.join(ROOT, ".jax_cache")
        self.problems = []
        self.first_out = {}         # source path -> kept output
        self.digests = {}           # (source, settings) -> first digest
        self.failed_jobs = set()
        self.memory_peak = 0
        self.mirror = None

    def require(self, ok, problem, job=None):
        if not ok:
            log(f"PROBLEM: {problem}")
            self.problems.append(problem)
            if job is not None:
                self.failed_jobs.add(job["name"])

    # -- set-up --------------------------------------------------------

    def prepare_dirs(self):
        os.makedirs(self.work, exist_ok=True)
        for sub in ("jobs", "library", "state", "profiles"):
            shutil.rmtree(os.path.join(self.work, sub), ignore_errors=True)
            os.makedirs(os.path.join(self.work, sub))
        os.makedirs(os.path.join(self.work, "src"), exist_ok=True)

    def start_daemon(self):
        cfg = self.cell.config
        extra = dict(cfg["env_by_chips"][str(self.cell.chips)])
        if self.rehearsal:
            extra.update(JAX_PLATFORMS="cpu", TVT_GOP_FRAMES=str(self.gop))
            if self.cell.chips > 1:
                extra["XLA_FLAGS"] = ("--xla_force_host_platform_device_"
                                      f"count={self.cell.chips}")
        self.env = dict(os.environ, PYTHONPATH=ROOT, **extra)
        self.daemon = Daemon(ROOT, self.work, self.env,
                             os.path.join(self.work, "daemon.log"))

    def make_sources(self):
        """The cell's one source, and the prefixes of it that warm-up
        and the traced job use where they are shorter."""
        mix = self.cell.traffic
        n = self.frames["frames_per_clip"]
        stem = (f"{mix['generator']}-{self.width}x{self.height}-"
                f"s{self.args.seed}")
        src = os.path.join(self.work, "src")
        # one seed's clips at a time: a check runs many seeds
        for old in os.listdir(src):
            if not old.startswith(stem + "-"):
                os.unlink(os.path.join(src, old))
        self.source = os.path.join(src, f"{stem}-{n}f.y4m")
        made = sources.write_clip(
            self.source, self.cell.generator, mix["generator_params"], n,
            self.width, self.height, self.args.seed)
        self.clips = {n: self.source}
        for k in (self.frames["warmup_frames"], self.frames["traced_frames"]):
            if k not in self.clips:
                path = os.path.join(src, f"{stem}-{k}f.y4m")
                sources.cut_prefix(self.source, path, k, self.width,
                                   self.height)
                self.clips[k] = path
        log(f"source {os.path.basename(self.source)} "
            f"{'generated' if made else 'found'}")

    def start_mirror(self):
        """The XLA mirror's IDR + first P frame, in a CPU child beside
        the daemon's start-up (tvtbench/mirror_child.py)."""
        self.mirror_out = os.path.join(self.work, "mirror.nals")
        if os.path.exists(self.mirror_out):
            os.unlink(self.mirror_out)
        bands = int(self.cell.traffic["job_settings"].get("sfe_bands", 0))
        env = dict(self.env, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        with open(os.path.join(self.work, "mirror.log"), "wb") as lg:
            self.mirror = subprocess.Popen(
                [sys.executable,
                 os.path.join(BENCH_DIR, "tvtbench", "mirror_child.py"),
                 self.source, self.mirror_out, str(bands)],
                cwd=ROOT, env=env, stdout=lg, stderr=subprocess.STDOUT)

    def wait_mirror(self, deadline):
        try:
            code = self.mirror.wait(timeout=max(1.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            self.mirror.kill()
            self.mirror.wait()
            code = "timeout"
        self.require(code == 0, f"the XLA mirror child ended {code} "
                                f"(see {self.work}/mirror.log)")

    def check_device(self, deadline):
        dev = self.daemon.wait_device(deadline)
        log(f"daemon reports {dev}")
        want = "cpu" if self.rehearsal else "tpu"
        if dev["platform"] != want or dev["count"] != self.cell.chips:
            raise BenchFailure(
                f"the daemon runs on {dev['platform']!r} ({dev['kind']} "
                f"x{dev['count']}); cell {self.cell.name} needs {want!r} "
                f"x{self.cell.chips}: no result")
        self.device = dev

    def check_settings(self):
        live = self.daemon.get("/settings")["settings"]
        self.ring = int(live["trace_ring_spans"])
        for key, want in self.cell.config["expect_settings"].items():
            if self.rehearsal and key == "gop_frames":
                continue
            self.require(live.get(key) == want,
                         f"the daemon runs {key}={live.get(key)!r}, the "
                         f"configuration states {want!r}")

    def warm_up(self, deadline):
        """One job per program shape of the cell (its traffic has one),
        through the same door as the window's jobs."""
        k = self.frames["warmup_frames"]
        job = self.uploader.run_one(
            self.clips[k], k, self.cell.traffic["job_settings"], deadline,
            tag="warm")
        self.job_done(job, in_window=False)

    # -- the window ----------------------------------------------------

    def plan(self, k):
        """(source, frames, settings) of the window's k-th job. In a
        traced run the third job carries `profile_dir`: steady state,
        never the first."""
        settings = dict(self.cell.traffic["job_settings"])
        n = self.frames["frames_per_clip"]
        if self.args.trace and k == 2:
            n = self.frames["traced_frames"]
            settings["profile_dir"] = os.path.join(self.work, "profiles")
        return self.clips[n], n, settings

    def tick(self):
        """About once a second: the device memory the daemon's own
        agent sampled (summed over its devices, so per chip it is the
        mean; the bands of a split frame are the same size)."""
        status, snap = self.daemon.call("/metrics_snapshot")
        if status != 200:
            # the route answers 500 while the first job's thread is
            # still importing parallel/dispatch (PERF.md, Open questions)
            return
        for row in snap["metrics"].values():
            used = int(row.get("hbm_used_bytes", 0) or 0)
            self.memory_peak = max(self.memory_peak,
                                   used // max(1, self.device["count"]))

    def job_done(self, job, in_window=True):
        """What is checked of every job as it comes back; its output is
        deleted unless it is the first of its source."""
        rec = job["record"]
        ok = rec["status"] == "done"
        self.require(ok, f"job {job['name']} ended {rec['status']}: "
                         f"{rec.get('failure_stage')}: "
                         f"{rec.get('failure_reason') or rec.get('reject_reason')}",
                     job)
        lines = self.daemon.get(f"/job_activity/{job['id']}")["lines"]
        gave = checks.give_way_lines(lines)
        self.require(not gave, f"job {job['name']} gave way: {gave}", job)
        self.require(int(rec.get("parts_retried", 0)) == 0,
                     f"job {job['name']} retried {rec.get('parts_retried')} "
                     f"parts", job)
        if self.args.trace and in_window:
            job["trace"] = evidence.fetch_trace(self.daemon, job["id"],
                                                 self.ring)
        if ok:
            out = rec["output_path"]
            job["video_bytes"], digest = mp4box.video_digest(out)
            group = (job["source"], json.dumps(
                {k: v for k, v in job["settings"].items()
                 if k != "profile_dir"}, sort_keys=True))
            first = self.digests.setdefault(group, digest)
            self.require(first == digest,
                         f"job {job['name']}: video bytes differ from the "
                         f"first output of the same source and settings",
                         job)
            if in_window and job["source"] not in self.first_out:
                self.first_out[job["source"]] = (out, job["frames"])
            else:
                os.unlink(out)
        os.unlink(job["input"])

    # -- after the window ----------------------------------------------

    def check_outputs(self):
        """Decode one output per distinct source in libavcodec; the
        mirror's NALs against the window's first output."""
        floor = float(self.cell.config["psnr_floor_db"])
        psnrs = []
        for source, (out, frames) in self.first_out.items():
            decoded, psnr = reference.decode_and_compare(
                out, source, frames, self.width, self.height)
            self.require(decoded == frames,
                         f"{os.path.basename(out)}: libavcodec decoded "
                         f"{decoded} frames, the source has {frames}")
            self.require(psnr >= floor or self.rehearsal,
                         f"{os.path.basename(out)}: PSNR-Y {psnr:.2f} dB "
                         f"under the {floor} dB floor")
            psnrs.append(psnr)
            if source == self.source:
                self.require(
                    checks.mirror_equal(out, self.mirror_out),
                    "the chip's IDR + first P frame differ from the XLA "
                    "mirror's: the encode is not bit-exact across backends")
            os.unlink(out)
        return psnrs

    def reduce_profile(self):
        """The traced job's .xplane.pb -> busy union, per-op sums, gaps
        (tvtbench/profile_reduce.py, in a CPU child: the parent stays
        off jax, and the daemon has given the chip back)."""
        found = [os.path.join(d, f) for d, _s, fs in
                 os.walk(os.path.join(self.work, "profiles"))
                 for f in fs if f.endswith(".xplane.pb")]
        if not found:
            log("no .xplane.pb was written: device metrics not measured")
            return None
        out = os.path.join(self.work, "profile.json")
        with open(os.path.join(self.work, "reduce.log"), "wb") as lg:
            code = subprocess.run(
                [sys.executable,
                 os.path.join(BENCH_DIR, "tvtbench", "profile_reduce.py"),
                 found[0], out],
                cwd=ROOT, env=dict(self.env, JAX_PLATFORMS="cpu"),
                stdout=lg, stderr=subprocess.STDOUT, timeout=200).returncode
        if code != 0:
            log(f"profile reduction ended {code} (see {self.work}/reduce.log)")
            return None
        reduced = load_json(out)
        log(f"profile of {os.path.getsize(found[0])} bytes reduced: "
            f"{len(reduced['device_planes'])} device plane(s)")
        if self.args.keep:
            shutil.copy(found[0], os.path.join(self.args.keep,
                                               "traced_job.xplane.pb"))
        # no op ran on a device plane (the CPU rehearsal): every device
        # metric is "not measured", never 0
        return reduced if reduced["device_planes"] else None

    # -- the whole run -------------------------------------------------

    def run(self):
        args, mix = self.args, self.cell.traffic
        t_limit = T_PROCESS_START + TIME_LIMIT_S
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
        if mix["submit"] != "add_job":
            raise BenchFailure(f"traffic {mix['name']}: no way to submit "
                               f"jobs called {mix['submit']!r}")
        self.prepare_dirs()
        self.start_daemon()
        try:
            self.make_sources()
            self.start_mirror()
            self.check_device(min(t_limit, time.time() + 180))
            self.check_settings()
            self.uploader = Uploader(
                self.daemon, os.path.join(self.work, "jobs"),
                on_tick=self.tick)
            self.warm_up(t_limit)
            self.wait_mirror(t_limit)
            entries_before = cache_entries(self.cache_dir)
            before = self.daemon.get("/metrics_snapshot")
            log(f"set-up took {time.time() - T_PROCESS_START:.1f} s; "
                f"window of {args.seconds} s opens")

            jobs, t_first, t_last = self.uploader.window(
                self.plan, args.seconds, int(mix["outstanding"]),
                self.job_done, t_limit)

            after = self.daemon.get("/metrics_snapshot")
            self.tick()
            entries_after = cache_entries(self.cache_dir)
            self.require(entries_after == entries_before,
                         f"the compile cache grew inside the window "
                         f"({entries_before} -> {entries_after} entries): "
                         f"a program compiled there")
            want = "xla" if self.rehearsal else "pallas"
            self.require(after.get("motion_search") == want,
                         f"motion search ran as "
                         f"{after.get('motion_search')!r}, expected {want!r}")
            psnrs = self.check_outputs()
            events = [e["message"] for e in
                      self.daemon.get("/activity?limit=2000")["events"]]
            code = self.daemon.stop()
            self.require(code == 0,
                         f"daemon exit code after SIGTERM: {code}")
            gave = checks.give_way_lines(self.daemon.log_lines() + events)
            self.require(not gave, f"the daemon gave way: {gave}")
        finally:
            self.daemon.kill()
            if self.mirror is not None and self.mirror.poll() is None:
                self.mirror.kill()
                self.mirror.wait()

        ev = {
            "cell": self.cell.name, "chips": self.cell.chips,
            "device": self.device, "width": self.width,
            "height": self.height, "rehearsal": self.rehearsal,
            "job_settings": mix["job_settings"],
            "setup_s": t_first - T_PROCESS_START,
            "window": {"t_first_submit": t_first, "t_last_done": t_last},
            "jobs": jobs, "psnr_y_db": psnrs,
            "snapshot": {"before": before["stage_ms"],
                         "after": after["stage_ms"]},
            "traced_job": next((j["name"] for j in jobs
                                if "profile_dir" in j["settings"]), None),
            "profile": self.reduce_profile() if args.trace else None,
        }
        if args.keep:
            with open(os.path.join(args.keep, "evidence.json"), "w",
                      encoding="utf-8") as fp:
                json.dump(ev, fp)
        return ev

    def result(self, ev):
        """The contract's line from the evidence."""
        kind, wanted = ("layer_metrics", self.cell.per_layer) \
            if self.args.trace else ("end_to_end", self.cell.end_to_end)
        metrics = {}
        for m in wanted:
            value = load_module(kind, m["name"]).read(ev)
            if value is None:
                log(f"{m['name']}: not measured")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        done = evidence.done_jobs(ev)
        log(f"{len(done)} of {len(ev['jobs'])} jobs done, "
            f"{evidence.frames_done(ev)} frames in "
            f"{evidence.window_s(ev):.2f} s (latency samples: {len(done)})")
        device = {"platform": self.device["platform"],
                  "kind": self.device["kind"],
                  "count": self.device["count"],
                  "memory_peak_bytes": self.memory_peak}
        line = {"correct": not self.problems,
                "attempted": len(ev["jobs"]),
                "failed": len(self.failed_jobs),
                "metrics": metrics, "device": device}
        if self.args.trace and ev["profile"]:
            prof = ev["profile"]
            device["busy_s"] = prof["busy_s"]
            device["window_s"] = prof["window_s"]
            line["breakdown"] = evidence.breakdown(ev)
        if self.rehearsal:
            # control flow only: which metrics could be computed, and
            # no value of any of them under its name
            line["rehearsal"] = True
            line["measured"] = sorted(metrics)
            line["metrics"] = {}
        return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="control-flow rehearsal without a chip")
    ap.add_argument("--keep", metavar="DIR",
                    help="leave the collected evidence (evidence.json: "
                         "jobs, spans, snapshots, reduced profile) and "
                         "the traced job's .xplane.pb in DIR")
    args = ap.parse_args(argv)
    try:
        if not os.path.isdir(os.path.join(ROOT, "thinvids_tpu")):
            raise BenchFailure(
                f"benchmark/run.py drives the repo it sits in, and "
                f"{ROOT}/thinvids_tpu is not there")
        run = Run(args)
        if args.seconds is None:
            args.seconds = float(run.cell.bench["run_seconds"])
        line = run.result(run.run())
    except BenchFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    if run.problems:
        log(f"{len(run.problems)} requirement(s) failed:\n- "
            + "\n- ".join(run.problems))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
