"""Benchmark: H.264 GOP (IDR + P) encode throughput on the current device.

Prints ONE JSON line:
  {"metric": ..., "value": fps, "unit": "fps", "vs_baseline": x, ...}

`value` is end-to-end 1080p fps through the production path: GOP-batched
wave dispatch over the mesh (thinvids_tpu/parallel/dispatch.py) + async
sparse level fetch + pooled host entropy pack (C++ CAVLC) + ordered
concat. `vs_baseline` is relative to real-time 30 fps — the reference's
per-node hardware encode operating point at 1080p
(/root/reference/worker/tasks.py:1558-1586); the reference publishes no
numbers (BASELINE.md), so 30 fps (1x real time) is the denominator.

Extra keys: `device_gop_fps` times the SAME GOP program device-side only
(comparable to `value`, unlike the old intra-only figure), `fps_2160p`
is the 4K end-to-end line (BASELINE config 3's resolution).
`host_gap_1080p` / `host_gap_2160p` pin the device→host boundary this
pipeline attacks: e2e fps ÷ device fps (1.0 = the host keeps up with
the encode engines, the split-frame-encoding literature's ideal), and
`d2h_bytes_per_frame` is the measured bulk-fetch traffic
(StageProfile's d2h_bytes counter over the fastest 1080p pass) — the
compact level-stream transfer must move this, and regressions show up
as a pinned number instead of anecdata.

For `value`, source frames are pre-staged in HBM before the timed
region (the design invariant: kernels run over HBM-resident YUV
planes). `fps_cold_1080p` drops that flattering boundary: the same clip
runs COLD through the production streaming path — y4m on disk →
range-seek decode → background staging thread (decode + stack + H2D,
`decode_ahead` waves ahead) → wave dispatch → pack → concat — so the
overlap of ingest with device compute is measured, not assumed. Its
per-stage breakdown (including the new `decode`/`stage` keys) rides as
`stage_ms_cold`.

`sfe_latency_ms_2160p` / `sfe_fps_2160p` are the split-frame-encoding
single-stream figures: every 4K frame sharded across the mesh as MB-row
band slices (one device per band, per-frame dispatch/collect —
parallel/dispatch.SfeShardEncoder), latency = the steady-state gap
between consecutive frames' bitstream-ready times. `fps_2160p` reports
the better of the GOP-wave and SFE paths (`fps_2160p_path` names the
winner).

`trace_overhead_pct` pins the cost of distributed tracing (obs/): the
same e2e 1080p wave set with a span recorder bound vs not — the
acceptance gate is < 3%, and the measurement itself asserts tracing
changed no output byte.

`live_latency_s` / `live_latency_p99_s` are the live LL-HLS pipeline's
glass-to-playlist latency (wall-clock from a frame landing in the
growing source file to its part being fetchable from the playlist)
over a paced 1080p 2-rung live job, with `live_dvr_segments` and the
paced `live_ingest_fps` as context.

Compile time is excluded (one warmup wave per resolution).
"""

from __future__ import annotations

import json
import time

import numpy as np


def make_frames(n: int, w: int, h: int, seed: int = 0, pan: int = 3):
    """Synthetic video-like content: a camera pan over a fixed detailed
    scene (gradient + texture + static grain), `pan` px/frame diagonal.
    Motion-predictable like real footage — unlike per-frame iid noise,
    which no codec (or hardware encoder) can inter-predict."""
    from thinvids_tpu.core.types import Frame

    rng = np.random.default_rng(seed)
    pad = pan * n + 2
    yy, xx = np.mgrid[0:h + pad, 0:w + pad]
    scene = (xx * 0.1 + yy * 0.05) % 256 \
        + 24.0 * np.sin(xx * 0.07) * np.cos(yy * 0.05) \
        + rng.normal(0, 6.0, (h + pad, w + pad))
    scene = np.clip(scene, 0, 255).astype(np.uint8)
    scene_u = np.clip(128 + 30 * np.sin(xx[::2, ::2] * 0.01),
                      0, 255).astype(np.uint8)
    scene_v = np.clip(128 + 30 * np.cos(yy[::2, ::2] * 0.01),
                      0, 255).astype(np.uint8)
    frames = []
    for i in range(n):
        dy = dx = pan * i
        frames.append(Frame(
            y=scene[dy:dy + h, dx:dx + w],
            u=scene_u[dy // 2:dy // 2 + h // 2, dx // 2:dx // 2 + w // 2],
            v=scene_v[dy // 2:dy // 2 + h // 2, dx // 2:dx // 2 + w // 2],
        ))
    return frames


def _quality(frames, stream) -> dict:
    """Luma PSNR/SSIM of the encoded stream vs source (libavcodec
    oracle decode; outside every timed region)."""
    from thinvids_tpu.tools import oracle
    from thinvids_tpu.tools.metrics import clip_quality

    if not oracle.oracle_available():
        return {}
    decoded = oracle.decode_h264(stream)
    q = clip_quality(frames, [d[0] for d in decoded])
    return {"psnr_y": round(q["psnr_y"], 2),
            "ssim_y": round(q["ssim_y"], 4)}


def _warm_staged_encoder(w: int, h: int, nframes: int, qp: int,
                         gop_frames: int):
    """(warmed encoder, HBM-staged waves, frames) — the shared timed-
    region prologue: stage every wave into HBM (block_until_ready),
    then compile EVERY distinct wave shape (the tail wave is usually
    smaller than the full ones) + build the native packer through a
    throwaway encode. One copy, so every e2e figure that compares
    against another warms identically."""
    import jax

    from thinvids_tpu.core.types import VideoMeta, concat_segments
    from thinvids_tpu.parallel.dispatch import GopShardEncoder

    frames = make_frames(nframes, w, h)
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=nframes)
    enc = GopShardEncoder(meta, qp=qp, gop_frames=gop_frames)
    _, waves = enc.prepare_waves(frames)
    jax.block_until_ready([wv[1:] for wv in waves])   # force HBM staging
    distinct = {}
    for wv in waves:
        distinct.setdefault(wv[1].shape, wv)
    concat_segments(enc.encode_waves(list(distinct.values())))
    return enc, waves, frames


def _run_pipeline(w: int, h: int, nframes: int, qp: int, gop_frames: int,
                  quality: bool = True) -> dict:
    """One resolution's numbers: {"fps", "device_fps", "bytes",
    "stage_ms", "quality"} — stage_ms is the host-stage wall-clock
    breakdown (parallel/dispatch.StageProfile) of the FASTEST e2e pass."""
    import jax

    from thinvids_tpu.core.types import concat_segments

    enc, waves, frames = _warm_staged_encoder(w, h, nframes, qp,
                                              gop_frames)

    # Device-only: dispatch every wave, then a value barrier — fetch the
    # last wave's (tiny) block-count array, which compiles nothing new
    # inside the timed region. Device execution is in-order, so the
    # last wave's completion implies all prior waves'. Best of 3, same
    # rationale as the e2e passes below.
    t_dev = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        outs = [enc.dispatch_wave(wv)[-1] for wv in waves]
        _ = jax.device_get(outs[-1][1])
        t_dev = min(t_dev, time.perf_counter() - t0)

    # End-to-end production path: best of 3 passes, so one noisy pass
    # is not baked into the reported number (the spread on a directly
    # attached chip is not measured — ROADMAP S1). The stage profile
    # resets per pass so the reported breakdown matches the reported
    # fps, not an average over noisy passes.
    t_e2e = float("inf")
    stage_ms: dict = {}
    for _ in range(3):
        enc.stages.reset()
        t0 = time.perf_counter()
        segs = enc.encode_waves(waves)
        with enc.stages.stage("concat"):
            stream = concat_segments(segs)
        t = time.perf_counter() - t0
        if t < t_e2e:
            t_e2e, stage_ms = t, enc.stages.snapshot()
    return {
        "fps": nframes / t_e2e,
        "device_fps": nframes / t_dev,
        "bytes": len(stream),
        "stage_ms": stage_ms,
        "quality": _quality(frames, stream) if quality else {},
    }


def _run_sfe(w: int, h: int, nframes: int, qp: int, gop_frames: int,
             bands: int = 0, runs: int = 3) -> dict:
    """Split-frame encoding single-stream figures: e2e fps plus
    per-frame glass-to-bitstream latency percentiles through the
    production SFE path (every frame sharded across the mesh as MB-row
    band slices, per-frame dispatch/collect —
    parallel/dispatch.SfeShardEncoder). The latency samples are the
    steady-state gaps between consecutive frames' bitstream-ready
    timestamps: at the live edge a frame entering the (device step →
    band fetch → band-slice pack) pipeline exits one such gap later.
    `bands=0` uses every local device (one band each)."""
    import statistics

    import jax

    from thinvids_tpu.core.types import VideoMeta, concat_segments
    from thinvids_tpu.parallel.dispatch import SfeShardEncoder

    frames = make_frames(nframes, w, h)
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=nframes)
    enc = SfeShardEncoder(meta, qp=qp, gop_frames=gop_frames, bands=bands)
    _, waves = enc.prepare_waves(frames)
    jax.block_until_ready([wv[1] for wv in waves])    # force HBM staging

    # Warmup compiles BOTH per-frame step programs (intra + P); unlike
    # the GOP-wave path there is no tail-shape recompile — every frame
    # runs the same two shapes.
    concat_segments(enc.encode_waves(waves[:1]))

    t_best = float("inf")
    lat: list[float] = []
    stage_ms: dict = {}
    stream = b""
    for _ in range(runs):
        enc.stages.reset()
        enc.frame_done_t.clear()
        t0 = time.perf_counter()
        segs = enc.encode_waves(waves)
        stream = concat_segments(segs)
        t = time.perf_counter() - t0
        if t < t_best:
            t_best = t
            lat = enc.frame_latencies_ms()
            stage_ms = enc.stages.snapshot()
    lat_sorted = sorted(lat) or [0.0]
    return {
        "fps": nframes / t_best,
        "latency_ms_p50": round(statistics.median(lat_sorted), 1),
        "latency_ms_p99": round(
            lat_sorted[int(0.99 * (len(lat_sorted) - 1))], 1),
        "bands": enc.num_bands,
        "halo_rows": enc.halo_rows,
        "bytes": len(stream),
        "stage_ms": stage_ms,
    }


def _run_rd(w: int, h: int, nframes: int, qp: int, gop_frames: int
            ) -> dict:
    """Rate-distortion point, features ON vs OFF on the same clip.

    One closed GOP per config through the production GOP program
    (encode_gop + emit_recon): bits/frame, PSNR-Y, SSIM-Y and the
    VMAF-proxy figure, measured on the reconstruction — which the
    conformance suite pins byte-identical to an independent decode of
    the emitted stream (including deblocked and skip-bearing streams),
    so the quality numbers are the decoder's, whether or not the
    libavcodec oracle is present. "on" = the full RD feature set
    (mode_decision + pskip + deblock + aq_strength 1.0); "off" = the
    historical encoder. This is the ROADMAP r4-gate measurement: the
    ON point must reach <= 300 kbit/frame at PSNR-Y >= 36.5 dB at
    1080p."""
    from thinvids_tpu.codecs.h264.encoder import encode_gop
    from thinvids_tpu.codecs.h264.rdo import RdConfig, aq_from_strength
    from thinvids_tpu.core.types import VideoMeta
    from thinvids_tpu.tools.metrics import psnr, ssim, vmaf_proxy

    frames = make_frames(nframes, w, h)
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=nframes)
    configs = {
        "off": RdConfig(),
        "on": RdConfig(mode_decision=True, pskip=True, deblock=True,
                       aq_q=aq_from_strength(1.0)),
    }
    out: dict = {"qp": qp, "gop_frames": gop_frames, "frames": nframes}
    for name, rd in configs.items():
        total_bits = 0
        ps, ss = [], []
        for g0 in range(0, nframes, gop_frames):
            chunk = frames[g0:g0 + gop_frames]
            stream, recons = encode_gop(
                chunk, meta, qp=qp, idr_pic_id=g0 // gop_frames,
                with_headers=(g0 == 0), return_recon=True, rd=rd)
            total_bits += len(stream) * 8
            ry = np.asarray(recons[0])
            for i, f in enumerate(chunk):
                ps.append(psnr(f.y, ry[i][:h, :w]))
                ss.append(ssim(f.y, ry[i][:h, :w]))
        p = float(np.mean([x for x in ps if np.isfinite(x)] or [99.0]))
        s = float(np.mean(ss))
        out[name] = {
            "bits_per_frame": round(total_bits / nframes),
            "psnr_y": round(p, 2),
            "ssim_y": round(s, 4),
            "vmaf_proxy": vmaf_proxy(p, s),
        }
    return out


def _run_sfe_farm(w: int, h: int, nframes: int, qp: int, gop_frames: int,
                  worker_counts: tuple[int, ...] = (1, 2, 4),
                  job_budget_s: float = 900.0) -> dict:
    """Farm split-frame encoding scaling curve: ONE stream encoded by
    N worker HOSTS, each owning a slice of the frame's band layout
    with per-frame halo exchange over the coordinator relay
    (cluster/remote.py band shards + cluster/halo.py). For each worker
    count the PRODUCTION stack runs end to end — in-process
    coordinator + HTTP API + RemoteExecutor planning band shards, real
    `cli.py worker` subprocesses (single CPU device each, so the
    worker count IS the band count) — and the figure is e2e job fps.
    The absolute numbers are CPU-worker numbers; the SCALING RATIO
    between counts is the measured quantity (N hosts → single-stream
    speedup, not just throughput). One caveat rides with it: each
    worker is a separate OS process, so the curve only rises when the
    host gives the workers real cores — on a 1-core harness the ratio
    measures pure farming overhead (≈ 1.0 once the halo exchange is
    amortized) and the speedup shows up on multi-core / multi-host
    runs."""
    import os
    import shutil
    import subprocess
    import sys
    import tempfile

    from thinvids_tpu.api.server import ApiServer
    from thinvids_tpu.cluster import Coordinator
    from thinvids_tpu.cluster.remote import RemoteExecutor
    from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
    from thinvids_tpu.core.status import Status
    from thinvids_tpu.core.types import VideoMeta
    from thinvids_tpu.io.y4m import write_y4m

    repo = os.path.dirname(os.path.abspath(__file__))
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=nframes)
    frames = make_frames(nframes, w, h)
    out: dict = {"workers": {}, "halo_rows": 0, "bands": {}}
    runs = 2                        # job 1 pays each worker's jit
                                    # compile; job 2 is the WARM
                                    # steady-state figure (the workers
                                    # persist across jobs, so their
                                    # program caches do too)
    for count in worker_counts:
        tmp = tempfile.mkdtemp(prefix=f"tvt-sfefarm{count}-")
        snap = Settings(values=dict(
            DEFAULT_SETTINGS, qp=qp, gop_frames=gop_frames,
            heartbeat_throttle_s=0.0, execution_backend="remote",
            sfe_bands=count, sfe_farm=True,
            pipeline_worker_count=count + 1, min_idle_workers=0,
            metrics_ttl_s=5.0, remote_retry_backoff_s=0.2,
            remote_no_worker_grace_s=60.0,
            remote_shard_timeout_s=60.0))
        coord = Coordinator(settings_fn=lambda s=snap: s)
        execu = RemoteExecutor(coord, output_dir=os.path.join(tmp, "lib"),
                               sync=False, poll_s=0.1)
        coord._launcher = execu.launch
        api = ApiServer(coord, work=execu.board).start()
        workers = []
        try:
            for i in range(count):
                workers.append(subprocess.Popen(
                    [sys.executable, "-m", "thinvids_tpu.cli", "worker",
                     "--coordinator", api.url,
                     "--node-name", f"sfefarm-w{i}",
                     "--interval", "0.3", "--poll", "0.1"],
                    env=dict(os.environ, JAX_PLATFORMS="cpu",
                             PYTHONPATH=repo, TVT_QP=str(qp),
                             TVT_GOP_FRAMES=str(gop_frames)),
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.STDOUT))
            deadline = time.time() + 60.0
            while time.time() < deadline:
                live = [n for n in coord.registry.active(5.0)
                        if n.metrics.get("worker")]
                if len(live) >= count:
                    break
                time.sleep(0.2)
            else:
                raise RuntimeError(
                    f"{count}-worker farm never registered")
            best = 0.0
            for r in range(runs):
                clip = os.path.join(tmp, f"sfefarm-r{r}.y4m")
                write_y4m(clip, meta, frames)
                t0 = time.perf_counter()
                job = coord.add_job(clip, meta)
                deadline = time.time() + job_budget_s
                while time.time() < deadline:
                    st = coord.store.get(job.id)
                    if st.status in (Status.DONE, Status.FAILED,
                                     Status.REJECTED):
                        break
                    time.sleep(0.1)
                st = coord.store.get(job.id)
                if st.status is not Status.DONE:
                    raise RuntimeError(
                        f"{count}-worker farm SFE job ended "
                        f"{st.status.value}: {st.failure_reason}")
                best = max(best,
                           nframes / (time.perf_counter() - t0))
            out["workers"][count] = best
            out["bands"][count] = count
            out["halo_rows"] = int(snap.get("sfe_halo_rows", 32))
        finally:
            for p in workers:
                p.kill()
            for p in workers:
                p.wait(10)
            api.stop()
            coord.stop_background()
            execu.join(5)
            shutil.rmtree(tmp, ignore_errors=True)
    return out


def _run_trace_overhead(w: int, h: int, nframes: int, qp: int,
                        gop_frames: int, runs: int = 3) -> dict:
    """Cost of distributed tracing on the e2e hot path: the same
    HBM-staged wave set encodes with NO span recorder bound, then with
    a live recorder on the stage profile (every timed stage + counter
    records a span, exactly what a traced production job pays).
    Returns best-of-N fps for both and the relative overhead —
    `trace_overhead_pct` is the pinned BENCH figure the <3% acceptance
    gate reads. Raises if tracing changes a single output byte (the
    parity invariant; also asserted by tests/test_obs.py)."""
    from thinvids_tpu.core.types import concat_segments
    from thinvids_tpu.obs import trace as obs_trace

    enc, waves, _frames = _warm_staged_encoder(w, h, nframes, qp,
                                               gop_frames)

    def best_of(n: int) -> tuple[float, bytes]:
        t_best, stream = float("inf"), b""
        for _ in range(n):
            t0 = time.perf_counter()
            out = concat_segments(enc.encode_waves(waves))
            t = time.perf_counter() - t0
            if t < t_best:
                t_best, stream = t, out
        return t_best, stream

    enc.stages.set_tracer(None)
    t_off, bytes_off = best_of(runs)
    trace_id = obs_trace.TRACE.start("bench-trace-overhead")
    if not trace_id:
        # trace_sample sampled the bench trace out: the "traced" pass
        # would measure the untraced path and the <3% gate would pass
        # vacuously — fail loudly instead of lying
        raise RuntimeError(
            "trace_sample sampled the bench trace out; overhead not "
            "measurable (set TVT_TRACE_SAMPLE=1 for the bench run)")
    enc.stages.set_tracer(
        obs_trace.TRACE.recorder("bench-trace-overhead"))
    try:
        t_on, bytes_on = best_of(runs)
    finally:
        enc.stages.set_tracer(None)
        obs_trace.TRACE.drop("bench-trace-overhead")
    if bytes_on != bytes_off:
        raise RuntimeError("tracing changed output bytes — parity "
                           "invariant broken")
    return {
        "fps_off": nframes / t_off,
        "fps_on": nframes / t_on,
        "overhead_pct": round(100.0 * (t_on - t_off) / t_off, 2),
        # always True (an unsampled trace raises above) — kept in the
        # schema as the explicit record that tracing was live
        "sampled": True,
    }


def _run_cold(w: int, h: int, nframes: int, qp: int, gop_frames: int,
              runs: int = 3) -> dict:
    """Cold end-to-end fps: decode → stage (H2D) → encode → concat
    through the production streaming ingest (ingest.open_video +
    GopShardEncoder.encode's background staging thread), nothing
    pre-staged in HBM. Source decode and upload overlap device compute,
    so this should track the HBM-resident figure closely — the gap IS
    the ingest pipeline's cost."""
    import os
    import tempfile

    from thinvids_tpu.core.types import VideoMeta, concat_segments
    from thinvids_tpu.ingest.decode import open_video
    from thinvids_tpu.io.y4m import write_y4m
    from thinvids_tpu.parallel.dispatch import GopShardEncoder

    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=nframes)
    fd, path = tempfile.mkstemp(suffix=".y4m")
    os.close(fd)
    try:
        write_y4m(path, meta, make_frames(nframes, w, h))
        enc = GopShardEncoder(meta, qp=qp, gop_frames=gop_frames)
        src = open_video(path)
        # warmup: compile every wave shape + build the native packer
        # through the very path being timed
        concat_segments(enc.encode(src))
        t_cold = float("inf")
        stage_ms: dict = {}
        for _ in range(runs):
            enc.stages.reset()
            t0 = time.perf_counter()
            stream = concat_segments(enc.encode(src))
            t = time.perf_counter() - t0
            if t < t_cold:
                t_cold, stage_ms = t, enc.stages.snapshot()
        return {"fps": nframes / t_cold, "bytes": len(stream),
                "stage_ms": stage_ms}
    finally:
        os.unlink(path)


def _run_ladder(w: int, h: int, nframes: int, qp: int, gop_frames: int,
                rungs_spec: str = "1080,720,480,360",
                runs: int = 3) -> dict:
    """ABR-ladder throughput: one staged wave stream fanned across the
    rung set (lower rungs derived on device — abr/scale.py), measured
    as AGGREGATE frames·rungs per second, plus per-rung bits/frame.
    Decode + H2D is shared across rungs, so the aggregate should beat
    rungs × the single-rendition cost; `h2d_bytes` rides along as the
    once-per-wave upload proof."""
    import jax

    from thinvids_tpu.abr.ladder import LadderShardEncoder, plan_ladder
    from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
    from thinvids_tpu.core.types import VideoMeta

    frames = make_frames(nframes, w, h)
    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=nframes)
    snap = Settings(values=dict(DEFAULT_SETTINGS, qp=qp,
                                ladder_rungs=rungs_spec))
    rungs = plan_ladder(meta, snap)
    enc = LadderShardEncoder(meta, rungs, gop_frames=gop_frames)
    _, waves = enc._stager.prepare_waves(frames)
    jax.block_until_ready([wv[1:] for wv in waves])

    def encode_staged(wvs):
        bundles = []
        for wv in wvs:                  # depth-1: the figure is about
            bundles.extend(             # rung fan-out, not pipelining
                enc.collect_wave(enc.dispatch_wave(wv)))
        return bundles

    distinct = {}
    for wv in waves:
        distinct.setdefault(wv[1].shape, wv)
    encode_staged(list(distinct.values()))      # warmup/compile

    t_best = float("inf")
    bundles = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = encode_staged(waves)
        t = time.perf_counter() - t0
        if t < t_best:
            t_best, bundles = t, out
    rung_bits = {}
    for rung in rungs:
        total = sum(len(b.renditions[rung.name].payload) for b in bundles)
        rung_bits[rung.name] = round(total * 8 / nframes)
    return {"fps": nframes * len(rungs) / t_best,
            "rungs": len(rungs),
            "rung_bits_per_frame": rung_bits,
            "h2d_bytes": enc.stages.snapshot().get("h2d_bytes", 0)}


def _measure_live_pace(meta, frames, rungs, gop_frames: int, fps: int,
                       segment_s: float,
                       warm_full: bool = False) -> tuple[float, float]:
    """Warm the live wave shapes and measure a sustainable ingest pace.

    The executor pins the GOP grid to gop_frames (_live_batch_plan), so
    warming must use the same pinned plans — the natural planner would
    compile different, useless shapes. `warm_full` also compiles the
    full-backlog catch-up wave (needed when the bench's writer can fall
    behind by more than one GOP).

    Edge rate: one-GOP waves are the live edge's steady state and on a
    wide mesh cost a full padded wave — batched catch-up waves amortize
    better, so the 1-GOP wave rate is the binding constraint on keeping
    up; pacing at half of it keeps backlog bounded so the metric
    measures PIPELINE latency, not unbounded backlog growth.

    The stream's segment duration is provisioned to measured
    capability, exactly as a live operator does on slower hardware: one
    GOP's wall-clock encode is the latency floor, so a segment shorter
    than ~2 GOP-walls would set an impossible latency budget. NOTE:
    bypasses the live tier's 60 s clamp on purpose; a bench host that
    slow still gets a correctly-judged (if dismal) number instead of a
    false fail. Returns (ingest_fps, segment_s)."""
    from thinvids_tpu.abr.ladder import LadderShardEncoder
    from thinvids_tpu.cluster.executor import _live_batch_plan

    warm = LadderShardEncoder(meta, rungs, gop_frames=gop_frames)
    if warm_full:
        warm.plan_override = _live_batch_plan(
            meta.num_frames, gop_frames, warm.num_devices)
        warm.encode(frames)
    warm.plan_override = _live_batch_plan(gop_frames, gop_frames,
                                          warm.num_devices)
    warm.encode(frames[:gop_frames])
    t0 = time.perf_counter()
    warm.encode(frames[:gop_frames])
    edge_fps = gop_frames / (time.perf_counter() - t0)
    ingest_fps = max(0.5, min(float(fps), 0.5 * edge_fps))
    gop_wall_s = gop_frames / max(edge_fps, 1e-3)
    return ingest_fps, max(float(segment_s), 2.0 * gop_wall_s)


def _start_paced_writer(path: str, meta, frames, ingest_fps: float):
    """Writer thread pacing y4m frames into a growing `.live` drop,
    closing the stream with the `.eos` marker. Returns (thread,
    write_times); write_times[i] is the wall-clock at which frame i
    finished hitting the source file."""
    import io as _io
    import threading

    from thinvids_tpu.io.y4m import Y4MWriter

    write_times: list[float] = []

    def writer() -> None:
        buf = _io.BytesIO()
        wtr = Y4MWriter(buf, meta)
        with open(path, "wb") as out:
            out.write(buf.getvalue())           # header
            out.flush()
            delay = 1.0 / ingest_fps
            next_at = time.monotonic()
            for frame in frames:
                buf.seek(0)
                buf.truncate()
                wtr.write(frame)
                out.write(buf.getvalue())
                out.flush()
                write_times.append(time.monotonic())
                next_at += delay
                time.sleep(max(0.0, next_at - time.monotonic()))
        with open(path + ".eos", "wb"):
            pass

    wt = threading.Thread(target=writer, daemon=True)
    wt.start()
    return wt, write_times


def _sample_live_edge(coord, job_id: str, media: str, write_times,
                      *, nframes: int, gop_frames: int, fps: int,
                      segment_s: float, sample_gate=None):
    """Poll a live job's top-rung media playlist until the job reaches
    a terminal state; every newly announced part yields one
    glass-to-playlist latency sample (wall-clock from the part's LAST
    frame hitting the source file to the part being fetchable).

    One part = one GOP, so the live edge (next_msn, next_part) maps
    exactly to announced source frames: every MID-STREAM closed
    segment holds seg_gops whole parts (the greedy segmenter closes
    only at the FIRST GOP crossing segment_s — ceil, not round, with
    an epsilon guarding exact-multiple float specs); only the FINAL
    segment can be short, so the cumulative count is capped at the
    stream's true GOP total. `sample_gate` (when given) must be true
    at announce time for the part to count — the origin bench uses it
    to keep only parts announced during the viewer-load window.
    Returns (samples, seen_gops, final_segments)."""
    import math as _math

    from thinvids_tpu.abr.hls import live_playlist_state
    from thinvids_tpu.core.status import Status

    seg_gops = max(1, _math.ceil(segment_s * fps / gop_frames - 1e-9))
    total_gops = -(-nframes // gop_frames)
    samples: list[float] = []
    seen_gops = 0
    final_segments = 0
    while True:
        st = coord.store.get(job_id)
        try:
            with open(media, encoding="utf-8") as fp:
                pl = live_playlist_state(fp.read())
        except OSError:
            pl = None
        if pl is not None:
            now = time.monotonic()
            final_segments = pl["segments"]
            gops = min(total_gops,
                       pl["next_msn"] * seg_gops + pl["next_part"])
            for g in range(seen_gops, gops):
                last_frame = min((g + 1) * gop_frames, nframes) - 1
                if last_frame < len(write_times) and (
                        sample_gate is None or sample_gate()):
                    samples.append(now - write_times[last_frame])
            seen_gops = max(seen_gops, gops)
        if st.status in (Status.DONE, Status.FAILED):
            return samples, seen_gops, final_segments
        time.sleep(0.005)


def _run_live(w: int, h: int, nframes: int, qp: int, gop_frames: int,
              rungs_spec: str = "540", segment_s: float = 1.0,
              dvr_window_s: float = 2.0, sfe_bands: int = 0) -> dict:
    """Glass-to-playlist latency through the PRODUCTION live pipeline:
    a writer thread paces y4m frames into a growing `.live.y4m` drop,
    the real coordinator + executor tail it (`_run_live`), and a
    poller watches the top rung's media playlist — each announced part
    yields one latency sample: wall-clock from the part's LAST frame
    hitting the source file to the part being fetchable.

    The writer paces at the sustainable ingest rate measured by a
    warmup ladder encode (never above the stream's nominal fps): a
    live deployment provisions encode >= real time, and on a harness
    slower than that the metric must measure PIPELINE latency, not
    unbounded backlog growth — the pacing rate rides along as
    `ingest_fps` so the context is pinned, not hidden."""
    import os
    import statistics
    import tempfile

    from thinvids_tpu.abr.ladder import plan_ladder
    from thinvids_tpu.cluster import Coordinator, WorkerRegistry
    from thinvids_tpu.cluster.executor import LocalExecutor
    from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
    from thinvids_tpu.core.status import Status
    from thinvids_tpu.core.types import VideoMeta

    fps = 30
    frames = make_frames(nframes, w, h)
    meta = VideoMeta(width=w, height=h, fps_num=fps, fps_den=1,
                     num_frames=nframes)
    snap = Settings(values=dict(
        DEFAULT_SETTINGS, qp=qp, gop_frames=gop_frames,
        ladder_rungs=rungs_spec, segment_s=segment_s,
        dvr_window_s=dvr_window_s, live_stall_s=10.0,
        heartbeat_throttle_s=0.0, sfe_bands=sfe_bands))
    rungs = plan_ladder(meta, snap)

    # warm the pinned live wave shapes (full backlog + 1-GOP edge) and
    # provision pace + segment duration to measured capability; the
    # chosen duration rides along as `live_segment_s` — the latency
    # metric is judged against the STREAM'S OWN segment duration
    ingest_fps, segment_s = _measure_live_pace(
        meta, frames, rungs, gop_frames, fps, segment_s, warm_full=True)
    if sfe_bands > 0:
        # the pace probe measures the GOP-wave ladder path; the SFE
        # live edge trades throughput for per-frame latency, so pace a
        # touch below the probe to keep the metric pipeline latency,
        # not backlog growth
        ingest_fps *= 0.8
    # rebuild the settings snapshot with the provisioned duration —
    # the executor reads segment_s from here
    snap = Settings(values=dict(snap.values, segment_s=segment_s))

    tmp = tempfile.mkdtemp(prefix="tvt-live-")
    path = os.path.join(tmp, "bench.live.y4m")

    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"bench{i}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=os.path.join(tmp, "lib"),
                          sync=False)
    coord._launcher = execu.launch
    wt, write_times = _start_paced_writer(path, meta, frames, ingest_fps)
    job = coord.add_job(path, meta)

    media = os.path.join(tmp, "lib", "bench.live.hls",
                         rungs[0].name, "media.m3u8")
    samples, seen_gops, final_segments = _sample_live_edge(
        coord, job.id, media, write_times, nframes=nframes,
        gop_frames=gop_frames, fps=fps, segment_s=segment_s)
    wt.join()
    execu.join(5)
    st = coord.store.get(job.id)
    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    if st.status is not Status.DONE or not samples:
        raise RuntimeError(f"live bench job ended {st.status.value}: "
                           f"{st.failure_reason}")
    samples.sort()
    return {
        "latency_s": statistics.median(samples),
        "latency_p99_s": samples[
            min(len(samples) - 1, int(0.99 * len(samples)))],
        "dvr_segments": final_segments,
        "segment_s": segment_s,
        "ingest_fps": round(ingest_fps, 2),
        "gops": seen_gops,
    }


def _run_origin(w: int, h: int, nframes: int, qp: int, gop_frames: int,
                sessions: int | None = None,
                duration_s: float | None = None,
                rungs_spec: str = "120") -> dict:
    """Origin-at-scale figures through the PRODUCTION serving stack:
    a real coordinator + HTTP API serve (1) a finished ladder job's
    VOD tree and (2) a live job being encoded from a paced writer,
    while `tools/loadgen.py` replays N concurrent player sessions
    against the VOD program. Emits `sessions_sustained` (sessions
    that ran the whole window error-free), measured per-segment fetch
    latency percentiles, and `live_latency_under_load_s` — the live
    stream's glass-to-playlist latency WHILE the origin carries the
    viewer load (the number a CDN-fronted deployment actually cares
    about). Session count / window default to the `loadgen_sessions` /
    `loadgen_duration_s` settings."""
    import os
    import shutil
    import statistics
    import tempfile
    import threading

    from thinvids_tpu.abr.ladder import plan_ladder
    from thinvids_tpu.api.server import ApiServer
    from thinvids_tpu.cluster import Coordinator, WorkerRegistry
    from thinvids_tpu.cluster.executor import LocalExecutor
    from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
    from thinvids_tpu.core.status import Status
    from thinvids_tpu.core.types import VideoMeta
    from thinvids_tpu.io.y4m import write_y4m
    from thinvids_tpu.tools import loadgen

    snap_defaults = Settings(values=dict(DEFAULT_SETTINGS))
    sessions = int(snap_defaults.get("loadgen_sessions", 500)) \
        if sessions is None else sessions
    duration_s = float(snap_defaults.get("loadgen_duration_s", 10.0)) \
        if duration_s is None else duration_s

    fps = 30
    frames = make_frames(nframes, w, h)
    meta = VideoMeta(width=w, height=h, fps_num=fps, fps_den=1,
                     num_frames=nframes)
    tmp = tempfile.mkdtemp(prefix="tvt-origin-")
    try:
        # -- measure a sustainable live pace (same rationale as
        # _run_live: the metric is pipeline latency, not backlog)
        snap = Settings(values=dict(
            DEFAULT_SETTINGS, qp=qp, gop_frames=gop_frames,
            ladder_rungs=rungs_spec, segment_s=0.5, dvr_window_s=0.0,
            live_stall_s=10.0, heartbeat_throttle_s=0.0))
        rungs = plan_ladder(meta, snap)
        ingest_fps, segment_s = _measure_live_pace(
            meta, frames, rungs, gop_frames, fps, 0.5)
        snap = Settings(values=dict(snap.values, segment_s=segment_s))

        reg = WorkerRegistry()
        for i in range(8):
            reg.heartbeat(f"origin{i}")
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        execu = LocalExecutor(coord, output_dir=os.path.join(tmp, "lib"),
                              sync=False)
        coord._launcher = execu.launch
        api = ApiServer(coord).start()
        try:
            # -- (1) VOD program: a tiny ladder job, encoded to DONE
            vod_src = os.path.join(tmp, "vod.ladder.y4m")
            write_y4m(vod_src, meta, frames)
            vod = coord.add_job(vod_src, meta)
            deadline = time.monotonic() + 600
            while coord.store.get(vod.id).status not in (Status.DONE,
                                                         Status.FAILED):
                if time.monotonic() > deadline:
                    raise RuntimeError("VOD ladder job never finished")
                time.sleep(0.05)
            if coord.store.get(vod.id).status is not Status.DONE:
                raise RuntimeError("VOD ladder job failed: "
                                   + coord.store.get(vod.id).failure_reason)

            # -- (2) live job: paced writer into a growing drop
            live_path = os.path.join(tmp, "cam.live.y4m")
            wt, write_times = _start_paced_writer(live_path, meta,
                                                  frames, ingest_fps)
            live_job = coord.add_job(live_path, meta)

            # -- (3) viewer load against the VOD program while the
            # live job encodes; loadgen runs in a thread so this
            # thread can sample the live edge under load
            load_out: dict = {}

            def load() -> None:
                load_out.update(loadgen.run_load(
                    api.url, vod.id, sessions=sessions,
                    duration_s=duration_s))

            lt = threading.Thread(target=load, daemon=True)
            lt.start()

            media = os.path.join(tmp, "lib", "cam.live.hls",
                                 rungs[0].name, "media.m3u8")
            # only parts announced DURING the viewer load window count
            # toward the under-load latency metric
            samples, _, _ = _sample_live_edge(
                coord, live_job.id, media, write_times,
                nframes=nframes, gop_frames=gop_frames, fps=fps,
                segment_s=segment_s, sample_gate=lt.is_alive)
            wt.join(30)
            lt.join(duration_s + 120)
            execu.join(30)
            st = coord.store.get(live_job.id)
            if st.status is not Status.DONE:
                raise RuntimeError(
                    f"live job under load ended {st.status.value}: "
                    f"{st.failure_reason}")
            origin_snap = api.origin.snapshot()
        finally:
            api.stop()
        return {
            "sessions": load_out.get("sessions", sessions),
            "sessions_sustained": load_out.get("sessions_sustained", 0),
            "p50_segment_ms": load_out.get("segment_ms_p50", 0.0),
            "p99_segment_ms": load_out.get("segment_ms_p99", 0.0),
            "requests": load_out.get("requests", 0),
            "errors": load_out.get("errors", 0),
            "live_latency_under_load_s": (
                round(statistics.median(samples), 3) if samples else -1.0),
            "origin_hits": origin_snap.get("origin_hits", 0),
            "origin_bytes": origin_snap.get("origin_bytes", 0),
            "duration_s": duration_s,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _run_autoscale(w: int, h: int, nframes: int, qp: int,
                   gop_frames: int, *, duration_s: float = 30.0,
                   hi_rps: float = 0.25, farm_max: int = 3,
                   kill_interval_s: float | None = None,
                   partition_s: float | None = None) -> dict:
    """Elastic-farm figures under chaos, through the PRODUCTION stack:
    a real coordinator + RemoteExecutor + HTTP API, a CapacityController
    with ``autoscale_enabled`` scaling REAL ``cli.py worker``
    subprocesses (farm.SubprocessProvider) between 0 and `farm_max`,
    and the loadgen chaos harness driving a diurnal job-submission
    curve while SIGKILLing workers and partitioning the /work routes.

    Reported: ``autoscale_p99_queue_s`` (p99 of each job's
    queued→dispatched wait — the price of scale-to-zero, since a job
    arriving at a dark farm waits for a wake), ``farm_active_worker_s``
    (the controller's integral of non-SUSPENDED worker-seconds) vs the
    always-on figure ``farm_max × wall-clock`` — the bench RAISES
    unless the farm measurably breathed below always-on at the trough —
    plus jobs completed and chaos-event counts. Every job must reach
    DONE with output bytes identical across the whole chaotic run (the
    same clip submitted N times under two tenants with weighted
    shares; kills and partitions may retry shards anywhere, and the
    deterministic encode means any divergence is a real bug).
    Submissions alternate tenants (acme:3, bravo:1) so the fair-share
    admission layer runs under fire too."""
    import os
    import shutil
    import tempfile
    import time as _time

    from thinvids_tpu.api.server import ApiServer
    from thinvids_tpu.cluster import Coordinator
    from thinvids_tpu.cluster.remote import RemoteExecutor
    from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
    from thinvids_tpu.core.status import Status
    from thinvids_tpu.core.types import VideoMeta
    from thinvids_tpu.farm import CapacityController, SubprocessProvider
    from thinvids_tpu.io.y4m import write_y4m
    from thinvids_tpu.tools import loadgen

    repo = os.path.dirname(os.path.abspath(__file__))
    chaos_knobs = loadgen.chaos_defaults(
        Settings(values=dict(DEFAULT_SETTINGS)))
    if kill_interval_s is None:
        kill_interval_s = chaos_knobs["kill_interval_s"] \
            or duration_s / 3.0
    if partition_s is None:
        partition_s = chaos_knobs["partition_s"] or 3.0

    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=nframes)
    snap = Settings(values=dict(
        DEFAULT_SETTINGS, qp=qp, gop_frames=gop_frames,
        heartbeat_throttle_s=0.0, execution_backend="remote",
        autoscale_enabled=True, farm_min_workers=0,
        farm_max_workers=farm_max, drain_grace_s=5.0,
        tenant_shares="acme:3,bravo:1",
        pipeline_worker_count=max(1, farm_max), min_idle_workers=0,
        max_active_jobs=2, scheduler_poll_s=0.25,
        metrics_ttl_s=5.0, remote_plan_devices=4, remote_shard_gops=1,
        remote_shard_timeout_s=15.0, remote_retry_backoff_s=0.2,
        remote_no_worker_grace_s=120.0))
    tmp = tempfile.mkdtemp(prefix="tvt-autoscale-")
    coord = Coordinator(settings_fn=lambda: snap)
    execu = RemoteExecutor(coord, output_dir=os.path.join(tmp, "lib"),
                           sync=False, poll_s=0.1)
    coord._launcher = execu.launch
    api = ApiServer(coord, work=execu.board).start()
    provider = SubprocessProvider(
        api.url,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
                 TVT_QP=str(qp), TVT_GOP_FRAMES=str(gop_frames)))
    farm = CapacityController(coord, provider=provider,
                              board=execu.board)
    coord.farm = farm
    farm.start(poll_s=0.5)
    coord.start_background()
    clip = os.path.join(tmp, "chaos-src.y4m")
    write_y4m(clip, meta, make_frames(nframes, w, h))
    job_ids: list[str] = []

    def submit(i: int) -> None:
        tenant = "acme" if i % 2 == 0 else "bravo"
        path = os.path.join(tmp, f"{tenant}__clip{i:04d}.y4m")
        shutil.copyfile(clip, path)
        job_ids.append(coord.add_job(path, meta).id)

    def kill() -> bool:
        victims = provider.hosts()
        if not victims:
            return False
        return provider.kill(sorted(victims)[0])

    t0 = _time.monotonic()
    try:
        chaos = loadgen.run_chaos_load(
            submit, duration_s, period_s=duration_s, lo_rps=0.0,
            hi_rps=hi_rps, kill=kill, kill_interval_s=kill_interval_s,
            partition=api.partition_work, partition_s=partition_s)
        if not job_ids:
            submit(0)       # a degenerate curve must still prove a job
        deadline = _time.monotonic() + 300.0
        while True:
            jobs = [coord.store.get(j) for j in job_ids]
            if all(j.status in (Status.DONE, Status.FAILED,
                                Status.REJECTED) for j in jobs):
                break
            if _time.monotonic() > deadline:
                raise RuntimeError(
                    "autoscale bench: jobs never drained: " + ", ".join(
                        f"{j.id[:8]}={j.status.value}" for j in jobs))
            _time.sleep(0.25)
        bad = [j for j in jobs if j.status is not Status.DONE]
        if bad:
            raise RuntimeError(
                "autoscale bench: job(s) did not reach DONE under "
                "chaos: " + "; ".join(
                    f"{j.id[:8]} {j.status.value}: {j.failure_reason}"
                    for j in bad))
        outputs = set()
        for j in jobs:
            with open(j.output_path, "rb") as fp:
                outputs.add(fp.read())
        if len(outputs) != 1:
            raise RuntimeError(
                f"autoscale bench: {len(outputs)} distinct output "
                f"byte streams for the same clip — the chaotic farm "
                f"broke encode determinism")
        # let the controller observe the empty queue and breathe down
        settle = _time.monotonic() + 3.0
        while _time.monotonic() < settle:
            _time.sleep(0.25)
        elapsed = _time.monotonic() - t0
        active_s = farm.active_worker_seconds()
        alwayson_s = farm_max * elapsed
        if active_s >= alwayson_s:
            raise RuntimeError(
                f"autoscale bench: farm never breathed — "
                f"{active_s:.1f} active worker-seconds vs "
                f"{alwayson_s:.1f} always-on")
        waits = sorted(max(0.0, j.started_at - j.queued_at)
                       for j in jobs)
        p99 = waits[min(len(waits) - 1, int(0.99 * len(waits)))]
        return {
            "p99_queue_s": round(p99, 3),
            "active_worker_s": round(active_s, 1),
            "alwayson_worker_s": round(alwayson_s, 1),
            "jobs_done": len(jobs),
            "peak_workers": farm_max,
            "kills": chaos["kills"],
            "partitions": chaos["partitions"],
            "duration_s": round(elapsed, 1),
        }
    finally:
        coord.stop_background()
        farm.stop()
        provider.stop_all()
        api.stop()
        execu.join(30)
        shutil.rmtree(tmp, ignore_errors=True)


def _run_crash_resume(w: int, h: int, nframes: int, qp: int,
                      gop_frames: int, *, workers: int = 2,
                      kill_after_done: int | None = None,
                      deadline_s: float = 300.0) -> dict:
    """Durable-checkpoint figures under coordinator crash + data
    corruption, through the PRODUCTION stack: a SUBPROCESS
    ``cli.py coordinator`` (so it can be SIGKILLed for real) farming a
    job to real worker daemons, with (1) one in-flight part upload
    bit-flipped at ingest (the /work/chaos hook), (2) the coordinator
    SIGKILLed once >= `kill_after_done` shards are spooled, and (3)
    one spooled part bit-flipped on disk while the coordinator is
    down. The restarted coordinator must resume from the board
    checkpoint: verified parts rehydrate DONE, the corrupt one
    re-encodes, and the job lands DONE byte-identical to an
    UNINTERRUPTED run of the same clip.

    Reported: ``crash_resume_shard_reuse_pct`` (rehydrated / total
    shards on the crashed run — the work NOT re-encoded),
    ``coordinator_recovery_s`` (restart exec → the resumed job
    reporting progress again), and ``part_integrity_rejects`` (must
    equal the injected corruption count — both flips caught, zero
    corrupt bytes in any output). RAISES on any miss."""
    import os
    import shutil
    import signal as _signal
    import subprocess
    import sys
    import tempfile
    import time as _time
    import urllib.error
    import urllib.request

    from thinvids_tpu.core.types import VideoMeta
    from thinvids_tpu.io.y4m import write_y4m
    from thinvids_tpu.tools import loadgen

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="tvt-crash-")
    import socket as socket_mod

    with socket_mod.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        port = sk.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    state_dir = os.path.join(tmp, "state")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
        TVT_EXECUTION_BACKEND="remote", TVT_MIN_IDLE_WORKERS="0",
        TVT_PIPELINE_WORKER_COUNT="2", TVT_REMOTE_PLAN_DEVICES="8",
        TVT_REMOTE_SHARD_GOPS="1", TVT_METRICS_TTL_S="3",
        TVT_REMOTE_RETRY_BACKOFF_S="0.2", TVT_GOP_FRAMES=str(gop_frames),
        TVT_QP=str(qp), TVT_SCHEDULER_POLL_S="0.5",
        TVT_REMOTE_HTTP_RETRIES="12", TVT_REMOTE_HTTP_BACKOFF_S="0.2")

    def call(path, method="GET", body=None, timeout=10):
        data = json.dumps(body).encode() if body is not None else None
        req = urllib.request.Request(base + path, data=data,
                                     method=method)
        if data:
            req.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    def wait_for(predicate, budget_s, interval=0.25, what="condition"):
        deadline = _time.monotonic() + budget_s
        while _time.monotonic() < deadline:
            try:
                out = predicate()
            except (urllib.error.URLError, ConnectionError, OSError):
                out = None
            if out:
                return out
            _time.sleep(interval)
        raise RuntimeError(f"crash bench: timed out waiting for {what}")

    def spawn_coordinator():
        return subprocess.Popen(
            [sys.executable, "-m", "thinvids_tpu.cli", "coordinator",
             "--host", "127.0.0.1", "--port", str(port),
             "--state-dir", state_dir,
             "--output-dir", os.path.join(tmp, "library")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    def job_view(job_id):
        return call(f"/job_properties/{job_id}")["job"]

    meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=nframes)
    clip_ref = os.path.join(tmp, "ref.y4m")
    write_y4m(clip_ref, meta, make_frames(nframes, w, h))
    clip_crash = os.path.join(tmp, "crash.y4m")
    shutil.copyfile(clip_ref, clip_crash)

    coord = spawn_coordinator()
    worker_procs = []
    try:
        wait_for(lambda: call("/health", timeout=3), 45,
                 what="coordinator API")
        worker_procs = [subprocess.Popen(
            [sys.executable, "-m", "thinvids_tpu.cli", "worker",
             "--coordinator", base, "--node-name", f"crash-w{i}",
             "--interval", "0.3", "--poll", "0.2"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            for i in range(workers)]
        wait_for(lambda: len([n for n in call("/nodes_data")["nodes"]
                              if n["host"].startswith("crash-w")])
                 == workers, 30, what="workers registered")

        # ---- reference: the same clip, uninterrupted ---------------
        ref_job = call("/add_job", "POST", {"input_path": clip_ref})
        ref_done = wait_for(
            lambda: (job_view(ref_job["id"])
                     if job_view(ref_job["id"])["status"]
                     in ("done", "failed") else None),
            deadline_s, what="reference job")
        if ref_done["status"] != "done":
            raise RuntimeError(f"crash bench: reference job failed: "
                               f"{ref_done}")
        with open(ref_done["output_path"], "rb") as fp:
            want = fp.read()

        # ---- crashed run -------------------------------------------
        # (1) in-flight corruption: flip a bit in the next part upload
        call("/work/chaos", "POST", {"corrupt_parts": 1})
        job = call("/add_job", "POST", {"input_path": clip_crash})
        wait_for(lambda: call("/metrics_snapshot")["work"]
                 ["integrity_rejects"] >= 1 or None, 60,
                 interval=0.1, what="in-flight corruption rejected")
        pre_rejects = call("/metrics_snapshot")["work"][
            "integrity_rejects"]
        # (2) SIGKILL once enough shards are durably spooled: the
        # reuse floor is 50% AFTER losing one part to the spool flip,
        # so wait for total/2 + 2 completions (total known once the
        # plan posts — it rounds GOPs to the plan-device width)
        total_shards = wait_for(
            lambda: int(job_view(job["id"])["parts_total"]) or None,
            60, interval=0.1, what="shard plan posted")
        threshold = kill_after_done if kill_after_done is not None \
            else total_shards // 2 + 2
        wait_for(lambda: (call("/work/board")["shards"]["done"]
                          >= threshold) or None, 120,
                 interval=0.05, what=f"{threshold}+ shards done")
        coord.kill()
        coord.wait(timeout=10)
        # (3) storage rot while the coordinator is down
        spooled = loadgen.corrupt_spooled_part(
            os.path.join(state_dir, "part-spool"), job["id"])
        if spooled is None:
            raise RuntimeError("crash bench: no spooled part found "
                               "to corrupt")
        t_restart = _time.monotonic()
        coord = spawn_coordinator()
        wait_for(
            lambda: (lambda v: v["status"] == "done"
                     or (v["status"] in ("starting", "running")
                         and v["parts_done"] > 0))(job_view(job["id"]))
            or None, 90, interval=0.1,
            what="resumed job reporting progress")
        recovery_s = _time.monotonic() - t_restart
        done = wait_for(
            lambda: (job_view(job["id"])
                     if job_view(job["id"])["status"]
                     in ("done", "failed") else None),
            deadline_s, what="crashed job terminal")
        if done["status"] != "done":
            raise RuntimeError(
                f"crash bench: resumed job failed: {done}")
        with open(done["output_path"], "rb") as fp:
            got = fp.read()
        if got != want:
            raise RuntimeError(
                "crash bench: resumed output is NOT byte-identical "
                "to the uninterrupted run — the crash/corruption "
                "path broke encode determinism")
        snap = call("/metrics_snapshot")["work"]
        resumed = int(snap["resumed"])
        total = int(done["parts_total"])
        reuse_pct = 100.0 * resumed / max(1, total)
        rejects = pre_rejects + int(snap["integrity_rejects"])
        if rejects != 2:
            raise RuntimeError(
                f"crash bench: {rejects} integrity rejects for 2 "
                f"injected corruptions — a flip went unnoticed (or "
                f"was double-counted)")
        if reuse_pct < 50.0:
            raise RuntimeError(
                f"crash bench: only {reuse_pct:.0f}% of shards "
                f"rehydrated from the spool (want >= 50%) — resume "
                f"re-encoded finished work")
        return {
            "reuse_pct": round(reuse_pct, 1),
            "recovery_s": round(recovery_s, 2),
            "integrity_rejects": rejects,
            "resumed_shards": resumed,
            "total_shards": total,
        }
    finally:
        for wp in worker_procs:
            if wp.poll() is None:
                wp.kill()
                wp.wait(timeout=10)
        if coord.poll() is None:
            coord.send_signal(_signal.SIGTERM)
            try:
                coord.wait(timeout=15)
            except subprocess.TimeoutExpired:
                coord.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def build_result(r1080: dict, r4k: dict, *, platform: str, qp: int,
                 gop: int, n_1080: int, cold: dict | None = None,
                 ladder: dict | None = None,
                 live: dict | None = None,
                 origin: dict | None = None,
                 sfe: dict | None = None,
                 sfe_farm: dict | None = None,
                 live_sfe: dict | None = None,
                 trace: dict | None = None,
                 autoscale: dict | None = None,
                 crash: dict | None = None,
                 rd: dict | None = None) -> dict:
    """Assemble the one-line BENCH JSON from the two resolutions' runs
    (kept separate from main() so tests can assert the schema — e.g.
    the `stage_ms` breakdown and the `fps_cold_1080p` cold figure — on
    a small CPU run)."""
    out = {
        "metric": "h264_gop_1080p_fps",
        "value": round(r1080["fps"], 2),
        "unit": "fps",
        "vs_baseline": round(r1080["fps"] / 30.0, 3),
        "platform": platform,
        "device_gop_fps": round(r1080["device_fps"], 2),
        "fps_2160p": round(r4k["fps"], 2),
        "device_gop_fps_2160p": round(r4k["device_fps"], 2),
        "bits_per_frame": round(r1080["bytes"] * 8 / n_1080),
        # host-boundary gap: 1.0 means e2e keeps pace with the device
        # GOP rate; the ISSUE 4 target is >= 0.8 at 1080p
        "host_gap_1080p": round(r1080["fps"] / r1080["device_fps"], 3),
        "host_gap_2160p": round(r4k["fps"] / r4k["device_fps"], 3),
        "d2h_bytes_per_frame": round(
            r1080["stage_ms"].get("d2h_bytes", 0) / n_1080),
        "qp": qp,
        "gop_frames": gop,
        "frames": n_1080,
        "stage_ms": r1080["stage_ms"],
        **r1080["quality"],
        **{f"{k}_2160p": v for k, v in r4k["quality"].items()},
    }
    if cold is not None:
        out["fps_cold_1080p"] = round(cold["fps"], 2)
        out["stage_ms_cold"] = cold["stage_ms"]
    if ladder is not None:
        # aggregate frames·rungs/s for the ABR ladder (one decode +
        # upload shared across all rungs) + per-rung bits/frame
        out["ladder_fps_1080p"] = round(ladder["fps"], 2)
        out["ladder_rungs"] = ladder["rungs"]
        out["ladder_bits_per_frame"] = ladder["rung_bits_per_frame"]
    if live is not None:
        # glass-to-playlist latency of the live LL-HLS pipeline
        # (median + p99 over the stream's announced parts), the final
        # DVR-window depth, and the paced ingest rate for context
        out["live_latency_s"] = round(live["latency_s"], 3)
        out["live_latency_p99_s"] = round(live["latency_p99_s"], 3)
        out["live_dvr_segments"] = live["dvr_segments"]
        out["live_segment_s"] = live["segment_s"]
        out["live_ingest_fps"] = live["ingest_fps"]
    if sfe is not None:
        # split-frame encoding: the single-stream 4K line. Latency is
        # the per-frame glass-to-bitstream pipeline gap (p50/p99 over
        # the run's steady-state frames); fps_2160p reports the BEST
        # single-stream path and names which one won, so the headline
        # can only improve when SFE engages (sfe_bands devices > 1)
        # and stays honest on a single chip.
        out["sfe_fps_2160p"] = round(sfe["fps"], 2)
        out["sfe_latency_ms_2160p"] = sfe["latency_ms_p50"]
        out["sfe_latency_p99_ms_2160p"] = sfe["latency_ms_p99"]
        out["sfe_bands"] = sfe["bands"]
        out["sfe_halo_rows"] = sfe["halo_rows"]
        if sfe["fps"] > r4k["fps"]:
            out["fps_2160p"] = round(sfe["fps"], 2)
            out["fps_2160p_path"] = "sfe"
        else:
            out["fps_2160p_path"] = "gop_wave"
    if sfe_farm is not None:
        # farm SFE: the single-stream worker-count scaling curve — one
        # stream's bands spread across N worker hosts with per-frame
        # halo exchange over the coordinator relay. The ratio between
        # counts is the headline (2-worker >= 1.5x 1-worker is the
        # acceptance bar); absolute values are CPU-worker figures.
        for wc in sorted(sfe_farm["workers"]):
            out[f"sfe_fps_2160p_w{wc}"] = round(
                sfe_farm["workers"][wc], 2)
    if live_sfe is not None:
        # glass-to-playlist latency with the live edge running BANDED
        # (single-rung stream + sfe_bands: per-frame SFE stepping
        # instead of whole-GOP waves at the edge)
        out["live_sfe_latency_s"] = round(live_sfe["latency_s"], 3)
        out["live_sfe_latency_p99_s"] = round(
            live_sfe["latency_p99_s"], 3)
    if trace is not None:
        # distributed-tracing cost on the e2e hot path (spans recorded
        # per stage per wave): must stay < 3%, and tracing must not
        # change a single output byte (the measurement raises if it
        # does)
        out["trace_overhead_pct"] = trace["overhead_pct"]
    if origin is not None:
        # origin-at-scale: concurrent HLS player sessions the origin
        # sustained error-free over the load window, MEASURED segment
        # fetch latency percentiles, and the live pipeline's
        # glass-to-playlist latency while carrying that viewer load
        out["origin_sessions_sustained"] = origin["sessions_sustained"]
        out["origin_p99_segment_ms"] = origin["p99_segment_ms"]
        out["origin_p50_segment_ms"] = origin["p50_segment_ms"]
        out["origin_requests"] = origin["requests"]
        out["live_latency_under_load_s"] = \
            origin["live_latency_under_load_s"]
    if autoscale is not None:
        # elastic farm under chaos (real worker subprocesses scaled by
        # the capacity controller while the loadgen chaos harness
        # kills workers and partitions /work): p99 queued→dispatched
        # wait, and worker-seconds consumed vs. the always-on farm —
        # the measurement inside raises unless every job reached DONE
        # byte-identical AND the farm breathed below always-on
        out["autoscale_p99_queue_s"] = autoscale["p99_queue_s"]
        out["farm_active_worker_s"] = autoscale["active_worker_s"]
        out["farm_alwayson_worker_s"] = autoscale["alwayson_worker_s"]
        out["autoscale_jobs_done"] = autoscale["jobs_done"]
        out["chaos_worker_kills"] = autoscale["kills"]
        out["chaos_partitions"] = autoscale["partitions"]
    if rd is not None:
        # rate-distortion gate (ROADMAP r4): bits/frame + PSNR-Y +
        # VMAF-proxy with the RD feature set ON vs OFF on the same
        # 1080p clip (one RD data point per config, recon == decode by
        # conformance). vmaf_1080p is the serving-quality headline:
        # the ON config's proxy score.
        out["rd_qp"] = rd["qp"]
        out["rd_gop_frames"] = rd["gop_frames"]
        out["rd_bits_per_frame"] = rd["on"]["bits_per_frame"]
        out["rd_psnr_y"] = rd["on"]["psnr_y"]
        out["rd_ssim_y"] = rd["on"]["ssim_y"]
        out["rd_bits_per_frame_off"] = rd["off"]["bits_per_frame"]
        out["rd_psnr_y_off"] = rd["off"]["psnr_y"]
        out["rd_ssim_y_off"] = rd["off"]["ssim_y"]
        out["vmaf_1080p"] = rd["on"]["vmaf_proxy"]
        out["vmaf_1080p_off"] = rd["off"]["vmaf_proxy"]
    if crash is not None:
        # durable shard checkpointing under coordinator SIGKILL + data
        # corruption: shards rehydrated from the verified spool (work
        # NOT re-encoded on the crashed run), restart-to-progress
        # recovery time, and the injected-corruption reject count —
        # the measurement inside raises unless the resumed output is
        # byte-identical, reuse >= 50% and rejects == injected flips
        out["crash_resume_shard_reuse_pct"] = crash["reuse_pct"]
        out["coordinator_recovery_s"] = crash["recovery_s"]
        out["part_integrity_rejects"] = crash["integrity_rejects"]
    return out


def main() -> None:
    import jax

    from thinvids_tpu.core.devices import configure_compile_cache

    configure_compile_cache()
    platform = jax.devices()[0].platform
    qp, gop = 27, 8

    # 64 frames = 8 GOPs = two full 4-GOP waves: every timed wave runs
    # the same compiled shape (no tail-wave recompile skew).
    n_1080 = 64
    r1080 = _run_pipeline(1920, 1080, n_1080, qp, gop)

    # Cold figure: the same clip through the production streaming
    # ingest (decode from disk overlapped with device compute) — the
    # wave-shape compiles are already warm from the resident run.
    r_cold = _run_cold(1920, 1080, n_1080, qp, gop)

    # Tracing overhead: the same e2e 1080p path with a span recorder
    # bound vs not — the acceptance gate is < 3%, byte parity asserted
    # inside the measurement.
    r_trace = _run_trace_overhead(1920, 1080, n_1080, qp, gop)

    # ABR ladder: the 4-rung production workload (1080/720/480/360)
    # over the same 1080p content, aggregate frames·rungs/s.
    r_ladder = _run_ladder(1920, 1080, n_1080, qp, gop)

    # Live LL-HLS: glass-to-playlist latency over a paced 1080p 2-rung
    # live job (48 frames = 6 GOP parts = 3 media segments).
    r_live = _run_live(1920, 1080, 48, qp, gop)

    # Origin at scale: N concurrent player sessions (loadgen_sessions,
    # default 500) replayed against a served VOD ladder while a live
    # job encodes — serving happens over HTTP, so the program content
    # stays small and the measured quantity is the ORIGIN, not the
    # encoder.
    r_origin = _run_origin(320, 180, 48, qp, gop)

    # Elastic farm under chaos: the capacity controller scales real
    # worker subprocesses (CPU devices — tiny frames, the measured
    # quantity is the CONTROL PLANE) against a diurnal submission
    # curve with worker kills and a /work partition; raises unless
    # every job lands DONE byte-identical and the farm breathes.
    r_autoscale = _run_autoscale(64, 48, 16, qp, 2)

    # Durable checkpointing under chaos: SIGKILL a subprocess
    # coordinator mid-farm-job, corrupt one in-flight upload and one
    # spooled part, restart, and measure shard reuse + recovery time;
    # raises unless the resumed output is byte-identical and every
    # injected corruption was rejected before stitch.
    r_crash = _run_crash_resume(64, 48, 24, qp, 2)

    # Rate-distortion gate (ROADMAP r4): the RD feature set on vs off
    # at the serving operating point (qp 25, production gop_frames 32;
    # the throughput figures above keep the historical qp 27 / gop 8
    # for cross-round comparability). The ON point must land at
    # <= 300k bits/frame with PSNR-Y >= 36.5 simultaneously.
    r_rd = _run_rd(1920, 1080, 32, 25, 32)

    # 4K rides with quality ON (psnr_y_2160p/ssim_y_2160p): 16 frames
    # keeps the untimed oracle decode affordable.
    n_4k = 16
    r4k = _run_pipeline(3840, 2160, n_4k, qp, gop, quality=True)

    # Split-frame encoding: the 4K SINGLE-STREAM line — per-frame
    # glass-to-bitstream latency + fps with every frame sharded across
    # the mesh as band slices (one band per local device).
    r_sfe = _run_sfe(3840, 2160, n_4k, qp, gop)

    # Farm SFE scaling: the SAME single 4K stream across 1/2/4 worker
    # subprocesses (one band slice each, halo per frame over the
    # coordinator relay) — the N-hosts→one-stream-speedup curve.
    r_sfe_farm = _run_sfe_farm(3840, 2160, 8, qp, gop)

    # Live with a banded edge: single-rung live stream whose edge GOP
    # steps through the SFE pipeline (per-frame latency) — the
    # glass-to-playlist figure for the SFE live path.
    r_live_sfe = _run_live(1920, 1080, 48, qp, gop,
                           rungs_spec="1080", sfe_bands=4)

    print(json.dumps(build_result(r1080, r4k, platform=platform, qp=qp,
                                  gop=gop, n_1080=n_1080, cold=r_cold,
                                  ladder=r_ladder, live=r_live,
                                  origin=r_origin, sfe=r_sfe,
                                  sfe_farm=r_sfe_farm,
                                  live_sfe=r_live_sfe,
                                  trace=r_trace,
                                  autoscale=r_autoscale,
                                  crash=r_crash, rd=r_rd)))


if __name__ == "__main__":
    main()
