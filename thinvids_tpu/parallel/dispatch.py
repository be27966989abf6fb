"""shard_map GOP dispatch: one GOP per mesh device per wave.

The reference's dispatch loop enqueued one encode task per segment onto a
Redis-backed queue consumed by worker nodes (/root/reference/worker/
tasks.py:1167-1281); here a wave of GOPs is one SPMD program over the mesh:
frames live HBM-resident per device, each device encodes its GOP as an
IDR frame and a chain of P frames (codecs/h264/jaxinter), and the
quantized levels return to host for entropy packing. Encoded segments
concat in index order; bit-identity with the single-device encode is
asserted by tests/test_parallel.py on an 8-device virtual mesh.

A wave is the pipeline's unit: ONE GOP per mesh device (`gops_per_wave`
1), one program shape per clip (every GOP is staged to the plan's
longest by tail-repeat; a plan made on scene cuts, whose GOPs are as
long as their shots, hands each GOP's real length to the program, which
encodes no frame past it). More GOPs per wave buy no device time (18.70
ms per 1080p frame at 1x32, 18.71 at 4x32, ledger PR 28) and cost a job
a lead-in and a tail of a whole wave each, with the device idle. The
pipeline's order: wave n's fetch is STARTED (start_fetch: counts in,
payload slice enqueued) before wave n+1's program is enqueued — the
slice is itself a program on the device's compute queue and would wait
for all of wave n+1 — and wave n's unpack and pack run under its compute.

Host side, the pipeline is instrumented per stage (StageProfile): every
wave's source decode / staging (each frame written once into the
wave's host arrays + H2D upload) / dispatch / device wait / D2H fetch /
sparse unpack / unflatten / CAVLC pack / concat
wall-clock accumulates on the encoder and is exported through the
API's /metrics_snapshot (`stage_ms`). The entropy pack fans out
at SLICE granularity across a per-encoder pool sized by `pack_workers`
(TVT_PACK_WORKERS; default: all cores; threads spawn on demand and
retire with the encoder), decoupled from the collector-thread window
`pipeline_window` (TVT_PIPELINE_WINDOW).

Ingest is a pipelined stage, not a blocking prologue: `stage_waves`
accepts a streaming FrameSource (ingest.open_video) or a materialized
list and holds ONE decoded frame at a time: each is written, padded in
place, into its slot of the wave's host arrays as it is decoded
(_FrameCursor.take, _fill_wave), and :func:`background_stage` runs the
whole decode→write→upload chain on a staging thread up to `decode_ahead`
waves (TVT_DECODE_AHEAD) ahead of dispatch, overlapping source decode
with device compute: wave n+1's inputs are on the device when wave n
ends.

The device→host boundary has ONE wire: the wave's program packs each
GOP's two-tier sparse streams into one contiguous byte payload
(jaxcore._compact_stream; format in codecs/h264/layout.py) and the bulk
fetch moves its `used` prefix, one transfer thread per device shard on
a mesh so the per-transfer latency overlaps instead of serializing. A
wave whose levels leave the sparse budgets (grainy footage) ships the
levels its program already computed, whole, as 32-bit words
(_levels_as_words) — the one fallback; nothing is encoded twice.

Beside the GOP-wave encoder lives the split-frame mode
(:class:`SfeShardEncoder`, `sfe_bands`/TVT_SFE_BANDS): ONE frame
sharded across the mesh as horizontal MB-row bands — one device per
band, ME halos exchanged over the interconnect (lax.ppermute), each
band entropy-coded as its own slice — with a PER-FRAME dispatch/collect
path (the `sfe` stage) for single-stream glass-to-bitstream latency.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from collections import deque

from ..core.config import get_settings
from ..core.log import get_logging
# jax-free observability layer: the process-cumulative stage totals
# bridge into the Prometheus registry, and a bound span recorder (the
# executor wires one per traced job) turns every timed stage into a
# span in the job's distributed trace
from ..obs import metrics as obs_metrics, trace as obs_trace
from ..core.types import (BandPlan, ChromaFormat, EncodedSegment, Frame,
                          GopSpec, SegmentPlan, VideoMeta)
from ..codecs.h264 import jaxcore
from ..codecs.h264.encoder import (FrameLevels, _mode_policy, pack_slice,
                                   gop_slice_thunks_frames, unpack_mode16,
                                   gop_slice_thunks_planes)
from ..codecs.h264.headers import PPS, SPS
from ..codecs.h264.rdo import RD_OFF, RdConfig, rd_from_settings
from ..codecs.h264.stages import stage
# Transfer-layout contract (jax-free module): per-MB flat sizes + the
# zero-copy host unflattens.
from ..codecs.h264.layout import _INTRA_FLAT_MB as _INTRA_MB
from ..codecs.h264.layout import (_P_FLAT_MB, p_flat_mb, unflatten_gop,
                                  unflatten_gop_parts, unflatten_intra,
                                  unflatten_p_planes)
from .planner import plan_bands, plan_fixed_segments, plan_segments

_LOG = get_logging(__name__)


def default_mesh(devices=None) -> Mesh:
    devices = list(jax.devices()) if devices is None else list(devices)
    return Mesh(np.array(devices), ("gop",))


# ---- host-stage wall-clock instrumentation --------------------------------

#: canonical stage keys, in pipeline order (decode = pulling frames
#: from the ingest source; stage = writing each decoded frame into its
#: slot of the wave's host arrays, pad rows, pad columns and repeats
#: included, + the H2D upload, which is also clocked alone as upload —
#: all on the staging thread when background_stage wraps the generator;
#: the split-frame path's stage is its per-frame row pad + device_put;
#: scale = dispatching the device-side ABR downscale that derives
#: lower ladder rungs from the staged wave (abr/scale.py);
#: dense_retry = what a wave that left the sparse budgets costs the
#: host on top of the common path — split out of "fetch" so the fetch
#: number answers only "what does the COMMON bulk transfer cost". On
#: the GOP wave path that is dense_fetch alone: waiting for the copy
#: of the whole levels, which the wave's one program left on the device
#: and start_fetch re-worded (int16 pairs as int32 words) and sent. No
#: wave is encoded twice, so dense_reencode reads 0; it stays a key: the
#: benchmark's files still ask for it (PERF.md §7). The split-frame
#: escape fallback re-runs its steps dense and files steps, copies and
#: packs per frame under dense_retry alone;
#: sfe = the split-frame path's per-frame host leg: band unpack, band
#: slice pack, frame assembly; scenecut = the executor's one read of
#: the source's luma for scene cuts, parallel/scenecut.py, 0 when off)
STAGE_NAMES = ("decode", "stage", "upload", "scale", "dispatch",
               "device_wait", "fetch", "dense_retry", "dense_reencode",
               "dense_fetch", "sparse_unpack", "unflatten", "pack", "concat",
               "sfe", "halo", "scenecut")

#: monotonic counters riding in the same snapshot as the stage clocks:
#: dense_fallback_waves (waves that overflowed the sparse budgets and shipped
#: their levels dense), h2d_bytes (host→device bytes uploaded while staging
#: waves: once per wave whatever the ladder's rung count), stage_copy_bytes
#: (host bytes the staging thread copies between the decoder's planes and the
#: arrays a GOP wave uploads: the planes' share of h2d_bytes, each byte written
#: once), d2h_bytes (device→host bytes fetched), fetch_shards (per-shard
#: concurrent fetch transfers issued; 0 = every fetch was one blocking
#: device_get), sfe_frames (frames through the split-frame per-frame collect),
#: sparse_{blocks,values}_{used,budget} (blocks with a level and non-zero
#: values counted on the device, against what the sparse transfer buffers hold,
#: summed over every GOP or split-frame band collected; used / budget over 1
#: means the wave went dense, and the value count is then a lower bound: the
#: device counts values in the blocks it kept), scene_cuts /
#: scene_cuts_suppressed (cuts that began a GOP / came too soon after one),
#: wave_frames / pad_frames / pad_frames_skipped (GOP waves' frames staged /
#: repeats among them / repeats never encoded), mvs_coded / mvs_quarter (P
#: macroblocks' vectors handed to the packers / those of them with an odd
#: quarter-sample component: 0 under subpel="half"; count_vectors), p_mbs_coded
#: / p_mbs_intra (macroblocks of P pictures handed to the packers under p_intra
#: / those of them intra; count_kinds), i_mbs_coded / i_mbs_4x4 (macroblocks of
#: IDR pictures handed to the packers under intra4x4 / those of them Intra4x4;
#: count_i_kinds), unpack_ranges (runs of a compact
#: payload's level vector unpacked inside slice thunks: 2 + (frames packed - 1)
#: x (5, or 6 under p_intra) a GOP; 0 on a dense wave, a split-frame job or a
#: host without the native library)
STAGE_COUNTERS = ("dense_fallback_waves", "h2d_bytes", "stage_copy_bytes",
                  "d2h_bytes", "fetch_shards", "sfe_frames",
                  "sparse_blocks_used", "sparse_blocks_budget",
                  "sparse_values_used", "sparse_values_budget", "scene_cuts",
                  "scene_cuts_suppressed", "wave_frames", "pad_frames",
                  "pad_frames_skipped", "mvs_coded", "mvs_quarter",
                  "p_mbs_coded", "p_mbs_intra", "i_mbs_coded", "i_mbs_4x4",
                  "unpack_ranges")
#: last-value readings riding in the same snapshot: me_candidates (what
#: the motion search of the last GOP / step program called scores per
#: macroblock: `program_build`; 0 until one ran)
STAGE_GAUGES = tuple(obs_metrics.STAGE_GAUGES)


class StageProfile:
    """Thread-safe per-stage wall-clock accumulator for the host half of
    the wave pipeline. Stages overlap across pool threads, so per-stage
    sums can exceed elapsed time — they answer "where do host cycles
    go", not "what is the critical path".

    `mirror` (the process-wide cumulative profile) receives every add
    too, so /metrics_snapshot keeps a job's totals after its encoder is
    garbage-collected."""

    def __init__(self, mirror: "StageProfile | None" = None,
                 metrics: bool = False) -> None:
        self._lock = threading.Lock()
        self._ms = {k: 0.0 for k in STAGE_NAMES}
        self._counts = {k: 0 for k in STAGE_COUNTERS + STAGE_GAUGES}
        self._waves = 0
        self._mirror = mirror
        #: bridge into the obs/ metrics registry — set ONLY on the
        #: process-cumulative _TOTALS instance, so every add lands in
        #: the registry exactly once (per-encoder profiles mirror into
        #: _TOTALS, which forwards)
        self._metrics = bool(metrics)
        #: optional span recorder (obs/trace): the executor binds one
        #: per traced job, so each timed stage is a span of its trace
        self._tracer = None

    def set_tracer(self, recorder) -> None:
        """Bind (or clear, with None/an inert recorder) the span sink
        this profile's stage() blocks record into."""
        with self._lock:
            self._tracer = recorder if recorder is not None \
                and getattr(recorder, "enabled", False) else None

    def tracer(self):
        """The bound span recorder, or None (instrumentation sites
        that record spans outside a stage() block read this)."""
        return self._tracer

    def add(self, stage: str, seconds: float) -> None:
        with self._lock:
            self._ms[stage] = self._ms.get(stage, 0.0) + seconds * 1e3
        if self._metrics:
            obs_metrics.STAGE_SECONDS.labels(stage).inc(seconds)
        if self._mirror is not None:
            self._mirror.add(stage, seconds)

    def gauge(self, name: str, value: int) -> None:
        """Set a last-value reading (STAGE_GAUGES) of this snapshot."""
        with self._lock:
            self._counts[name] = int(value)
        if self._metrics:
            obs_metrics.STAGE_GAUGES[name].set(value)

    def bump(self, counter: str, n: int = 1) -> None:
        """Increment a monotonic counter (STAGE_COUNTERS) by `n`."""
        with self._lock:
            self._counts[counter] = self._counts.get(counter, 0) + int(n)
        if self._metrics:
            metric = obs_metrics.STAGE_COUNTER_TOTALS.get(counter)
            if metric is not None:
                metric.inc(n)
        if self._mirror is not None:
            self._mirror.bump(counter, n)

    @contextlib.contextmanager
    def stage(self, name: str, part_of: str | None = None, **tags):
        """Time a stage (and record its span; `tvt:<name>` in a live
        device profile). `part_of` names the stage this one is a part
        of: it gets the same seconds, the whole stays its parts' sum."""
        tracer = self._tracer
        t0_wall = time.time() if tracer is not None else 0.0
        t0 = time.perf_counter()
        try:
            with obs_trace.annotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            self.add(name, dt)
            if part_of is not None:
                self.add(part_of, dt)
            if tracer is not None:
                tracer.record(name, t0_wall, dt, **tags)

    def count_wave(self) -> None:
        with self._lock:
            self._waves += 1
        if self._metrics:
            obs_metrics.WAVES_TOTAL.inc()
        if self._mirror is not None:
            self._mirror.count_wave()

    def snapshot(self) -> dict:
        with self._lock:
            out = {k: round(v, 2) for k, v in self._ms.items()}
            out.update(self._counts)
            out["waves"] = self._waves
            return out


#: process-cumulative stage totals (every encoder mirrors into this;
#: the metrics flag bridges each add into the obs/ Prometheus registry)
_TOTALS = StageProfile(metrics=True)


def stage_snapshot() -> dict:
    """Process-cumulative stage_ms across every GopShardEncoder that ran
    here (the /metrics_snapshot exporter — running jobs' waves land as
    they complete, and finished jobs' totals persist)."""
    return _TOTALS.snapshot()


#: process-cumulative SFE per-frame latency samples (ms) — the gaps
#: between consecutive frames' bitstream-ready times across every
#: SfeShardEncoder that ran here (_note_frame_done). /metrics_snapshot
#: and the dashboard surface p50/p99 from this ring, and each sample
#: also observes the tvt_sfe_frame_latency_seconds histogram.
_SFE_LAT_MS: deque = deque(maxlen=4096)
#: guards ring iteration vs the collector threads' appends (a deque
#: mutated mid-iteration raises RuntimeError — the snapshot endpoint
#: must not 500 exactly while an SFE job is hot)
_SFE_LAT_LOCK = threading.Lock()


def frame_latency_percentiles() -> dict:
    """{"p50_ms", "p99_ms", "count"} over the recent SFE per-frame
    latency ring; {} when no SFE frame ever completed here."""
    with _SFE_LAT_LOCK:
        samples = sorted(_SFE_LAT_MS)
    pct = obs_metrics.percentiles(samples, {"p50_ms": 0.50,
                                            "p99_ms": 0.99})
    if not pct:
        return {}
    return {k: round(v, 1) for k, v in pct.items()} \
        | {"count": len(samples)}


class _FrameCursor:
    """Decoded frames for wave staging, pulled on demand from a
    materialized list or a streaming FrameSource (anything exposing
    ``iter_frames()``), each exactly once and in order.

    The GOP path takes ONE frame at a time (:meth:`take`): the staging
    loop writes it into its slot of the wave's host arrays and drops
    it, so a single decoded frame is resident whatever the clip's or
    the wave's length. The split-frame path keeps a sliding window of
    frames padded to macroblock multiples (:meth:`padded`), released
    below the staged GOP's end. Either way resident decoded frames
    stay bounded by one wave regardless of clip length (the paper's
    never-hold-a-whole-clip invariant)."""

    def __init__(self, frames, profile: StageProfile,
                 require_420: bool = False,
                 stats: dict | None = None) -> None:
        iter_fn = getattr(frames, "iter_frames", None)
        self._it = iter_fn() if iter_fn is not None else iter(frames)
        self._profile = profile
        self._require_420 = require_420
        self._stats = stats if stats is not None else {}
        self._buf: deque = deque()      # padded frames [lo, hi)
        self._lo = 0
        self._hi = 0

    def _pull(self, want: int) -> Frame:
        """The source's next frame (index `_hi`), under "decode"."""
        with self._profile.stage("decode"):
            try:
                f = next(self._it)
            except StopIteration:
                raise ValueError(
                    f"frame stream ended at {self._hi}, but the "
                    f"wave plan needs frame {want}") from None
        if self._require_420 and f.chroma is not ChromaFormat.YUV420:
            raise ValueError(
                f"GopShardEncoder supports only 4:2:0 input, got "
                f"{f.chroma.name}; convert before encoding")
        self._hi += 1
        resident = len(self._buf) + 1
        if resident > self._stats.get("peak_resident_frames", 0):
            self._stats["peak_resident_frames"] = resident
        return f

    def take(self, i: int) -> Frame:
        """Frame `i` as the source decoded it (no pad, no copy), handed
        over and NOT retained: every index is taken at most once, in
        rising order."""
        if i < self._hi:
            raise IndexError(
                f"frame {i} already released (the source stands at "
                f"{self._hi})")
        while True:
            f = self._pull(i)
            if self._hi > i:
                self._lo = self._hi
                return f

    def padded(self, i: int) -> Frame:
        """Frame `i` padded to macroblock multiples, kept in the window
        until released (must not be released already)."""
        if i < self._lo:
            raise IndexError(
                f"frame {i} already released (window starts at "
                f"{self._lo})")
        while self._hi <= i:
            self._buf.append(self._pull(i).padded(16))
        return self._buf[i - self._lo]

    def release_below(self, i: int) -> None:
        while self._lo < i and self._buf:
            self._buf.popleft()
            self._lo += 1


def _write_plane(slot: np.ndarray, plane: np.ndarray) -> None:
    """Write a decoded plane into its (PH, PW) slot of a wave's host
    array, padded in place by edge replication (core.types.
    pad_to_shape's result, without the array it would allocate)."""
    h, w = plane.shape
    slot[:h, :w] = plane
    if w < slot.shape[1]:
        slot[:h, w:] = plane[:, -1:]
    if h < slot.shape[0]:
        slot[h:] = slot[h - 1]


class _WaveArrays:
    """Host arrays of staged GOP waves that are free again, kept for
    the process's next wave of the same shapes, of this job or the
    next. A new array's pages fault in as they are first written: on
    the chip's host that is 2.96 ms a 1080p frame against 0.28 into
    memory the process already has (PERF.md §5), so a wave's arrays
    come back here once their upload has completed (_upload) and the
    next wave is written over them, every byte (_fill_wave). At most
    KEEP sets, all of the same shapes: a set of other shapes (another
    resolution, a luma-only pass) takes the store over."""

    KEEP = 2

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._free: list[list[np.ndarray]] = []

    def take(self, shapes: list[tuple]) -> list[np.ndarray]:
        """uint8 arrays of `shapes`: a free set, or new ones."""
        with self._lock:
            if self._free and [a.shape for a in self._free[-1]] == shapes:
                return self._free.pop()
        return [np.empty(shape, np.uint8) for shape in shapes]

    def give(self, arrays: list[np.ndarray]) -> None:
        """`arrays` are free: nothing reads them any more."""
        shapes = [a.shape for a in arrays]
        with self._lock:
            if self._free and [a.shape for a in self._free[-1]] != shapes:
                self._free.clear()
            if len(self._free) < self.KEEP:
                self._free.append(arrays)


_WAVE_ARRAYS = _WaveArrays()


def background_stage(staged_waves, decode_ahead: int = 2):
    """Run a staging generator (stage_waves: source decode + one write
    of each frame into the wave's host arrays + H2D upload) on its own
    thread, up to `decode_ahead` staged waves ahead of the consumer —
    ingest becomes a pipelined stage that overlaps device compute
    instead of a blocking prologue on the dispatch thread.

    Each queued wave is ALREADY H2D-uploaded: device-side input
    residency is the consumer's in-flight window plus `decode_ahead`
    (+1 blocked in the put) waves of HBM YUV arrays — size the knob
    against HBM headroom, not just source latency.

    Returns a generator yielding the staged tuples in order; close()
    (or exhaustion, or an exception propagating out) stops the staging
    thread and releases its decode window. Exceptions raised while
    staging (bad chroma, truncated source) re-raise at the consumer's
    next pull."""
    import queue as queue_mod

    q: queue_mod.Queue = queue_mod.Queue(max(1, int(decode_ahead)))
    stop = threading.Event()
    done = object()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue_mod.Full:
                continue
        return False

    def feed() -> None:
        try:
            for staged in staged_waves:
                if not _put(staged):
                    return
            _put(done)
        except BaseException as exc:    # noqa: BLE001 - relay to consumer
            _put(exc)
        finally:
            close = getattr(staged_waves, "close", None)
            if close is not None:
                close()

    thread = threading.Thread(target=feed, daemon=True, name="tvt-stage")

    def drain():
        thread.start()
        try:
            while True:
                item = q.get()
                if item is done:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()

    return drain()


def _per_gop_sparse(y, u, v, qp, mbw: int, mbh: int, rd=RD_OFF,
                    n_frames=None):
    """(F, H, W) GOP → (mv8, dense, nblk, nval, n_esc, used, payload,
    flat): mv int8, the dense intra-DC segments, and the two-tier
    sparse levels of the rest folded into one contiguous byte payload
    (jaxcore._compact_stream) with its counts; `n_frames` as
    encode_gop_planes'.

    BOTH intra hadamard DC segments — luma DC (nmb * 16) and chroma DC
    (nmb * 8), ~390 KB combined at 1080p — ship DENSE: hadamard DC
    levels are the only ones that exceed int8 at practical QPs (chroma
    DC crosses at QP <~ 20), and the sparse pack has no escape
    side-channel (full-size scatters, the op PR 25 took out of the
    pack, would carry it) — an escape anywhere forces the wave-wide dense
    fallback, so low-QP encodes would otherwise fall permanently into
    the slow path (ADVICE round 5).

    The LAST output is `flat` itself: the GOP's whole
    int16 levels, as encode_gop_planes built them. It stays on the
    device (dispatch_wave starts no copy of it) and start_fetch either
    drops it — the budgets held, which is every wave of ordinary
    content — or sends it to the host as the dense fallback: a wave
    that leaves the budgets ships what its one program already
    computed, and nothing is encoded twice."""
    from ..codecs.h264 import jaxinter

    mv8, flat = jaxinter.encode_gop_planes(y, u, v, qp, mbw=mbw, mbh=mbh,
                                           rd=rd, n_frames=n_frames)
    nmb = mbw * mbh
    ndc, nlac, ncdc = nmb * 16, nmb * 240, nmb * 8
    with stage("pack"):
        dense_parts = [flat[:ndc], flat[ndc + nlac:ndc + nlac + ncdc]]
        if rd.ships_modes:
            # intra [mode16 | dqp16 (| block modes)] tail rides the dense
            # prefix (small, and mode 0 = V would defeat the sparse pack)
            tail = nmb * rd.intra_tail_mb
            dense_parts.append(flat[-tail:])
            rest = jnp.concatenate([flat[ndc:ndc + nlac],
                                    flat[ndc + nlac + ncdc:-tail]])
        else:
            rest = jnp.concatenate([flat[ndc:ndc + nlac],
                                    flat[ndc + nlac + ncdc:]])
        dense = jnp.concatenate(dense_parts)
    nblk, nval, n_esc, bitmap, bmask16, vals = \
        jaxcore._block_sparse_pack2(rest)
    used, payload = jaxcore._compact_stream(nblk, nval, bitmap, bmask16,
                                            vals)
    return (mv8, dense, nblk, nval, n_esc, used, payload, flat)


@stage("layout")
def _map_gops(one, xs):
    """`lax.map` over a device's GOPs. The scope names the loop
    itself; the stages inside `one` keep their own names
    (codecs/h264/stages.py)."""
    return jax.lax.map(one, xs)


@functools.partial(jax.jit, static_argnames=("mbw", "mbh", "mesh", "rd"))
def _encode_wave_gop(ys, us, vs, qps, n_frames=None, *, mbw: int, mbh: int,
                     mesh: Mesh, rd=RD_OFF):
    """ys: (G, F, H, W) uint8 sharded over `gop`, G = devices x k; each
    device sequentially encodes its k GOPs (IDR + P, jaxinter) at its
    per-GOP QP (qps: (G,) int32, the rate-control hook) and sparse-packs
    the plane-layout levels (_per_gop_sparse). `n_frames` as
    _encode_gop_single's: each device's loop has its own GOP's bound."""

    def per_dev(y_g, u_g, v_g, qp_g, n_g):
        def one(args):
            y, u, v, qp, n = args
            return _per_gop_sparse(y, u, v, qp, mbw, mbh, rd=rd,
                                   n_frames=n)
        return _map_gops(one, (y_g, u_g, v_g, qp_g, n_g))

    shard = shard_map(
        per_dev, mesh=mesh,
        in_specs=(P("gop"),) * 5,        # n_frames None: no leaf, no spec
        out_specs=(P("gop"),) * 8,
    )
    return shard(ys, us, vs, qps, n_frames)


@functools.partial(jax.jit, static_argnames=("mbw", "mbh", "rd"))
def _encode_gop_single(ys, us, vs, qps, n_frames=None, *, mbw: int,
                       mbh: int, rd=RD_OFF):
    """Single-device wave: the same per-GOP program WITHOUT the
    shard_map wrapper, which on one chip buys nothing and cost a lot
    under an older jax (compile 33 s → 810 s on a v5e; under jax 0.9.0
    the two forms compile alike, 66.5 s vs 65.3 s for 2 GOPs x 20 1080p
    frames, PR 21). `n_frames` ((G,) int32 beside `qps`, from a plan
    made on scene cuts and no other): each GOP's real length; its P
    frames past it are not encoded (jaxinter._loop_p_frames)."""
    def one(args):
        y, u, v, qp, n = args
        return _per_gop_sparse(y, u, v, qp, mbw, mbh, rd=rd, n_frames=n)
    return _map_gops(one, (ys, us, vs, qps, n_frames))


#: levels in a row of the re-wording program (_levels_as_words): the
#: matrix unit permutes within a row, 256 lanes of it at a time
_WORD_ROW = 256


@jax.jit
@stage("pack")
def _levels_as_words(levels):
    """A wave's whole levels re-worded for the link: (..., L) int16 →
    (..., ceil(L / 256) * 128) int32, word k holding level 2k in its
    low half and level 2k + 1 in its high half — the same bytes in the
    same order on a little-endian host, where `words.view(np.int16)` is
    the levels again, followed by under a row of zeros. No value
    changes. The link moves 32-bit words seven times faster than a
    one-row int16 array, which it re-lays on the way (0.075 s against
    0.54 s for the 199 MB of a 1080p GOP, PERF.md §6 PR 37). A program
    of its own, enqueued by start_fetch for a wave that left the
    sparse budgets and for no other: the wave programs above are not
    touched, and a wave that holds the budgets never runs (or
    compiles) it. Every op keeps the leading dimensions, so a wave
    sharded over `gop` stays sharded.

    Neighbours in a row are a stride of 2 along the lanes, which the
    chip's vector unit takes slowly (11–26 ms a slice at 1080p, and
    `lax.bitcast_convert_type` of (L / 2, 2) pairs does not compile:
    the minor 2 is laid out on 128 lanes); its matrix unit permutes a
    row's lanes in passing. So each row of 256 levels, as unsigned
    16-bit values in f32, goes through one matmul with the permutation
    "evens, then odds" — exact at Precision.HIGHEST: every output is
    one product by 1 and a sum with zeros, of a value under 2**16."""
    *lead, L = levels.shape
    rows = -(-L // _WORD_ROW)
    pad = [(0, 0)] * len(lead) + [(0, rows * _WORD_ROW - L)]
    rowed = jnp.pad(levels, pad).reshape(*lead, rows, _WORD_ROW)
    half = _WORD_ROW // 2
    order = np.concatenate([np.arange(0, _WORD_ROW, 2),
                            np.arange(1, _WORD_ROW, 2)])
    evens_then_odds = jnp.asarray(
        np.eye(_WORD_ROW, dtype=np.float32)[:, order])
    u16 = (rowed.astype(jnp.int32) & 0xFFFF).astype(jnp.float32)
    turned = jax.lax.dot_general(
        u16, evens_then_odds, (((u16.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    words = turned[..., :half] | (turned[..., half:] << 16)
    return words.reshape(*lead, rows * half)


class _WaveFetch:
    """The fetch state of a dispatched wave, on its handle between
    :meth:`GopShardEncoder.dispatch_wave`,
    :meth:`GopShardEncoder.start_fetch` and
    :meth:`GopShardEncoder.collect_wave`. From dispatch: `dense`, the
    wave's whole int16 levels — the last output of its one program,
    left on the device with no copy started. From start_fetch: the
    tiny counts, whether the sparse budgets held, and either the
    payload's used prefixes already sliced on the device and on their
    way to the host (`dense` is then dropped and its HBM goes back) or,
    where the budgets did not hold, `dense` replaced by the levels as
    int32 words (:func:`_levels_as_words`; the int16 form goes once
    that program has read it) and those on their way likewise. The
    lock makes the step run once whoever comes first (the dispatch
    loop, or the wave's own collector thread)."""

    __slots__ = ("lock", "tiny", "sparse_ok", "payload", "dense")

    def __init__(self, dense) -> None:
        self.lock = threading.Lock()
        # tiny is set last: it says the step has run
        self.tiny = self.payload = None
        self.dense = dense
        self.sparse_ok = False


class GopShardEncoder:
    """Encode a clip as closed GOPs fanned across a device mesh."""

    def __init__(self, meta: VideoMeta, qp: int = 27, mesh: Mesh | None = None,
                 gop_frames: int = 32, max_segments: int = 200,
                 gops_per_wave: int = 1,
                 pack_workers: int | None = None,
                 pipeline_window: int | None = None,
                 decode_ahead: int | None = None,
                 rd: RdConfig | None = None):
        self.meta = meta
        self.qp = qp
        self.mesh = mesh if mesh is not None else default_mesh()
        self.gop_frames = gop_frames
        self.max_segments = max_segments
        #: GOPs encoded per device per wave (lax.map'd inside one
        #: program). One: the wave is the pipeline's unit, so a job's
        #: bare lead-in (decode + stage before the first program) and
        #: tail (unpack + pack after the last) are each one wave long,
        #: and a longer wave buys no device time (18.70 ms per 1080p
        #: frame at 1x32, 18.71 at 4x32: ledger PR 28). Analysis passes
        #: and tests may still batch more.
        self.gops_per_wave = max(1, int(gops_per_wave))
        self.sps = SPS(width=meta.width, height=meta.height,
                       fps_num=meta.fps_num, fps_den=meta.fps_den)
        self.pps = PPS(init_qp=qp)
        snap = get_settings()
        #: static RD feature set (codecs/h264/rdo.RdConfig): per-MB
        #: intra mode decision, P_Skip bias, in-loop deblocking,
        #: perceptual AQ. None resolves from settings (the
        #: mode_decision/pskip/deblock/aq_strength knobs) so every
        #: settings-built encoder — executor, remote worker, ladder,
        #: live — inherits the job's RD config without new plumbing.
        if rd is None:
            rd = rd_from_settings(snap)
        self.rd = rd
        #: slice-granular CAVLC pack threads (0/None in config = all
        #: cores). Decoupled from the wave window: the pack pool sizes
        #: to the HOST (cpu count), the window to device queue depth.
        if pack_workers is None:
            pack_workers = int(snap.get("pack_workers", 0) or 0)
        self.pack_workers = int(pack_workers) or (os.cpu_count() or 2)
        #: collector-thread window: outputs of this many waves may be
        #: in fetch / unpack / pack at once (encode_waves). The device
        #: queue itself holds one GOP-wave program at a time (the order
        #: rule, start_fetch); split-frame encoders dispatch this many
        #: GOPs ahead.
        if pipeline_window is None:
            pipeline_window = int(snap.get("pipeline_window", 0) or 0)
        self.pipeline_window = int(pipeline_window) or self.PIPELINE_WINDOW
        #: staged waves decoded + uploaded ahead of dispatch by the
        #: background staging thread (encode() / background_stage).
        #: ADDS to input HBM residency on top of the in-flight window
        #: (each staged-ahead wave is already uploaded).
        if decode_ahead is None:
            decode_ahead = int(snap.get("decode_ahead", 0) or 0)
        self.decode_ahead = int(decode_ahead) or self.DECODE_AHEAD
        #: per-stage host wall-clock (/metrics_snapshot `stage_ms`)
        self.stages = StageProfile(mirror=_TOTALS)
        #: streaming-ingest instrumentation: peak decoded frames the
        #: staging cursor held at once (tests assert the bound)
        self.staging_stats: dict = {"peak_resident_frames": 0}
        #: eager so concurrent collect_wave threads never race a lazy
        #: init; the executor spawns NO threads until first submit
        self._pack_pool = self._new_pack_pool()
        #: bulk-fetch transfer threads: one in-flight transfer per
        #: device shard so the per-fetch latency overlaps across the
        #: mesh instead of serializing (what that buys on a directly
        #: attached chip is not measured). None on single-device
        #: meshes (nothing to overlap — plain device_get).
        self._fetch_pool = self._new_fetch_pool()
        #: one warning per encoder when async D2H prefetch is refused
        #: (a platform where copy_to_host_async silently no-ops must be
        #: visible in the logs, not swallowed)
        self._async_copy_unavailable = False
        #: Optional per-GOP QP overrides (rate control): gop index → qp.
        #: GOPs absent from the map encode at the base `qp`; slice
        #: headers carry the delta vs PPS init_qp.
        self.gop_qp: dict[int, int] = {}
        #: Elastic-replan continuation: when encoding a clip SUFFIX on a
        #: rebuilt mesh, emitted GopSpecs shift by these so indices /
        #: frame ranges (and idr_pic_id) stay globally consistent with
        #: the segments already completed (cluster/executor.py).
        self.gop_index_offset = 0
        self.frame_offset = 0
        #: Externally supplied plan (remote shards, cluster/remote.py):
        #: the EXACT shard-local GOP boundaries to encode, bypassing the
        #: local planner so a worker reproduces the coordinator's global
        #: plan bit-for-bit regardless of its own device count.
        self.plan_override: SegmentPlan | None = None
        #: Scene cuts of the clip this encoder is about to plan (the
        #: `scenecut` setting; the executor sets them before it asks
        #: for the plan): frames that start a shot and so a GOP. None =
        #: the setting is off and the plan is the fixed balanced one.
        self.scene_cuts: tuple[int, ...] | None = None

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    def plan(self, num_frames: int) -> SegmentPlan:
        if self.plan_override is not None:
            return self.plan_override
        return plan_segments(num_frames, self.gop_frames, self.num_devices,
                             self.max_segments, cuts=self.scene_cuts)

    def stage_waves(self, frames):
        """Host-side staging generator: per-wave (G, F, H, W) device
        arrays (HBM-resident input is the design invariant — SURVEY.md
        §0: kernels run over HBM-resident YUV planes). Lazily, one wave
        per iteration, so a long clip never pins more than the pipeline
        window of waves in HBM.

        `frames` may be a materialized list or a streaming FrameSource
        (ingest.open_video); either way each frame is written ONCE, as
        it is decoded, into the wave's host arrays (_fill_wave) and
        those arrays are what is uploaded. Wrap the result in
        :func:`background_stage` — or use :meth:`encode` — to run the
        decode + write + H2D upload on a staging thread ahead of the
        dispatch loop. A wave of a plan made on scene cuts carries one
        array more, last: each GOP's real frame count (_wave_groups)."""
        for wave, full, F, cursor, real in self._wave_groups(frames,
                                                             encode=True):
            planes = self._fill_wave(cursor, wave, len(full), F, "yuv")
            qps = np.asarray([self.gop_qp.get(g.index, self.qp)
                              for g in full], np.int32)
            small = (qps,) if real is None else (qps, real)
            yield (wave, *self._upload(planes, small))

    def stage_luma_waves(self, frames):
        """Luma-only staging for analysis passes (rate control): chroma
        never leaves the host, halving the upload of a pass that only
        reads Y. Yields (wave, ys)."""
        for wave, full, F, cursor, _ in self._wave_groups(frames):
            yield (wave, *self._upload(
                self._fill_wave(cursor, wave, len(full), F, "y")))

    def _fill_wave(self, cursor: _FrameCursor, wave: list, G: int, F: int,
                   planes: str) -> list[np.ndarray]:
        """The wave's host arrays, one (G, F, PH, PW) uint8 array per
        plane of `planes` (chroma at half of each of PH, PW), every
        decoded frame written ONCE: as the source hands it over
        ("decode" clock) it goes into its slot, padded to macroblock
        multiples in place ("stage" clock), and is dropped. A GOP's
        tail repeats (to the wave's static F: the program's shape; what
        it encodes of them is the plan's to say, _wave_groups) and the
        pad GOPs past `wave`, up to G, are filled from the slot they
        repeat. Every byte of the arrays is written, so they may come
        from an earlier wave (_WAVE_ARRAYS); `stage_copy_bytes` counts
        what was written."""
        bufs: list[np.ndarray] = []
        for g, gop in enumerate(wave):
            for k, i in enumerate(range(gop.start_frame, gop.end_frame)):
                f = cursor.take(i)
                with self.stages.stage("stage"):
                    ph, pw = (-(-n // 16) * 16 for n in f.y.shape)
                    if not bufs:
                        bufs = _WAVE_ARRAYS.take([(G, F) + (
                            (ph, pw) if p == "y" else (ph // 2, pw // 2))
                            for p in planes])
                    elif bufs[0].shape[2:] != (ph, pw):
                        raise ValueError(
                            f"frame {i} pads to {pw}x{ph}, the wave's "
                            f"first to {bufs[0].shape[3]}x{bufs[0].shape[2]}")
                    for buf, p in zip(bufs, planes):
                        _write_plane(buf[g, k], getattr(f, p))
            if gop.num_frames < F:
                with self.stages.stage("stage"):
                    for buf in bufs:
                        buf[g, gop.num_frames:] = buf[g, gop.num_frames - 1]
        if len(wave) < G:
            with self.stages.stage("stage"):
                for buf in bufs:
                    buf[len(wave):] = buf[len(wave) - 1]
        self.stages.bump("stage_copy_bytes", sum(b.nbytes for b in bufs))
        return bufs

    def _upload(self, planes: list[np.ndarray], small: tuple = ()) -> list:
        """H2D: a filled wave's host arrays (and its `small` ones: QPs,
        lengths) as device arrays — clock `upload`, part of `stage`;
        counter `h2d_bytes`. Waits for the copies: the first program of
        a job cannot start before its inputs have arrived, and the
        planes' arrays are free for the next wave from here on
        (_WAVE_ARRAYS) — unless the backend made a device array a view
        of its host array, as the CPU client does with aligned ones:
        those stay the device's."""
        arrays = (*planes, *small)
        self.stages.bump("h2d_bytes", sum(a.nbytes for a in arrays))
        with self.stages.stage("upload", part_of="stage"):
            sent = [jnp.asarray(a) for a in arrays]
            for dev in sent:
                dev.block_until_ready()
        if not any(dev.unsafe_buffer_pointer() == host.ctypes.data
                   for dev, host in zip(sent, planes)):
            _WAVE_ARRAYS.give(planes)
        return sent

    def _wave_groups(self, frames, encode: bool = False):
        """Shared wave grouping: (wave, device-padded wave, static F,
        frame cursor, real lengths). Stacks into (G, F, ...) with
        tail-repeat padding to static F; the wave itself pads to a
        multiple of D gops (the pad GOPs are encoded then discarded).
        F is the longest GOP of the PLAN, not of the wave: the planner
        balances GOPs to `base` and `base + 1` frames, and a per-wave F
        would compile one program shape for each (the one repeated
        frame is encoded, and dropped by collect_wave). A plan made on
        scene cuts pins F to `frames_per_gop` (`pin_frames`): its GOP
        lengths follow the content, and a clip whose shots are all
        short would otherwise compile a program shape of its own. Such
        a plan's waves, where they go to the GOP programs (`encode`),
        come with `real`, the (G,) int32 frame counts
        of their GOPs: the program's P-frame loop stops there, so the
        repeats are staged and sent but not encoded. Every other wave
        has None in its place and runs the loop over all F frames: a
        job runs ONE of the two programs, chosen by its plan. Counted:
        `wave_frames` staged in all, `pad_frames` of them repeats,
        `pad_frames_skipped` of those not encoded. The cursor decodes
        frames on demand, each once; the caller takes them one at a
        time as it fills the wave's host arrays (_fill_wave)."""
        plan = self.plan(len(frames))
        cursor = _FrameCursor(frames, self.stages, require_420=encode,
                              stats=self.staging_stats)
        D = self.num_devices
        per_wave = D * self.gops_per_wave
        gops = list(plan.gops)
        F = max((g.num_frames for g in gops), default=0)
        if plan.pin_frames:
            F = max(F, plan.frames_per_gop)
        for wave_start in range(0, len(gops), per_wave):
            wave = gops[wave_start:wave_start + per_wave]
            pad_n = (-len(wave)) % D
            full = wave + [wave[-1]] * pad_n
            staged = len(full) * F
            self.stages.bump("wave_frames", staged)
            self.stages.bump("pad_frames",
                             staged - sum(g.num_frames for g in wave))
            real = None
            if plan.pin_frames and encode:
                real = np.asarray([g.num_frames for g in full], np.int32)
                self.stages.bump("pad_frames_skipped",
                                 staged - int(real.sum()))
            yield wave, full, F, cursor, real

    def encode(self, frames) -> list[EncodedSegment]:
        """Stream-encode: source decode + staging run on a background
        thread up to `decode_ahead` waves ahead (background_stage);
        dispatch/collect pipeline on the calling thread."""
        feed = background_stage(self.stage_waves(frames), self.decode_ahead)
        try:
            return self.encode_waves(feed)
        finally:
            feed.close()

    def dispatch_wave(self, staged: tuple) -> tuple:
        """Enqueue one staged wave's device compute (async); returns an
        opaque pending handle for :meth:`collect_wave`."""
        with self.stages.stage("dispatch"):
            # reald: the GOPs' real lengths, of a cut-aligned plan alone
            wave, ysd, usd, vsd, qpsd, *reald = staged
            ph, pw = ysd.shape[2], ysd.shape[3]
            mbh, mbw = ph // 16, pw // 16
            with program_build("bounded" if reald else "scan", self.rd,
                               ysd.shape):
                if self.num_devices == 1:
                    out = _encode_gop_single(ysd, usd, vsd, qpsd, *reald,
                                             mbw=mbw, mbh=mbh, rd=self.rd)
                else:
                    out = _encode_wave_gop(ysd, usd, vsd, qpsd, *reald,
                                           mbw=mbw, mbh=mbh, mesh=self.mesh,
                                           rd=self.rd)
            # The last output is the wave's whole int16 levels (199 MB
            # per 1080p GOP): it goes onto the handle, not among the
            # outputs the sparse path indexes, and NO copy of it is
            # started here — start_fetch drops it or sends it once the
            # counts say which.
            *out, dense = out
            if not self._async_copy_unavailable:
                for i, arr in enumerate(out):
                    # Start the device->host copies now, overlapped with
                    # the next wave's compute. The payload (index 6)
                    # is NOT prefetched: collect_wave fetches only its
                    # used prefix, and an async copy would drag the
                    # whole budget-padded buffer across the link anyway.
                    if i == 6:
                        continue
                    try:
                        arr.copy_to_host_async()
                    except Exception as exc:   # noqa: BLE001 - visible,
                        # once per encoder: a platform where async D2H
                        # no-ops must show up in the activity log, not
                        # silently serialize every fetch.
                        self._async_copy_unavailable = True
                        _LOG.warning(
                            "copy_to_host_async rejected (%s: %s); "
                            "device→host prefetch disabled for this "
                            "encoder", type(exc).__name__, exc)
                        break
            return (wave, ysd, usd, vsd, qpsd, mbw, mbh, out,
                    _WaveFetch(dense))

    def _new_pack_pool(self):
        """This encoder's slice-pack pool (threads spawn on demand up
        to pack_workers), or None for inline packing (pack_workers <=
        1). Shut down when the encoder is garbage-collected — a
        long-lived coordinator running many jobs must not accumulate
        parked pack threads."""
        if self.pack_workers <= 1:
            return None
        import concurrent.futures as cf
        import weakref

        pool = cf.ThreadPoolExecutor(self.pack_workers,
                                     thread_name_prefix="tvt-pack")
        weakref.finalize(self, pool.shutdown, False)
        return pool

    def _new_fetch_pool(self):
        """Per-shard D2H transfer threads (collect_wave), or None on a
        single-device mesh. Two slots per device so the next wave's
        shard fetches queue behind the current one's without a new
        round of pool growth."""
        if self.num_devices <= 1:
            return None
        import concurrent.futures as cf
        import weakref

        pool = cf.ThreadPoolExecutor(min(32, 2 * self.num_devices),
                                     thread_name_prefix="tvt-fetch")
        weakref.finalize(self, pool.shutdown, False)
        return pool

    def _slice_pool(self):
        return self._pack_pool

    #: payload fetch slice quantum cap (bytes): used prefixes round up
    #: to a quantum of max(256, min(this, PB // 8)) so the device-side
    #: slice shapes repeat across waves (each distinct shape
    #: jit-compiles once) instead of recompiling per wave — the
    #: PB // 8 term keeps the rounding proportional at small payloads,
    #: the cap bounds the over-fetch at < 64 KB per GOP at 4K scale.
    PAYLOAD_QUANTUM = 1 << 16

    def _fetch_bulk(self, arrays) -> list[np.ndarray]:
        """Bulk device→host fetch: one transfer per device shard, all
        shards of all arrays in flight at once on the fetch pool, so
        the per-transfer latency overlaps across the mesh instead of
        adding up. Plain blocking device_get on single-device meshes
        (nothing to overlap)."""
        arrays = list(arrays)
        pool = self._fetch_pool
        if pool is None:
            host = jax.device_get(arrays)
            self.stages.bump("d2h_bytes",
                             sum(int(a.nbytes) for a in host))
            return host
        futss = []
        for arr in arrays:
            shards = sorted(arr.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            self.stages.bump("fetch_shards", len(shards))
            futss.append([pool.submit(np.asarray, s.data)
                          for s in shards])
        host = []
        for futs in futss:
            parts = [f.result() for f in futs]
            a = parts[0] if len(parts) == 1 else np.concatenate(parts)
            self.stages.bump("d2h_bytes", int(a.nbytes))
            host.append(a)
        return host

    def _payload_cuts(self, payload, used) -> list[tuple]:
        """[(device array, cut)] per shard, in GOP order: payloads are
        fetched SLICED to their used prefix, max(used) bytes per GOP
        (rounded up to PAYLOAD_QUANTUM), not the whole padded buffer."""
        used = np.asarray(used)
        PB = payload.shape[1]
        q = max(256, min(self.PAYLOAD_QUANTUM, PB // 8))

        def cut(n) -> int:
            return min(PB, -(-max(int(n), 1) // q) * q)

        if self._fetch_pool is None:
            return [(payload, cut(used.max()))]
        shards = sorted(payload.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        self.stages.bump("fetch_shards", len(shards))
        cuts = []
        for s in shards:
            a = s.index[0].start or 0
            cuts.append((s.data, cut(used[a:a + s.data.shape[0]].max())))
        return cuts

    def _slice_payload_rows(self, payload, used) -> list:
        """Enqueue the payload slices ON THE DEVICE and start their
        copies to the host. A slice is a program on the compute queue:
        it runs after everything enqueued before it, so start_fetch
        enqueues it before the next wave's program. Returns the sliced
        device arrays for :meth:`_gather_payload_rows`."""
        parts = [d[:, :m] for d, m in self._payload_cuts(payload, used)]
        self._start_copies(parts)
        return parts

    def _start_copies(self, arrays) -> None:
        """Start the device→host copies of `arrays` (a platform that
        rejects async copies was logged by dispatch_wave)."""
        if not self._async_copy_unavailable:
            with contextlib.suppress(Exception):
                for arr in arrays:
                    arr.copy_to_host_async()

    def _gather_payload_rows(self, parts: list) -> list[np.ndarray]:
        """Host side of :meth:`_slice_payload_rows`: a 1-D uint8 row
        per GOP (row length >= that GOP's used bytes)."""
        return self._payload_rows([np.asarray(part) for part in parts])

    def _fetch_payload_rows(self, payload, used) -> list[np.ndarray]:
        """Slice and fetch in one go, a transfer thread per shard: the
        split-frame encoders' per-frame collect."""
        cuts = self._payload_cuts(payload, used)
        pool = self._fetch_pool
        if pool is None:
            return self._payload_rows([np.asarray(d[:, :m])
                                       for d, m in cuts])
        futs = [pool.submit(lambda d=d, m=m: np.asarray(d[:, :m]))
                for d, m in cuts]
        return self._payload_rows([f.result() for f in futs])

    def _payload_rows(self, hosts: list) -> list[np.ndarray]:
        self.stages.bump("d2h_bytes", sum(int(h.nbytes) for h in hosts))
        return [row for host in hosts for row in host]

    @staticmethod
    def _unpack_compact(payload_row: np.ndarray, nblk: int, nval: int,
                        used: int, L: int) -> np.ndarray:
        """Compact payload's used prefix → flat int16 levels (the
        native-or-numpy dispatch lives with the format contract,
        layout.unpack_compact_auto)."""
        from ..codecs.h264.layout import unpack_compact_auto

        return unpack_compact_auto(payload_row[:used], nblk, nval, L)

    def _level_sizes(self, F: int, nmb: int) -> tuple[int, int]:
        """(L, Lr) of one GOP's flat levels: the whole vector, and its
        sparse remainder once both intra hadamard DC segments (luma +
        chroma) and the side channel's tail, when shipped, go dense
        (_per_gop_sparse)."""
        tail = nmb * self.rd.intra_tail_mb
        L = nmb * _INTRA_MB \
            + (F - 1) * nmb * p_flat_mb(self.rd.p_intra) + tail
        return L, L - nmb * 16 - nmb * 8 - tail

    def _note_sparse_fill(self, nblk, nval, L: int,
                          budget_div: int = jaxcore._BLOCK_BUDGET_DIV,
                          val_div: int = jaxcore._VAL_BUDGET_DIV) -> None:
        """Count how full the sparse transfer buffers of the collected
        GOPs (or split-frame bands) were, from the counts the host
        holds anyway: `nblk` / `nval` per GOP against the budgets of a
        level vector of length `L`. `nval` undercounts once the blocks
        overflow (_block_sparse_pack2 counts the values of the blocks
        it kept), so past the block budget the value fill is a lower
        bound."""
        blocks, values = jaxcore.block_sparse2_budgets(L, budget_div,
                                                       val_div)
        prof, n = self.stages, int(np.size(nblk))
        prof.bump("sparse_blocks_used", int(np.sum(nblk)))
        prof.bump("sparse_blocks_budget", blocks * n)
        prof.bump("sparse_values_used", int(np.sum(nval)))
        prof.bump("sparse_values_budget", values * n)

    def start_fetch(self, pending: tuple) -> None:
        """First step of collecting a dispatched wave, split out so the
        dispatch loop can run it BEFORE it enqueues the next wave's
        program: wait for the tiny counts (they complete when the
        wave's compute does — `device_wait`), decide whether the sparse
        budgets held, and enqueue the payload's used-prefix slices with
        their copies to the host. The slice is a program on the
        compute queue: enqueued after the next wave's program it would
        wait for all of it, and the wave's unpack and pack with it
        (9.4 ms per frame of `fetch` in `hd-backlog`, ledger PR 28).
        The wave's whole int16 levels are on the device already, the
        last output of its one program (:class:`_WaveFetch`). Budgets
        held: the reference is dropped here, so the HBM goes back
        before the next wave's program is enqueued and the levels never
        cross. Budgets left: one small program re-words them here
        (:func:`_levels_as_words`: int16 pairs as int32 words, the form
        the link moves fast) — on the compute queue ahead of the next
        wave's program, as the payload slice is — and the copy of the
        words is started: the 199 MB of a 1080p GOP cross under the
        next wave's compute, and after a job's last wave, with nothing
        to hide under, in a seventh of the time the int16 form took
        (PERF.md §6 PR 37). No wave is encoded twice.
        Idempotent, and :meth:`collect_wave` performs it itself for
        callers that have not."""
        _wave, ysd, _usd, _vsd, _qpsd, mbw, mbh, out, fetch = pending
        with fetch.lock:
            if fetch.tiny is not None:
                return
            prof = self.stages
            tracer = prof.tracer()
            with (tracer.span("wave_fetch_start") if tracer is not None
                  else contextlib.nullcontext()):
                # Barrier on the tiny count outputs first: splits
                # "waiting on the device" from the bulk D2H fetch in
                # the stage breakdown — and lets a budget overflow
                # skip the bulk sparse fetch entirely.
                with prof.stage("device_wait"):
                    tiny = jax.device_get(list(out[2:6]))
                prof.bump("d2h_bytes", sum(int(a.nbytes) for a in tiny))
                _, Lr = self._level_sizes(ysd.shape[1], mbw * mbh)
                nblk, nval, n_esc, used = tiny
                self._note_sparse_fill(nblk, nval, Lr)
                fetch.sparse_ok = jaxcore.block_sparse2_fits(
                    nblk.max(), nval.max(), n_esc.max(), Lr)
                if fetch.sparse_ok:
                    fetch.payload = self._slice_payload_rows(out[6], used)
                    fetch.dense = None
                else:
                    with program_build("words", None, fetch.dense.shape):
                        fetch.dense = _levels_as_words(fetch.dense)
                    self._start_copies([fetch.dense])
            fetch.tiny = tiny

    def collect_wave(self, pending: tuple) -> list[EncodedSegment]:
        """Fetch one dispatched wave's levels — the payload's used
        prefixes, or the whole levels as words where the sparse budgets
        did not hold — and entropy-pack its GOPs on host, every slice
        of the wave a thunk on the slice pool."""
        self.start_fetch(pending)
        wave, ysd, _usd, _vsd, qpsd, mbw, mbh, out, fetch = pending
        prof = self.stages
        F = ysd.shape[1]
        ships_modes = self.rd.ships_modes
        L, Lr = self._level_sizes(F, mbw * mbh)
        nblk, nval, _n_esc, used = fetch.tiny
        if fetch.sparse_ok:
            with prof.stage("fetch"):
                mv8, dc16 = self._fetch_bulk(out[0:2])
                payload_rows = self._gather_payload_rows(fetch.payload)
        else:
            # Wave-wide dense fallback: the wide fetch of the levels
            # the wave's program left on the device, re-worded and sent
            # by start_fetch. Not rare on grainy footage: white grain of
            # sigma 3.5 at CQP 27 already fills the block budget
            # (jaxcore _VAL_BUDGET_DIV has the table), and every GOP of
            # such a clip comes through here. Its own stage (not
            # "fetch") so the fetch number answers only "what does the
            # common bulk transfer cost", plus a counter so
            # overflow-prone content is visible in metrics.
            prof.bump("dense_fallback_waves")
            with prof.stage("dense_fetch", part_of="dense_retry"):
                words = jax.device_get(fetch.dense)
                fetch.dense = None      # the device's copy may go
                # the levels again, no copy (less the last row's padding)
                flat = words.view(np.int16)[..., :L]
                prof.bump("d2h_bytes", int(flat.nbytes))
                # MVs come from the sparse outputs, as ever
                (mv8,) = self._fetch_bulk(out[0:1])
        # Header QP must match what the device QUANTIZED with — read it
        # from the staged per-wave array, not the live gop_qp dict (a
        # caller mutating gop_qp between passes must not desync slices
        # already in flight).
        qps_host = np.asarray(qpsd)
        if self.gop_index_offset or self.frame_offset:
            import dataclasses as _dc

            wave = [_dc.replace(g, index=g.index + self.gop_index_offset,
                                start_frame=(g.start_frame
                                             + self.frame_offset))
                    for g in wave]
        # Phase 1: SUBMIT every GOP's pack work — the slice pool packs
        # the whole wave's slices concurrently; phase 2 gathers in GOP
        # order. A compact payload is not unpacked here: this thread
        # validates and indexes it (`sparse_unpack`) and the thunk that
        # packs a slice unpacks its runs of the level vector
        # (_compact_gop_thunks): a GOP's first slice is with the pool a
        # millisecond after its payload. The dense fallback takes views.
        pool = self._slice_pool()
        jobs: list[tuple] = []
        for gi, gop in enumerate(wave):
            count_vectors(prof, mv8[gi][:gop.num_frames - 1], self.rd)
            # gop.num_frames (not F) drops the wave's tail-repeat
            # padding.
            slices = (gop.num_frames, mbw, mbh, self.sps, self.pps,
                      int(qps_host[gi]))
            if fetch.sparse_ok:
                thunks = _compact_gop_thunks(
                    prof, payload_rows[gi][:int(used[gi])], int(nblk[gi]),
                    int(nval[gi]), Lr, dc16[gi], mv8[gi], F, slices,
                    gop.index, self.rd)
            else:
                with prof.stage("unflatten"):
                    intra, planes = unflatten_gop(
                        flat[gi], mv8[gi], F, mbw, mbh,
                        ships_modes=ships_modes, p_intra=self.rd.p_intra,
                        intra4x4=self.rd.intra4x4)
                if self.rd.p_intra:
                    count_kinds(prof, planes[6][:gop.num_frames - 1])
                count_i_kinds(prof, intra, self.rd)
                thunks = gop_slice_thunks_planes(
                    intra, planes, *slices, idr_pic_id=gop.index,
                    rd=self.rd)
            if pool is None:
                jobs.append(
                    (gop, lambda ts=thunks: [t() for t in ts]))
            else:
                futs = [pool.submit(t) for t in thunks]
                jobs.append(
                    (gop, lambda fs=futs: [f.result() for f in fs]))
        segments: list[EncodedSegment] = []
        for gop, gather in jobs:
            with prof.stage("pack"):
                payload = gather()
            with prof.stage("concat"):
                seg = EncodedSegment(
                    gop=gop, payload=b"".join(payload),
                    frame_sizes=tuple(len(p) for p in payload))
            segments.append(seg)
        prof.count_wave()
        return segments

    #: default in-flight wave window when neither the constructor nor
    #: the `pipeline_window` setting (TVT_PIPELINE_WINDOW) override it.
    PIPELINE_WINDOW = 4

    #: default staged-waves-ahead depth for the background staging
    #: thread when neither the constructor nor the `decode_ahead`
    #: setting (TVT_DECODE_AHEAD) override it.
    DECODE_AHEAD = 2

    def encode_waves(self, waves, window: int | None = None,
                     pack_workers: int | None = None
                     ) -> list[EncodedSegment]:
        """Dispatch staged waves: device compute → sparse fetch → host
        entropy pack, in wave order.

        The order rule: wave n's fetch is started (:meth:`start_fetch`:
        its counts are in, its payload slice is enqueued) BEFORE wave
        n+1's program is enqueued, so the slice never queues behind a
        whole program and wave n's unpack and pack run under wave
        n+1's compute. The staged inputs of wave n+1 are on the device
        already (background_stage), so the device waits one host
        reaction at a boundary. Each wave's fetch + unpack runs on a
        collector thread — at most `window` (default: the
        `pipeline_window` setting) of them in flight — and every slice
        of every in-flight GOP packs on this encoder's `pack_workers`
        pool (collect_wave), so host packing scales with cores instead
        of with the window. An encoder whose start_fetch does nothing
        (the split-frame encoders: per-frame collect) keeps `window`
        waves dispatched ahead.
        """
        import concurrent.futures as cf

        window = window or self.pipeline_window
        if pack_workers is not None and int(pack_workers) != self.pack_workers:
            self.pack_workers = int(pack_workers)
            if self._pack_pool is not None:   # resize: retire the old pool
                self._pack_pool.shutdown(wait=False)
            self._pack_pool = self._new_pack_pool()
        segments: list[EncodedSegment] = []
        waves = iter(waves)
        pending: list[cf.Future] = []
        newest = None               # the last dispatched wave's handle

        with cf.ThreadPoolExecutor(window) as pool:
            def dispatch_next():
                nonlocal newest
                try:
                    staged = next(waves)
                except StopIteration:
                    return False
                if newest is not None:
                    self.start_fetch(newest)
                newest = self.dispatch_wave(staged)
                pending.append(pool.submit(self.collect_wave, newest))
                return True

            for _ in range(window):
                if not dispatch_next():
                    break
            while pending:
                segs = pending.pop(0).result()
                dispatch_next()
                segments.extend(segs)
        return segments


# ---------------------------------------------------------------------------
# split-frame encoding (SFE): shard ONE frame across the mesh
#
# All parallelism above is GOP-level — ideal for farm throughput,
# useless for the latency of a single stream (a 2160p frame still
# encodes on one chip). SFE instead splits every frame into horizontal
# MB-row bands, one device per band (parallel/planner.plan_bands), and
# steps ONE FRAME per device program: the recon carry chains between
# steps on device, motion estimation reads a halo of reference rows
# from the neighbor bands over the mesh interconnect
# (jaxme.band_halo_exchange → lax.ppermute), and every band
# entropy-codes as its own H.264 slice (first_mb_in_slice = band start)
# so the concat of a frame's band slices is a legal picture with no
# host-side re-mux. Per-frame latency divides by the band count
# instead of amortizing across GOPs — and a frame that doesn't fit one
# device's HBM (8K) fits as bands.
# ---------------------------------------------------------------------------


def _sfe_pack_band(flat):
    """Per-band compact transfer pack: two-tier sparse + byte-payload
    fold with UNIT budget divisors — the buffers are per-frame-band
    sized (small), the fetch moves only the used prefix, and the only
    overflow left is an int8 escape (n_esc > 0 → the GOP reruns dense,
    exactly the wave path's fallback contract)."""
    nblk, nval, n_esc, bitmap, bmask16, vals = \
        jaxcore._block_sparse_pack2(flat, 1, 1)
    used, payload = jaxcore._compact_stream(nblk, nval, bitmap, bmask16,
                                            vals)
    return nblk, nval, n_esc, used, payload


@functools.partial(jax.jit, static_argnames=("mbw", "mbh_band", "mesh",
                                             "rd", "total_mb_rows"))
def _sfe_intra_step(y, u, v, qp, real_rows, *, mbw: int, mbh_band: int,
                    mesh: Mesh | None, rd=RD_OFF, total_mb_rows: int = 0):
    """One IDR frame, banded: y/u/v are full (padded) frame planes
    sharded over rows; each band runs the slice-local intra core and
    compact-packs its level streams. Returns per-band transfer arrays
    (leading dim = bands) + the recon carry, row-sharded on device.
    `mesh=None` = single band, no shard_map wrapper (on one chip the
    manual-axes lowering costs and buys nothing — same rationale as
    _encode_gop_single); outputs keep the leading band dim of 1 so the
    host collect path is band-count agnostic."""
    from ..codecs.h264 import jaxinter

    def per_band(y_b, u_b, v_b, qp_, real_b):
        dense, rest, (ry, ru, rv, pmv) = jaxinter.sfe_intra_band(
            y_b, u_b, v_b, qp_, real_b[0, 0], mbw=mbw, mbh_band=mbh_band,
            rd=rd, total_mb_rows=total_mb_rows,
            axis_name="band" if mesh is not None else None,
            num_bands=mesh.devices.size if mesh is not None else 1)
        nblk, nval, n_esc, used, payload = _sfe_pack_band(rest)
        return (dense[None], nblk[None], nval[None], n_esc[None],
                used[None], payload[None], ry, ru, rv, pmv[None])

    if mesh is None:
        return per_band(y, u, v, qp, real_rows)
    shard = shard_map(
        per_band, mesh=mesh,
        in_specs=(P("band"), P("band"), P("band"), P(), P("band")),
        out_specs=(P("band"),) * 10)
    return shard(y, u, v, qp, real_rows)


@functools.partial(jax.jit, static_argnames=("mbw", "mbh_band", "mesh",
                                             "halo_rows", "num_bands",
                                             "rd", "total_mb_rows"))
def _sfe_p_step(y, u, v, ry, ru, rv, pmv, qp, real_rows, *, mbw: int,
                mbh_band: int, mesh: Mesh | None, halo_rows: int,
                num_bands: int, rd=RD_OFF, total_mb_rows: int = 0):
    """One P frame, banded: the halo exchange + psum'd search centers
    live inside jaxinter.sfe_p_band; this wrapper shards the frame and
    recon carry over rows and compact-packs each band's levels.
    `mesh=None` as in :func:`_sfe_intra_step`."""
    from ..codecs.h264 import jaxinter

    def per_band(y_b, u_b, v_b, ry_b, ru_b, rv_b, pmv_b, qp_, real_b):
        mv8, flat, (ry2, ru2, rv2, med) = jaxinter.sfe_p_band(
            y_b, u_b, v_b, (ry_b, ru_b, rv_b, pmv_b[0]), qp_,
            real_b[0, 0], mbw=mbw, mbh_band=mbh_band,
            halo_rows=halo_rows, num_bands=num_bands,
            axis_name="band" if mesh is not None else None,
            rd=rd, total_mb_rows=total_mb_rows)
        nblk, nval, n_esc, used, payload = _sfe_pack_band(flat)
        return (mv8[None], nblk[None], nval[None], n_esc[None],
                used[None], payload[None], ry2, ru2, rv2, med[None])

    if mesh is None:
        return per_band(y, u, v, ry, ru, rv, pmv, qp, real_rows)
    shard = shard_map(
        per_band, mesh=mesh,
        in_specs=(P("band"),) * 7 + (P(), P("band")),
        out_specs=(P("band"),) * 10)
    return shard(y, u, v, ry, ru, rv, pmv, qp, real_rows)


@functools.partial(jax.jit, static_argnames=("mbw", "mbh_band", "mesh",
                                             "rd", "total_mb_rows"))
def _sfe_intra_step_dense(y, u, v, qp, real_rows, *, mbw: int,
                          mbh_band: int, mesh: Mesh | None, rd=RD_OFF,
                          total_mb_rows: int = 0):
    """Escape fallback: the same intra step emitting the flat int16
    levels uncompressed (layout.unflatten_intra's inverse per band)."""
    from ..codecs.h264 import jaxinter

    def per_band(y_b, u_b, v_b, qp_, real_b):
        flat, (ry, ru, rv, pmv) = jaxinter.sfe_intra_band_dense(
            y_b, u_b, v_b, qp_, real_b[0, 0], mbw=mbw, mbh_band=mbh_band,
            rd=rd, total_mb_rows=total_mb_rows,
            axis_name="band" if mesh is not None else None,
            num_bands=mesh.devices.size if mesh is not None else 1)
        return flat[None], ry, ru, rv, pmv[None]

    if mesh is None:
        return per_band(y, u, v, qp, real_rows)
    shard = shard_map(per_band, mesh=mesh,
                      in_specs=(P("band"),) * 3 + (P(), P("band")),
                      out_specs=(P("band"),) * 5)
    return shard(y, u, v, qp, real_rows)


@functools.partial(jax.jit, static_argnames=("mbw", "mbh_band", "mesh",
                                             "halo_rows", "num_bands",
                                             "rd", "total_mb_rows"))
def _sfe_p_step_dense(y, u, v, ry, ru, rv, pmv, qp, real_rows, *,
                      mbw: int, mbh_band: int, mesh: Mesh | None,
                      halo_rows: int, num_bands: int, rd=RD_OFF,
                      total_mb_rows: int = 0):
    from ..codecs.h264 import jaxinter

    def per_band(y_b, u_b, v_b, ry_b, ru_b, rv_b, pmv_b, qp_, real_b):
        mv8, flat, (ry2, ru2, rv2, med) = jaxinter.sfe_p_band(
            y_b, u_b, v_b, (ry_b, ru_b, rv_b, pmv_b[0]), qp_,
            real_b[0, 0], mbw=mbw, mbh_band=mbh_band,
            halo_rows=halo_rows, num_bands=num_bands,
            axis_name="band" if mesh is not None else None,
            rd=rd, total_mb_rows=total_mb_rows)
        return mv8[None], flat[None], ry2, ru2, rv2, med[None]

    if mesh is None:
        return per_band(y, u, v, ry, ru, rv, pmv, qp, real_rows)
    shard = shard_map(per_band, mesh=mesh,
                      in_specs=(P("band"),) * 7 + (P(), P("band")),
                      out_specs=(P("band"),) * 6)
    return shard(y, u, v, ry, ru, rv, pmv, qp, real_rows)


# ---------------------------------------------------------------------------
# farm-split SFE steps (cross-HOST band slices, parallel/sfefarm.py)
#
# The local steps above run the halo exchange and the probe/median
# psums inside ONE program over the full band mesh. When the band
# layout spans HOSTS, the cross-host halves of those collectives move
# to the host side: neighbor reference rows arrive as injected inputs
# (cluster/halo.py carries them between hosts per frame), the probe
# splits into a per-host partial-cost program + a host-side argmin,
# and the median histogram leaves the device as a per-host partial.
# All three are integer sums, so host-side reduction is bit-identical
# to the device psum — the farm stream equals the local-mesh stream.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mesh", "num_bands"))
def _sfe_probe_step(cur_y, ref_y, real_rows, top_y, bot_y, edges, *,
                    mesh: Mesh | None, num_bands: int):
    """Per-host half of the split global-motion probe: each local
    band's partial per-window SAD cost, psum'd over THIS mesh only.
    Returns (num_bands, n*n) int32 — every row identical; the host
    ships row 0 to its peers and argmins the cross-host sum
    (jaxme.probe_center_from_cost). `edges` is the traced (2,) bool
    [edge_top, edge_bot] — an INPUT, not a static, so one compiled
    program serves a band slice at any position in the layout."""
    from ..codecs.h264 import jaxme

    def per_band(cur_b, ref_b, real_b, ty_b, by_b, edges_):
        cost = jaxme.banded_probe_cost(
            cur_b.astype(jnp.int16), ref_b, real_b[0, 0],
            "band" if mesh is not None else None, num_bands,
            top_ext=ty_b, bot_ext=by_b,
            edge_top=edges_[0], edge_bot=edges_[1])
        return cost[None]

    if mesh is None:
        return per_band(cur_y, ref_y, real_rows, top_y, bot_y, edges)
    shard = shard_map(per_band, mesh=mesh,
                      in_specs=(P("band"),) * 5 + (P(),),
                      out_specs=P("band"))
    return shard(cur_y, ref_y, real_rows, top_y, bot_y, edges)


@functools.partial(jax.jit, static_argnames=(
    "mbw", "mbh_band", "mesh", "halo_rows", "num_bands", "rd"))
def _sfe_p_step_farm(y, u, v, ry, ru, rv, pred_mv, probe, ty, by, tu,
                     bu, tv, bv, qp, real_rows, edges, *, mbw: int,
                     mbh_band: int, mesh: Mesh | None, halo_rows: int,
                     num_bands: int, rd=RD_OFF):
    """One P frame of a band SLICE: the search runs on halo-extended
    planes whose slice-edge rows were injected by the host (`ty..bv`,
    band-sharded — only the edge bands' shards are read), the probe
    center and temporal median arrive as replicated host inputs, and
    the per-host histogram partial rides out beside the compact level
    streams. `mesh=None` = single local band, as in the local steps.
    `edges` = traced (2,) bool [edge_top, edge_bot] (an input, not a
    static: a worker re-claiming a DIFFERENT band slice reuses the
    same compiled program)."""
    from ..codecs.h264 import jaxinter

    def per_band(y_b, u_b, v_b, ry_b, ru_b, rv_b, pred_, probe_, ty_b,
                 by_b, tu_b, bu_b, tv_b, bv_b, qp_, real_b, edges_):
        mv8, flat, cnt, n, (ry2, ru2, rv2, _pm) = jaxinter.sfe_p_band(
            y_b, u_b, v_b, (ry_b, ru_b, rv_b, pred_), qp_, real_b[0, 0],
            mbw=mbw, mbh_band=mbh_band, halo_rows=halo_rows,
            num_bands=num_bands,
            axis_name="band" if mesh is not None else None,
            ext=(ty_b, by_b, tu_b, bu_b, tv_b, bv_b),
            edge_top=edges_[0], edge_bot=edges_[1], probe=probe_,
            return_hist=True, rd=rd)
        nblk, nval, n_esc, used, payload = _sfe_pack_band(flat)
        return (mv8[None], nblk[None], nval[None], n_esc[None],
                used[None], payload[None], cnt[None],
                n.reshape(1), ry2, ru2, rv2)

    if mesh is None:
        return per_band(y, u, v, ry, ru, rv, pred_mv, probe, ty, by,
                        tu, bu, tv, bv, qp, real_rows, edges)
    shard = shard_map(
        per_band, mesh=mesh,
        in_specs=(P("band"),) * 6 + (P(), P()) + (P("band"),) * 6
        + (P(), P("band"), P()),
        out_specs=(P("band"),) * 11)
    return shard(y, u, v, ry, ru, rv, pred_mv, probe, ty, by, tu, bu,
                 tv, bv, qp, real_rows, edges)


@functools.partial(jax.jit, static_argnames=(
    "mbw", "mbh_band", "mesh", "halo_rows", "num_bands", "rd"))
def _sfe_p_step_farm_dense(y, u, v, ry, ru, rv, pred_mv, probe, ty, by,
                           tu, bu, tv, bv, qp, real_rows, edges, *,
                           mbw: int, mbh_band: int, mesh: Mesh | None,
                           halo_rows: int, num_bands: int, rd=RD_OFF):
    """Escape fallback for the farm P step: same compute, uncompressed
    int16 levels. The replay is host-local (the cached per-frame
    injected inputs fully determine this slice's bits), so no
    histogram needs to leave the device."""
    from ..codecs.h264 import jaxinter

    def per_band(y_b, u_b, v_b, ry_b, ru_b, rv_b, pred_, probe_, ty_b,
                 by_b, tu_b, bu_b, tv_b, bv_b, qp_, real_b, edges_):
        mv8, flat, _cnt, _n, (ry2, ru2, rv2, _pm) = jaxinter.sfe_p_band(
            y_b, u_b, v_b, (ry_b, ru_b, rv_b, pred_), qp_, real_b[0, 0],
            mbw=mbw, mbh_band=mbh_band, halo_rows=halo_rows,
            num_bands=num_bands,
            axis_name="band" if mesh is not None else None,
            ext=(ty_b, by_b, tu_b, bu_b, tv_b, bv_b),
            edge_top=edges_[0], edge_bot=edges_[1], probe=probe_,
            return_hist=True, rd=rd)
        return mv8[None], flat[None], ry2, ru2, rv2

    if mesh is None:
        return per_band(y, u, v, ry, ru, rv, pred_mv, probe, ty, by,
                        tu, bu, tv, bv, qp, real_rows, edges)
    shard = shard_map(
        per_band, mesh=mesh,
        in_specs=(P("band"),) * 6 + (P(), P()) + (P("band"),) * 6
        + (P(), P("band"), P()),
        out_specs=(P("band"),) * 5)
    return shard(y, u, v, ry, ru, rv, pred_mv, probe, ty, by, tu, bu,
                 tv, bv, qp, real_rows, edges)


class SfeShardEncoder(GopShardEncoder):
    """Split-frame encoding: ONE frame sharded across the mesh as
    horizontal MB-row bands, each entropy-coded as its own H.264 slice.

    The GOP walk is sequential (this is the single-stream latency mode
    — GOP-level parallelism is the parent class); within a GOP, frames
    step one device program at a time with the recon carry resident on
    device, and the collect path is PER FRAME: a frame's band levels
    are fetched and its band slices packed (concurrently on the pack
    pool) as soon as its step completes, while the device runs the
    next frame — the gap between consecutive frames' bitstream-ready
    times is the per-frame latency (`_note_frame_done`, read through
    :func:`frame_latency_percentiles`).

    A "wave" for the executor's retry/progress machinery is one GOP
    (closed: an IDR step resets the carry, so a failed GOP re-dispatches
    from its retained staged frames like any wave).

    Output contract: byte-stream-legal multi-slice pictures — the
    concat of a GOP's frames is a closed GOP exactly like the parent's,
    just with `num_bands` slices per picture; downstream (MP4 mux, HLS)
    groups slices into access units by first_mb_in_slice.
    """

    def __init__(self, meta: VideoMeta, qp: int = 27,
                 mesh: Mesh | None = None, gop_frames: int = 32,
                 max_segments: int = 200, bands: int = 0,
                 halo_rows: int | None = None,
                 pack_workers: int | None = None,
                 pipeline_window: int | None = None,
                 decode_ahead: int | None = None,
                 total_bands: int = 0,
                 band_range: tuple[int, int] | None = None,
                 rd: RdConfig | None = None):
        snap = get_settings()
        full_mesh = mesh if mesh is not None else default_mesh()
        devices = list(full_mesh.devices.flat)
        mbh = (meta.height + 15) // 16
        mbw = (meta.width + 15) // 16
        #: pinned GLOBAL band layout. Locally `total_bands=0` sizes it
        #: to this process's devices; on a farm the coordinator pins
        #: `total_bands` for the whole frame and `band_range=(lo, hi)`
        #: assigns this process a contiguous slice of it (the cross-
        #: host SFE shard, parallel/sfefarm.py) — the layout (and so
        #: the slice structure of the bitstream) never depends on any
        #: one host's device count.
        if total_bands:
            self.global_band_plan: BandPlan = plan_bands(
                mbh, mbw, max(1, int(total_bands)))
        else:
            want = int(bands) or len(devices)
            self.global_band_plan = plan_bands(
                mbh, mbw, max(1, min(want, len(devices))))
        lo, hi = band_range if band_range is not None \
            else (0, self.global_band_plan.num_bands)
        lo, hi = int(lo), min(int(hi), self.global_band_plan.num_bands)
        if not 0 <= lo < hi:
            raise ValueError(f"empty band range [{lo}, {hi})")
        if hi - lo > len(devices):
            raise ValueError(
                f"band slice [{lo}, {hi}) needs {hi - lo} devices; "
                f"this host has {len(devices)}")
        #: this process's slice of the layout (band indices, and hence
        #: slice first_mb coordinates, stay GLOBAL)
        self.band_lo, self.band_hi = lo, hi
        self.band_plan: BandPlan = BandPlan(
            bands=self.global_band_plan.bands[lo:hi],
            band_mb_rows=self.global_band_plan.band_mb_rows,
            mb_width=self.global_band_plan.mb_width)
        #: frame 0 of each GOP opens the picture's access unit with
        #: SPS/PPS — only the band slice that owns band 0 emits them
        #: (a farm peer's slices join the SAME access unit downstream)
        self.emit_parameter_sets = lo == 0
        band_mesh = Mesh(np.array(devices[:self.band_plan.num_bands]),
                         ("band",))
        super().__init__(meta, qp=qp, mesh=band_mesh,
                         gop_frames=gop_frames, max_segments=max_segments,
                         gops_per_wave=1, pack_workers=pack_workers,
                         pipeline_window=pipeline_window,
                         decode_ahead=decode_ahead, rd=rd)
        if halo_rows is None:
            halo_rows = int(snap.get("sfe_halo_rows", 32) or 32)
        #: reference rows exchanged per side (multiple of 16). >= 23
        #: (SEARCH_RANGE + window + taps) keeps the banded search
        #: bit-identical to full-frame; smaller clamps the vertical
        #: search range (jaxme.halo_clamp) — bounded, not drifting.
        #: Capped at the band height: one ppermute hop reaches one
        #: neighbor, so very thin bands trade vertical range for width.
        self.halo_rows = max(16, (int(halo_rows) // 16) * 16)
        self.halo_rows = min(self.halo_rows,
                             self.band_plan.band_mb_rows * 16)
        #: previous frame's bitstream-ready perf_counter — the source
        #: of the per-frame latency gap fed to the process-global
        #: _SFE_LAT_MS ring + the tvt_sfe_frame_latency_seconds
        #: histogram (concurrent collectors append near-order; a
        #: benign race here only drops/shifts one sample)
        self._last_frame_done: float | None = None
        #: test hook: device_get each frame's recon carry into
        #: `recon_frames` (absolute frame index → display-cropped
        #: y/u/v) for conformance parity against an independent decode
        #: — keyed, not appended: pipelined GOPs collect on concurrent
        #: threads in completion order
        self.keep_recon = False
        self.recon_frames: dict[int, tuple] = {}
        # RD feature gates for the banded shape: perceptual AQ would
        # make the per-band activity mean band-local (a different map
        # than the unbanded program) — strip it with a log line rather
        # than encode something byte-different per band count. The
        # in-loop filter runs slice-locally in a band (every band slice
        # signals disable_deblocking_filter_idc 2); cross-host (farm)
        # slices have never run with it and stay refused.
        if self.rd.p_intra:
            # the band steps (jaxinter.sfe_p_band) have no intra /
            # inter decision: refuse, as admission does
            # (cluster/policy.py), rather than encode all-inter
            raise ValueError(
                "p_intra is not supported by split-frame encoding; "
                "encode this job in GOP shape (sfe_bands 0)")
        if self.rd.intra4x4:
            # the band steps code an IDR band Intra16x16 alone (their
            # slice-local rows have no wavefront): refuse, as admission
            # does (cluster/policy.py)
            raise ValueError(
                "intra4x4 is not supported by split-frame encoding; "
                "encode this job in GOP shape (sfe_bands 0)")
        if self.rd.aq_q:
            _LOG.warning("perceptual AQ is not supported by split-frame "
                         "encoding; encoding this job with aq off")
            import dataclasses as _dc

            self.rd = _dc.replace(self.rd, aq_q=0)
        if self.rd.deblock and (self.band_lo, self.band_hi) != (
                0, self.global_band_plan.num_bands):
            raise ValueError(
                "deblock is not supported on cross-host band slices; "
                "the remote planner must fall back to GOP shards")
        #: the picture's REAL MB rows (band-grid padding rows beyond it
        #: carry no coded MBs): the deblock masks key off this
        self._total_mb_rows = mbh
        #: 2 = each band slice filters its own rows, edges between
        #: slices stay as they are (what jaxinter._deblock_band computes)
        self._deblock_idc = 2 if self.rd.deblock else 1
        bp = self.band_plan
        self._real_rows = jax.device_put(
            np.asarray([[b.mb_rows * 16] for b in bp.bands], np.int32),
            NamedSharding(self.mesh, P("band")))

    @property
    def num_bands(self) -> int:
        return self.band_plan.num_bands

    def plan(self, num_frames: int) -> SegmentPlan:
        if self.plan_override is not None:
            return self.plan_override
        # fixed grid: GOP boundaries are a pure function of
        # (num_frames, gop_frames, max_segments) — the mesh
        # parallelizes WITHIN frames, so the parent's wave balancing
        # (GOP count rounded to mesh width) would only distort
        # latency-ordered boundaries. max_segments is still honored by
        # growing the GOP length once up front (the parent's cap
        # semantics; long clips must not overshoot segment bookkeeping
        # 8x just because SFE is on).
        gop = max(self.gop_frames,
                  -(-num_frames // max(1, self.max_segments)))
        return plan_fixed_segments(num_frames, gop, self.num_bands)

    # -- staging --------------------------------------------------------

    def _pad_rows(self, plane: np.ndarray, rows: int) -> np.ndarray:
        if plane.shape[0] == rows:
            return np.ascontiguousarray(plane)
        pad = rows - plane.shape[0]
        return np.concatenate([plane, np.repeat(plane[-1:], pad, axis=0)])

    def stage_waves(self, frames):
        """One GOP per staged wave: each frame device_put row-sharded
        over the band mesh (padded to the band grid's height with edge
        replication — the padding rows are computed and discarded). A
        band SLICE (farm mode) pads to the GLOBAL grid height and
        uploads only its own rows — each host decodes the full frame
        but stages O(slice) pixels."""
        plan = self.plan(len(frames))
        cursor = _FrameCursor(frames, self.stages, require_420=True,
                              stats=self.staging_stats)
        rows16 = self.band_plan.band_mb_rows * 16
        Hg = self.global_band_plan.padded_mb_height * 16
        y0, y1 = self.band_lo * rows16, self.band_hi * rows16
        shard = NamedSharding(self.mesh, P("band"))
        for gop in plan.gops:
            cursor.padded(gop.end_frame - 1)   # decode outside "stage"
            with self.stages.stage("stage"):
                ys, us, vs = [], [], []
                for i in range(gop.start_frame, gop.end_frame):
                    f = cursor.padded(i)
                    ya = self._pad_rows(f.y, Hg)[y0:y1]
                    ua = self._pad_rows(f.u, Hg // 2)[y0 // 2:y1 // 2]
                    va = self._pad_rows(f.v, Hg // 2)[y0 // 2:y1 // 2]
                    self.stages.bump("h2d_bytes", ya.nbytes + ua.nbytes
                                     + va.nbytes)
                    ys.append(jax.device_put(ya, shard))
                    us.append(jax.device_put(ua, shard))
                    vs.append(jax.device_put(va, shard))
                qp = int(self.gop_qp.get(gop.index, self.qp))
            yield (gop, ys, us, vs, qp)
            cursor.release_below(gop.end_frame)

    # -- device steps ---------------------------------------------------

    def encode_waves(self, waves, window: int | None = None,
                     pack_workers: int | None = None):
        # fresh latency baseline per encode pass: the idle gap since a
        # PREVIOUS pass's last frame is not a per-frame latency and
        # must not become the reported p99 (one encoder may run
        # several passes)
        self._last_frame_done = None
        return super().encode_waves(waves, window=window,
                                    pack_workers=pack_workers)

    def start_fetch(self, pending: tuple) -> None:
        """Nothing to start ahead: the collect is per FRAME and owns
        its fetches (each frame's slice follows that frame's step), so
        the dispatch loops keep their order here."""

    def _step_mesh(self) -> Mesh | None:
        """None on a single band: the per-band program runs without the
        shard_map wrapper (and without collectives)."""
        return self.mesh if self.band_plan.num_bands > 1 else None

    def _intra_step(self, y, u, v, qp):
        bp = self.band_plan
        with program_build("sfe_intra", self.rd, y.shape, bp.num_bands):
            return _sfe_intra_step(
                y, u, v, qp, self._real_rows, mbw=bp.mb_width,
                mbh_band=bp.band_mb_rows, mesh=self._step_mesh(),
                rd=self.rd, total_mb_rows=self._total_mb_rows)

    def _p_step(self, y, u, v, carry, qp):
        bp = self.band_plan
        ry, ru, rv, pmv = carry
        with program_build("sfe_p", self.rd, y.shape, bp.num_bands):
            return _sfe_p_step(
                y, u, v, ry, ru, rv, pmv, qp, self._real_rows,
                mbw=bp.mb_width, mbh_band=bp.band_mb_rows,
                mesh=self._step_mesh(), halo_rows=self.halo_rows,
                num_bands=bp.num_bands, rd=self.rd,
                total_mb_rows=self._total_mb_rows)

    def dispatch_wave(self, staged: tuple) -> tuple:
        """Enqueue one GOP's per-frame steps (all async — jax dispatch
        returns immediately; the device runs them in order as the recon
        carry chains). Returns the per-frame output handles + each
        frame's dispatch timestamp."""
        with self.stages.stage("dispatch"):
            gop, ys, us, vs, qp = staged
            qpj = jnp.asarray(qp, jnp.int32)
            outs: list[tuple] = []
            carries: list[tuple] = []
            carry = None
            for fi in range(gop.num_frames):
                if fi == 0:
                    r = self._intra_step(ys[0], us[0], vs[0], qpj)
                else:
                    r = self._p_step(ys[fi], us[fi], vs[fi], carry, qpj)
                carry = r[6:]
                outs.append(r[:6])
                # retain per-frame carries ONLY for the test hook: each
                # is a full set of band recon planes (~100 MB at 8K),
                # and the step-to-step chain keeps the live one alive
                carries.append(carry if self.keep_recon else None)
                if not self._async_copy_unavailable:
                    try:
                        for arr in r[1:5]:      # tiny counts only: the
                            arr.copy_to_host_async()  # payload fetches a
                    except Exception:           # used-prefix slice
                        self._async_copy_unavailable = True
            return (gop, staged, outs, carries)

    # -- per-frame collect ---------------------------------------------

    def _band_sizes(self, intra: bool) -> tuple[int, int]:
        """(nmb_band, L) of one band's transfer vector."""
        bp = self.band_plan
        nmb = bp.mb_width * bp.band_mb_rows
        L = nmb * (_INTRA_MB - 24) if intra else nmb * _P_FLAT_MB
        return nmb, L

    def _pack_intra_levels(self, intra, bi: int, qp: int,
                           idr_pic_id: int) -> bytes:
        """Shared tail of the sparse and dense-fallback intra band
        packs (which must stay bit-identical): truncate to the band's
        REAL MB rows and emit its IDR band slice. The mode raster —
        shipped per MB when rd.ships_modes, the slice-local
        _mode_policy otherwise — is BAND-relative either way: the
        band's first MB row is its slice's row 0."""
        bp = self.band_plan
        band = bp.bands[bi]
        mbw = bp.mb_width
        n_real = band.mb_rows * mbw
        if len(intra) == 6:
            il_dc, il_ac, ic_dc, ic_ac, mode16, _dqp = intra
            luma_mode, chroma_mode = unpack_mode16(mode16[:n_real])
        else:
            il_dc, il_ac, ic_dc, ic_ac = intra
            luma_mode, chroma_mode = _mode_policy(mbw, band.mb_rows)
        levels = FrameLevels(
            luma_mode=luma_mode, chroma_mode=chroma_mode,
            luma_dc=il_dc[:n_real], luma_ac=il_ac[:n_real],
            chroma_dc=ic_dc[:n_real], chroma_ac=ic_ac[:n_real])
        return pack_slice(levels, mbw, band.mb_rows, self.sps, self.pps,
                          qp, frame_num=0, idr=True,
                          idr_pic_id=idr_pic_id,
                          first_mb=band.start_mb_row * mbw,
                          deblock_idc=self._deblock_idc)

    def _pack_intra_band(self, dense_b, rest, bi: int, qp: int,
                         idr_pic_id: int) -> bytes:
        bp = self.band_plan
        intra = unflatten_gop_parts(dense_b, rest,
                                    np.empty((0, 0, 2), np.int8), 1,
                                    bp.mb_width, bp.band_mb_rows,
                                    ships_modes=self.rd.ships_modes)[0]
        return self._pack_intra_levels(intra, bi, qp, idr_pic_id)

    def _pack_p_band(self, mv8_b, rest, bi: int, qp: int,
                     frame_num: int) -> bytes:
        from ..codecs.h264 import inter as inter_mod

        bp = self.band_plan
        band = bp.bands[bi]
        mbw = bp.mb_width
        mv, lp, udc, vdc, uac, vac = unflatten_p_planes(
            rest, mv8_b, 2, mbw, bp.band_mb_rows)
        rr = band.mb_rows * 16
        n_real = band.mb_rows * mbw
        count_vectors(self.stages, mv[:n_real], self.rd)
        return inter_mod.pack_p_slice_plane(
            mv[:n_real], lp[0][:rr], udc[0][:n_real], vdc[0][:n_real],
            uac[0][:rr // 2], vac[0][:rr // 2], mbw, band.mb_rows,
            self.sps, self.pps, qp, frame_num=frame_num,
            first_mb=band.start_mb_row * mbw,
            deblock_idc=self._deblock_idc,
            mv_per_pel=self.rd.mv_per_pel)

    def _gather_frame(self, thunks: list) -> list[bytes]:
        pool = self._slice_pool()
        if pool is None:
            return [t() for t in thunks]
        return [f.result() for f in [pool.submit(t) for t in thunks]]

    def _note_frame_done(self, frame_index: int) -> None:
        """One SFE frame's bitstream is ready: count it, and — when a
        previous frame exists — record the steady-state gap as a
        latency sample (global percentile ring + histogram) and a
        `sfe_frame` span in the job's trace."""
        now = time.perf_counter()
        prev, self._last_frame_done = self._last_frame_done, now
        self.stages.bump("sfe_frames")
        if prev is None or now <= prev:
            return
        gap = now - prev
        with _SFE_LAT_LOCK:
            _SFE_LAT_MS.append(gap * 1e3)
        obs_metrics.SFE_FRAME_SECONDS.observe(gap)
        tracer = self.stages.tracer()
        if tracer is not None:
            tracer.record("sfe_frame", time.time() - gap, gap,
                          frame=frame_index)

    def _keep_recon(self, carry, frame_index: int) -> None:
        ry, ru, rv = jax.device_get(carry[:3])
        h, w = self.meta.height, self.meta.width
        self.recon_frames[frame_index] = (
            np.asarray(ry)[:h, :w].astype(np.uint8),
            np.asarray(ru)[:h // 2, :w // 2].astype(np.uint8),
            np.asarray(rv)[:h // 2, :w // 2].astype(np.uint8))

    def collect_wave(self, pending: tuple) -> list[EncodedSegment]:
        """Per-FRAME collect: barrier on frame fi's tiny counts, fetch
        its band payloads (one transfer per band shard), entropy-pack
        its band slices on the pack pool, and emit the frame's bytes —
        all while the device runs frames fi+1.. of this GOP (and the
        next dispatched GOP). An int8 escape in any band reruns the
        whole GOP through the dense-transfer steps (bit-identical
        levels, wider fetch), the wave path's fallback contract."""
        gop, staged, outs, carries = pending
        prof = self.stages
        bp = self.band_plan
        qp = staged[4]
        if self.gop_index_offset or self.frame_offset:
            import dataclasses as _dc

            gop = _dc.replace(gop, index=gop.index + self.gop_index_offset,
                              start_frame=(gop.start_frame
                                           + self.frame_offset))
        idr_pic_id = gop.index % 65536
        nals: list[bytes] = []
        dense_from = None
        for fi, out in enumerate(outs):
            head, nblk, nval, n_esc, used, payload = out
            with prof.stage("device_wait"):
                tiny = jax.device_get([nblk, nval, n_esc, used])
            prof.bump("d2h_bytes", sum(int(a.nbytes) for a in tiny))
            nblk_h, nval_h, nesc_h, used_h = tiny
            if int(np.asarray(nesc_h).max()) > 0:
                dense_from = fi         # escape: rerun the GOP dense
                break
            _, L = self._band_sizes(intra=(fi == 0))
            # unit budgets (_sfe_pack_band): only an escape overflows
            self._note_sparse_fill(nblk_h, nval_h, L, 1, 1)
            with prof.stage("fetch"):
                (head_h,) = self._fetch_bulk([head])
                rows = self._fetch_payload_rows(payload, used_h)
            with prof.stage("sfe"):
                thunks = []
                for bi in range(bp.num_bands):
                    rest = functools.partial(
                        self._unpack_compact, rows[bi], int(nblk_h[bi]),
                        int(nval_h[bi]), int(used_h[bi]), L)
                    if fi == 0:
                        thunks.append(functools.partial(
                            lambda r, b: self._pack_intra_band(
                                head_h[b], r(), b, qp, idr_pic_id),
                            rest, bi))
                    else:
                        thunks.append(functools.partial(
                            lambda r, b, fn: self._pack_p_band(
                                head_h[b], r(), b, qp, fn),
                            rest, bi, fi % 256))
                frame_nal = b"".join(self._gather_frame(thunks))
            if fi == 0 and self.emit_parameter_sets:
                frame_nal = self.sps.to_nal() + self.pps.to_nal() \
                    + frame_nal
            nals.append(frame_nal)
            self._note_frame_done(gop.start_frame + fi)
            if self.keep_recon:
                self._keep_recon(carries[fi], gop.start_frame + fi)
        if dense_from is not None:
            nals = self._collect_dense(gop, staged, nals, dense_from)
        with prof.stage("concat"):
            seg = EncodedSegment(gop=gop, payload=b"".join(nals),
                                 frame_sizes=tuple(len(n) for n in nals))
        prof.count_wave()
        return [seg]

    def _collect_dense(self, gop: GopSpec, staged: tuple,
                       nals: list[bytes], dense_from: int) -> list[bytes]:
        """Escape fallback: rerun the GOP through the dense-transfer
        steps (same compute, uncompressed int16 levels) and pack every
        frame from `dense_from` on. Frames already packed from the
        sparse path are kept — levels are identical either way."""
        prof = self.stages
        bp = self.band_plan
        _, ys, us, vs, qp = staged
        qpj = jnp.asarray(qp, jnp.int32)
        mesh = self._step_mesh()
        idr_pic_id = gop.index % 65536
        prof.bump("dense_fallback_waves")
        with prof.stage("dense_retry"):
            carry = None
            for fi in range(gop.num_frames):
                if fi == 0:
                    r = _sfe_intra_step_dense(
                        ys[0], us[0], vs[0], qpj, self._real_rows,
                        mbw=bp.mb_width, mbh_band=bp.band_mb_rows,
                        mesh=mesh, rd=self.rd,
                        total_mb_rows=self._total_mb_rows)
                    head, flat, carry = None, r[0], r[1:]
                else:
                    r = _sfe_p_step_dense(
                        ys[fi], us[fi], vs[fi], *carry[:3], carry[3],
                        qpj, self._real_rows, mbw=bp.mb_width,
                        mbh_band=bp.band_mb_rows, mesh=mesh,
                        halo_rows=self.halo_rows, num_bands=bp.num_bands,
                        rd=self.rd, total_mb_rows=self._total_mb_rows)
                    head, flat, carry = r[0], r[1], r[2:]
                if fi < dense_from:
                    continue            # already packed from sparse
                if head is None:
                    flat_h = self._fetch_bulk([flat])[0]
                    head_h = None
                else:
                    head_h, flat_h = self._fetch_bulk([head, flat])
                thunks = []
                for bi in range(bp.num_bands):
                    if fi == 0:
                        thunks.append(functools.partial(
                            lambda b, f: self._pack_intra_band_dense(
                                f[b], b, qp, idr_pic_id),
                            bi, flat_h))
                    else:
                        thunks.append(functools.partial(
                            lambda b, m, f, fn: self._pack_p_band(
                                m[b], f[b], b, qp, fn),
                            bi, head_h, flat_h, fi % 256))
                frame_nal = b"".join(self._gather_frame(thunks))
                if fi == 0 and self.emit_parameter_sets:
                    frame_nal = self.sps.to_nal() + self.pps.to_nal() \
                        + frame_nal
                nals.append(frame_nal)
                self._note_frame_done(gop.start_frame + fi)
                if self.keep_recon:
                    self._keep_recon(carry, gop.start_frame + fi)
        return nals

    def _pack_intra_band_dense(self, flat_b, bi: int, qp: int,
                               idr_pic_id: int) -> bytes:
        bp = self.band_plan
        nmb = bp.mb_width * bp.band_mb_rows
        flat_b = np.asarray(flat_b)
        intra = unflatten_intra(flat_b[:nmb * _INTRA_MB], nmb)
        if self.rd.ships_modes:
            t = nmb * _INTRA_MB
            intra = intra + (flat_b[t:t + nmb], flat_b[t + nmb:])
        return self._pack_intra_levels(intra, bi, qp, idr_pic_id)


def make_shard_encoder(meta: VideoMeta, settings, mesh, *,
                       shape: str | None = None, rungs=None,
                       qp: int | None = None, total_bands: int = 0,
                       band_range: tuple[int, int] | None = None,
                       halo_rows: int | None = None, session=None,
                       rd: RdConfig | None = None):
    """The ONE plan-driven shard-executor seam: every encode path —
    local executor, remote worker, live pipeline — resolves its
    encoder here, keyed off the unified plan shape
    (parallel/planner.EncodePlan) instead of per-call-site if/else
    ladders.

    shape=None resolves from settings (`sfe_bands > 0` → band shape,
    else GOP waves); `rungs` selects the ladder form (which stages
    once and fans renditions); `band_range`/`total_bands` select the
    cross-host band-slice form (parallel/sfefarm.py) with `session`
    carrying the halo exchange. `rd` None: each encoder reads the
    process's live settings (rd_from_settings); a remote worker passes
    the one its shard was planned with."""
    qp = int(settings.qp) if qp is None else int(qp)
    gop_frames = int(settings.gop_frames)
    max_segments = int(settings.max_segments)
    if rungs:
        from ..abr.ladder import LadderShardEncoder

        return LadderShardEncoder(meta, list(rungs), mesh=mesh,
                                  gop_frames=gop_frames,
                                  max_segments=max_segments, rd=rd)
    if shape is None:
        shape = "band" if int(settings.get("sfe_bands", 0) or 0) > 0 \
            else "gop"
    if shape == "band":
        if halo_rows is None:
            halo_rows = int(settings.get("sfe_halo_rows", 32) or 32)
        if band_range is not None or total_bands:
            from .sfefarm import FarmBandEncoder

            return FarmBandEncoder(
                meta, qp=qp, mesh=mesh, gop_frames=gop_frames,
                max_segments=max_segments, total_bands=total_bands,
                band_range=band_range, halo_rows=halo_rows,
                session=session, rd=rd)
        return SfeShardEncoder(
            meta, qp=qp, mesh=mesh, gop_frames=gop_frames,
            max_segments=max_segments,
            bands=int(settings.get("sfe_bands", 0) or 0),
            halo_rows=halo_rows, rd=rd)
    if shape != "gop":
        raise ValueError(f"unknown shard shape {shape!r}")
    return GopShardEncoder(meta, qp=qp, mesh=mesh,
                           gop_frames=gop_frames,
                           max_segments=max_segments, rd=rd)


def encode_clip_sharded(frames: list[Frame], meta: VideoMeta, qp: int = 27,
                        mesh: Mesh | None = None,
                        gop_frames: int = 32) -> bytes:
    """Convenience: plan → shard encode → order-restoring concat."""
    from ..core.types import concat_segments

    enc = GopShardEncoder(meta, qp=qp, mesh=mesh, gop_frames=gop_frames)
    return concat_segments(enc.encode(frames))


def job_stage_profile() -> StageProfile:
    """A stage profile for host work of a job that no encoder of this
    process owns (the remote coordinator's look for scene cuts): it
    mirrors into the process totals as an encoder's does."""
    return StageProfile(mirror=_TOTALS)


#: the executor's clocks for the phases of a job that no encoder's
#: profile times, in the order a job passes them: starting the device
#: profile of a job with `profile_dir`, building the encoder, the plan
#: (`scenecut` nests in it), [the wave pipeline], stopping the profile
#: (its collection), joining the segments, the MP4 mux, write + rename,
#: the journal's completion records. They live in the process totals
#: alone (`/metrics_snapshot.stage_ms`, `tvt_stage_seconds_total`) and
#: never in a job's span ring: the benchmark takes the extent of the
#: wave pipeline from that ring's first and last span (PERF.md §7).
JOB_CLOCKS = ("profile_start", "job_build", "job_plan", "profile_stop",
              "job_stitch", "job_mux", "job_write", "job_commit")
for _clock in JOB_CLOCKS:       # keys of the first snapshot already: a
    _TOTALS.add(_clock, 0.0)    # reader takes growth between two


def job_clock(name: str):
    """Context manager: the stage clock `name` (one of JOB_CLOCKS) of
    the process totals, `tvt:<name>` in a live device profile."""
    return _TOTALS.stage(name)


#: the seconds a process spends setting its executables up: the clock
#: `program_build` runs round the FIRST call of each GOP / step program
#: (trace, lower, compile or load from the compile cache, until the
#: call returns with the program enqueued) and `programs_built` counts
#: them; a later call of that program starts neither. Process totals
#: like the job clocks above, never a span of a job's ring. They are
#: registered HERE and not in STAGE_NAMES / STAGE_COUNTERS because the
#: compile cache's key of every program with the ME kernel holds the
#: lines of this file above the step programs (PERF.md §7): an entry
#: added up there would recompile every one of them for nothing.
_TOTALS.add("program_build", 0.0)
_TOTALS.bump("programs_built", 0)
_PROGRAMS_BUILT: set = set()
_PROGRAMS_LOCK = threading.Lock()


#: the forms of `program_build` that search motion
_P_FORMS = ("scan", "bounded", "sfe_p")


def count_vectors(profile: StageProfile, mv, rd) -> None:
    """Counters `mvs_coded` / `mvs_quarter` for the (..., 2) vectors of
    P macroblocks on their way to the packers: how many, and how many
    of them have an odd quarter-sample component (none can under
    subpel="half", whose units are half samples)."""
    profile.bump("mvs_coded", mv.size // 2)
    if rd.mv_per_pel == 4:
        profile.bump("mvs_quarter",
                     int(np.count_nonzero((np.asarray(mv) & 1).any(-1))))


def count_kinds(profile: StageProfile, pmode) -> None:
    """Counters `p_mbs_coded` / `p_mbs_intra` for the (..., nmb) kind
    channel of P pictures on their way to the packers (rd.p_intra):
    how many macroblocks, and how many of them are intra."""
    profile.bump("p_mbs_coded", int(pmode.size))
    profile.bump("p_mbs_intra", int(np.count_nonzero(pmode)))


def count_i_kinds(profile: StageProfile, intra: tuple, rd) -> None:
    """Counters `i_mbs_coded` / `i_mbs_4x4` for an IDR picture's levels
    on their way to the packers (rd.intra4x4; nothing without): its
    macroblocks, and those whose mode16 word says Intra4x4."""
    if rd.intra4x4:
        kind = np.asarray(intra[4]) & 15
        profile.bump("i_mbs_coded", int(kind.size))
        profile.bump("i_mbs_4x4", int(np.count_nonzero(kind == 4)))


@contextlib.contextmanager
def program_build(form: str, rd, shape, *more):
    """Round one call of a step program from OUTSIDE its jit: the first
    call of each (form, rd, shape, *more) of this process is clocked
    (`program_build`; `tvt:program_build` in a live device profile),
    counted and named once in the log. `form` is the executable's kind
    (`scan` | `bounded` for a GOP program by its P-frame loop,
    `sfe_intra`, `sfe_p`, `words`), `shape` its leading operand's.
    Every call of a form that searches motion also sets the gauge
    `me_candidates`: what that executable scores per macroblock."""
    from ..codecs.h264 import jaxme

    candidates = len(jaxme.offset_table(rd.subpel)) \
        if form in _P_FORMS else None
    if candidates is not None:
        _TOTALS.gauge("me_candidates", candidates)
    key = (form, rd, tuple(shape), *more)
    with _PROGRAMS_LOCK:
        first = key not in _PROGRAMS_BUILT
        _PROGRAMS_BUILT.add(key)
    if not first:
        yield
        return
    t0 = time.perf_counter()
    with _TOTALS.stage("program_build"):
        yield
    _TOTALS.bump("programs_built")
    _LOG.info("program built: form=%s rd=%s shape=%s %s me_candidates=%s "
              "in %.2f s (trace, lower, compile or cache load, enqueue)",
              form, rd, tuple(shape), more, candidates,
              time.perf_counter() - t0)


#: each packing thread's level scratch (_level_scratch)
_SCRATCH = threading.local()


def _level_scratch(levels: int) -> np.ndarray:
    """`levels` int16 of the calling thread's own memory, made at its
    first slice and kept as long as the thread lives (a pack pool
    thread: as long as its encoder), so its pages are faulted in once
    and not once a GOP. Whatever the thread's last slice left is still
    in it."""
    room = getattr(_SCRATCH, "room", None)
    if room is None or room.shape[0] < levels:
        room = _SCRATCH.room = np.empty(levels, np.int16)
    return room[:levels]


def _compact_gop_thunks(stages: StageProfile, payload: np.ndarray,
                        nblk: int, nval: int, Lr: int, dense: np.ndarray,
                        mv8: np.ndarray, F: int, slices: tuple,
                        idr_pic_id: int, rd) -> list:
    """Slice thunks of one GOP whose levels crossed as a compact
    payload (`slices`: gop_slice_thunks_*'s positional arguments from
    the frame count on). With the native library the payload is
    indexed here and unpacked in ranges by the thunks
    (:class:`_CompactGop`); without it numpy unpacks the whole vector
    here — it has no cheap range — and the thunks take views, as the
    dense fallback's do. The clock `sparse_unpack` is what the calling
    thread spends on the payload before it can submit a thunk, either
    way."""
    from .. import native
    from ..codecs.h264.layout import unpack_compact_host

    num_frames, mbw, mbh = slices[:3]
    if native.available():
        with stages.stage("sparse_unpack"):
            levels = _CompactGop(stages, payload, nblk, nval, Lr, dense,
                                 mv8, F, mbw, mbh, rd)
        return gop_slice_thunks_frames(levels.intra, levels.p_frame, *slices,
                                       idr_pic_id=idr_pic_id, rd=rd)
    with stages.stage("sparse_unpack"):
        rest = unpack_compact_host(payload, nblk, nval, Lr)
    with stages.stage("unflatten"):
        intra, planes = unflatten_gop_parts(
            dense, rest, mv8, F, mbw, mbh, ships_modes=rd.ships_modes,
            p_intra=rd.p_intra)
    if rd.p_intra:
        count_kinds(stages, planes[6][:num_frames - 1])
    count_i_kinds(stages, intra, rd)
    return gop_slice_thunks_planes(intra, planes, *slices,
                                   idr_pic_id=idr_pic_id, rd=rd)


class _CompactGop:
    """One GOP of a sparse wave as its slice thunks read it: the
    compact payload, validated and indexed (the constructor: the one
    pass the collecting thread makes), and the dense DC prefix. A
    thunk unpacks the runs of the level vector its slice is made of
    (layout.rest_spans) into :func:`_level_scratch`'s memory — frame-
    sized, kept by the thread that runs it, dirty with its last slice
    — and packs the views; no array of the GOP's length exists. The
    views are the ones unflatten_gop_parts gives on the whole vector,
    value for value."""

    def __init__(self, stages: StageProfile, payload: np.ndarray,
                 nblk: int, nval: int, Lr: int, dense: np.ndarray,
                 mv8: np.ndarray, F: int, mbw: int, mbh: int, rd) -> None:
        from .. import native
        from ..codecs.h264.layout import rest_spans, split_dense_dc

        self._stages = stages
        self._stream = (nblk, nval, payload, Lr)
        self._index = native.index_compact(*self._stream)
        self._unpack_range = native.unpack_compact_range
        self._dc = split_dense_dc(dense, mbw * mbh, rd.ships_modes)
        self._mv8, self._rd = mv8, rd
        self._intra, self._frames = rest_spans(F, mbw, mbh, rd.p_intra)
        self._frame_levels = mbw * mbh * p_flat_mb(rd.p_intra)

    def _unpack(self, spans) -> list[np.ndarray]:
        """The spans' levels, side by side in this thread's scratch."""
        room = _level_scratch(self._frame_levels)
        views, o = [], 0
        for l0, n, shape in spans:
            self._unpack_range(*self._stream, self._index, l0, l0 + n,
                               room[o:o + n])
            views.append(room[o:o + n].reshape(shape))
            o += n
        self._stages.bump("unpack_ranges", len(spans))
        return views

    def intra(self) -> tuple:
        il_dc, ic_dc, modes = self._dc
        il_ac, ic_ac = self._unpack(self._intra)
        intra = (il_dc, il_ac, ic_dc, ic_ac) + modes
        count_i_kinds(self._stages, intra, self._rd)
        return intra

    def p_frame(self, i: int) -> tuple:
        views = self._unpack(self._frames[i])
        if self._rd.p_intra:
            count_kinds(self._stages, views[5])
        return (self._mv8[i], *views)
