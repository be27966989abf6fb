"""GOP segment planner — the parts-planner math, TPU-shaped.

Port of the reference's two-step plan (/root/reference/worker/tasks.py:
597-609 and 1019-1031): pick a target shard size, derive the shard count,
then round the count UP to a multiple of the usable worker count so every
dispatch wave fills the farm. Here "workers" are mesh devices and the unit
is frames (closed GOPs), not bytes: a GOP boundary is the only place an
H.26x stream can be cut without cross-shard prediction.
"""

from __future__ import annotations

import dataclasses
import math

from ..core.types import BandPlan, BandSpec, GopSpec, SegmentPlan


def min_gop_frames(gop_frames: int) -> int:
    """Shortest GOP a scene cut may close: x264's `min-keyint` auto rule,
    `keyint / 10` (3 at GOP 32), no setting of its own."""
    return max(1, int(gop_frames) // 10)


def _cut_bias(distance: int, gop_frames: int) -> tuple[int, int]:
    """(num, den) of the share of `scenecut` that applies `distance`
    frames after the last cut taken: x264's ramp, a quarter of it at
    the shortest GOP, the whole of it at `gop_frames` and beyond, and
    falling to nothing below the shortest GOP."""
    lo = min_gop_frames(gop_frames)
    if distance >= gop_frames:
        return 1, 1
    if distance < lo:
        return distance, 4 * lo
    return (gop_frames - lo) + 3 * (distance - lo), 4 * (gop_frames - lo)


def take_cuts(inter, intra, gop_frames: int, scenecut: int
              ) -> tuple[tuple[int, ...], int]:
    """Which frames of a clip start a GOP because the picture changed:
    (cuts taken, cuts suppressed) from the per-frame inter and intra
    costs of `parallel/scenecut.frame_costs` (entry 0 is not read).

    Frame t is a cut where `100 * inter >= (100 - bias) * intra` with
    `bias` the `_cut_bias` share of `scenecut` (x264's name and scale,
    0-100) at t's distance from the last cut taken, or from frame 0;
    a picture equal to the last one (inter 0) is never one. A cut
    closer than `min_gop_frames` to the last one is not taken: its
    frame stays a P frame and is counted as suppressed. The ramp runs
    from the last CUT, not from the last GOP start, because the GOP
    starts inside a shot are placed by `plan_segments` once the shot's
    end is known; none of them lies closer than `min_gop_frames` to
    the next cut. Integers throughout, so every host decides alike.
    `tools/scenecut_plain.py` is the plain form tier-1 holds this to."""
    scenecut = min(100, max(0, int(scenecut)))
    lo = min_gop_frames(gop_frames)
    taken, suppressed, last = [], 0, 0
    for t in range(1, len(inter)):
        num, den = _cut_bias(t - last, gop_frames)
        p, i = int(inter[t]), int(intra[t])
        if p > 0 and 100 * den * p >= (100 * den - scenecut * num) * i:
            if t - last >= lo:
                taken.append(t)
                last = t
            else:
                suppressed += 1
    return tuple(taken), suppressed


def suffix_cuts(cuts, start_frame: int):
    """The cuts of a clip as its suffix from `start_frame` sees them
    (the elastic replan re-plans a suffix); None stays None."""
    if cuts is None:
        return None
    return tuple(c - start_frame for c in cuts if c > start_frame)


def _plan_shots(num_frames: int, gop_frames: int, max_segments: int,
                cuts) -> list[GopSpec] | None:
    """GOPs of a clip cut into shots at `cuts`, each shot planned as a
    clip of its own on one device; None where that takes more than
    `max_segments` GOPs."""
    bounds = [0, *cuts, num_frames]
    if any(b <= a for a, b in zip(bounds, bounds[1:])):
        raise ValueError(f"cuts must rise strictly inside (0, "
                         f"{num_frames}): {list(cuts)}")
    gops: list[GopSpec] = []
    for a, b in zip(bounds, bounds[1:]):
        for g in plan_segments(b - a, gop_frames, 1, max_segments).gops:
            gops.append(GopSpec(index=len(gops),
                                start_frame=a + g.start_frame,
                                num_frames=g.num_frames))
    return gops if len(gops) <= max_segments else None


def plan_segments(num_frames: int, gop_frames: int, num_devices: int,
                  max_segments: int = 200, cuts=None) -> SegmentPlan:
    """Plan closed-GOP shards for `num_frames` over `num_devices`.

    - `gop_frames` is the TARGET GOP length (the ~10 MB analog).
    - The GOP count is rounded up to a multiple of `num_devices` (when that
      doesn't push GOPs below 1 frame), mirroring the reference's wave
      balancing; bounded by `max_segments`.
    - Every frame is covered exactly once; all GOPs are closed (IDR-led).
    - `cuts` (the `scenecut` setting: frames where `take_cuts` found a
      new shot; None = the setting is off) split the clip into shots,
      and each shot is planned as a clip of its own on ONE device: no
      GOP crosses a cut, indices run on, and the boundaries do not
      depend on the mesh (waves take consecutive GOPs whatever shot
      they belong to). With no cut the plan is the uncut one; so it is
      where the shots would take more than `max_segments` GOPs (the
      cuts then stay P frames). A plan made with cuts, even none,
      pins the wave's frame count to `gop_frames` (`pin_frames`), so
      every clip of a resolution runs one program shape however its
      shots fall; its waves carry each GOP's real frame count and the
      program encodes no frame past it (parallel/dispatch._wave_groups:
      the repeats are staged, not encoded).
    """
    if num_frames <= 0:
        raise ValueError("num_frames must be positive")
    if gop_frames <= 0 or num_devices <= 0:
        raise ValueError("gop_frames and num_devices must be positive")
    if cuts:
        shots = _plan_shots(num_frames, gop_frames, max_segments, cuts)
        if shots is not None:
            return SegmentPlan(gops=tuple(shots), num_devices=num_devices,
                               frames_per_gop=gop_frames, pin_frames=True)

    n = math.ceil(num_frames / gop_frames)
    # Round up to fill waves — only useful when there's at least one frame
    # per shard; tiny clips keep their natural count.
    rounded = math.ceil(n / num_devices) * num_devices
    if rounded <= num_frames:
        n = rounded
    n = min(n, max_segments, num_frames)

    base = num_frames // n
    extra = num_frames % n          # first `extra` GOPs get one more frame
    gops = []
    start = 0
    for i in range(n):
        length = base + (1 if i < extra else 0)
        gops.append(GopSpec(index=i, start_frame=start, num_frames=length))
        start += length
    assert start == num_frames
    return SegmentPlan(gops=tuple(gops), num_devices=num_devices,
                       frames_per_gop=gop_frames,
                       pin_frames=cuts is not None)


def plan_fixed_segments(num_frames: int, gop_frames: int,
                        num_devices: int = 1) -> SegmentPlan:
    """Fixed GOP grid: exactly `gop_frames` per GOP (short tail at the
    end), indices from 0 — boundaries a pure function of the frame
    index, never of mesh width or batch size. The live pipeline pins
    its part boundaries with this (cluster/executor._run_live) and the
    split-frame-encoding path pins its latency-ordered GOP walk
    (parallel/dispatch.SfeShardEncoder), where the mesh parallelizes
    WITHIN a frame and must not reshape the GOP grid."""
    if num_frames <= 0:
        raise ValueError("num_frames must be positive")
    if gop_frames <= 0:
        raise ValueError("gop_frames must be positive")
    gops = []
    start = 0
    while start < num_frames:
        n = min(gop_frames, num_frames - start)
        gops.append(GopSpec(index=len(gops), start_frame=start,
                            num_frames=n))
        start += n
    return SegmentPlan(gops=tuple(gops), num_devices=num_devices,
                       frames_per_gop=gop_frames)


def plan_bands(mb_height: int, mb_width: int, num_bands: int) -> BandPlan:
    """Pin the split-frame-encoding band layout for one job.

    Each of the (at most) `num_bands` devices owns an EQUAL
    `band_mb_rows = ceil(mb_height / num_bands)` MB-row shard — equal
    shapes are a shard_map requirement — and entropy-codes only its
    REAL rows. When `band_mb_rows` covers `mb_height` in fewer than
    `num_bands` bands (short frames on wide meshes), the plan shrinks
    to the bands that hold at least one real MB row: a fully-padded
    band would have no real edge row to source halo pixels from, and
    its device would only ever encode discarded rows.

    Boundaries are MB-aligned by construction and a pure function of
    (mb_height, num_bands): the slice layout of a stream never depends
    on which frame or wave is being encoded.
    """
    if mb_height <= 0 or mb_width <= 0:
        raise ValueError("mb_height and mb_width must be positive")
    if num_bands <= 0:
        raise ValueError("num_bands must be positive")
    rows = math.ceil(mb_height / num_bands)
    n = math.ceil(mb_height / rows)          # bands with >= 1 real row
    bands = []
    for i in range(n):
        start = i * rows
        bands.append(BandSpec(index=i, start_mb_row=start,
                              mb_rows=min(rows, mb_height - start)))
    assert bands[-1].end_mb_row == mb_height
    return BandPlan(bands=tuple(bands), band_mb_rows=rows,
                    mb_width=mb_width)


def plan_band_groups(num_bands: int, groups: int
                     ) -> tuple[tuple[int, int], ...]:
    """Partition a band layout into `groups` contiguous [lo, hi)
    slices — one per band shard / worker host (cluster/remote.py farm
    SFE). Near-equal sizes, first slices take the remainder; a pure
    function of (num_bands, groups) so a crash-resumed plan (and every
    peer's descriptor) reproduces the identical partition."""
    if num_bands <= 0:
        raise ValueError("num_bands must be positive")
    groups = max(1, min(int(groups), num_bands))
    base, extra = divmod(num_bands, groups)
    out = []
    lo = 0
    for i in range(groups):
        n = base + (1 if i < extra else 0)
        out.append((lo, lo + n))
        lo += n
    assert lo == num_bands
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class EncodePlan:
    """The unified, shape-tagged shard plan record every encode path
    keys off (the collapse of the GopShardEncoder / SfeShardEncoder /
    LadderShardEncoder dispatch seams): `shape` picks the executor
    form, `segments` pins the GOP grid, and the band fields pin the
    cross-host SFE layout when `shape == "band"`. The record is pure
    data — JSON-able via `record()` so the durable board checkpoint
    (cluster/partstore.py) can journal it and a crash-resumed
    coordinator re-plans deterministically from the record, never from
    the live farm width — nor from a second look at the source: the
    scene cuts the GOP grid was planned on (`cuts`, gop shape; None =
    `scenecut` off) ride in the record, and `plan_encode(...,
    cuts=record["cuts"])` gives the same segments."""

    shape: str                        # "gop" | "band"
    segments: SegmentPlan
    total_bands: int = 0              # band shape: global layout width
    halo_rows: int = 0                # band shape: pinned halo depth
    band_groups: tuple[tuple[int, int], ...] = ()
    cuts: tuple[int, ...] | None = None

    def record(self) -> dict:
        return {
            "shape": self.shape,
            "cuts": None if self.cuts is None
            else [int(c) for c in self.cuts],
            "total_bands": int(self.total_bands),
            "halo_rows": int(self.halo_rows),
            "band_groups": [[int(lo), int(hi)]
                            for lo, hi in self.band_groups],
        }


def plan_shape(settings) -> str:
    """The plan shape a job's settings ask for: `sfe_bands > 0` → the
    split-frame band shape, else GOP waves."""
    return "band" if int(settings.get("sfe_bands", 0) or 0) > 0 else "gop"


def plan_encode(num_frames: int, settings, *, num_devices: int,
                shape: str | None = None, total_bands: int = 0,
                group_count: int = 1, mb_height: int = 0,
                cuts=None) -> EncodePlan:
    """Build the unified plan for one job. `shape=None` resolves from
    settings (`plan_shape`); the band shape uses the SFE fixed GOP
    grid (boundaries a pure function of the frame count, never of
    mesh or farm width) and partitions `total_bands` over
    `group_count` shards.

    Scene cuts (`cuts`, the `scenecut` setting) move GOP boundaries in
    the gop shape alone — a ladder too: one list from the source, the
    same boundaries on every rung. The band shape and live batches
    (`plan_fixed_segments`) keep the fixed grid their contracts pin,
    whatever the setting says: nothing looks for cuts there."""
    gop_frames = int(settings.gop_frames)
    max_segments = int(settings.max_segments)
    if shape is None:
        shape = plan_shape(settings)
    if shape == "gop":
        return EncodePlan(
            shape="gop",
            segments=plan_segments(num_frames, gop_frames, num_devices,
                                   max_segments, cuts=cuts),
            cuts=None if cuts is None else tuple(int(c) for c in cuts))
    if shape != "band":
        raise ValueError(f"unknown plan shape {shape!r}")
    # the SFE grid: honor max_segments by growing the GOP once up
    # front (SfeShardEncoder.plan's cap semantics)
    gop = max(gop_frames, -(-num_frames // max(1, max_segments)))
    bands = plan_bands(max(1, mb_height), 1, max(1, total_bands))
    groups = plan_band_groups(bands.num_bands, group_count)
    halo = int(settings.get("sfe_halo_rows", 32) or 32)
    return EncodePlan(
        shape="band",
        segments=plan_fixed_segments(num_frames, gop, num_devices),
        total_bands=bands.num_bands,
        halo_rows=max(16, (halo // 16) * 16),
        band_groups=groups)
