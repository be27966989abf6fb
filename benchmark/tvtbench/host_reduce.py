"""Host spans and device ops of the traced job on ONE clock.

    python host_reduce.py TRACE.xplane.pb [FRAMES]

While the traced job's device profile is live the program files every
span it records as an annotation `tvt:<name>` on the thread that runs it
(`thinvids_tpu/obs/trace.annotation`), and the executor marks the encode
stage itself as `tvt:encode_stage` between the profiler's start and its
stop. They land in the `/host:CPU` plane of the `.xplane.pb`, one line a
thread, on the clock the device planes' ops are on: no second clock has
to agree with it, as `evidence.breakdown` needs of `/trace/<job>`.

Read at the protobuf wire level, like `scope_reduce.py` and with its
helpers. Every `XLine` has a time base of its own (`timestamp_ns`, from
the profile's start) and an event's `offset_ps` counts from it; lines
are compared here, so the base is added. Times are integer picoseconds
from the profile's start until a metric is made of them: the parts of
the idle time then add up to the whole exactly.

What it computes (`reduce_host`), with the ops of the FIRST device plane
as `profile_reduce`'s gaps have it:

- the window's excess: what of the profile's own window (the one
  `device_idle_pct` divides by) lies before `tvt:encode_stage` starts
  and after it ends — the profiler's start and stop, not the job's;
- busy and idle time inside `tvt:encode_stage`;
- the idle time inside it by what the host was doing: every instant
  goes to the SHORTEST `tvt:*` annotation of any thread that covers it
  (the innermost, as `evidence.host_span_at` has it), and to "unnamed"
  where none but `tvt:encode_stage` does;
- lead-in and tail: stage start to the first device op, last device op
  to stage end.

A profile with no `tvt:encode_stage` (a program without the
annotations, a CPU rehearsal's profile without a device plane) reads
"not measured", never 0.
"""

import heapq
import os
import sys

if __name__ == "__main__":      # run by hand: find the package beside
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from tvtbench import evidence                     # noqa: E402
from tvtbench import profile_reduce as pr         # noqa: E402
from tvtbench import scope_reduce as sr           # noqa: E402

HOST_PLANE = "/host:CPU"
ENV_PLANE = "Task Environment"
PREFIX = "tvt:"
STAGE = "tvt:encode_stage"
UNNAMED = "unnamed"


# -- the file ---------------------------------------------------------------

def plane_parts(plane):
    """(name, [line], [event metadata entry], [stat]) of an XPlane, the
    last three as stored."""
    name, lines, metadata, stats = "", [], [], []
    for num, field in sr.fields(plane):
        if num == 2:
            name = sr._text(field)
        elif num == 3:
            lines.append(field)
        elif num == 4:
            metadata.append(field)
        elif num == 6:
            stats.append(field)
    return name, lines, metadata, stats


def annotation_names(metadata):
    """{event metadata id: name} of the entries named `tvt:*`."""
    names = {}
    for entry in metadata:
        key, meta = sr._map_entry(entry)
        for num, field in sr.fields(meta):
            if num == 2 and bytes(field[:len(PREFIX)]) == PREFIX.encode():
                names[key] = sr._text(field)
    return names


def line_events(line, keep=None):
    """(the line's time base in ps, [(metadata id, offset_ps,
    duration_ps)] as stored) of an XLine; an event's place on the
    profile's clock is the base plus its offset. `keep`: the metadata
    ids wanted (None: all)."""
    base_ps, events = 0, []
    for num, field in sr.fields(line):
        if num == 3:
            base_ps = field * 1000
        elif num == 4:
            meta_id = offset = duration = 0
            for enum, val in sr.fields(field):
                if enum == 1:
                    meta_id = val
                elif enum == 2:
                    offset = val
                elif enum == 3:
                    duration = val
            if keep is None or meta_id in keep:
                events.append((meta_id, offset, duration))
    return base_ps, events


def _window_ps(stats, plane):
    """profile_stop_time - profile_start_time of the `Task Environment`
    plane (unix nanoseconds, as uint64 stats), in picoseconds."""
    stat_names = sr._stat_names(plane)
    found = {}
    for stat in stats:
        stat_id = value = None
        for num, field in sr.fields(stat):
            if num == 1:
                stat_id = field
            elif num in (3, 4):
                value = field
        found[stat_names.get(stat_id)] = value
    start, stop = (found.get("profile_start_time"),
                   found.get("profile_stop_time"))
    if start is None or stop is None:
        return None
    return (stop - start) * 1000


def read_host(path):
    """{"annotations": [(start_ps, end_ps, name)] of every `tvt:*`
    event of every line of the host plane, "ops": [(start_ps, end_ps)]
    of the op line of the first device plane that ran ops, "window_ps":
    the profile's own window or None}."""
    with open(path, "rb") as fp:
        data = fp.read()
    annotations, ops, window_ps = [], [], None
    for num, plane in sr.fields(data):
        if num != 1:
            continue
        name, lines, metadata, stats = plane_parts(plane)
        if name == HOST_PLANE:
            wanted = annotation_names(metadata)
            for line in lines:
                base, events = line_events(line, wanted)
                annotations += [(base + off, base + off + dur, wanted[m])
                                for m, off, dur in events]
        elif name == ENV_PLANE:
            window_ps = _window_ps(stats, plane)
        elif name.startswith("/device:") and not ops:
            for line in lines:
                if sr._line_name(line) == sr.OP_LINE:
                    base, events = line_events(line)
                    ops += [(base + off, base + off + dur)
                            for _m, off, dur in events]
    return {"annotations": annotations, "ops": ops, "window_ps": window_ps}


# -- the arithmetic, on plain lists -------------------------------------------

def clip(merged, lo, hi):
    """The part of a sorted union that lies inside [lo, hi]."""
    return [[max(a, lo), min(b, hi)] for a, b in merged
            if b > lo and a < hi]


def complement(merged, lo, hi):
    """What of [lo, hi] no interval of the sorted union covers."""
    out, pos = [], lo
    for a, b in clip(merged, lo, hi):
        if a > pos:
            out.append([pos, a])
        pos = max(pos, b)
    if pos < hi:
        out.append([pos, hi])
    return out


def innermost(annotations):
    """[(lo, hi, name)], sorted and disjoint: each stretch of time that
    some annotation covers, under the name of the SHORTEST annotation
    covering it (ties: the one that started last)."""
    edges = sorted({t for lo, hi, _n in annotations for t in (lo, hi)})
    starts = sorted(annotations)
    out, k = [], 0
    live = []       # heap of (length, -start, end, name); ended ones are
    #                 dropped when they come to the top
    for lo, hi in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= lo:
            a, b, name = starts[k]
            heapq.heappush(live, (b - a, -a, b, name))
            k += 1
        while live and live[0][2] <= lo:
            heapq.heappop(live)
        if not live:
            continue
        name = live[0][3]
        if out and out[-1][2] == name and out[-1][1] == lo:
            out[-1][1] = hi
        else:
            out.append([lo, hi, name])
    return [tuple(row) for row in out]


def overlap_by_name(segments, idle):
    """{name: picoseconds} of the sorted disjoint `segments` [(lo, hi,
    name)] that fall inside the sorted disjoint intervals `idle`."""
    out, j = {}, 0
    for lo, hi, name in segments:
        while j < len(idle) and idle[j][1] <= lo:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < hi:
            got = min(hi, idle[k][1]) - max(lo, idle[k][0])
            if got > 0:
                out[name] = out.get(name, 0) + got
            k += 1
    return out


def reduce_host(annotations, ops, window_ps):
    """The numbers the seven readers share, from `read_host`'s lists; None
    where there is no `tvt:encode_stage`, no device op or no window.
    Times in picoseconds; `idle_by` holds the idle time inside the
    stage by annotation name (without the `tvt:` prefix) and UNNAMED,
    and sums to `idle_ps` exactly."""
    stages = [(lo, hi) for lo, hi, name in annotations if name == STAGE]
    if not stages or not ops or not window_ps:
        return None
    lo, hi = min(s for s, _e in stages), max(e for _s, e in stages)
    busy = pr.union(ops)
    inside = clip(busy, lo, hi)
    idle = complement(busy, lo, hi)
    named = overlap_by_name(
        innermost([a for a in annotations if a[2] != STAGE]), idle)
    idle_ps = pr.total(idle)
    idle_by = {name[len(PREFIX):]: ps for name, ps in named.items()}
    idle_by[UNNAMED] = idle_ps - sum(named.values())
    return {
        "window_ps": window_ps, "stage_ps": (lo, hi),
        "excess_ps": lo + (window_ps - hi),
        "busy_ps": pr.total(inside), "idle_ps": idle_ps,
        "idle_by": idle_by,
        "lead_in_ps": inside[0][0] - lo if inside else None,
        "tail_ps": hi - inside[-1][1] if inside else None,
    }


# -- what the readers share ---------------------------------------------------

_CACHE = {}


def _say(msg):
    print(f"[host_reduce] {msg}", file=sys.stderr, flush=True)


def host_of(ev):
    """`reduce_host` of the run's traced job, parsed once per run; None
    ("not measured", the reason on stderr) where there is no device
    profile, no file, or no `tvt:encode_stage` in it."""
    if not ev.get("profile"):
        return None
    path = sr.traced_profile(ev["cell"])
    if path is None:
        _say("no .xplane.pb under the cell's work directory: the host "
             "clock's metrics are not measured")
        return None
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if key not in _CACHE:
        _CACHE.clear()
        got = reduce_host(**read_host(path))
        if got is None:
            _say(f"no {STAGE} annotation (or no device op, or no window) "
                 f"in the traced job's profile: a program without the "
                 f"annotations; the host clock's metrics are not measured")
        _CACHE[key] = got
    return _CACHE[key]


def ms(ev, key):
    """`reduce_host`'s `key` (picoseconds) in milliseconds."""
    got = host_of(ev)
    if got is None or got[key] is None:
        return None
    return got[key] * 1e-9


def jobs_ms(ev, plus, minus=()):
    """Growth over the window of the stage clocks `plus` less that of
    `minus`, per job done, in ms; None where the program has no such
    clock."""
    jobs = len(evidence.done_jobs(ev))
    if not jobs or any(k not in ev["snapshot"]["after"] for k in plus):
        return None
    return (evidence.stage_delta(ev, *plus)
            - evidence.stage_delta(ev, *minus)) / jobs


# -- by hand --------------------------------------------------------------------

def main(path, frames=None):
    got = reduce_host(**read_host(path))
    if got is None:
        print(f"no {STAGE} annotation, no device op or no window: "
              f"not measured")
        return 1
    lo, hi = got["stage_ps"]
    for label, ps in (
            ("profile window", got["window_ps"]),
            ("  before the stage", lo), ("  after it", got["window_ps"] - hi),
            ("encode stage", hi - lo), ("  busy", got["busy_ps"]),
            ("  idle", got["idle_ps"]), ("  lead-in", got["lead_in_ps"]),
            ("  tail", got["tail_ps"])):
        print(f"{label:22s} {ps * 1e-9:12.3f} ms")
    print("idle inside the stage, by the innermost annotation:")
    for name, ps in sorted(got["idle_by"].items(), key=lambda kv: -kv[1]):
        print(f"  {name:20s} {ps * 1e-9:12.3f} ms "
              f"{100 * ps / max(1, got['idle_ps']):6.2f} %")
    if frames:
        print(f"unnamed per frame      "
              f"{got['idle_by'][UNNAMED] * 1e-9 / int(frames):12.4f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
