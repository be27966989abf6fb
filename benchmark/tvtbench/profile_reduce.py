"""`.xplane.pb` of one traced job -> the numbers the device metrics read.

    JAX_PLATFORMS=cpu python profile_reduce.py TRACE.xplane.pb OUT.json

Runs in a child so the benchmark's parent stays off jax
(`jax.profiler.ProfileData` is the only reader of the format this
image has). `reduce_planes` works on plain lists, so the arithmetic is
tested without a trace.

What it computes, per device plane and averaged over them:

- busy: the union of the intervals of the plane's op line (`XLA Ops`),
  so nested events (a `while` and the ops of its body) count once;
- per-op self time: an event's duration less that of the events nested
  in it, summed by name, so the ranking names leaves, not loops;
- the motion-search kernel: custom calls whose instruction is named
  after `jit(_me_pallas)` (`%_me_pallas.N = ... custom-call(...)`; the
  Pallas call itself carries no name);
- collectives: the synchronous ones on the op line (`all-reduce`) and
  the spans of the asynchronous ones on `Async XLA Ops` (a
  `collective-permute-start` there lasts until its `-done`; on the op
  line the two are microseconds), and the part of their union during
  which no compute leaf ran on that device;
- idle gaps of the first device plane, longest first, the stretches
  before its first and after its last op included.

Times are seconds; `t0_epoch_s` is the profile's own start on the unix
clock (the `Task Environment` plane), event times are relative to it.
"""

import json
import re
import sys

OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
ME_PATTERN = re.compile(r"_me_pallas")
CUSTOM_CALL_PATTERN = re.compile(r"custom-call|custom_call|mosaic",
                                 re.IGNORECASE)
COLLECTIVE_PATTERN = re.compile(
    r"collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter",
    re.IGNORECASE)
ASYNC_HALF_PATTERN = re.compile(r"-(start|done)(\.\d+)?$")
GAP_MIN_S = 100e-6
GAPS_KEPT = 2000
OPS_KEPT = 40


def union(intervals):
    """Sorted, merged copy of [(start, end), ...]."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return out


def total(merged):
    return sum(hi - lo for lo, hi in merged)


def subtract(merged_a, merged_b):
    """Length of the part of union A that no interval of union B covers."""
    left, j = 0.0, 0
    for lo, hi in merged_a:
        pos = lo
        while j < len(merged_b) and merged_b[j][1] <= pos:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < hi:
            if merged_b[k][0] > pos:
                left += merged_b[k][0] - pos
            pos = max(pos, merged_b[k][1])
            k += 1
        if pos < hi:
            left += hi - pos
    return left


def self_times(events):
    """{name: [self seconds, count]} of events [(start, end, name)] on
    one line, where an event may enclose later ones."""
    out = {}
    stack = []      # [end, name, duration, children's duration]

    def close(item):
        end, name, dur, kids = item
        cell = out.setdefault(name, [0.0, 0])
        cell[0] += max(0.0, dur - kids)
        cell[1] += 1

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] += end - start
        stack.append([end, name, end - start, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce_planes(planes, window_s, t0_epoch_s=None):
    """`planes`: [{"name", "events": [(start_s, end_s, name, text)],
    "async_events": [(start_s, end_s, name)]}] of the device planes' op
    and async-op lines, `text` being what a pattern may match (the
    whole instruction and string stats). Event times count from the
    profile's start, so the window is [0, window_s]."""
    per_plane = []
    ops = {}
    me_s = me_n = 0
    coll_s = coll_exposed_s = 0.0
    coll_n = 0
    for plane in planes:
        evs = plane["events"]
        merged = union((s, e) for s, e, _n, _t in evs)
        per_plane.append({
            "name": plane["name"], "events": len(evs),
            "busy_s": total(merged)})
        for name, (sec, cnt) in self_times(
                [(s, e, n) for s, e, n, _t in evs]).items():
            cell = ops.setdefault(name, [0.0, 0])
            cell[0] += sec
            cell[1] += cnt
        me = [(s, e) for s, e, n, t in evs if ME_PATTERN.search(n)
              and CUSTOM_CALL_PATTERN.search(t)]
        me_s += sum(e - s for s, e in me)
        me_n += len(me)
        coll = [(s, e) for s, e, n, _t in evs
                if COLLECTIVE_PATTERN.search(n)
                and not ASYNC_HALF_PATTERN.search(n)]
        coll += [(s, e) for s, e, n in plane.get("async_events", ())
                 if COLLECTIVE_PATTERN.search(n)]
        if coll:
            merged_coll = union(coll)
            coll_s += total(merged_coll)
            coll_exposed_s += subtract(merged_coll, _leaves(evs))
            coll_n += len(coll)
    n = max(1, len(planes))
    gaps, small = [], 0.0
    if planes:
        merged = union((s, e) for s, e, _n, _t in planes[0]["events"])
        edges = [[0.0, 0.0]] + merged + [[window_s, window_s]]
        for (_, hi), (lo, _) in zip(edges, edges[1:]):
            if lo - hi >= GAP_MIN_S:
                gaps.append([hi, lo - hi])
            else:
                small += lo - hi
        gaps.sort(key=lambda g: -g[1])
        small += sum(g[1] for g in gaps[GAPS_KEPT:])
        gaps = gaps[:GAPS_KEPT]
    ranked = sorted(ops.items(), key=lambda kv: -kv[1][0])[:OPS_KEPT]
    return {
        "device_planes": per_plane,
        "busy_s": sum(p["busy_s"] for p in per_plane) / n,
        "window_s": window_s,
        "t0_epoch_s": t0_epoch_s,
        "ops": [[name, sec / n, cnt] for name, (sec, cnt) in ranked],
        "me": {"seconds": me_s / n, "events": me_n} if me_n else None,
        "collectives": {"seconds": coll_s / n,
                        "exposed_seconds": coll_exposed_s / n,
                        "events": coll_n},
        "gaps": gaps, "gaps_small_s": small,
    }


def _leaves(evs):
    """Union of the non-collective events that enclose nothing: the
    compute that can hide a collective. (A `while` that spans the whole
    step would hide everything.)"""
    plain = sorted(((s, e) for s, e, n, _t in evs
                    if not COLLECTIVE_PATTERN.search(n)),
                   key=lambda x: (x[0], -x[1]))
    return union((s, e) for (s, e), nxt in
                 zip(plain, plain[1:] + [(float("inf"), 0.0)])
                 if nxt[0] >= e)


def read_xplane(path):
    """(device planes for reduce_planes, window seconds, start on the
    unix clock or None) of an .xplane.pb."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes, start_ns, stop_ns = [], None, None
    extent = [None, None]
    for plane in data.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start_ns = stats.get("profile_start_time")
            stop_ns = stats.get("profile_stop_time")
            continue
        if not plane.name.startswith("/device:"):
            continue
        events, async_events = [], []
        for line in plane.lines:
            if line.name == ASYNC_LINE:
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    async_events.append(
                        (start, start + ev.duration_ns * 1e-9,
                         ev.name.split(" = ", 1)[0].lstrip("%")))
            if line.name != OP_LINE:
                continue
            for ev in line.events:
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                # the op line names an event by its whole HLO
                # instruction: `%fusion.17 = s8[...] fusion(...)`
                text = ev.name + " " + " ".join(
                    v for _k, v in ev.stats if isinstance(v, str))
                name = ev.name.split(" = ", 1)[0].lstrip("%")
                events.append((start, end, name, text))
        if events:
            planes.append({"name": plane.name, "events": events,
                           "async_events": async_events})
            lo = min(e[0] for e in events)
            hi = max(e[1] for e in events)
            extent[0] = lo if extent[0] is None else min(extent[0], lo)
            extent[1] = hi if extent[1] is None else max(extent[1], hi)
    if start_ns is not None and stop_ns is not None:
        window_s = (stop_ns - start_ns) * 1e-9
    elif extent[0] is not None:
        window_s = extent[1] - extent[0]
    else:
        window_s = 0.0
    return planes, window_s, (start_ns * 1e-9 if start_ns else None)


def main(trace_path, out_path):
    planes, window_s, t0 = read_xplane(trace_path)
    with open(out_path, "w", encoding="utf-8") as fp:
        json.dump(reduce_planes(planes, window_s, t0), fp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
