"""Device time per stage of the encode, from the traced job's `.xplane.pb`.

    python scope_reduce.py TRACE.xplane.pb [FRAMES]

The program files every device op under a stage: a component
`tvt.<stage>` in the op's path (`thinvids_tpu/codecs/h264/stages.py`),
which the profile keeps as the `tf_op` stat of the op's *event
metadata*. `jax.profiler.ProfileData`, which `profile_reduce.py` reads
with, shows an event's own stats and never its metadata's, so this
module walks the file at the protobuf wire level instead, with no
import beyond the standard library: the benchmark's parent calls it.

What it computes, per device plane and averaged over them like
`profile_reduce.reduce_planes`:

- self seconds by stage: an op's duration less that of the ops nested
  in it (`profile_reduce.self_times`, keyed by metadata id: `fusion.17`
  exists once per program), summed by the LAST `tvt.*` component of
  the op's path. What a `while` keeps for itself goes to the stage the
  `while` is filed under: the profiler gives a `while` no path, so it
  takes the path the ops nested in it share (`inherited_paths`);
- self seconds of the ops with no such component;
- busy seconds, the union of the ops' intervals. Self times partition
  the union where nothing overlaps on the op line, so the stages and
  the rest add up to it.

Where no op of the profile carries a stage at all, or no op of one of
its larger programs does, executables were built without the names
(jax's compile cache keys on the module with debug info stripped, so a
cache filled by an older tree hands back its own executables): that
reads as "not measured", never as 0 ms.
"""

import os
import re
import sys

if __name__ == "__main__":      # run by hand: find the package beside
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from tvtbench import evidence   # noqa: E402
from tvtbench import profile_reduce as pr    # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OP_LINE = pr.OP_LINE
SCOPE = re.compile(r"(?:^|/)(tvt\.[A-Za-z0-9_]+)(?=/|:|$)")
#: a program with no stage on any op is taken for one built without the
#: names once it holds this share of the busy time (the payload fetch's
#: `jit(dynamic_slice)` and its like hold far less)
UNNAMED_PROGRAM_SHARE = 0.01
#: (instruction, tf_op, source) of an event whose metadata is missing
_NO_META = ("", "", "")


# -- the protobuf wire format, as far as an XSpace needs it -------------

def varint(buf, pos):
    shift = val = 0
    while True:
        byte = buf[pos]
        pos += 1
        val |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return val, pos
        shift += 7


def fields(buf):
    """(field number, value) of a message: an int for a varint, the
    payload for a length-delimited field; fixed-width ones are passed
    over (an XSpace keeps nothing wanted in them)."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = varint(buf, pos)
        elif wire == 2:
            size, pos = varint(buf, pos)
            val = buf[pos:pos + size]
            pos += size
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, val


def _text(field):
    return bytes(field).decode("utf-8", "replace")


def _map_entry(buf):
    """(key, value message) of one entry of a map<int64, message>."""
    key = val = None
    for num, field in fields(buf):
        if num == 1:
            key = field
        elif num == 2:
            val = field
    return key, val


def _stat_names(plane):
    """XPlane.stat_metadata: id -> name."""
    names = {}
    for num, entry in fields(plane):
        if num == 5:
            key, meta = _map_entry(entry)
            for mnum, field in fields(meta):
                if mnum == 2:
                    names[key] = _text(field)
    return names


def _event_metadata(plane, stat_names):
    """XPlane.event_metadata: id -> (instruction name, tf_op, source)."""
    wanted = {k: n for k, n in stat_names.items()
              if n in ("tf_op", "source")}
    out = {}
    for num, entry in fields(plane):
        if num != 4:
            continue
        key, meta = _map_entry(entry)
        name, found = "", {}
        for mnum, field in fields(meta):
            if mnum == 2 and not name:
                name = _text(field)
            elif mnum == 4:
                name = _text(field)
            elif mnum == 5:
                stat_id = text = None
                for snum, sval in fields(field):
                    if snum == 1:
                        stat_id = sval
                    elif snum == 5:
                        text = _text(sval)
                    elif snum == 7:     # a string kept once, by reference
                        text = stat_names.get(sval, "")
                if stat_id in wanted and text is not None:
                    found[wanted[stat_id]] = text
        out[key] = (name.split(" = ", 1)[0].lstrip("%"),
                    found.get("tf_op", ""), found.get("source", ""))
    return out


def _line_name(line):
    for num, field in fields(line):
        if num == 2:
            return _text(field)
    return ""


def read_xplane(path):
    """The device planes of an `.xplane.pb` that ran ops:
    [{"name", "events": [(start_s, end_s, metadata id)],
      "meta": {id: (instruction, tf_op, source)}}]."""
    with open(path, "rb") as fp:
        data = fp.read()
    planes = []
    for num, plane in fields(data):
        if num != 1:
            continue
        name, lines = "", []
        for pnum, field in fields(plane):
            if pnum == 2:
                name = _text(field)
            elif pnum == 3:
                lines.append(field)
        if not name.startswith("/device:"):
            continue
        events = []
        for line in lines:
            if _line_name(line) != OP_LINE:
                continue
            for lnum, event in fields(line):
                if lnum != 4:
                    continue
                meta_id = offset = duration = 0
                for enum, val in fields(event):
                    if enum == 1:
                        meta_id = val
                    elif enum == 2:
                        offset = val
                    elif enum == 3:
                        duration = val
                events.append((offset * 1e-12, (offset + duration) * 1e-12,
                               meta_id))
        if events:
            planes.append({"name": name, "events": events,
                           "meta": _event_metadata(plane,
                                                   _stat_names(plane))})
    return planes


# -- the arithmetic, on plain lists --------------------------------------

def scope_of(tf_op):
    """The last `tvt.*` component of an op's path, or None."""
    found = SCOPE.findall(tf_op or "")
    return found[-1] if found else None


def program_of(tf_op):
    """The program an op was compiled in: the path's first component
    (`jit(_encode_gop_single)`), or "" where the op has no path."""
    head = (tf_op or "").split("/", 1)[0].rstrip(":")
    return head if head.startswith("jit(") else ""


def _shared(parts, more):
    """The components two paths share from the start (`parts` None:
    nothing seen yet)."""
    if parts is None:
        return more
    same = 0
    for a, b in zip(parts, more):
        if a != b:
            break
        same += 1
    return parts[:same]


def inherited_paths(events, meta):
    """{metadata id: path} for the ops that have no path of their own
    and enclose ops that have one: the components those ops' paths
    share. The profiler gives a `while` no `tf_op`; the loop over P
    frames is then filed where its body's and its condition's ops all
    are (`.../tvt.layout/while`). An op whose children have no path
    either (a loop the compiler made) inherits nothing."""
    found = {}
    stack = []      # [end, metadata id, what the children's paths share]

    def close(item):
        _end, meta_id, parts = item
        if parts and not meta.get(meta_id, _NO_META)[1]:
            # every occurrence of the op has its say
            found[meta_id] = parts = _shared(found.get(meta_id), parts)
            if stack:
                stack[-1][2] = _shared(stack[-1][2], parts)

    for start, end, meta_id in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        path = meta.get(meta_id, _NO_META)[1]
        if stack and path:
            stack[-1][2] = _shared(stack[-1][2],
                                   path.rstrip(":").split("/"))
        stack.append([end, meta_id, None])
    while stack:
        close(stack.pop())
    return {k: "/".join(v) for k, v in found.items() if v}


def reduce_scopes(planes):
    """`planes` as `read_xplane` gives them -> {"scopes": {scope:
    seconds}, "unscoped_s", "busy_s", "ops": [[seconds, scope or None,
    instruction, tf_op, source]] by self time, "stale": why the stage
    times cannot be trusted, or None}, each time the mean over the
    planes. Stale is a profile in which a program that takes a share of
    the busy time has not one op with a stage: its executable was built
    without the names, and its time must not read as "no stage"."""
    scopes, ops, programs = {}, {}, {}
    unscoped = busy = 0.0
    for plane in planes:
        busy += pr.total(pr.union((s, e) for s, e, _m in plane["events"]))
        inherited = inherited_paths(plane["events"], plane["meta"])
        for meta_id, (sec, _cnt) in pr.self_times(plane["events"]).items():
            name, tf_op, source = plane["meta"].get(meta_id, _NO_META)
            tf_op = tf_op or inherited.get(meta_id, "")
            scope = scope_of(tf_op)
            if scope is None:
                unscoped += sec
            else:
                scopes[scope] = scopes.get(scope, 0.0) + sec
            cell = ops.setdefault((scope, name, tf_op, source), [0.0])
            cell[0] += sec
            seen = programs.setdefault(program_of(tf_op), [0.0, False])
            seen[0] += sec
            seen[1] = seen[1] or scope is not None
    n = max(1, len(planes))
    unnamed = sorted(prog for prog, (sec, named) in programs.items()
                     if prog and not named
                     and sec > UNNAMED_PROGRAM_SHARE * busy)
    stale = None
    if not scopes:
        stale = "no op carries a tvt.* stage"
    elif unnamed:
        stale = f"no op of {', '.join(unnamed)} carries a tvt.* stage"
    ranked = sorted(((sec / n, *key) for key, (sec,) in ops.items()),
                    key=lambda row: -row[0])
    return {"scopes": {k: v / n for k, v in scopes.items()},
            "unscoped_s": unscoped / n, "busy_s": busy / n,
            "ops": [list(row) for row in ranked], "stale": stale}


# -- what the six readers share -------------------------------------------

_CACHE = {}


def _say(msg):
    print(f"[scope_reduce] {msg}", file=sys.stderr, flush=True)


def traced_profile(cell):
    """The `.xplane.pb` run.py's traced job left under the cell's work
    directory (`Run.work`; found as `Run.reduce_profile` finds it)."""
    found = [os.path.join(d, f) for d, _s, fs in
             os.walk(os.path.join(ROOT, ".smoke_work", "benchmark", cell,
                                  "profiles"))
             for f in fs if f.endswith(".xplane.pb")]
    return found[0] if found else None


def scopes_of(ev):
    """`reduce_scopes` of the run's traced job, parsed once per run;
    None ("not measured") where there is no device profile, no file,
    or no stage name in it."""
    if not ev.get("profile"):
        return None
    path = traced_profile(ev["cell"])
    if path is None:
        _say("no .xplane.pb under the cell's work directory: the "
             "dev_* metrics are not measured")
        return None
    key = (path, os.path.getmtime(path), os.path.getsize(path))
    if key not in _CACHE:
        _CACHE.clear()
        got = reduce_scopes(read_xplane(path))
        if got["stale"]:
            _say(f"{got['stale']} in the traced job's profile: the "
                 f"executables were built without the names (a compile "
                 f"cache filled by an older tree is the usual cause, "
                 f"PERF.md §7); the dev_* metrics are not measured")
            got = None
        _CACHE[key] = got
    return _CACHE[key]


def stage_ms_per_frame(ev, *scopes):
    """Self time of the named stages per frame of the traced job."""
    got = scopes_of(ev)
    if got is None:
        return None
    return evidence.profile_per_frame(
        ev, sum(got["scopes"].get(s, 0.0) for s in scopes))


def unscoped_pct(ev):
    got = scopes_of(ev)
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * got["unscoped_s"] / got["busy_s"]


# -- by hand ----------------------------------------------------------------

def main(path, frames=None):
    got = reduce_scopes(read_xplane(path))
    if got["stale"]:
        print(f"stale: {got['stale']}")
    if not got["busy_s"]:
        return 1
    per = 1e3 / int(frames) if frames else 1e3
    unit = "ms/frame" if frames else "ms"
    rows = sorted(got["scopes"].items(), key=lambda kv: -kv[1]) \
        + [("(no stage)", got["unscoped_s"])]
    for scope, sec in rows:
        print(f"{scope:16s} {sec * per:12.4f} {unit} "
              f"{100 * sec / got['busy_s']:6.2f} %")
    print(f"{'sum':16s} {sum(s for _n, s in rows) * per:12.4f} {unit}")
    print(f"{'busy (union)':16s} {got['busy_s'] * per:12.4f} {unit}")
    for title, keep in (
            ("ops with no stage", lambda row: row[1] is None),
            ("loops (self time)", lambda row: row[2].startswith("while")),
            ("all ops", lambda row: True)):
        print(f"\n{title}, by self time:")
        for sec, scope, name, tf_op, source in \
                [row for row in got["ops"] if keep(row)][:12]:
            print(f"  {sec * per:10.4f} {unit} {scope or '-':14s} "
                  f"{name:28s} {tf_op}  [{os.path.basename(source)}]")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
