"""What a run collects, and the arithmetic the metric readers share.

The evidence is one JSON-clean dict (run.py builds it, `--keep DIR`
writes it): the window's jobs with their client clocks, job records
and spans, the `/metrics_snapshot` stage counters before and after the
window, and the reduced device profile of the traced job. A reader
(`end_to_end/<name>.py`, `layer_metrics/<name>.py`) is one function
`read(ev)` that returns a number, or None for "not measured"."""

import statistics


def fetch_trace(daemon, job_id, ring):
    """The job's spans from GET /trace/<job>, seconds on the unix
    clock; `wrapped` when the ring was full, so the oldest are gone."""
    status, doc = daemon.call(f"/trace/{job_id}")
    if status != 200:
        return None
    spans = [{"name": e["name"], "t0": e["ts"] / 1e6, "dur": e["dur"] / 1e6}
             for e in doc["traceEvents"] if e.get("ph") == "X"]
    return {"spans": spans, "wrapped": len(spans) >= ring}


# -- what the readers share ---------------------------------------------

def done_jobs(ev):
    return [j for j in ev["jobs"] if j["record"]["status"] == "done"]


def frames_done(ev):
    return sum(j["frames"] for j in done_jobs(ev))


def window_s(ev):
    return ev["window"]["t_last_done"] - ev["window"]["t_first_submit"]


def median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def stage_delta(ev, *keys):
    """Growth over the window of the sum of `stage_ms` entries."""
    before, after = ev["snapshot"]["before"], ev["snapshot"]["after"]
    return sum(float(after.get(k, 0)) - float(before.get(k, 0))
               for k in keys)


def per_frame(ev, *keys):
    frames = frames_done(ev)
    return stage_delta(ev, *keys) / frames if frames else None


def pipeline_extent(job):
    """(first start, last end) of the job's wave-pipeline spans, or
    None where the trace is missing, empty or wrapped: a shortened
    extent would read as fixed cost."""
    trace = job.get("trace")
    if not trace or trace["wrapped"] or not trace["spans"]:
        return None
    return (min(s["t0"] for s in trace["spans"]),
            max(s["t0"] + s["dur"] for s in trace["spans"]))


def traced_job(ev):
    return next((j for j in ev["jobs"] if j["name"] == ev["traced_job"]),
                None)


def profile_per_frame(ev, seconds):
    """Seconds of the traced job's profile -> ms per frame of that job."""
    job = traced_job(ev)
    if seconds is None or job is None:
        return None
    return seconds * 1e3 / job["frames"]


# -- the traced run's breakdown -------------------------------------------

def host_span_at(spans, t):
    """Name of the innermost (shortest) span of the job that covers the
    instant `t`."""
    best = None
    for s in spans:
        if s["t0"] <= t <= s["t0"] + s["dur"] \
                and (best is None or s["dur"] < best["dur"]):
            best = s
    return best["name"] if best else "no host span"


def attribute_gap(spans, lo, hi, shares):
    """Add the gap [lo, hi] to `shares` by what the host was doing:
    long gaps are looked at in 20 places, short ones in the middle."""
    n = 20 if hi - lo >= 0.01 else 1
    for i in range(n):
        what = host_span_at(spans, lo + (hi - lo) * (i + 0.5) / n)
        shares[what] = shares.get(what, 0.0) + (hi - lo) / n


def breakdown(ev):
    """`device_ops`: the ten ops with most self time. `idle_gaps`: the
    device's idle time by what the host was doing, from the traced
    job's spans where the profile's clock can be set against theirs
    (its start on the unix clock lies inside the job), else
    unattributed."""
    prof = ev["profile"]
    out = {"device_ops": [[name, sec] for name, sec, _n in prof["ops"][:10]]}
    job = traced_job(ev)
    trace = job.get("trace") if job else None
    t0 = prof.get("t0_epoch_s")
    aligned = bool(
        trace and trace["spans"] and not trace["wrapped"] and t0
        and job["record"]["started_at"] - 1 <= t0
        <= job["record"]["finished_at"] + 1)
    by_what = {}
    for start, dur in prof["gaps"]:
        if aligned:
            attribute_gap(trace["spans"], t0 + start, t0 + start + dur,
                          by_what)
        else:
            what = "unattributed (clocks not aligned)"
            by_what[what] = by_what.get(what, 0.0) + dur
    if prof["gaps_small_s"]:
        by_what["gaps under 0.1 ms"] = prof["gaps_small_s"]
    out["idle_gaps"] = [[k, v] for k, v in sorted(
        by_what.items(), key=lambda kv: -kv[1])[:10]]
    return out
