"""executor: sum of the jobs' wave-pipeline extents / the window: how
much of the window lies inside some encode stage. The profiled job is
left out of both (the profiler's start and stop stretch it by seconds
that no untraced run has). Not measured when a job's span ring wrapped."""

from tvtbench import evidence


def read(ev):
    profiled = evidence.traced_job(ev)
    jobs = [j for j in evidence.done_jobs(ev) if j is not profiled]
    extents = [evidence.pipeline_extent(j) for j in jobs]
    if not extents or any(e is None for e in extents):
        return None
    window = evidence.window_s(ev)
    if profiled is not None:
        window -= float(profiled["record"].get("elapsed_s") or 0.0)
    return 100.0 * sum(hi - lo for lo, hi in extents) / window
