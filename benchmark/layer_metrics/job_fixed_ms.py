"""executor: median, over the window's jobs, of the job's run time
(`finished_at - started_at`) less the extent of its wave-pipeline spans
in GET /trace/<job>: probe, open, encoder construction before the first
wave; stitch, mux, write and journal after the last."""

from tvtbench import evidence


def read(ev):
    fixed = []
    for j in evidence.done_jobs(ev):
        extent = evidence.pipeline_extent(j)
        if extent is not None:
            run = j["record"]["finished_at"] - j["record"]["started_at"]
            fixed.append((run - (extent[1] - extent[0])) * 1e3)
    return evidence.median(fixed)
