"""mux: median of `finished_at` less the end of the job's last pipeline
span: segment stitch, `mux_mp4`, the write and rename, the journal's
completion record. (`stage_ms.concat` is the per-GOP payload join inside
the pipeline, not the mux; the program times no mux stage.)"""

from tvtbench import evidence


def read(ev):
    tails = []
    for j in evidence.done_jobs(ev):
        extent = evidence.pipeline_extent(j)
        if extent is not None:
            tails.append((j["record"]["finished_at"] - extent[1]) * 1e3)
    return evidence.median(tails)
