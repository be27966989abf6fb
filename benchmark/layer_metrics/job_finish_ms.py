"""mux: growth over the window of the executor's clocks `job_stitch`
(joining the segments) + `job_mux` (`mux_mp4`) + `job_write` (write and
rename) + `job_commit` (the journal's completion records) / jobs done:
what a job pays after its last wave, timed from the inside
(`mux_ms_per_job` takes the same stretch from outside). Not measured
where the program has no such clocks."""

from tvtbench import host_reduce


def read(ev):
    return host_reduce.jobs_ms(ev, ("job_stitch", "job_mux", "job_write",
                                    "job_commit"))
