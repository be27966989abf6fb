"""executor: growth over the window of the executor's clocks `job_build`
(encoder construction) + `job_plan` (the plan) less `scenecut` (which
nests in the plan and has `scenecut_ms_per_frame` of its own) / jobs
done: what a job pays before its first wave is staged, timed from the
inside. Not measured where the program has no such clocks."""

from tvtbench import host_reduce


def read(ev):
    return host_reduce.jobs_ms(ev, ("job_build", "job_plan"),
                               minus=("scenecut",))
