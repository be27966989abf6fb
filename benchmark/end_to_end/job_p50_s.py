"""Median, over the jobs of the window, of the client clock from POST
/add_job sent to the first poll that sees the job done."""

from tvtbench import evidence


def read(ev):
    return evidence.median(j["done_t"] - j["submit_t"]
                           for j in evidence.done_jobs(ev))
