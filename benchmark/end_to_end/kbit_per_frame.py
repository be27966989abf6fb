"""Video bytes of the outputs (the mdat payload) x 8 / frames / 1000."""

from tvtbench import evidence


def read(ev):
    jobs = evidence.done_jobs(ev)
    if not jobs:
        return None
    return sum(j["video_bytes"] for j in jobs) * 8 / 1000 \
        / evidence.frames_done(ev)
