"""The one general traffic generator: a closed loop of uploaders.

A farm's clients are a watch folder and uploaders that wait for their
result, so the loop is closed: `outstanding` jobs are kept in flight,
the next is sent when one completes. Each job is a hard link of the
cell's source under a name of its own (the output is named after the
input), sent with `POST /add_job` and the mix's `job_settings`.
Everything else a mix can vary is a parameter of its data file."""

import os
import time

from .daemon import BenchFailure

FINAL = ("done", "failed", "rejected", "stopped")

#: a client polls its jobs this often, so a completion is seen at most
#: this late
POLL_S = 0.05
#: submitting stops when the time left in the window is under this many
#: times the slowest job so far
STOP_MARGIN = 1.5


class Uploader:
    def __init__(self, daemon, jobs_dir, on_tick=None):
        self.daemon = daemon
        self.jobs_dir = jobs_dir
        self.on_tick = on_tick      # called about once a second
        self._serial = 0
        self._next_tick = 0.0

    def submit(self, source, frames, settings, tag="w"):
        """Link, POST, and the job's client-side record. The clock
        starts before the request is sent."""
        self._serial += 1
        name = f"{tag}{self._serial:04d}"
        path = os.path.join(self.jobs_dir, name + ".y4m")
        os.link(source, path)
        t_submit = time.time()
        body = {"input_path": path}
        if settings:
            body["settings"] = settings
        status, doc = self.daemon.call("/add_job", body)
        if status != 201:
            raise BenchFailure(f"POST /add_job answered {status}: {doc}")
        return {"name": name, "id": doc["id"], "input": path,
                "source": source, "frames": frames,
                "settings": dict(settings or {}),
                "submit_t": t_submit, "done_t": None, "record": doc}

    def poll(self, pending):
        """One pass over the jobs in flight; those that reached a final
        state, stamped with the client clock of the poll that saw it."""
        ended = []
        for job in pending:
            view = self.daemon.get(f"/job_properties/{job['id']}?limit=1")
            rec = view["job"]
            if rec["status"] in FINAL:
                job["done_t"] = time.time()
                job["record"] = rec
                ended.append(job)
        now = time.time()
        if self.on_tick is not None and now >= self._next_tick:
            self._next_tick = now + 1.0
            self.daemon.alive()
            self.on_tick()
        return ended

    def run_one(self, source, frames, settings, deadline, tag):
        """One job from submit to its end (set-up's warm-up jobs)."""
        job = self.submit(source, frames, settings, tag)
        while time.time() < deadline:
            if self.poll([job]):
                return job
            time.sleep(POLL_S)
        raise BenchFailure(self._stuck(job))

    def _stuck(self, job):
        snap = self.daemon.get("/metrics_snapshot")
        rec = self.daemon.get(f"/job_properties/{job['id']}?limit=1")["job"]
        return (f"job {job['name']} not done by its deadline: status "
                f"{rec['status']}, stage {rec.get('heartbeat_stage')} "
                f"({rec.get('heartbeat_note')}); scheduler wait reason: "
                f"{snap['scheduler']['wait_reason']!r}\n"
                + self.daemon.log_tail())

    def window(self, plan, seconds, outstanding, on_done, hard_deadline):
        """The measured window. `plan(k)` gives (source, frames,
        settings) of the k-th job. Submitting stops when the time left
        is under STOP_MARGIN x the slowest job so far (its run time,
        started -> finished on the job record); every submitted job
        must reach a final state. `on_done(job)` runs after the next
        job has been sent. Returns (jobs in submit order, first submit,
        last completion seen)."""
        jobs, pending = [], []
        slowest = 0.0
        t0 = t_last = None

        def refill():
            nonlocal t0
            while len(pending) < outstanding:
                left = seconds if t0 is None \
                    else seconds - (time.time() - t0)
                if left < STOP_MARGIN * slowest:
                    return
                job = self.submit(*plan(len(jobs)))
                if t0 is None:
                    t0 = job["submit_t"]
                jobs.append(job)
                pending.append(job)

        while True:
            refill()
            if not pending:
                return jobs, t0, t_last
            if time.time() > hard_deadline:
                raise BenchFailure(self._stuck(pending[0]))
            ended = self.poll(pending)
            if not ended:
                time.sleep(POLL_S)
                continue
            for job in ended:
                pending.remove(job)
                t_last = job["done_t"]
                slowest = max(slowest,
                              float(job["record"].get("elapsed_s") or 0.0))
            refill()            # the next job goes out first,
            for job in ended:   # then what came back is looked at
                on_done(job)
