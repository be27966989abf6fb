"""The system under test: `python -m thinvids_tpu.cli coordinator` as
the one chip-holding child, driven over its HTTP API as a client would.

The pattern is `chip_smoke.py`'s: the parent
stays off jax, the evidence comes from the serving process itself."""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time

class BenchFailure(Exception):
    """The run cannot produce a result (no device, daemon died, a job
    did not finish): non-zero exit, nothing printed."""


class Daemon:
    def __init__(self, root, work, env, log_path):
        with socket.socket() as sk:
            sk.bind(("127.0.0.1", 0))
            self.port = sk.getsockname()[1]
        self.log_path = log_path
        self._log = open(log_path, "wb")
        self._conn = None
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "thinvids_tpu.cli", "coordinator",
             "--host", "127.0.0.1", "--port", str(self.port),
             "--state-dir", os.path.join(work, "state"),
             "--output-dir", os.path.join(work, "library")],
            cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    # -- HTTP (one keep-alive connection: one handler thread there) ----

    def call(self, path, body=None, timeout=10.0):
        """(status, parsed JSON) of one request."""
        data = json.dumps(body).encode() if body is not None else None
        headers = {"Content-Type": "application/json"} if data else {}
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=timeout)
            try:
                self._conn.request("POST" if data else "GET", path,
                                   body=data, headers=headers)
                resp = self._conn.getresponse()
                return resp.status, json.loads(resp.read())
            except (http.client.HTTPException, OSError):
                self._conn.close()
                self._conn = None
                if attempt:
                    raise

    def get(self, path):
        status, doc = self.call(path)
        if status != 200:
            raise BenchFailure(f"GET {path} answered {status}: {doc}")
        return doc

    # -- lifecycle -----------------------------------------------------

    def alive(self):
        if self.proc.poll() is not None:
            raise BenchFailure(
                f"daemon exited with code {self.proc.returncode}:\n"
                + self.log_tail())

    def log_lines(self):
        with open(self.log_path, encoding="utf-8", errors="replace") as fp:
            return fp.readlines()

    def log_tail(self, n=40):
        return "".join(ln[:400].rstrip("\n") + "\n"
                       for ln in self.log_lines()[-n:])

    def wait_device(self, deadline):
        """The daemon's own agent row once it has sampled its devices:
        platform, kind and count as jax reported them there."""
        while time.time() < deadline:
            self.alive()
            try:
                nodes = self.get("/nodes_data")["nodes"]
            except (OSError, http.client.HTTPException):
                nodes = []
            for node in nodes:
                if node.get("devices", 0) >= 1:
                    return {"platform": node["platform"],
                            "kind": node["device_kind"],
                            "count": int(node["devices"])}
            time.sleep(0.2)
        raise BenchFailure("daemon never reported a device:\n"
                           + self.log_tail())

    def stop(self):
        """SIGTERM, then the exit code (None if it had to be killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        code = self.proc.poll()
        self.kill()
        return code

    def kill(self):
        if self._conn is not None:
            self._conn.close()
            self._conn = None
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()
        self._log.close()
