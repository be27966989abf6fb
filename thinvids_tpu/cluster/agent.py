"""Node agent: device-health metrics, heartbeats, role sync, idle hook.

Port of the reference's per-node agent (/root/reference/agent/agent.py:
355-496) onto TPU-VM terms: instead of `intel_gpu_top` GPU busyness it
samples accelerator HBM occupancy via `Device.memory_stats()`, plus
host cpu/mem/disk/net from psutil. Metrics flow into the coordinator's
WorkerRegistry — in-process via a direct submitter, or cross-host via
``POST /node_heartbeat`` on the HTTP API — where the 15 s TTL makes
them the liveness signal (the reference's `metrics:node:<host>` hash
with EXPIRE 15, agent.py:417-436).

Idle suspend (agent.py:445-496) keeps the same gate structure — cpu
below threshold AND all jobs idle for `suspend_idle_s` — but the
suspend action is an injected callable: on a TPU-VM there is no WOL to
wake a suspended node, so the default action only emits an activity
event; deployments wire in their own (e.g. scale-down API call).
"""

from __future__ import annotations

import functools
import socket
import threading
import time
from typing import Any, Callable, Mapping

from ..core.log import get_logging


@functools.cache
def _warn_once(message: str) -> None:
    """The sampler runs at 1 Hz: say each distinct failure once."""
    get_logging(__name__).warning(message)


def sample_device_metrics() -> dict[str, Any]:
    """Accelerator health: platform, device kind and count as jax
    reports them, plus per-device HBM occupancy where the backend
    reports memory stats (the CPU backend returns None).

    Touches the jax backend, so on a TPU host this process takes the
    chip: run a metrics-only agent on hosts WITHOUT a coordinator or
    worker (deploy/README.md)."""
    out: dict[str, Any] = {}
    try:
        import jax

        devices = jax.local_devices()
    except Exception as exc:             # noqa: BLE001 - no backend
        _warn_once("device metrics unavailable, reporting 0 devices "
                   f"({type(exc).__name__}: {exc})")
        return {"devices": 0}
    out["devices"] = len(devices)
    out["platform"] = devices[0].platform if devices else ""
    out["device_kind"] = devices[0].device_kind if devices else ""
    used = limit = 0
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:                # noqa: BLE001
            stats = None
        if stats:
            used += int(stats.get("bytes_in_use", 0))
            limit += int(stats.get("bytes_limit", 0))
    if limit > 0:
        out["hbm_used_bytes"] = used
        out["hbm_total_bytes"] = limit
        out["hbm_pct"] = round(100.0 * used / limit, 1)
    return out


def sample_host_metrics() -> dict[str, Any]:
    """Host health: cpu/mem/disk/net — the fields the reference agent
    published at 1 Hz (agent.py:396-415)."""
    import psutil

    vm = psutil.virtual_memory()
    disk = psutil.disk_usage("/")
    io = psutil.net_io_counters()
    return {
        "cpu": psutil.cpu_percent(interval=None),
        "mem": vm.percent,
        "mem_used": vm.used,
        "mem_total": vm.total,
        "disk": disk.percent,
        "net_rx_bytes": io.bytes_recv,
        "net_tx_bytes": io.bytes_sent,
    }


def coordinator_submitter(coordinator) -> Callable[[str, Mapping], None]:
    """In-process heartbeat sink: registry.heartbeat directly."""
    def submit(host: str, metrics: Mapping[str, Any]) -> None:
        coordinator.registry.heartbeat(host, metrics=dict(metrics))
    return submit


def http_submitter(base_url: str, timeout_s: float = 5.0
                   ) -> Callable[[str, Mapping], None]:
    """Cross-host heartbeat sink: POST /node_heartbeat on the API.

    Transient transport failures (connection refused while a restarted
    coordinator replays its journal, 5xx) retry with the same
    jittered-backoff policy as the worker's /work client
    (`remote_http_retries` / `remote_http_backoff_s`) — one short
    restart window must not let heartbeat TTLs lapse and sweep healthy
    workers' leases. A heartbeat is trivially idempotent."""
    import json
    import urllib.request

    from ..core.config import get_settings
    from ..core.retry import call_with_backoff

    def submit(host: str, metrics: Mapping[str, Any]) -> None:
        snap = get_settings()
        body = json.dumps({"host": host, "metrics": dict(metrics)}).encode()

        def send() -> None:
            req = urllib.request.Request(
                base_url.rstrip("/") + "/node_heartbeat", data=body,
                method="POST",
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=timeout_s).read()

        call_with_backoff(send, int(snap.get("remote_http_retries", 4)),
                          float(snap.get("remote_http_backoff_s", 0.5)))
    return submit


class NodeAgent:
    """Periodic metrics heartbeat + idle detection.

    `submit(host, metrics)` is the injection point (see the two
    submitters above). `idle_probe()` must answer "is the whole cluster
    idle?" (the reference's all_jobs_are_idle); `suspend_action()` runs
    once per idle episode after the gates hold for `suspend_idle_s`.
    """

    def __init__(self, submit: Callable[[str, Mapping], None],
                 host: str | None = None, interval_s: float = 1.0,
                 settings_fn=None, idle_probe: Callable[[], bool] = None,
                 suspend_action: Callable[[], None] | None = None,
                 resume_action: Callable[[], None] | None = None,
                 extra_metrics: Callable[[], Mapping[str, Any]] | None = None,
                 clock: Callable[[], float] = time.time) -> None:
        from ..core.config import get_settings

        self.host = host or socket.gethostname()
        self.submit = submit
        self.interval_s = interval_s
        #: optional per-process gauge source merged into every
        #: heartbeat — the worker daemon reports its shard counters
        #: (busy/done/failed) through this seam (cluster/remote.py)
        self._extra_metrics = extra_metrics
        self._settings_fn = settings_fn or get_settings
        self._idle_probe = idle_probe or (lambda: False)
        self._suspend_action = suspend_action
        #: inverse of suspend_action (the reference's WoL wake from
        #: the node's own point of view): fires ONCE when a suspended
        #: episode ends — work arrived, the operator toggled
        #: suspend_enabled off mid-episode, or resume() was called
        #: explicitly (the capacity controller's wake path)
        self._resume_action = resume_action
        self._clock = clock
        self._idle_since: float | None = None
        self._suspended_this_episode = False
        #: guards the idle-episode state: tick() is public (tests and
        #: embedding code call it directly) while _loop ticks on the
        #: agent thread — without this the check-and-set on
        #: _suspended_this_episode can fire suspend_action twice per
        #: episode (`cli.py check` TVT-T001)
        self._gate_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.role = "encode"

    # -- one tick ------------------------------------------------------

    def tick(self) -> dict[str, Any]:
        """Sample + submit one heartbeat; run the idle gate. Returns the
        metrics submitted (tests introspect it). Sampling errors degrade
        to a minimal heartbeat — a failed psutil call must never kill
        the liveness signal."""
        metrics: dict[str, Any] = {"role": self.role, "ts": self._clock()}
        samplers = [sample_host_metrics, sample_device_metrics]
        if self._extra_metrics is not None:
            samplers.append(self._extra_metrics)
        for sampler in samplers:
            try:
                metrics.update(sampler())
            except Exception:            # noqa: BLE001 - degrade, don't die
                pass
        try:
            self.submit(self.host, metrics)
        except Exception:                # noqa: BLE001 - keep sampling;
            pass                         # the TTL marks us dead anyway
        self._idle_gate(metrics)
        return metrics

    def _idle_gate(self, metrics: Mapping[str, Any]) -> None:
        snap = self._settings_fn()
        idle = False
        if bool(snap.get("suspend_enabled", False)):
            cpu_ok = float(metrics.get("cpu", 100.0)) \
                <= float(snap.get("suspend_cpu_pct", 20.0))
            idle = cpu_ok and self._idle_probe()
        now = self._clock()
        fire = False
        resume = False
        with self._gate_lock:
            if not idle:
                # episode over — work arrived OR suspend_enabled was
                # toggled off mid-episode. Either way the gate RE-ARMS
                # (fresh idle window next time), and a suspended
                # episode ends CLEANLY: resume_action fires once, the
                # inverse the idle gate never had.
                resume = self._suspended_this_episode \
                    and self._resume_action is not None
                self._idle_since = None
                self._suspended_this_episode = False
            elif self._idle_since is None:
                self._idle_since = now
            elif (now - self._idle_since
                    >= float(snap.get("suspend_idle_s", 300))
                    and not self._suspended_this_episode
                    and self._suspend_action is not None):
                self._suspended_this_episode = True
                fire = True
        if fire:
            # outside the lock: the action may suspend the host —
            # holding the gate across it would stall a concurrent tick
            self._suspend_action()
        if resume:
            self._resume_action()

    # -- episode state (the capacity controller's poll seam) -----------

    def episode_state(self) -> dict[str, Any]:
        """Point-in-time idle-episode facts: whether this agent's
        suspend_action has fired for the current episode, and since
        when the node has been idle. The capacity controller (or any
        manager) polls this instead of guessing from metrics."""
        with self._gate_lock:
            return {"suspended": self._suspended_this_episode,
                    "idle_since": self._idle_since}

    def resume(self) -> bool:
        """Explicitly end a suspended episode (the controller's wake
        path, or an operator kick): fires resume_action once and
        re-arms the idle gate. Returns True when an episode actually
        ended; False when nothing was suspended."""
        with self._gate_lock:
            if not self._suspended_this_episode:
                return False
            self._suspended_this_episode = False
            self._idle_since = None
            action = self._resume_action
        if action is not None:
            action()
        return True

    # -- loop ----------------------------------------------------------

    def start(self) -> "NodeAgent":
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"tvt-agent-{self.host}")
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:            # noqa: BLE001 - the loop IS the
                pass                     # liveness signal; never die

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
