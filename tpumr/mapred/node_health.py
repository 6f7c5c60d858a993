"""Node health checking + task memory management.

≈ the reference's TaskTracker self-checks (SURVEY.md §5):
``NodeHealthCheckerService`` (367 LoC — runs an operator-supplied script;
any output starting with ERROR marks the node unhealthy and the
JobTracker stops assigning to it) and ``TaskMemoryManagerThread`` (kills
tasks whose process tree exceeds the configured memory limit).

The memory manager watches *subprocess* tasks (pipes/streaming children)
via /proc RSS — in-process kernel tasks live inside the runner and are
bounded by the runner process itself (documented divergence: the
reference's every task is a child JVM).
"""

from __future__ import annotations

import os
import subprocess
import threading
import time
from typing import Any, Callable


class NodeHealthChecker:
    """≈ NodeHealthCheckerService: periodic external script."""

    def __init__(self, script: str, interval_s: float = 10.0,
                 timeout_s: float = 30.0) -> None:
        self.script = script
        self.interval_s = interval_s
        self.timeout_s = timeout_s
        self.healthy = True
        self.report = ""
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def check_once(self) -> None:
        try:
            proc = subprocess.run(
                ["/bin/sh", "-c", self.script], capture_output=True,
                text=True, timeout=self.timeout_s)
            out = (proc.stdout or "").strip()
            # reference contract: a line starting with ERROR == unhealthy;
            # nonzero exit alone is NOT unhealthy (script bugs must not
            # depool nodes — NodeHealthCheckerService semantics)
            bad = [l for l in out.splitlines() if l.startswith("ERROR")]
            self.healthy = not bad
            self.report = "; ".join(bad)
        except subprocess.TimeoutExpired:
            self.healthy = False
            self.report = "health script timed out"
        except Exception as e:  # noqa: BLE001
            self.healthy = True  # can't run the script ≠ unhealthy node
            self.report = f"health script error: {e}"

    def start(self) -> "NodeHealthChecker":
        if self._thread is None:
            self.check_once()
            self._thread = threading.Thread(target=self._loop,
                                            name="node-health", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.check_once()


def process_rss_bytes(pid: int) -> int | None:
    """VmRSS of one process from /proc (Linux)."""
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None
    return None


class TaskMemoryManager:
    """≈ TaskMemoryManagerThread: sample registered task subprocesses,
    kill those above their limit (the kill callback owns process-tree
    semantics)."""

    def __init__(self, interval_s: float = 1.0) -> None:
        self.interval_s = interval_s
        self._lock = threading.Lock()
        #: attempt_id -> (pid, limit_bytes, kill_cb)
        self._tasks: dict[str, tuple[int, int, Callable[[str], None]]] = {}
        self.killed: list[str] = []
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def register(self, attempt_id: str, pid: int, limit_bytes: int,
                 kill_cb: Callable[[str], None]) -> None:
        with self._lock:
            self._tasks[attempt_id] = (pid, limit_bytes, kill_cb)
        # self-starting: a limit set only in the JOB conf must still be
        # enforced even when the tracker conf never started the sampler
        self.start()

    def unregister(self, attempt_id: str) -> None:
        with self._lock:
            self._tasks.pop(attempt_id, None)

    def check_once(self) -> list[str]:
        with self._lock:
            tasks = list(self._tasks.items())
        over = []
        for aid, (pid, limit, kill_cb) in tasks:
            rss = process_rss_bytes(pid)
            if rss is not None and limit > 0 and rss > limit:
                over.append(aid)
                self.killed.append(aid)
                try:
                    kill_cb(aid)
                except Exception:  # noqa: BLE001
                    pass
                self.unregister(aid)
        return over

    def start(self) -> "TaskMemoryManager":
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            name="task-memory", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.check_once()


#: process-wide manager — subprocess task runners (pipes/streaming)
#: register their children here; the owning NodeRunner starts/stops it
GLOBAL_MEMORY_MANAGER = TaskMemoryManager()


def default_tpu_probe(device_id: int) -> None:
    """Trivial device liveness op: put a tiny array on the device and
    force materialization. Raises when the device (or the runtime path
    to it) is sick — exactly the signal the quarantine cares about."""
    import jax
    import numpy as np

    from tpumr.parallel.jaxruntime import accelerator_device
    jax.device_put(np.ones(8, np.float32),
                   accelerator_device(device_id)).block_until_ready()


class TpuDeviceHealth:
    """Per-device accelerator quarantine (new capability — the reference
    has no device-granular health at all: a sick GPU kept receiving
    tasks until the tracker blacklisted wholesale).

    ``threshold`` CONSECUTIVE device-classed task failures on device *d*
    mark it bad: the tracker stops advertising its slot and the
    scheduler stops deriving free device ids from it. A background probe
    (``probe(device_id)`` — default a trivial jnp op) retries the device
    on a capped exponential backoff and re-admits it on the first
    success, so a transient runtime wedge doesn't depool hardware
    forever. A success between failures resets the consecutive count
    (intermittent flakiness is the penalty box's job, not quarantine's).
    """

    def __init__(self, n_devices: int, threshold: int = 3,
                 probe: "Callable[[int], Any] | None" = None,
                 probe_interval_s: float = 10.0,
                 probe_max_interval_s: float = 300.0) -> None:
        self.n_devices = max(0, n_devices)
        self.threshold = threshold
        self.probe = probe if probe is not None else default_tpu_probe
        self.probe_interval_s = max(0.05, probe_interval_s)
        self.probe_max_interval_s = max(self.probe_interval_s,
                                        probe_max_interval_s)
        self._lock = threading.Lock()
        self._consecutive: dict[int, int] = {}
        #: device -> (next_probe_monotonic, current_backoff_s)
        self._quarantined: dict[int, tuple[float, float]] = {}
        #: total quarantine ENTRIES (monotone counter for /metrics)
        self.quarantine_events = 0
        #: quarantines lifted by a successful probe
        self.restore_events = 0
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------- recording

    def record_failure(self, device_id: int) -> bool:
        """One device-classed task failure on ``device_id``. Returns
        True when this failure newly quarantined the device."""
        if not 0 <= device_id < self.n_devices or self.threshold <= 0:
            return False
        with self._lock:
            if device_id in self._quarantined:
                return False
            n = self._consecutive.get(device_id, 0) + 1
            self._consecutive[device_id] = n
            if n < self.threshold:
                return False
            self._quarantined[device_id] = (
                time.monotonic() + self.probe_interval_s,
                self.probe_interval_s)
            self._consecutive.pop(device_id, None)
            self.quarantine_events += 1
        self._ensure_thread()
        self._wake.set()
        return True

    def record_success(self, device_id: int) -> None:
        """A task completed fine on the device — consecutive-failure
        streak broken."""
        with self._lock:
            self._consecutive.pop(device_id, None)

    # --------------------------------------------------------- queries

    def quarantined(self) -> "list[int]":
        with self._lock:
            return sorted(self._quarantined)

    def is_quarantined(self, device_id: int) -> bool:
        with self._lock:
            return device_id in self._quarantined

    # ----------------------------------------------------------- probe

    def _ensure_thread(self) -> None:
        with self._lock:   # concurrent quarantines must not double-start
            if self._thread is not None:
                return
            self._thread = threading.Thread(target=self._probe_loop,
                                            name="tpu-device-probe",
                                            daemon=True)
        self._thread.start()

    def probe_once(self, now: "float | None" = None) -> "list[int]":
        """Probe every quarantined device whose deadline passed; restore
        the ones whose probe succeeds. Returns restored ids (also the
        deterministic seam the tests drive instead of the thread)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            due = [d for d, (at, _b) in self._quarantined.items()
                   if at <= now]
        restored = []
        for d in due:
            try:
                self.probe(d)
            except Exception:  # noqa: BLE001 — still sick: back off
                with self._lock:
                    if d in self._quarantined:
                        _at, backoff = self._quarantined[d]
                        backoff = min(backoff * 2,
                                      self.probe_max_interval_s)
                        self._quarantined[d] = (now + backoff, backoff)
                continue
            with self._lock:
                if self._quarantined.pop(d, None) is not None:
                    self.restore_events += 1
                    restored.append(d)
        return restored

    def _next_deadline(self) -> "float | None":
        with self._lock:
            if not self._quarantined:
                return None
            return min(at for at, _b in self._quarantined.values())

    def _probe_loop(self) -> None:
        while not self._stop.is_set():
            deadline = self._next_deadline()
            if deadline is None:
                self._wake.wait(self.probe_max_interval_s)
                self._wake.clear()
                continue
            delay = max(0.0, deadline - time.monotonic())
            if delay:
                if self._stop.wait(min(delay, 1.0)):
                    return
                continue
            self.probe_once()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
