"""Simulated trackers: the real heartbeat wire protocol, fake execution.

A ``SimTracker`` is what a ``NodeRunner`` looks like FROM THE MASTER:
it registers with the protocol-version handshake, heartbeats its status
(slot pools, task statuses, metrics piggyback, fetch-failure reports —
full on contact, change-only deltas afterwards, exactly the NodeRunner
encoding from ``tpumr.mapred.heartbeat``) through a real ``RpcClient``
socket, honors the response-id replay protocol, and applies
launch/kill/reinit/disallowed actions. The
one thing it fakes is the work: an assigned task becomes a timed no-op
whose duration is drawn from a configurable distribution, and a
simulated reduce only completes after it has polled the master's
completion-event feed to "see" every map — so event polls (and their
master-side lag series) scale with the fleet exactly like real ones.

``SimFleet`` drives N of them from a bounded worker pool on a
fixed-rate schedule: each tracker has a due time every ``interval_s``,
and the gap between due and actual send is the CLIENT-side heartbeat
lag (the master independently measures arrival-gap lag). A saturated
master shows up here first as climbing round-trip latency, then as lag
when round trips exceed the interval.
"""

from __future__ import annotations

import heapq
import random
import threading
import time
from typing import Any, Callable

from tpumr.core import confkeys
from tpumr.ipc.rpc import RpcClient
from tpumr.mapred.heartbeat import HeartbeatEncoder
from tpumr.mapred.ids import TaskAttemptID
from tpumr.mapred.jobtracker import PROTOCOL_VERSION
from tpumr.mapred.task import TaskPhase, TaskState, TaskStatus
from tpumr.metrics.core import MetricsRegistry
from tpumr.metrics.histogram import Histogram
from tpumr.net import DEFAULT_RACK
from tpumr.utils.fi import fires


def default_task_time(rng: random.Random, is_map: bool,
                      mean_s: float = 0.1) -> float:
    """Uniform 0.5–1.5× the mean — enough spread that assignment order
    and completion order decorrelate (like real stragglers) without a
    heavy tail that would stall smoke-sized runs."""
    return rng.uniform(0.5, 1.5) * mean_s * (1.0 if is_map else 1.5)


class _SimTask:
    """One fake in-flight attempt: a deadline and a wire status."""

    __slots__ = ("job_id", "num_maps", "duration", "started", "status")

    def __init__(self, job_id: str, num_maps: int, duration: float,
                 status: TaskStatus) -> None:
        self.job_id = job_id
        self.num_maps = num_maps
        self.duration = max(1e-4, duration)
        self.started = time.monotonic()
        self.status = status


class SimTracker:
    """One simulated tracker speaking the real InterTracker protocol."""

    def __init__(self, name: str, master_host: str, master_port: int,
                 *, secret: "bytes | None" = None, cpu_slots: int = 2,
                 reduce_slots: int = 2,
                 task_time: "Callable[..., float] | None" = None,
                 task_time_mean_s: float = 0.1,
                 rng: "random.Random | None" = None,
                 fetch_failure_rate: float = 0.0,
                 piggyback: bool = True,
                 piggyback_interval_s: float = 1.0,
                 handshake: bool = True,
                 delta: bool = True,
                 rpc_timeout_s: float = 30.0,
                 index: int = -1,
                 fi_conf: Any = None) -> None:
        self.name = name
        #: fleet slot (the ``t<n>`` of the targeted ``tracker.crash.t<n>``
        #: chaos seam) — -1 when driven outside a fleet
        self.index = int(index)
        #: conf consulted for fault-injection seams (``tracker.crash``,
        #: ``task.slow``); None disables chaos entirely
        self.fi_conf = fi_conf
        self.crashed = False
        #: monotonic deadline while "partitioned away" (scenario-lab
        #: churn): the fleet skips this tracker's beats until then —
        #: the process stays alive, tasks keep finishing locally, and
        #: the master is left to expire it and adopt the rejoin
        self.paused_until = 0.0
        self.cpu_slots = cpu_slots
        self.reduce_slots = reduce_slots
        self._task_time = task_time or (
            lambda r, is_map: default_task_time(r, is_map,
                                                task_time_mean_s))
        self._rng = rng or random.Random(hash(name) & 0xFFFFFFFF)
        self._fetch_failure_rate = float(fetch_failure_rate)
        self.master = RpcClient(master_host, master_port, secret=secret,
                                timeout=rpc_timeout_s)
        if handshake:
            remote = self.master.call("get_protocol_version")
            if remote != PROTOCOL_VERSION:
                raise RuntimeError(f"master protocol {remote} != "
                                   f"{PROTOCOL_VERSION}")
        self._running: "dict[str, _SimTask]" = {}
        self._kill_requested: "set[str]" = set()
        self._fetch_failures: "list[dict]" = []
        self._reported_ff: "set[tuple[str, str]]" = set()
        self._response_id = 0
        self._initial_contact = True
        #: per-job completion-event cursor + live map outputs seen
        #: (OBSOLETE tombstones evict, like the real MapLocator fold)
        self._event_cursor: "dict[str, int]" = {}
        self._maps_live: "dict[str, dict[int, dict]]" = {}
        #: consecutive empty polls per starving job — rewinds the
        #: cursor like the real MapLocator (a pre-restart cursor can
        #: sit past a recovered job's shorter feed)
        self._empty_polls: "dict[str, int]" = {}
        self.stopped = False
        self.heartbeats = 0
        self.tasks_completed = 0
        # the metrics piggyback: a REAL registry shipped in the real
        # cumulative typed form, so the master's ClusterAggregator does
        # per-fleet-scale work on every beat exactly as in production
        self._reg = MetricsRegistry("tasktracker") if piggyback else None
        if self._reg is not None:
            self._task_hist = self._reg.histogram("sim_task_seconds")
        #: piggyback dirty flag + minimum ship interval: the registry
        #: only moves when a task completes, so idle beats skip
        #: building (and shipping) the typed snapshot entirely; under
        #: load the snapshot rides at most once per interval (metrics
        #: freshness is a seconds-scale concern, heartbeats are not) —
        #: mirrors the NodeRunner's tpumr.metrics.piggyback.interval.ms
        self._metrics_dirty = True
        self._piggyback_interval_s = float(piggyback_interval_s)
        self._piggyback_last = 0.0
        # the real tracker's delta encoding (tpumr.mapred.heartbeat):
        # the sim fleet must exercise the same wire protocol the master
        # optimizes for — near-empty idle beats included
        self._hb_encoder = HeartbeatEncoder(delta)
        #: RUNNING-status report-rate limit, mirroring the NodeRunner's
        #: tpumr.task.status.report.interval.ms (state transitions and
        #: terminal statuses always ship; unchanged RUNNING at most
        #: once per interval on delta beats)
        self._status_interval_s = 1.0
        self._status_shipped: "dict[str, tuple]" = {}
        #: in-flight pipelined beat (heartbeat_begin → heartbeat_finish)
        self._beat_ctx: "tuple | None" = None
        #: master-instructed heartbeat interval (adaptive cadence);
        #: None until the first response — the fleet schedules this
        #: tracker's next beat from it, exactly like a NodeRunner
        self.next_interval_s: "float | None" = None

    # ------------------------------------------------------------ protocol

    def heartbeat_once(self) -> None:
        """One full heartbeat round: advance fake work, poll completion
        events for gated reduces, send status, apply the response."""
        if self.heartbeat_begin():
            self.heartbeat_finish()

    def heartbeat_build(self) -> "tuple | None":
        """Build (but don't send) one beat: advance fake work, poll
        events, encode the wire status. Returns the heartbeat RPC args
        ``(status, initial_contact, ask, response_id)`` — the member
        shape ``heartbeat_batch`` carries — or None when stopped. The
        caller MUST follow with exactly one of :meth:`heartbeat_apply`
        (response delivered) or :meth:`heartbeat_abort` (delivery
        unknown)."""
        if self.stopped:
            return None
        self._poll_completion_events()
        self._advance_tasks()
        full = self._status_dict()
        now = time.monotonic()
        ship_metrics = (self._reg is not None and self._metrics_dirty
                        and now - self._piggyback_last
                        >= self._piggyback_interval_s)
        metrics = ({"tasktracker": self._reg.typed_snapshot()}
                   if ship_metrics else None)
        wire = full
        if self._hb_encoder.will_delta():
            wire = dict(full, task_statuses=self._suppress_statuses(
                full["task_statuses"], now))
        status = self._hb_encoder.encode(wire, metrics)
        cpu, red = self._counts()
        ask = cpu < self.cpu_slots or red < self.reduce_slots
        self._beat_ctx = (full, metrics, now)
        return (status, self._initial_contact, ask, self._response_id)

    def heartbeat_abort(self) -> None:
        """The built/sent beat's delivery is unknown (transport error
        anywhere between build and response) — same contract as
        NodeRunner: the next beat re-ships the full status."""
        self._beat_ctx = None
        self._hb_encoder.reset()

    def crash_seam_fired(self) -> bool:
        """BEHAVIORAL churn seam, checked right after a beat went on
        the wire: hard-kill mid-beat — the master may well fold the
        request, but the response is never read and the socket just
        dies, like a tracker SIGKILLed between send and receive."""
        if self.fi_conf is not None and (
                fires(f"tracker.crash.t{self.index}", self.fi_conf)
                or fires("tracker.crash", self.fi_conf)):
            self.crash()
            return True
        return False

    def heartbeat_begin(self) -> bool:
        """First half of a beat: advance fake work, poll events, SEND
        the status — without waiting for the response. Returns True
        when a request is now outstanding (pair with
        :meth:`heartbeat_finish`). The fleet pipelines many trackers'
        begins back-to-back so the master's handling overlaps the
        client side of other trackers instead of context-switching
        once per beat."""
        args = self.heartbeat_build()
        if args is None:
            return False
        try:
            self.master.call_begin("heartbeat", *args)
        except Exception:
            # delivery unknown — same contract as NodeRunner: the next
            # beat re-ships the full status
            self.heartbeat_abort()
            raise
        return not self.crash_seam_fired()

    def heartbeat_finish(self) -> None:
        """Second half: receive the response of the outstanding
        :meth:`heartbeat_begin` and apply it."""
        try:
            resp = self.master.call_finish()
        except Exception:
            # delivery unknown — same contract as NodeRunner: the next
            # beat re-ships the full status
            self._hb_encoder.reset()
            raise
        self.heartbeat_apply(resp)

    def heartbeat_apply(self, resp: dict) -> None:
        """Apply one delivered response to the beat built by
        :meth:`heartbeat_build` — the shared receive half of the
        pipelined and batched paths. A member-level error marker (a
        batch isolates member failures server-side) counts as a failed
        delivery: reset the encoder and raise."""
        full, metrics, now = self._beat_ctx
        self._beat_ctx = None
        if "error" in resp:
            self._hb_encoder.reset()
            raise RuntimeError(f"heartbeat member failed: "
                               f"{resp['error']}")
        self._hb_encoder.delivered()
        if metrics is not None:
            self._metrics_dirty = False
            self._piggyback_last = now
        self._initial_contact = False
        self._response_id = resp["response_id"]
        nxt = resp.get("next_interval_ms")
        if isinstance(nxt, (int, float)) and nxt > 0:
            self.next_interval_s = nxt / 1000.0
        self.heartbeats += 1
        if any(a.get("type") == "resend_full"
               for a in resp.get("actions", [])):
            # master folded nothing (it wants the full status first):
            # keep statuses + reports for the re-send (NodeRunner rule)
            for action in resp.get("actions", []):
                self._apply_action(action)
            return
        # delivered fetch-failure reports are done; ones appended since
        # the snapshot would stay — mirrors NodeRunner's contract
        sent_ff = len(full.get("fetch_failures", []))
        if sent_ff:
            del self._fetch_failures[:sent_ff]
        # drop statuses whose SENT snapshot was terminal (same rule as
        # the real tracker: a completion racing the RPC must survive)
        for sd in full.get("task_statuses", []):
            if sd["state"] in TaskState.TERMINAL:
                self._running.pop(sd["attempt_id"], None)
                self._kill_requested.discard(sd["attempt_id"])
                self._status_shipped.pop(sd["attempt_id"], None)
        for action in resp.get("actions", []):
            self._apply_action(action)

    def close(self) -> None:
        self.stopped = True
        self.master.close()

    def crash(self) -> None:
        """Hard kill: drop the connection with whatever was in flight,
        no deregistration, no encoder flush — exactly what the master
        sees when a tracker process dies. Master-side state (believed-
        running attempts, the replay cache entry) is left for the
        eviction sweep or the cold re-registration path to clean up."""
        self.stopped = True
        self.crashed = True
        self._beat_ctx = None
        self.master.close()

    # ------------------------------------------------------------ fake work

    def _counts(self) -> "tuple[int, int]":
        cpu = red = 0
        for t in self._running.values():
            if t.status.state != TaskState.RUNNING:
                continue
            if t.status.is_map:
                cpu += 1
            else:
                red += 1
        return cpu, red

    def _advance_tasks(self) -> None:
        now = time.monotonic()
        for aid, t in self._running.items():
            st = t.status
            if st.state != TaskState.RUNNING:
                continue
            if aid in self._kill_requested:
                st.state = TaskState.KILLED
                st.finish_time = time.time()
                st.diagnostics = "killed by master action (simulated)"
                continue
            elapsed = now - t.started
            if not st.is_map:
                live = self._maps_live.get(t.job_id, {})
                self._maybe_report_fetch_failure(t, live)
                if len(live) < t.num_maps:
                    # shuffle-gated: a reduce cannot finish before the
                    # event feed showed it every map output
                    st.progress = min(
                        0.3, 0.3 * len(live) / max(1, t.num_maps))
                    continue
                st.phase = TaskPhase.REDUCE
            if elapsed >= t.duration:
                st.state = TaskState.SUCCEEDED
                st.progress = 1.0
                st.finish_time = time.time()
                self.tasks_completed += 1
                if self._reg is not None:
                    self._reg.incr("sim_tasks_completed")
                    self._task_hist.observe(t.duration)
                    self._metrics_dirty = True
            else:
                st.progress = min(0.99, elapsed / t.duration)

    def _poll_completion_events(self) -> None:
        """Per running reduce's job, one incremental completion-event
        poll per beat — the real umbilical cadence, carried over the
        same master RPC surface (and observed by its lag series). A
        reduce that has already seen every map output stops polling,
        exactly like the real ReduceCopier once its fetch set is
        complete (OBSOLETE withdrawals can't strand it: a sim reduce
        past its shuffle gate no longer re-fetches)."""
        jobs = {t.job_id for t in self._running.values()
                if not t.status.is_map
                and t.status.state == TaskState.RUNNING
                and len(self._maps_live.get(t.job_id, {})) < t.num_maps}
        for job_id in jobs:
            cursor = self._event_cursor.get(job_id, 0)
            try:
                events = self.master.call("get_map_completion_events",
                                          job_id, cursor, 10_000)
            except Exception:  # noqa: BLE001 — purged job / master load
                continue
            self._event_cursor[job_id] = cursor + len(events)
            if events:
                self._empty_polls[job_id] = 0
            else:
                n = self._empty_polls.get(job_id, 0) + 1
                self._empty_polls[job_id] = n
                if n >= 25:
                    # starving: rewind — the cursor may predate a master
                    # restart (re-folds are idempotent, like MapLocator)
                    self._empty_polls[job_id] = 0
                    self._event_cursor[job_id] = 0
            live = self._maps_live.setdefault(job_id, {})
            for e in events:
                idx = e.get("map_index")
                if e.get("status") == "OBSOLETE":
                    cur = live.get(idx)
                    if cur is not None \
                            and cur["attempt_id"] == e["attempt_id"]:
                        del live[idx]
                else:
                    live[idx] = e

    def _maybe_report_fetch_failure(self, t: _SimTask,
                                    live: "dict[int, dict]") -> None:
        """Optional chaos: with probability ``fetch_failure_rate`` per
        beat, a running reduce reports one seen map output unfetchable —
        driving the master's withdraw/re-execute path under load. Each
        (reduce, map attempt) pair reports at most once, like a real
        copier that penalty-boxes after reporting."""
        if not self._fetch_failure_rate or not live:
            return
        if self._rng.random() >= self._fetch_failure_rate:
            return
        ev = live[self._rng.choice(list(live))]
        key = (str(t.status.attempt_id), ev["attempt_id"])
        if key in self._reported_ff:
            return
        self._reported_ff.add(key)
        self._fetch_failures.append({
            "map_attempt": ev["attempt_id"],
            "reduce_attempt": str(t.status.attempt_id)})

    # ------------------------------------------------------------ wire

    def _suppress_statuses(self, statuses: "list[dict]",
                           now: float) -> "list[dict]":
        """NodeRunner._suppress_statuses's sim twin: rate-limit
        unchanged RUNNING statuses on delta beats."""
        if not self._status_interval_s:
            return statuses
        out = []
        for sd in statuses:
            if sd["state"] != TaskState.RUNNING:
                out.append(sd)
                continue
            aid = sd["attempt_id"]
            key = (sd["state"], sd.get("phase"))
            prev = self._status_shipped.get(aid)
            if prev is not None and prev[:2] == key \
                    and now - prev[2] < self._status_interval_s:
                continue
            self._status_shipped[aid] = (*key, now)
            out.append(sd)
        return out

    def _status_dict(self) -> dict:
        cpu, red = self._counts()
        status = {
            "tracker_name": self.name,
            "host": f"sim-{self.name}",
            "shuffle_addr": f"sim-{self.name}:0",
            "shuffle_port": 0,
            "max_cpu_map_slots": self.cpu_slots,
            "max_tpu_map_slots": 0,
            "quarantined_tpu_devices": [],
            "max_reduce_slots": self.reduce_slots,
            "count_cpu_map_tasks": cpu,
            "count_tpu_map_tasks": 0,
            "count_reduce_tasks": red,
            "available_tpu_devices": [],
            "available_memory_mb": -1,
            "task_statuses": [t.status.to_dict()
                              for t in self._running.values()],
            "fetch_failures": list(self._fetch_failures),
            "rack": DEFAULT_RACK,
            "healthy": True,
            "health_report": "",
        }
        return status

    def _apply_action(self, action: dict) -> None:
        kind = action.get("type")
        if kind == "launch":
            d = action["task"]
            attempt = TaskAttemptID.parse(d["attempt_id"])
            is_map = attempt.task.is_map
            status = TaskStatus(
                attempt_id=attempt, is_map=is_map,
                state=TaskState.RUNNING,
                phase=TaskPhase.MAP if is_map else TaskPhase.SHUFFLE,
                run_on_tpu=bool(d.get("run_on_tpu", False)),
                tpu_device_id=int(d.get("tpu_device_id", -1)))
            duration = self._task_time(self._rng, is_map)
            if self.fi_conf is not None and fires("task.slow",
                                                  self.fi_conf):
                # straggler phase (scenario lab): the fake task stays
                # alive tpumr.fi.task.slow.ms longer — the sim twin of
                # the real task.slow behavioral seam in map_task
                duration += confkeys.get_int(
                    self.fi_conf, "tpumr.fi.task.slow.ms") / 1000.0
            self._running[d["attempt_id"]] = _SimTask(
                action["job_id"], int(d.get("num_maps", 0)),
                duration, status)
        elif kind == "kill_task":
            self._kill_requested.add(action["attempt_id"])
        elif kind == "reinit":
            self._running.clear()
            self._kill_requested.clear()
            self._fetch_failures.clear()
            self._initial_contact = True
            self._response_id = 0
            self._hb_encoder.reset()   # re-register with a full status
            self._status_shipped.clear()
        elif kind == "resend_full":
            # master lost our baseline (restart): re-ship the full
            # status next beat; unlike reinit, fake in-flight work
            # survives — the master adopts it (NodeRunner semantics)
            self._hb_encoder.reset()
            self._status_shipped.clear()
        elif kind == "disallowed":
            self.stopped = True


class SimFleet:
    """N ``SimTracker``s on a fixed-rate heartbeat schedule, driven by a
    bounded worker pool (hundreds of trackers don't need hundreds of
    client threads — a beat is one blocking RPC)."""

    def __init__(self, master_host: str, master_port: int,
                 n_trackers: int, *, secret: "bytes | None" = None,
                 interval_s: float = 0.2, workers: "int | None" = None,
                 name_prefix: str = "sim", seed: int = 0,
                 batch: int = 0,
                 stagger_s: "float | None" = None,
                 **tracker_kwargs: Any) -> None:
        self.master_host, self.master_port = master_host, master_port
        self.n = int(n_trackers)
        self.interval_s = float(interval_s)
        #: window the first beats spread over (default: one configured
        #: interval). Under adaptive cadence the steady schedule can be
        #: much coarser than the floor — spreading joins over THAT
        #: window keeps fleet start from being a synthetic herd whose
        #: full-status registrations arrive at many times the rate the
        #: master will ever instruct again.
        self.stagger_s = float(stagger_s) if stagger_s else self.interval_s
        self.secret = secret
        self.workers = workers or min(64, max(4, self.n // 4))
        self._prefix = name_prefix
        self._seed = seed
        #: members per coalesced ``heartbeat_batch`` RPC (0/1 keeps the
        #: per-tracker pipelined path) — the client twin of the
        #: master's ``tpumr.heartbeat.batch`` knob
        self.batch = int(batch)
        self._tracker_kwargs = tracker_kwargs
        self.trackers: "list[SimTracker]" = []
        self._heap: "list[tuple[float, int]]" = []
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._threads: "list[threading.Thread]" = []
        # churn accounting (scenario lab): crashes and cold respawns
        self.trackers_crashed = 0
        self.trackers_respawned = 0
        self.trackers_partitioned = 0
        self._respawn_timers: "list[threading.Timer]" = []
        # client-side observability (the harness's own view, independent
        # of the master's): round-trip latency, schedule overrun, errors
        self.registry = MetricsRegistry("simfleet")
        self._rtt = self.registry.histogram("hb_rtt_seconds")
        self._lag = self.registry.histogram("hb_lag_seconds")

    def start(self) -> "SimFleet":
        rng = random.Random(self._seed)
        for i in range(self.n):
            self.trackers.append(SimTracker(
                f"{self._prefix}_{i:04d}", self.master_host,
                self.master_port, secret=self.secret, index=i,
                rng=random.Random(rng.randrange(1 << 30)),
                **self._tracker_kwargs))
        now = time.monotonic()
        # stagger first beats across one interval so fleet start doesn't
        # land as one synchronized thundering herd (unless saturation
        # makes it one — which is then a real measurement)
        self._heap = [(now + (i * self.stagger_s) / max(1, self.n), i)
                      for i in range(self.n)]
        heapq.heapify(self._heap)
        for w in range(self.workers):
            t = threading.Thread(target=self._worker,
                                 name=f"{self._prefix}-fleet-{w}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        return self

    #: max due beats one worker drains per wakeup: begins are PIPELINED
    #: (send all, then collect all responses) so the master handles a
    #: batch while this worker is still building the next request —
    #: at fleet rates the per-beat context-switch ping-pong was costing
    #: more CPU than the beats themselves. Bounded so one worker can't
    #: hoard a saturated heap (lag is recorded per beat either way).
    BATCH = 16

    def _worker(self) -> None:
        #: this worker's own ``heartbeat_batch`` client: the pipelined
        #: RpcClient surface is single-threaded by contract
        client = (RpcClient(self.master_host, self.master_port,
                            secret=self.secret)
                  if self.batch > 1 else None)
        cap = max(self.BATCH, self.batch)
        try:
            while not self._stop.is_set():
                batch: "list[tuple[float, int]]" = []
                with self._cv:
                    while not self._stop.is_set():
                        now = time.monotonic()
                        while self._heap and len(batch) < cap \
                                and self._heap[0][0] <= now:
                            batch.append(heapq.heappop(self._heap))
                        if batch:
                            break
                        wait = (self._heap[0][0] - now) if self._heap \
                            else 0.05
                        self._cv.wait(min(max(wait, 0.0), 0.05))
                    else:
                        return
                if self.batch > 1:
                    self._beat_batched(batch, client)
                else:
                    self._beat_pipelined(batch)
                # fixed-rate schedule AGAINST THE INSTRUCTED CADENCE
                # (the master's adaptive interval, once a response
                # carried one); when more than a full interval behind,
                # skip ahead (the lag was recorded — re-queueing a
                # backlog of missed beats would only spiral the
                # overload)
                now = time.monotonic()
                with self._cv:
                    for due, idx in batch:
                        tracker = self.trackers[idx]
                        if not tracker.stopped \
                                and not self._stop.is_set():
                            iv = tracker.next_interval_s \
                                or self.interval_s
                            nxt = due + iv
                            if nxt <= now:
                                nxt = now + iv
                            if nxt < tracker.paused_until:
                                nxt = tracker.paused_until
                            heapq.heappush(self._heap, (nxt, idx))
                    self._cv.notify()
        finally:
            if client is not None:
                try:
                    client.close()
                except Exception:  # noqa: BLE001 — teardown
                    pass

    def _beat_pipelined(self, batch: "list[tuple[float, int]]") -> None:
        now = time.monotonic()
        begun: "list[tuple[float, int, float]]" = []
        for due, idx in batch:
            self._lag.observe(max(0.0, now - due))
            tracker = self.trackers[idx]
            if tracker.stopped:
                continue
            if now < tracker.paused_until:
                continue   # partitioned away; rescheduled by caller
            t0 = time.monotonic()
            try:
                if tracker.heartbeat_begin():
                    begun.append((due, idx, t0))
            except Exception:  # noqa: BLE001 — master down/overload
                self.registry.incr("hb_errors")
        for due, idx, t0 in begun:
            try:
                self.trackers[idx].heartbeat_finish()
                self._rtt.observe(time.monotonic() - t0)
            except Exception:  # noqa: BLE001 — master down/overload
                self.registry.incr("hb_errors")

    def _beat_batched(self, batch: "list[tuple[float, int]]",
                      client: RpcClient) -> None:
        """Coalesce this wakeup's due beats into ONE ``heartbeat_batch``
        RPC: build all members first, send, then apply the responses
        member-by-member. One syscall round-trip carries up to
        ``batch`` beats."""
        now = time.monotonic()
        built: "list[tuple[SimTracker, tuple]]" = []
        for due, idx in batch:
            self._lag.observe(max(0.0, now - due))
            tr = self.trackers[idx]
            if tr.stopped or now < tr.paused_until:
                continue
            try:
                args = tr.heartbeat_build()
            except Exception:  # noqa: BLE001 — event-poll hiccup
                self.registry.incr("hb_errors")
                continue
            if args is not None:
                built.append((tr, args))
        if not built:
            return
        t0 = time.monotonic()
        try:
            client.call_begin("heartbeat_batch",
                              [list(a) for _, a in built])
        except Exception:  # noqa: BLE001 — master down/overload
            for tr, _ in built:
                tr.heartbeat_abort()
            self.registry.incr("hb_errors")
            return
        for tr, _ in built:
            tr.crash_seam_fired()
        try:
            resps = client.call_finish()
        except Exception:  # noqa: BLE001 — master down/overload
            for tr, _ in built:
                if not tr.crashed:
                    tr.heartbeat_abort()
            self.registry.incr("hb_errors")
            return
        self._rtt.observe(time.monotonic() - t0)
        self.registry.incr("hb_batches")
        for (tr, _), resp in zip(built, resps or []):
            if tr.crashed or tr.stopped:
                continue
            try:
                tr.heartbeat_apply(resp)
            except Exception:  # noqa: BLE001 — member error
                self.registry.incr("hb_errors")

    def stop(self) -> None:
        self._stop.set()
        for timer in self._respawn_timers:
            timer.cancel()
        with self._cv:
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)
        for tr in self.trackers:
            tr.close()

    # ------------------------------------------------------------ churn

    def crash(self, idx: int) -> str:
        """Hard-kill tracker ``idx`` (scenario-lab churn): the socket
        drops mid-schedule, nothing deregisters, the master is left to
        notice. Returns the tracker's name."""
        tracker = self.trackers[idx]
        tracker.crash()
        self.trackers_crashed += 1
        return tracker.name

    def respawn(self, idx: int) -> SimTracker:
        """Cold-restart tracker ``idx`` under its old name: a brand-new
        process image (fresh response id, initial-contact beat, empty
        task table). The master either adopts it back through the
        rejoin/adoption path (if the old incarnation was already
        evicted) or takes the cold re-registration path (if not). The
        replacement RNG is derived from (fleet seed, slot, generation)
        so churn replays bit-identically under a pinned seed."""
        self.trackers_respawned += 1
        rng = random.Random(
            f"{self._seed}:respawn:{idx}:{self.trackers_respawned}")
        deadline = time.monotonic() + 15.0
        while True:
            try:
                tracker = SimTracker(
                    f"{self._prefix}_{idx:04d}", self.master_host,
                    self.master_port, secret=self.secret, index=idx,
                    rng=rng, **self._tracker_kwargs)
                break
            except OSError:
                # master mid-restart: a real tracker would retry too
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.05)
        with self._cv:
            self.trackers[idx] = tracker
            heapq.heappush(self._heap, (time.monotonic(), idx))
            self._cv.notify()
        return tracker

    def churn(self, idxs: "list[int] | None" = None, n: int = 1,
              rejoin_after_s: "float | None" = None,
              rng: "random.Random | None" = None) -> "list[str]":
        """Crash ``idxs`` (or ``n`` slots drawn from ``rng``, defaulting
        to a fleet-seed RNG) right now; when ``rejoin_after_s`` is set,
        cold-respawn each slot after that delay on daemon timers
        (cancelled by :meth:`stop`). Returns the crashed names."""
        if idxs is None:
            r = rng or random.Random(self._seed)
            idxs = sorted(r.sample(range(self.n), min(int(n), self.n)))
        names = [self.crash(i) for i in idxs]
        if rejoin_after_s is not None:
            for i in idxs:
                timer = threading.Timer(rejoin_after_s,
                                        self._respawn_quiet, args=(i,))
                timer.daemon = True
                timer.start()
                self._respawn_timers.append(timer)
        return names

    def partition(self, idxs: "list[int] | None" = None, n: int = 1,
                  duration_s: float = 2.5,
                  rng: "random.Random | None" = None) -> "list[str]":
        """Partition ``idxs`` (or ``n`` seed-drawn slots) away from the
        master for ``duration_s``: beats stop but the PROCESS survives —
        tasks keep finishing locally, state and response id intact.
        When the silence outlives the expiry sweep the master evicts
        the tracker, so the rejoin beat arrives from an \"unknown\"
        name: delta → ``resend_full`` → a full NON-initial status, the
        adoption path (``trackers_adopted``), in-flight work and all.
        Returns the partitioned names."""
        if idxs is None:
            r = rng or random.Random(self._seed)
            idxs = sorted(r.sample(range(self.n), min(int(n), self.n)))
        until = time.monotonic() + float(duration_s)
        names = []
        with self._cv:
            for i in idxs:
                self.trackers[i].paused_until = until
                names.append(self.trackers[i].name)
            self.trackers_partitioned += len(idxs)
            self._cv.notify()
        return names

    def _respawn_quiet(self, idx: int) -> None:
        if self._stop.is_set():
            return
        try:
            self.respawn(idx)
        except Exception:  # noqa: BLE001 — fleet stopping under us
            self.registry.incr("respawn_errors")

    # ------------------------------------------------------------ read side

    def stats(self) -> dict:
        """Client-side summary: heartbeat round-trip and schedule-lag
        distributions, error count, beats delivered, tasks completed."""
        snap = self.registry.snapshot()
        return {
            "heartbeats": sum(t.heartbeats for t in self.trackers),
            "tasks_completed": sum(t.tasks_completed
                                   for t in self.trackers),
            "hb_errors": snap.get("hb_errors", 0),
            "trackers_crashed": self.trackers_crashed,
            "trackers_respawned": self.trackers_respawned,
            "trackers_partitioned": self.trackers_partitioned,
            "hb_rtt": snap.get("hb_rtt_seconds",
                               Histogram("x").snapshot()),
            "hb_lag": snap.get("hb_lag_seconds",
                               Histogram("x").snapshot()),
        }
