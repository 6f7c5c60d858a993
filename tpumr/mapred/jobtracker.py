"""JobMaster — the cluster master daemon.

≈ ``org.apache.hadoop.mapred.JobTracker`` (reference: src/mapred/org/apache/
hadoop/mapred/JobTracker.java, 5405 LoC): job registry + tracker registry +
the heartbeat endpoint. Reproduced contracts:

- heartbeat dedupe by response id: a tracker retrying a lost response gets
  the PREVIOUS actions replayed, never double-assigned work
  (JobTracker.java:3336-3375);
- unknown/expired trackers are told to reinitialize
  (ReinitTrackerAction, :3358);
- scheduler delegation at :3405 → ``TaskScheduler.assign_tasks``;
- TaskReport placement stamping at assign time (:3414-3433) — done inside
  JobInProgress.obtain_new_map_task here;
- tracker liveness by heartbeat lease (ExpireTrackers) → lost trackers'
  running attempts killed and completed map outputs re-queued
  (lostTaskTracker);
- per-tracker fault counting + blacklisting (faultyTrackers, :3330-3333);
- the commit gate: first attempt to ask wins the right to promote its
  output (≈ CommitTaskAction gating, TaskTracker.java:1725-1731).

Structural divergence (by design, SURVEY.md §3.2): no global synchronized
heartbeat monitor around O(jobs×tasks) recomputation — job profiling uses
O(1) running sums and the master lock only guards registries.

Lock decomposition (in place of the reference's single synchronized
monitor): the heartbeat fast path touches the GLOBAL lock briefly or not
at all.

- ``self.lock`` (rank ``global``) guards only the job table, commit
  grants, and admin swaps; the job table itself is insert-only, so
  lookups (``self.jobs.get``) are lock-free dict reads under the GIL.
- the tracker registry is striped (``tracker_registry.TrackerRegistry``,
  rank ``trackers``): heartbeats from different trackers never contend
  on registration/status-store, and the response-replay cache
  (``self._last_response``) is read and written lock-free (single-key
  dict ops are GIL-atomic; each tracker's beats are serialized by its
  own ``hb_lock``, so a retry can never interleave with its original).
- the per-task STATUS FOLD, accel-event drain, and fetch-failure
  protocol run under the per-job locks only (``JobInProgress.lock``,
  rank ``job``).
- ``get_map_completion_events`` serves from the append-only
  ``CompletionEventFeed`` with NO lock at all — reducer polls never
  queue behind the fold.
- scheduler entry (``before_heartbeat`` / ``assign_tasks``) runs under
  a dedicated ``sched_lock`` (rank ``scheduler``); the ordering rule —
  scheduler → job, never the reverse — is asserted in debug mode
  (metrics/locks.py).

Each lock class feeds ``jt_lock_wait_seconds{lock=global|trackers|
scheduler}`` (+ hold twins) so the decomposition itself is observable.
"""

from __future__ import annotations

import threading
import time
from typing import Any

from tpumr.ipc.rpc import RpcServer
from tpumr.core import confkeys
from tpumr.mapred.history import JobHistory
from tpumr.mapred.ids import JobID, TaskAttemptID
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.job_in_progress import (JobInProgress, JobState,
                                          normalize_priority)
from tpumr.mapred.map_cost import CarriedCost
from tpumr.mapred.scheduler import HybridQueueScheduler, TaskScheduler
from tpumr.mapred.task import TaskState, TaskStatus
from tpumr.utils.reflection import new_instance

#: ≈ InterTrackerProtocol versionID 29 (InterTrackerProtocol.java:75)
PROTOCOL_VERSION = 29

#: method → service keys ≈ MapReducePolicyProvider (reference:
#: security.job.submission / inter.tracker / task.umbilical /
#: admin.operations / refresh.policy .protocol.acl). Unmapped methods
#: default to the job-submission (client) key.
JOBTRACKER_POLICY = {
    "heartbeat": ["security.inter.tracker.protocol.acl"],
    "get_job_conf": ["security.inter.tracker.protocol.acl",
                     "security.job.submission.protocol.acl"],
    "get_job_token": ["security.inter.tracker.protocol.acl"],
    # trackers RELAY the umbilical surface for their children (and call
    # get_job_status in the purge loop), so the inter-tracker identity
    # must reach these too — a restricted umbilical/submission ACL must
    # never break commit grants, completion events, or job purging
    "can_commit": ["security.task.umbilical.protocol.acl",
                   "security.inter.tracker.protocol.acl"],
    "get_map_completion_events": ["security.task.umbilical.protocol.acl",
                                  "security.inter.tracker.protocol.acl",
                                  "security.job.submission.protocol.acl"],
    # pipeline surface: submission-tier for clients; trackers reach the
    # handoff feed (downstream maps resolve upstream reduce partitions)
    # and the purge oracle through their inter-tracker identity
    "get_handoff_completion_events": [
        "security.task.umbilical.protocol.acl",
        "security.inter.tracker.protocol.acl",
        "security.job.submission.protocol.acl"],
    "handoff_purgeable": ["security.inter.tracker.protocol.acl",
                          "security.job.submission.protocol.acl"],
    "get_pipeline_status": ["security.inter.tracker.protocol.acl",
                            "security.job.submission.protocol.acl"],
    "get_job_status": ["security.inter.tracker.protocol.acl",
                       "security.job.submission.protocol.acl"],
    "get_recovered_jobs": ["security.inter.tracker.protocol.acl",
                           "security.job.submission.protocol.acl"],
    "get_job_trace": ["security.inter.tracker.protocol.acl",
                      "security.job.submission.protocol.acl"],
    "refresh_queues": ["security.admin.operations.protocol.acl"],
    "refresh_nodes": ["security.admin.operations.protocol.acl"],
    "refresh_service_acl": ["security.refresh.policy.protocol.acl"],
    "get_protocol_version": ["security.job.submission.protocol.acl",
                             "security.inter.tracker.protocol.acl",
                             "security.task.umbilical.protocol.acl"],
}


class _TrackerInfo:
    def __init__(self, status: dict) -> None:
        self.status = status
        #: wall-clock, for the status surfaces (/json/trackers)
        self.last_seen = time.time()
        #: monotonic twin for the lease DEADLINE — an NTP step on the
        #: master must not mass-expire (or immortalize) trackers
        self.seen_mono = time.monotonic()
        self.failures = 0
        self.blacklisted = False
        #: the heartbeat interval the master last INSTRUCTED this
        #: tracker to keep (adaptive cadence); lag is judged against
        #: the schedule the tracker was actually told to run. None
        #: until the first response (use the configured floor).
        self.interval_s: "float | None" = None
        #: serializes THIS tracker's heartbeat processing end-to-end:
        #: a retry racing its own lost original must fold after it and
        #: hit the replay cache, never double-assign. Different
        #: trackers' beats never touch each other's lock — this is the
        #: bottom rank of the master's lock order, held across the
        #: fold/assign phases while the registry's stripe lock is not.
        from tpumr.metrics.locks import (RANK_TRACKER_BEAT,
                                         InstrumentedRLock)
        self.hb_lock = InstrumentedRLock(name="tracker-beat",
                                         rank=RANK_TRACKER_BEAT)
        #: fault charges arrive from OTHER trackers' heartbeats too
        #: (fetch-failure blame), so the counter needs its own tiny
        #: leaf lock now that the global lock no longer covers it
        self._fault_lock = threading.Lock()
        #: attempts the master believes are RUNNING on this tracker —
        #: maintained from launch actions + folded statuses (under
        #: ``hb_lock``) because delta beats may suppress unchanged
        #: RUNNING statuses: the last beat's ``task_statuses`` list is
        #: no longer the full picture, and eviction/kill scans need one
        self.running: "set[str]" = set()

    @property
    def name(self) -> str:
        return self.status["tracker_name"]

    def fold_status(self, status: dict) -> dict:
        """Store one beat's status — reconstructing the full dict first
        when the tracker sent a change-only delta — and stamp the
        lease. Returns the full status the rest of the heartbeat works
        on. Caller holds the registry shard lock."""
        from tpumr.mapred.heartbeat import fold_delta
        status = fold_delta(self.status, status)
        self.status = status
        self.last_seen = time.time()
        self.seen_mono = time.monotonic()
        return status

    def charge_fault(self, limit: int) -> bool:
        """One blacklist fault (failed task / lost shuffle output).
        Returns True when THIS fault newly blacklisted the tracker (the
        master keeps an approximate blacklist count off it)."""
        with self._fault_lock:
            self.failures += 1
            if self.failures >= limit and not self.blacklisted:
                self.blacklisted = True
                return True
            return False


def _profiler_line(snaps: dict, jt_snap: dict, flightrec_on: bool) -> str:
    """One cluster-page paragraph answering "what is the master's CPU
    doing, and is watching it costing anything" — cpu_share by
    subsystem, GIL-delay p99, sampler overhead, and the tracer's
    ring-drop count, off the already-taken metrics snapshot."""
    prof = snaps.get("prof", {})
    shares = []
    for name in sorted(prof):
        if name.startswith("cpu_share|subsystem="):
            v = prof[name]
            if isinstance(v, (int, float)) and v > 0:
                shares.append(
                    f"{name.split('subsystem=', 1)[-1]} {v:.0%}")
    gil = prof.get("gil_delay_seconds", {})
    dropped = jt_snap.get("trace_spans_dropped", 0)
    bits = []
    if shares:
        bits.append("cpu share " + " · ".join(shares))
    if isinstance(gil, dict) and gil.get("count"):
        bits.append(f"gil delay p99 {gil.get('p99', 0):.4g}s")
    ov = prof.get("prof_overhead_share")
    if isinstance(ov, (int, float)):
        bits.append(f"sampler overhead {ov:.2%}")
    bits.append(f"trace spans dropped {dropped:.0f}")
    link = (" · <a href='/flame'>flame</a> / <a href='/stacks'>stacks"
            "</a>" if prof else "")
    link += (" / <a href='/incidents'>incidents</a>"
             if flightrec_on else "")
    if not prof:
        return ("<p class='dim'>profiler off (tpumr.prof.enabled) · "
                f"trace spans dropped {dropped:.0f}</p>")
    return "<p>" + " · ".join(bits) + link + "</p>"


class JobMaster:
    def __init__(self, conf: Any, host: str = "127.0.0.1",
                 port: int = 0) -> None:
        self.conf = conf
        # the GLOBAL lock — after the PR-8 decomposition it guards only
        # the job table, commit grants, and admin swaps (tracker
        # registry, fold, completion feed, and scheduler each have
        # their own synchronization). Wait/hold distributions bind to
        # jt_lock_wait_seconds{lock=global} once the registry exists.
        from tpumr.metrics.locks import (RANK_GLOBAL, RANK_PIPELINE,
                                         RANK_SCHEDULER,
                                         InstrumentedRLock)
        self.lock = InstrumentedRLock(name="global", rank=RANK_GLOBAL)
        #: scheduler entry (before_heartbeat/assign_tasks) serializes
        #: here, NOT on the global lock; ordering rule: scheduler → job,
        #: never the reverse (asserted in debug mode, metrics/locks.py)
        self.sched_lock = InstrumentedRLock(name="scheduler",
                                            rank=RANK_SCHEDULER)
        #: DAG-engine state lock (rank pipeline, below global: planning
        #: reads member-job state and recording a submission both happen
        #: under it, but every BLOCKING part of stage submission — split
        #: computation, conf hooks, submit_job's history write — runs
        #: outside; advancement lives in the heartbeat's DEFERRED phase
        #: and the expiry loop, never on the fast path
        self._pipe_lock = InstrumentedRLock(name="pipeline",
                                            rank=RANK_PIPELINE)
        #: pipeline table: insert-only like the job table, so the
        #: `if self.pipelines` fast-path guard is a lock-free dict read
        self.pipelines: dict[str, Any] = {}
        self._next_pipe = 0
        #: INSERT-ONLY (jobs are never removed from the table), so
        #: heartbeat-path lookups read it lock-free under the GIL;
        #: writers still serialize on the global lock
        self.jobs: dict[str, JobInProgress] = {}
        #: what the last finished job of each ``map_cost_key`` learned a
        #: CPU map and a TPU slot's turn cost, for the next job with the
        #: key to start from (a loop's round 7 knows what round 6
        #: learned). Lives and dies with the job table: in memory, one
        #: entry a key, gone with a restart. Single-key get/set,
        #: GIL-atomic, lock-free
        self._map_costs: dict[tuple, CarriedCost] = {}
        from tpumr.mapred.tracker_registry import TrackerRegistry
        self.trackers = TrackerRegistry(
            confkeys.get_int(conf, "tpumr.tracker.registry.shards"))
        #: response-replay cache: read and written LOCK-FREE (single-key
        #: dict get/set are GIL-atomic; same-tracker races are excluded
        #: by _TrackerInfo.hb_lock, and the value is an immutable tuple)
        self._last_response: dict[str, tuple[int, list]] = {}
        self._commit_grants: dict[str, str] = {}   # task_id -> attempt_id
        #: old job id -> resubmitted job id for jobs this master
        #: recovered at startup (restart survival). Insert-only, written
        #: before the RPC server starts — read lock-free everywhere a
        #: job id off the wire may predate the restart (heartbeat folds,
        #: kill scans, commit grants, client status polls).
        self._recovered: dict[str, str] = {}
        self._next_job = 0
        #: running-job-set change counter + the cache it keys (see
        #: jobs_version/running_jobs) — the scheduler's per-pass reads
        self._jobs_version = 0
        self._running_cache: "tuple[int, list]" = (-1, [])
        #: approximate count of blacklisted trackers (num_trackers'
        #: lock-free divisor; the exact set still comes from scans)
        self._blacklisted = 0
        #: TTL cache for devcache_tag_index (monotonic stamp, index) —
        #: the affinity pass asks once per heartbeat, and rescanning the
        #: striped registry for every beat of every tracker would make
        #: the warm-placement hint a fleet-rate O(trackers) tax
        self._devcache_index_cache: "tuple[float, dict]" = (-1.0, {})
        # start-time-in-ms identifier ≈ JobTracker's trackerIdentifier —
        # must differ across restarts or recovered job ids collide with
        # the original's history file
        self.cluster_id = str(int(time.time() * 1000))
        self.expiry_s = conf.get_int("tpumr.tracker.expiry.ms", 10_000) / 1000.0
        self.blacklist_faults = conf.get_int("tpumr.tracker.max.faults", 4)
        sched_cls = conf.get_class("mapred.jobtracker.taskScheduler",
                                   HybridQueueScheduler)
        self.scheduler: TaskScheduler = new_instance(sched_cls, conf)
        self.scheduler.set_manager(self)
        #: does this scheduler override the per-beat observation hook?
        #: The stock schedulers don't — skipping the no-op saves a
        #: sched_lock round trip on every heartbeat of every tracker
        self._sched_observes = (
            type(self.scheduler).before_heartbeat
            is not TaskScheduler.before_heartbeat)
        # per-queue submit/administer ACLs ≈ QueueManager.java +
        # mapred-queue-acls.xml, enforced in submit_job and kill_job
        from tpumr.mapred.queue_manager import QueueManager
        self.queue_manager = QueueManager(conf)
        self.history = JobHistory(conf)
        from tpumr.security import rpc_secret
        self._rpc_secret = rpc_secret(conf)
        # the master's transport is the selector reactor (≈ the
        # reference's NIO Listener/Reader + Handler pool) with the
        # heartbeat fast path served INLINE in the loop: at fleet scale
        # the thread-per-connection transport spent more CPU waking
        # handler threads than handling beats. The inline set must stay
        # short-running and never block on an RPC back to this server;
        # everything else (submit_job's history I/O, admin surface)
        # runs on the reactor's handler pool.
        use_reactor = True
        if hasattr(conf, "get_boolean"):
            use_reactor = conf.get_boolean(
                "tpumr.jobtracker.rpc.reactor", True)
        self._server = RpcServer(self, host=host, port=port,
                                 secret=self._rpc_secret,
                                 reactor=use_reactor,
                                 fast_methods={
                                     "heartbeat",
                                     "get_map_completion_events",
                                     "get_handoff_completion_events",
                                     "get_job_status",
                                     "can_commit",
                                     "get_protocol_version",
                                 })
        # delegation-token liveness (≈ JobTracker's
        # DelegationTokenSecretManager): issued/renewed/canceled here,
        # validated by the RPC layer per request
        from tpumr.security.tokens import TokenStore
        self.token_store = TokenStore(conf)
        self._server.token_store = self.token_store
        # service-level authorization ≈ hadoop-policy.xml (off unless
        # tpumr.security.authorization=true)
        from tpumr.security.authorize import ServiceAuthorizationManager
        self._server.authz = ServiceAuthorizationManager(
            conf, JOBTRACKER_POLICY,
            "security.job.submission.protocol.acl")
        # impersonation rules (hadoop.proxyuser.*) are consulted from
        # the daemon conf; without this, doas frames are rejected
        self._server.proxy_conf = conf
        #: require cryptographically verified identity (user key or
        #: delegation token) for ACL-relevant identity claims — with it
        #: off (default), cluster-secret assertions keep working (the
        #: flat round-3 trust domain, documented in docs/OPERATIONS.md)
        self._require_verified = conf.get_boolean(
            "tpumr.acls.require.verified", False) \
            if hasattr(conf, "get_boolean") else False
        # tracker admission lists ≈ mapred.hosts / mapred.hosts.exclude
        # (JobTracker.hostsReader + DisallowedTaskTrackerException):
        # one hostname per line, re-read by mradmin -refreshNodes
        self._hosts_include, self._hosts_exclude = self._read_hosts_lists()
        self._stop = threading.Event()
        self._expire_thread = threading.Thread(
            target=self._expire_loop, name="expire-trackers", daemon=True)
        # ALL advancement runs on its own thread: stage submission can
        # block on DFS (split listing, output checks, conf hooks), and
        # a wedged submission must stall pipelines — never tracker
        # eviction (the expiry loop) or heartbeats. The heartbeat
        # deferred phase and submit_pipeline just set the wake event.
        self._pipe_wake = threading.Event()
        self._pipe_thread = threading.Thread(
            target=self._pipeline_loop, name="pipeline-advance",
            daemon=True)

        # instrumentation ≈ JobTrackerInstrumentation + JobTrackerMXBean:
        # backend placement is a first-class metric (SURVEY.md §5)
        from tpumr.metrics import MetricsSystem
        self.metrics = MetricsSystem(
            "jobtracker",
            period_s=confkeys.get_int(conf, "tpumr.metrics.period.ms") / 1000)
        self._mreg = self.metrics.new_registry("jobtracker")
        def _locked(fn):
            def sample():
                with self.lock:
                    return fn()
            return sample

        self._mreg.set_gauge("jobs_running",
                             _locked(lambda: len(self.running_jobs())))
        self._mreg.set_gauge("jobs_total", _locked(lambda: len(self.jobs)))
        # tracker gauges read the striped registry; the global lock has
        # no say over trackers since the decomposition
        self._mreg.set_gauge("trackers", lambda: len(self.trackers))
        self._mreg.set_gauge(
            "trackers_blacklisted",
            lambda: sum(1 for t in self.trackers.values()
                        if t.blacklisted))
        self._mreg.set_gauge("slots", self.total_slots)
        # shuffle fault tolerance: map attempts with outstanding
        # (sub-threshold) fetch-failure reports across running jobs —
        # the master-side penalty ledger behind fetch_failures_reported
        # / maps_reexecuted_fetch_failure counters
        self._mreg.set_gauge(
            "fetch_failure_penalty_box",
            _locked(lambda: sum(j.fetch_failure_pending_count()
                                for j in self.jobs.values())))
        # shuffle merge engine, cluster-wide: background in-memory merges
        # and bounded-fan-in passes summed from every job's aggregated
        # framework counters (same names the task pages show per attempt)
        from tpumr.core.counters import TaskCounter

        def _merge_engine_totals() -> dict:
            out: dict[str, int] = {}
            for name in ("SHUFFLE_INMEM_MERGES",
                         "SHUFFLE_INMEM_MERGE_SEGMENTS",
                         "MERGE_PASSES", "MERGE_PASS_SEGMENTS"):
                out[name.lower()] = sum(
                    j.counters.value(TaskCounter.FRAMEWORK_GROUP, name)
                    for j in self.jobs.values())
            return out

        self._mreg.set_gauge("shuffle_merge",
                             _locked(_merge_engine_totals))
        # accelerator fault tolerance: cluster-wide demotion/quarantine
        # visibility (the per-event counters are incremented inline in
        # the heartbeat as the decisions arrive)
        self._mreg.set_gauge(
            "jobs_tpu_quarantined_now",
            _locked(lambda: sum(1 for j in self.jobs.values()
                                if j.tpu_disabled)))
        # DAG engine: running pipelines (table is insert-only; the scan
        # is over a handful of pipelines, not jobs)
        self._mreg.set_gauge(
            "pipelines_running",
            lambda: sum(1 for p in self.pipelines.values()
                        if p.state == "RUNNING"))
        self._mreg.set_gauge("pipelines_total",
                             lambda: len(self.pipelines))
        self._mreg.set_gauge(
            "tpu_devices_quarantined",
            lambda: sum(
                len(t.status.get("quarantined_tpu_devices", []) or [])
                for t in self.trackers.values()))
        # control-plane latency distributions: heartbeat handling wall
        # time (hoisted Histogram object — the heartbeat path must not
        # pay a registry lookup), per-method RPC server latency + wire
        # request sizes (the heartbeat payload-size series is the rpc
        # source's rpc_heartbeat_request_bytes — measured from the frame
        # length the transport already read, never re-serialized), and
        # scheduler decision timing. These are the series the ROADMAP's
        # control-plane scale-out work reads first.
        self._hb_seconds = self._mreg.histogram("heartbeat_seconds")
        self._hb_batch_size = self._mreg.histogram(
            "heartbeat_batch_size")
        # async history backpressure: queue depth + events dropped past
        # the bound — a healthy run keeps the drop counter at exactly 0
        self._mreg.set_gauge("history_queue_depth",
                             self.history.queue_depth)
        self._mreg.set_gauge("history_writes_dropped",
                             lambda: self.history.writes_dropped)
        # master saturation series (the scale harness's read side, all
        # hoisted off the registry lookup path):
        # - lock wait/hold PER DECOMPOSED LOCK CLASS as one labeled
        #   family each (jt_lock_wait_seconds{lock=global|trackers|
        #   scheduler} via the `name|k=v` registry convention) — the
        #   decomposition itself is observable, and "which lock is the
        #   wall now" is one scrape away,
        # - heartbeat phase breakdown (fold = task-status/fetch-failure
        #   folding under the per-job locks, assign = the scheduler
        #   pass, deferred_io = history/finalize I/O, replay =
        #   response-id replays of lost responses) as ONE labeled
        #   family,
        # - per-tracker heartbeat LAG: observed inter-heartbeat gap
        #   minus the configured interval — trackers overrunning their
        #   schedule is the first externally visible saturation symptom,
        # - completion-event feed lag: backlog REMAINING after each
        #   reduce poll was served (a poll that fully catches up
        #   records 0 — the series measures pollers falling behind, not
        #   job width).
        from tpumr.metrics.histogram import COUNTS
        self.lock.bind(
            self._mreg.histogram("jt_lock_wait_seconds|lock=global"),
            self._mreg.histogram("jt_lock_hold_seconds|lock=global"))
        self.sched_lock.bind(
            self._mreg.histogram("jt_lock_wait_seconds|lock=scheduler"),
            self._mreg.histogram("jt_lock_hold_seconds|lock=scheduler"))
        self._pipe_lock.bind(
            self._mreg.histogram("jt_lock_wait_seconds|lock=pipeline"),
            self._mreg.histogram("jt_lock_hold_seconds|lock=pipeline"))
        self.trackers.bind(
            self._mreg.histogram("jt_lock_wait_seconds|lock=trackers"),
            self._mreg.histogram("jt_lock_hold_seconds|lock=trackers"))
        self._hb_phase = {
            phase: self._mreg.histogram(
                f"heartbeat_phase_seconds|phase={phase}")
            for phase in ("fold", "assign", "deferred_io", "replay")}
        self._hb_lag = self._mreg.histogram("heartbeat_lag_seconds")
        self._hb_interval_s = conf.get_int(
            "tpumr.heartbeat.interval.ms", 1000) / 1000.0
        # Master-controlled adaptive heartbeat cadence
        # (≈ mapreduce.jobtracker.heartbeats.in.second / JobTracker.
        # getNextHeartbeatInterval, MAPREDUCE-1906): the master targets
        # an AGGREGATE beat rate and instructs each tracker's next
        # interval in the heartbeat response (`next_interval_ms`), so
        # cadence degrades smoothly with fleet size instead of the whole
        # fleet missing schedule at once past the master's beat-rate
        # capacity. The configured interval is the FLOOR (small fleets
        # see no change); `tpumr.heartbeat.interval.max.ms` bounds the
        # staleness an operator will tolerate (0 = uncapped, like the
        # reference). Off by default (0): existing clusters keep exact
        # fixed-cadence semantics unless an operator opts in with a
        # target rate.
        self._hb_target_rate = conf.get_int(
            "tpumr.heartbeat.beats.per.second", 0)
        self._hb_interval_max_s = conf.get_int(
            "tpumr.heartbeat.interval.max.ms", 0) / 1000.0
        self._mreg.set_gauge(
            "heartbeat_interval_instructed_ms",
            lambda: int(self._instructed_interval_s() * 1000))
        self._event_lag = self._mreg.histogram("completion_event_lag",
                                               COUNTS)
        self._server.metrics = self.metrics.new_registry("rpc")
        self.scheduler.metrics = self.metrics.new_registry("scheduler")
        # speculative attempts in flight, summed over running jobs —
        # each term is a lock-free set len, so the gauge never queues
        # on a job lock from the metrics scrape path
        self.scheduler.metrics.set_gauge(
            "speculative_in_flight",
            lambda: sum(j.speculative_in_flight()
                        for j in self.running_jobs()))
        # heartbeat-aggregated cluster view: trackers piggyback their
        # metrics on heartbeats; one scrape of THIS daemon yields
        # cluster-wide distributions (metrics/cluster.py)
        from tpumr.metrics.cluster import ClusterAggregator
        cluster_reg = self.metrics.new_registry("cluster")
        self.cluster_agg = ClusterAggregator(cluster_reg)
        cluster_reg.set_gauge("trackers_reporting",
                              lambda: len(self.trackers))
        # named to match the trackers' own flattened slot_utilization
        # gauge, so one dashboard query covers the cluster series and
        # the per-host rows (only the source label differs)
        for kind in ("cpu", "tpu", "reduce"):
            cluster_reg.set_gauge(
                f"slot_utilization_{kind}",
                (lambda k: lambda: self._slot_utilization(k))(kind))
        # cluster-wide observed acceleration derived from the MERGED
        # distributions (global means) — per-tracker ratio gauges can't
        # be summed, but merged count/sum histograms aggregate exactly
        _exe = cluster_reg.histogram("tpu_execute_seconds")
        _cpu = cluster_reg.histogram("tpu_cpu_batch_seconds")

        def _cluster_observed_accel() -> float:
            if not _exe.count or not _cpu.count or _exe.sum <= 0:
                return 0.0
            return (_cpu.sum / _cpu.count) / (_exe.sum / _exe.count)

        cluster_reg.set_gauge("tpu_observed_acceleration",
                              _cluster_observed_accel)
        from tpumr.metrics import sinks_from_conf
        for sink in sinks_from_conf(conf):
            self.metrics.add_sink(sink)
        # distributed tracing (core/tracing.py): the tracer always
        # exists (cheap buffer object); spans are recorded ONLY for jobs
        # whose conf enables tracing — jip.trace_root None is the
        # zero-overhead-off fast path on every heartbeat
        from tpumr.core.tracing import (Tracer, trace_dir_from_conf,
                                        trace_enabled)
        self.tracer = Tracer("jobtracker",
                             trace_dir=trace_dir_from_conf(conf))
        self._trace_all = trace_enabled(conf)
        # trace shedding is a loss signal, not a log line: the buffer's
        # shed-oldest counter rides the same scrape as everything else
        self._mreg.set_gauge("trace_spans_dropped",
                             lambda: self.tracer.dropped)
        # master brownout (mapred/brownout.py): None unless
        # tpumr.brownout.enabled. The flight recorder's tick drives it;
        # every deferrable path consults it lock-free. Level + counters
        # ride the scrape so operators see sheds as they happen.
        from tpumr.mapred.brownout import BrownoutController
        self.brownout = BrownoutController.from_conf(conf)
        if self.brownout is not None:
            _b = self.brownout
            self._mreg.set_gauge("brownout_level", lambda: _b.level)
            self._mreg.set_gauge("brownout_step_ups",
                                 lambda: _b.step_ups)
            self._mreg.set_gauge("brownout_step_downs",
                                 lambda: _b.step_downs)
            self._mreg.set_gauge("brownout_events_shed",
                                 lambda: _b.events_shed)
        # scenario lab: the active scenario's name (stamped into the
        # master conf by the scenario runner) annotates incident bundles
        self.scenario_name = str(confkeys.get(
            conf, "tpumr.scenario.name") or "")
        #: per-traffic-class latency histograms keyed (kind, class),
        #: created lazily at first observation; the flight recorder
        #: windows them into online per-class SLO verdicts
        self._class_hists: "dict[tuple[str, str], Any]" = {}
        # continuous profiler + flight recorder (both None unless
        # tpumr.prof.enabled — the recorder alone also comes up under
        # tpumr.brownout.enabled, stacks-less, to drive the brownout):
        # where the master's CPU goes, and an automatic postmortem
        # bundle when an SLO breaches
        from tpumr.metrics.flightrec import FlightRecorder
        from tpumr.metrics.sampler import StackSampler
        self.sampler = StackSampler.from_conf(conf, self.metrics)
        self.flightrec = FlightRecorder.from_conf(conf, self, self.sampler)
        self._http: Any = None
        self._http_port = conf.get_int("mapred.job.tracker.http.port", -1)

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "JobMaster":
        # recovery runs BEFORE the RPC server accepts its first frame:
        # a re-joining tracker's heartbeat must find the recovered jobs
        # (and the old→new id aliases) already in place, or its adopted
        # in-flight attempts would be killed as unknown
        if self.conf.get_boolean("mapred.jobtracker.restart.recover", False):
            self._recover_jobs()
            # pipelines recover AFTER jobs: the stage-job alias table
            # (_recovered) must be complete before stage replay maps
            # old ids to the resubmitted jobs
            self._recover_pipelines()
        self._server.start()
        self._expire_thread.start()
        self._pipe_thread.start()
        self.metrics.start()
        if self.sampler is not None:
            self.sampler.start()
        if self.flightrec is not None:
            self.flightrec.start()
        if self._http_port >= 0:
            self._http = self._build_http(self._http_port).start()
        return self

    def _read_hosts_lists(self) -> "tuple[set | None, set]":
        """``mapred.hosts`` / ``mapred.hosts.exclude`` host sets
        (≈ HostsFileReader; include=None admits all)."""
        from tpumr.utils.hostsfile import read_hosts_lists
        return read_hosts_lists(self.conf, "mapred.hosts",
                                "mapred.hosts.exclude")

    def _host_allowed(self, host: str) -> bool:
        if host in self._hosts_exclude:
            return False
        return self._hosts_include is None or host in self._hosts_include

    def refresh_nodes(self, user: str = "") -> dict:
        """≈ AdminOperationsProtocol.refreshNodes (mradmin
        -refreshNodes): re-read the include/exclude files and evict any
        registered tracker that is no longer admitted — its running
        attempts and completed map outputs re-queue like a lost
        tracker's. Admin-gated exactly like refresh_queues."""
        ugi = self._acl_caller(user)
        qm = self.queue_manager
        if qm.acls_enabled and not qm.is_admin(ugi):
            raise PermissionError(
                f"user {ugi.user!r} is not a cluster administrator "
                f"(mapred.cluster.administrators)")
        include, exclude = self._read_hosts_lists()
        with self.lock:
            self._hosts_include, self._hosts_exclude = include, exclude
        evicted = [n for n, t in self.trackers.items()
                   if not self._host_allowed(t.status.get("host", ""))]
        for name in evicted:
            self._evict_tracker(name)
        return {"excluded": sorted(exclude),
                "included": sorted(include) if include is not None else "*",
                "evicted_trackers": sorted(evicted)}

    def _recover_jobs(self) -> None:
        """Restart recovery ≈ RecoveryManager (JobTracker.java:1203):
        resubmit jobs whose history shows a submission but no terminal
        event, then replay their ATTEMPT-level outcome from the event
        log (≈ the reference's RecoveryManager walking each job's
        history file) — completed maps are adopted with their original
        attempt ids and surviving shuffle outputs instead of re-running,
        completed reduces are counted done, and the old→new job id
        mapping is kept for every party still speaking the old id
        (re-joining trackers, in-flight task children, polling clients).
        A recovered output that turns out to be gone re-executes through
        the PR-1 fetch-failure protocol."""
        for ev in self.history.incomplete_jobs():
            old_id = ev["job_id"]
            if ev.get("conf_dropped"):
                # conf keys lost in serialization (in-process classes) —
                # a replay would fail every task; flag instead
                self._mreg.incr("jobs_recovery_failed")
                self.history.task_event(
                    old_id, "JOB_RECOVERY_FAILED",
                    error=f"non-serializable conf keys: "
                          f"{ev['conf_dropped']}")
                continue
            try:
                # _submit_job directly: a recovered PIPELINE STAGE job
                # must keep its pipeline stamps (the public RPC strips
                # them from untrusted direct submissions)
                new_id = self._submit_job(ev["conf"], ev["splits"],
                                          verified=None)
            except Exception as e:  # noqa: BLE001 — recovery is best-effort
                self._mreg.incr("jobs_recovery_failed")
                self.history.task_event(old_id, "JOB_RECOVERY_FAILED",
                                        error=str(e))
                continue
            jip = self.jobs[new_id]
            recovered = 0
            try:
                state = self.history.recovered_attempt_state(old_id)
                recovered = jip.recover_attempts(state, old_id)
            except Exception:  # noqa: BLE001 — attempt replay is an
                pass           # optimization; a failed one just re-runs
            # recovery grace (≈ the reference RecoveryManager waiting
            # for trackers to report back): trackers still RUNNING this
            # job's attempts re-join within a couple of heartbeats —
            # scheduling its tasks before they do would duplicate
            # in-flight work (and break the zero-re-run contract)
            grace_s = self.conf.get_int(
                "mapred.jobtracker.restart.recovery.grace.ms",
                3000) / 1000.0
            if grace_s > 0:
                jip.schedule_hold_until = time.monotonic() + grace_s
            self._recovered[old_id] = new_id
            self.history.job_recovered(old_id, new_id)
            self._mreg.incr("jobs_recovered")
            if recovered:
                self._mreg.incr("attempts_recovered", recovered)
                self.history.task_event(
                    new_id, "JOB_ATTEMPTS_RECOVERED", from_job=old_id,
                    attempts=recovered)
            if jip.state in JobState.TERMINAL:
                # every task had already completed — the crash fell in
                # the completion→finalization window; just finalize
                self._bump_jobs_version()
                self._finalize_job(jip)

    def _resolve_job(self, job_id: str) -> "JobInProgress | None":
        """Job lookup that follows the restart-recovery alias: ids off
        the wire (attempt ids on heartbeats, client polls, commit asks)
        may still name the pre-restart job. Lock-free — both dicts are
        insert-only."""
        jip = self.jobs.get(job_id)
        if jip is None and self._recovered:
            jip = self.jobs.get(self._recovered.get(job_id, ""))
        return jip

    def get_recovered_jobs(self) -> dict:
        """old job id → resubmitted job id for every job this master
        recovered at startup — the client-facing rebinding surface
        (``tpumr job status/trace <old-id>`` and polling JobClients
        follow the mapping instead of reporting the job vanished)."""
        return dict(self._recovered)

    def stop(self) -> None:
        self._stop.set()
        self._pipe_wake.set()   # unblock the advancement thread's wait
        if self.flightrec is not None:
            self.flightrec.stop()
        if self.sampler is not None:
            self.sampler.stop()
        self.metrics.stop()
        self.tracer.flush()
        if self._http is not None:
            self._http.stop()
        self._server.stop()
        # history LAST (after the RPC server can no longer enqueue):
        # the event log must be complete on disk before stop() returns —
        # restart recovery replays it immediately
        self.history.stop()

    @property
    def http_url(self) -> str | None:
        return self._http.url if self._http is not None else None

    def _build_http(self, port: int):
        """Status endpoints ≈ webapps/job JSP dashboards + /jmx."""
        from tpumr.http import StatusHttpServer
        srv = StatusHttpServer("jobtracker", port=port)
        def cluster_info(q: dict) -> dict:
            with self.lock:
                jobs_running = len(self.running_jobs())
                jobs_total = len(self.jobs)
            return {
                "cluster_id": self.cluster_id,
                "trackers": len(self.trackers),
                "slots": self.total_slots(),
                "jobs_running": jobs_running,
                "jobs_total": jobs_total,
            }

        def jobs_info(q: dict) -> list:
            with self.lock:
                jips = [self.jobs[j] for j in sorted(self.jobs)]
            return [j.status_dict() for j in jips]

        def trackers_info(q: dict) -> list:
            rows = [(n, t.last_seen, t.blacklisted, t.failures, t.status)
                    for n, t in sorted(self.trackers.items())]
            return [{"name": n, "last_seen": seen, "blacklisted": bl,
                     "failures": f, "status": st}
                    for n, seen, bl, f, st in rows]

        srv.add_json("cluster", cluster_info)
        srv.add_json("jobs", jobs_info)
        srv.add_json("job", lambda q: self._job(q["id"]).status_dict(),
                     parameterized=True)
        srv.add_json("counters", lambda q: self.get_counters(q["id"]),
                     parameterized=True)
        srv.add_json("tasks", lambda q: self.get_task_reports(
            q["id"], q.get("kind", "map")), parameterized=True)
        srv.add_json("trackers", trackers_info)
        # registers both /metrics (uniform, scraper-facing) and the
        # long-standing /json/metrics with one handler
        srv.attach_metrics(self.metrics)
        from tpumr.core.configuration import redacted_dict
        srv.add_json("conf", lambda q: redacted_dict(self.conf))

        # distributed tracing: /tracejson?job= serves the merged trace
        # in Chrome trace-event format (chrome://tracing / Perfetto
        # load it directly); /trace?job= renders the swimlane timeline
        from tpumr.core import tracing as _tracing

        def tracejson(q: dict):
            return _tracing.to_chrome_trace(
                self.get_job_trace(q["job"])["spans"])

        srv.add_raw("tracejson", tracejson)
        srv.add_json("trace", lambda q: self.get_job_trace(q["job"]),
                     parameterized=True)

        # continuous profiler: /stacks (collapsed folded-stack text) and
        # /flame (self-contained SVG) when tpumr.prof.enabled; the
        # flight recorder's bundle listing is always registered so the
        # page can say WHY it is empty
        if self.sampler is not None:
            self.sampler.attach_http(srv)

        def incidents_json(q: dict) -> list:
            return (self.flightrec.list_incidents()
                    if self.flightrec is not None else [])

        def incident_raw(q: dict) -> dict:
            if self.flightrec is None:
                raise ValueError(
                    "flight recorder disabled (tpumr.prof.enabled off "
                    "or no incident dir)")
            return self.flightrec.read_incident(q["name"])

        srv.add_json("incidents", incidents_json)
        srv.add_raw("incident", incident_raw)

        # HTML views ≈ webapps/job/{jobtracker,jobdetails,jobtasks}.jsp
        from tpumr.http import (RawHtml, html_escape, html_table,
                                progress_bar)

        def index_page(q: dict) -> str:
            c = cluster_info(q)
            jobs = jobs_info(q)
            rows = []
            for j in jobs:
                jid = j["job_id"]
                state_cls = ("ok" if j["state"] == "SUCCEEDED" else
                             "bad" if j["state"] in ("FAILED", "KILLED")
                             else "dim")
                rows.append([
                    RawHtml(f"<a href='/job?id={html_escape(jid)}'>"
                            f"{html_escape(jid)}</a>"),
                    RawHtml(f"<span class='{state_cls}'>"
                            f"{html_escape(j['state'])}</span>"),
                    progress_bar(j["map_progress"]),
                    progress_bar(j["reduce_progress"]),
                    f"{j['num_maps']}", f"{j['num_reduces']}",
                    f"{j['finished_tpu_maps']}", f"{j['finished_cpu_maps']}",
                    (f"{j['acceleration_factor']:.2f}"
                     if j.get("acceleration_factor") else "—"),
                ])
            slots = c["slots"]
            slots_txt = (" / ".join(f"{k} {v}" for k, v in slots.items())
                         if isinstance(slots, dict) else str(slots))
            snap = self.metrics.snapshot().get("jobtracker", {})
            return (
                f"<h1>JobTracker — cluster {html_escape(self.cluster_id)}"
                f"</h1>"
                f"<p>{c['trackers']} trackers · slots "
                f"{html_escape(slots_txt)} · "
                f"{c['jobs_running']} running / {c['jobs_total']} total "
                f"jobs · <a href='/pipelines'>"
                f"{len(self.pipelines)} pipelines</a></p>"
                f"<p>shuffle fault tolerance: "
                f"{snap.get('fetch_failures_reported', 0):.0f} fetch "
                f"failures reported · "
                f"{snap.get('maps_reexecuted_fetch_failure', 0):.0f} maps "
                f"re-executed · penalty box "
                f"{snap.get('fetch_failure_penalty_box', 0)}</p>"
                f"<p>accelerator fault tolerance: "
                f"{snap.get('tpu_demotions', 0):.0f} TIP demotions · "
                f"{snap.get('jobs_tpu_quarantined_now', 0)} jobs TPU-"
                f"quarantined · {snap.get('tpu_devices_quarantined', 0)} "
                f"devices quarantined · "
                f"{snap.get('tasks_reaped_timeout', 0):.0f} tasks reaped "
                f"(timeout)</p>"
                f"<h2>Jobs</h2>"
                + html_table(
                    ["job", "state", "maps", "reduces", "#maps",
                     "#reduces", "tpu maps", "cpu maps", "accel"], rows))

        def job_page(q: dict) -> str:
            jid = q.get("id", "")
            jip = self._job(jid)
            st = jip.status_dict()
            parts = [f"<h1>Job {html_escape(jid)}</h1>",
                     f"<p>state <b>{html_escape(st['state'])}</b>"
                     + (f" — {html_escape(st['error'])}"
                        if st.get("error") else "") + "</p>",
                     # stage jobs link back to their pipeline
                     (f"<p>pipeline <a href='/pipeline?id="
                      f"{html_escape(st['pipeline'])}'>"
                      f"{html_escape(st['pipeline'])}</a> · stage "
                      f"{html_escape(st['pipeline_node'])} · round "
                      f"{st['pipeline_round']}</p>"
                      if st.get("pipeline") else ""),
                     "<p>map ", progress_bar(st["map_progress"]),
                     " reduce ", progress_bar(st["reduce_progress"]),
                     "</p>",
                     f"<p>TPU maps {st['finished_tpu_maps']} · CPU maps "
                     f"{st['finished_cpu_maps']} · mean map time "
                     f"tpu {st['tpu_map_mean_time']:.3f}s / "
                     f"cpu {st['cpu_map_mean_time']:.3f}s</p>",
                     # assignment-order placement (T=tpu, c=cpu): the
                     # convergence curve at a glance — optional
                     # scheduling shows as a c→T flip mid-string
                     (f"<p>placement <code>"
                      f"{html_escape(st['placement_seq'][-512:])}"
                      f"</code></p>" if st.get("placement_seq") else "")]
            for kind in ("map", "reduce"):
                reports = self.get_task_reports(jid, kind)
                rows = []
                for t in reports:
                    backend = ("—" if kind == "reduce"
                               else f"tpu:{t['tpu_device_id']}"
                               if t["run_on_tpu"] else "cpu")
                    runtime = (t["finish_time"] - t["start_time"]
                               if t["finish_time"] and t["start_time"]
                               else 0.0)
                    rows.append([
                        t["task_id"], t["state"],
                        progress_bar(t["progress"]), backend,
                        f"{runtime:.2f}s" if runtime else "—",
                        t["successful_attempt"] or "—",
                    ])
                parts.append(f"<h2>{kind} tasks ({len(rows)})</h2>")
                parts.append(html_table(
                    ["task", "state", "progress", "backend", "runtime",
                     "attempt"], rows))
            counters = self.get_counters(jid)
            crows = [[g, n, f"{v}"]
                     for g, cs in sorted(counters.items())
                     for n, v in sorted(cs.items())]
            parts.append("<h2>Counters</h2>")
            parts.append(html_table(["group", "counter", "value"], crows))
            if jip.trace_id:
                parts.append(
                    f"<p><a href='/trace?job={html_escape(jid)}'>span "
                    f"timeline</a> · <a href='/tracejson?job="
                    f"{html_escape(jid)}'>chrome trace json</a></p>")
            return "".join(parts)

        def trace_page(q: dict) -> str:
            jid = q["job"]
            t = self.get_job_trace(jid)
            if not t["spans"]:
                return (f"<h1>Trace {html_escape(jid)}</h1>"
                        f"<p class='dim'>{html_escape(t.get('error') or 'no spans yet')}</p>")
            cp = _tracing.critical_path(t["spans"])
            crit_rows = [[p["name"], p["role"], p["backend"] or "—",
                          f"{p['duration_s']:.4f}s",
                          f"{p['self_s']:.4f}s",
                          f"{p['contribution_pct']:.1f}%"]
                         for p in cp["path"]]
            return (
                f"<h1>Trace {html_escape(jid)}</h1>"
                f"<p>{len(t['spans'])} spans · makespan "
                f"{cp['makespan_s']:.3f}s · <a href='/tracejson?job="
                f"{html_escape(jid)}'>chrome trace json</a> (load in "
                f"chrome://tracing or Perfetto)</p>"
                + RawHtml(_tracing.swimlane_svg(t["spans"]))
                + "<h2>Critical path</h2>"
                + html_table(["span", "role", "backend", "duration",
                              "self", "contribution"], crit_rows))

        def trackers_page(q: dict) -> str:
            import time as _time
            rows = []
            for t in trackers_info(q):
                st = t["status"] or {}
                quarantined = set(
                    st.get("quarantined_tpu_devices", []) or [])
                # ✖ = quarantined by the device-health monitor (the slot
                # vanished from the advertised pool until a probe passes)
                devices = "".join(
                    "✖" if i in quarantined else "●" if free else "○"
                    for i, free in enumerate(
                        st.get("available_tpu_devices", [])))
                state = ("<span class='bad'>blacklisted</span>"
                         if t["blacklisted"] else
                         "<span class='ok'>healthy</span>"
                         if st.get("healthy", True) else
                         "<span class='bad'>unhealthy</span>")
                # the NodeHealthChecker's ERROR reason — previously
                # invisible cluster-wide (satellite)
                report = st.get("health_report", "")
                rows.append([
                    t["name"],
                    st.get("host", "?"),
                    f"{st.get('count_cpu_map_tasks', 0)}"
                    f"/{st.get('max_cpu_map_slots', 0)}",
                    f"{st.get('count_tpu_map_tasks', 0)}"
                    f"/{st.get('max_tpu_map_slots', 0)}",
                    f"{st.get('count_reduce_tasks', 0)}"
                    f"/{st.get('max_reduce_slots', 0)}",
                    devices,
                    # display ages off the wall stamp kept for status
                    # surfaces (seen_mono owns the lease deadline)
                    f"{max(0.0, _time.time() - t['last_seen']):.1f}s ago",  # tpulint: disable=clock-arith
                    RawHtml(state + (f" — {html_escape(report)}"
                                     if report else "")),
                ])
            return "<h1>Trackers</h1>" + html_table(
                ["tracker", "host", "cpu slots", "tpu slots",
                 "reduce slots", "tpu devices (●=free ✖=quarantined)",
                 "last heartbeat", "state / health report"], rows)

        def cluster_page(q: dict) -> str:
            """Heartbeat-aggregated cluster view: what one scrape of the
            master knows about the whole cluster — slot utilization,
            merged tracker distributions (shuffle fetch, TPU stage/
            execute, tracker RPC), and per-tracker gauge rows."""
            import time as _time
            util = {k: self._slot_utilization(k)
                    for k in ("cpu", "tpu", "reduce")}
            # wall display ages, as on the trackers page
            hb_ages = {n: max(0.0, _time.time() - t.last_seen)  # tpulint: disable=clock-arith
                       for n, t in self.trackers.items()}
            n_trackers = len(hb_ages)
            snaps = self.metrics.snapshot()
            snap = snaps.get("cluster", {})
            jt_snap = snaps.get("jobtracker", {})
            hb = jt_snap.get("heartbeat_seconds", {})
            # per-lock wait/hold of the decomposed master locks — the
            # "which lock is the wall now" table (lock=global|trackers|
            # scheduler via the labeled-family convention)
            lock_rows = []
            for name in sorted(jt_snap):
                if not name.startswith("jt_lock_wait_seconds|"):
                    continue
                which = name.split("lock=", 1)[-1]
                w = jt_snap[name]
                h = jt_snap.get(
                    f"jt_lock_hold_seconds|lock={which}", {})
                lock_rows.append([
                    which, f"{w.get('count', 0):.0f}",
                    f"{w.get('p99', 0):.4g}", f"{w.get('max', 0):.4g}",
                    f"{h.get('p99', 0):.4g}", f"{h.get('max', 0):.4g}"])
            rows, hist_rows = [], []
            for name in sorted(snap):
                v = snap[name]
                if isinstance(v, dict) and "p99" in v:
                    hist_rows.append([
                        name, f"{v['count']:.0f}",
                        f"{v['p50']:.4g}", f"{v['p95']:.4g}",
                        f"{v['p99']:.4g}", f"{v['max']:.4g}"])
                elif isinstance(v, (int, float)):
                    rows.append([name, f"{v:.4g}"])
            parts = [
                "<h1>Cluster</h1>",
                f"<p>{n_trackers} trackers reporting · slot utilization "
                + " · ".join(f"{k} {v:.0%}" for k, v in util.items())
                + (f" · heartbeat p99 {hb.get('p99', 0):.4g}s over "
                   f"{hb.get('count', 0):.0f} beats" if hb else "")
                + "</p>",
                _profiler_line(snaps, jt_snap,
                               self.flightrec is not None),
                "<h2>Master locks (wait vs hold)</h2>",
                html_table(["lock", "acquires", "wait p99", "wait max",
                            "hold p99", "hold max"], lock_rows)
                if lock_rows else "<p class='dim'>none yet</p>",
                "<h2>Merged distributions</h2>",
                html_table(["metric", "count", "p50", "p95", "p99",
                            "max"], hist_rows)
                if hist_rows else "<p class='dim'>none yet</p>",
                "<h2>Merged counters / gauges</h2>",
                html_table(["metric", "value"], rows)
                if rows else "<p class='dim'>none yet</p>",
            ]
            gauge_rows = self.cluster_agg.gauge_rows()
            if gauge_rows:
                keys = sorted({k for g in gauge_rows.values() for k in g})
                parts.append("<h2>Per-tracker gauges</h2>")
                # last-heartbeat age leads each row: merged gauges alone
                # made a wedged tracker look healthy (its last-reported
                # numbers persist) until eviction — staleness is the
                # signal that says whether the row is even current
                parts.append(html_table(
                    ["tracker", "last heartbeat"] + keys,
                    [[t,
                      (f"{hb_ages[t]:.1f}s ago" if t in hb_ages
                       else "evicted")]
                     + [f"{gauge_rows[t].get(k, 0):.4g}" for k in keys]
                     for t in sorted(gauge_rows)]))
            return "".join(parts)

        # pipeline surfaces: /json/pipelines (+/json/pipeline?id=) for
        # tooling, /pipelines + /pipeline?id= for operators, and the
        # merged end-to-end trace of a traced pipeline
        def pipelines_page(q: dict) -> str:
            with self._pipe_lock:
                rows_src = [self.pipelines[p].status_dict()
                            for p in sorted(self.pipelines)]
            rows = []
            for p in rows_src:
                state_cls = ("ok" if p["state"] == "SUCCEEDED" else
                             "bad" if p["state"] in ("FAILED", "KILLED")
                             else "dim")
                done = sum(1 for n in p["nodes"].values()
                           if n["state"] == "SUCCEEDED")
                rows.append([
                    RawHtml(f"<a href='/pipeline?id="
                            f"{html_escape(p['pipeline_id'])}'>"
                            f"{html_escape(p['pipeline_id'])}</a>"),
                    html_escape(p.get("name", "") or "—"),
                    RawHtml(f"<span class='{state_cls}'>"
                            f"{html_escape(p['state'])}</span>"),
                    f"{done}/{len(p['nodes'])}",
                ])
            return ("<h1>Pipelines</h1>"
                    + (html_table(["pipeline", "name", "state",
                                   "stages done"], rows)
                       if rows else "<p class='dim'>none</p>"))

        def pipeline_page(q: dict) -> str:
            pid = q.get("id", "")
            st = self.get_pipeline_status(pid)
            rows = []
            for nid in sorted(st["nodes"]):
                n = st["nodes"][nid]
                state_cls = ("ok" if n["state"] == "SUCCEEDED" else
                             "bad" if n["state"] == "FAILED"
                             else "dim")
                jid = n.get("job_id", "")
                rows.append([
                    html_escape(nid),
                    RawHtml(f"<span class='{state_cls}'>"
                            f"{html_escape(n['state'])}</span>"),
                    (RawHtml(f"<a href='/job?id={html_escape(jid)}'>"
                             f"{html_escape(jid)}</a>") if jid else "—"),
                    f"{n.get('rounds_run', 0)}",
                    html_escape(n.get("output_dir", "") or "—"),
                    html_escape(n.get("error", "") or ""),
                ])
            pip = self.pipelines.get(pid)
            trace_link = (
                f"<p><a href='/pipelinetrace?id={html_escape(pid)}'>"
                f"end-to-end trace json</a> (chrome://tracing / "
                f"Perfetto)</p>"
                if pip is not None and pip.trace_id else "")
            return (
                f"<h1>Pipeline {html_escape(pid)}"
                + (f" — {html_escape(st.get('name', ''))}"
                   if st.get("name") else "") + "</h1>"
                + f"<p>state <b>{html_escape(st['state'])}</b>"
                + (f" — {html_escape(st['error'])}"
                   if st.get("error") else "") + "</p>"
                + html_table(["stage", "state", "job", "rounds",
                              "output", "error"], rows)
                + trace_link)

        def pipelinetrace(q: dict):
            return _tracing.to_chrome_trace(
                self.get_pipeline_trace(q["id"])["spans"])

        srv.add_json("pipelines", lambda q: self.list_pipelines())
        srv.add_json("pipeline",
                     lambda q: self.get_pipeline_status(q["id"]),
                     parameterized=True)
        srv.add_raw("pipelinetrace", pipelinetrace)
        srv.add_page("pipelines", pipelines_page)
        def incidents_page(q: dict) -> str:
            if self.flightrec is None:
                return ("<h1>Incidents</h1><p class='dim'>flight "
                        "recorder disabled — set tpumr.prof.enabled "
                        "and an incident dir (tpumr.prof.incident.dir "
                        "or tpumr.history.dir)</p>")
            import time as _time
            rows = []
            for r in self.flightrec.list_incidents():
                reason = " · ".join(
                    f"{b.get('metric', '?')} p99 "
                    f"{b.get('p99_s', 0):.3f}s > {b.get('slo_s', 0):.3f}s"
                    for b in r.get("reason", []))
                rows.append([
                    RawHtml(f"<a href='/incident?name="
                            f"{html_escape(r['name'])}'>"
                            f"{html_escape(r['name'])}</a>"),
                    (_time.strftime("%Y-%m-%d %H:%M:%S",
                                    _time.localtime(r["ts"]))
                     if r.get("ts") else "?"),
                    html_escape(reason),
                    f"{r.get('bytes', 0)}",
                ])
            return ("<h1>Incidents</h1>"
                    "<p>SLO-breach snapshots written by the flight "
                    "recorder (folded stacks + lock table + rpc/"
                    "heartbeat state + recent spans)</p>"
                    + (html_table(["bundle", "written", "reason",
                                   "bytes"], rows)
                       if rows else "<p class='dim'>none — the "
                       "heartbeat p99 has stayed under the SLO</p>"))

        srv.add_page("incidents", incidents_page)
        srv.add_page("pipeline", pipeline_page, parameterized=True)
        srv.add_page("index", index_page)
        srv.add_page("job", job_page, parameterized=True)
        srv.add_page("trace", trace_page, parameterized=True)
        srv.add_page("trackers", trackers_page)
        srv.add_page("cluster", cluster_page)
        return srv

    @property
    def address(self) -> tuple[str, int]:
        return self._server.address

    # ------------------------------------------------------------ SPI seams

    def jobs_version(self) -> int:
        """Monotone-ish counter bumped whenever the running-job set (or
        a job's priority) changes — the scheduler's FIFO-order cache key.
        Bumps are plain int increments (a lost race just means one
        extra re-sort or one pass on a stale order; obtain re-checks job
        state under the job lock, so staleness is never incorrect)."""
        return self._jobs_version

    def _bump_jobs_version(self) -> None:
        self._jobs_version += 1

    def running_jobs(self) -> list[JobInProgress]:
        # version-cached: the scheduler asks once per assign pass, and
        # rebuilding (under the global lock) per pass was measurable at
        # fleet heartbeat rates. Rebuilt only when the version moved.
        ver = self._jobs_version
        cached_ver, cached = self._running_cache
        if cached_ver == ver:
            return cached
        with self.lock:
            jobs = [j for j in self.jobs.values()
                    if j.state == JobState.RUNNING]
        self._running_cache = (ver, jobs)
        return jobs

    def num_trackers(self) -> int:
        # lock-free approximation for the scheduler's per-pass divisor:
        # per-stripe dict lens (GIL-atomic) minus the blacklist counter.
        # The exact blacklisted set still comes from the full scan on
        # the metrics/status paths; mid-pass the scheduler must never
        # queue on (or take, the ordering rule forbids it) the global
        # lock — and at 400+ trackers even the striped values() walk
        # per pass was a measurable share of assign time.
        return max(1, self.trackers.approx_len() - self._blacklisted)

    def total_slots(self) -> dict:
        out = {"cpu": 0, "tpu": 0, "reduce": 0}
        for t in self.trackers.values():
            out["cpu"] += t.status.get("max_cpu_map_slots", 0)
            out["tpu"] += t.status.get("max_tpu_map_slots", 0)
            out["reduce"] += t.status.get("max_reduce_slots", 0)
        return out

    def devcache_tag_index(self) -> "dict[str, set[str]]":
        """Devcache tag → names of live trackers holding it warm, from
        the trackers' piggybacked ``devcache_tags`` inventories (their
        last folded heartbeat statuses). The scheduler's affinity pass
        reads this once per heartbeat; a short monotonic TTL keeps the
        striped-registry walk off the fleet-rate fast path — staleness
        of a fraction of a beat only costs one cold placement, never
        correctness (placement is a hint, execution works anywhere)."""
        now = time.monotonic()
        stamp, cached = self._devcache_index_cache
        if now - stamp < 0.5:
            return cached
        index: "dict[str, set[str]]" = {}
        for t in self.trackers.values():
            tags = t.status.get("devcache_tags")
            if not tags:
                continue
            name = t.name
            for tag in tags:
                index.setdefault(str(tag), set()).add(name)
        self._devcache_index_cache = (now, index)
        return index

    _SLOT_KEYS = {"cpu": ("count_cpu_map_tasks", "max_cpu_map_slots"),
                  "tpu": ("count_tpu_map_tasks", "max_tpu_map_slots"),
                  "reduce": ("count_reduce_tasks", "max_reduce_slots")}

    def _slot_utilization(self, kind: str) -> float:
        """Cluster-wide busy fraction of one slot pool, from the
        trackers' last heartbeat statuses (registry-striped reads).
        0.0 with no slots of the kind — a present-but-zero series beats
        a missing one for dashboards on heterogeneous clusters."""
        busy_key, max_key = self._SLOT_KEYS[kind]
        busy = total = 0
        for t in self.trackers.values():
            busy += int(t.status.get(busy_key, 0))
            total += int(t.status.get(max_key, 0))
        return busy / total if total else 0.0

    # ------------------------------------------------------------ RPC: jobs

    def get_protocol_version(self) -> int:
        return PROTOCOL_VERSION

    def _acl_caller(self, asserted: str):
        """UGI for an ACL decision. Order: a cryptographically VERIFIED
        rpc identity (user key / delegation token) wins outright; else
        the asserted simple-auth name — unless the cluster demands
        verified identities (tpumr.acls.require.verified), in which case
        unverified assertions count as anonymous. A missing identity is
        always anonymous, never the daemon's own (administrator) user."""
        from tpumr.ipc.rpc import current_rpc_user, current_rpc_verified
        from tpumr.security import UserGroupInformation, server_side_ugi
        if current_rpc_verified():
            return server_side_ugi(str(current_rpc_user()), self.conf)
        if self._require_verified and self.queue_manager.acls_enabled:
            return UserGroupInformation("anonymous", [])
        if asserted:
            return server_side_ugi(asserted, self.conf)
        return UserGroupInformation("anonymous", [])

    def submit_job(self, conf_dict: dict, splits: list) -> str:
        from tpumr.ipc.rpc import current_rpc_user, current_rpc_verified
        # the pipeline stamps are the ENGINE's to set (via _submit_job
        # directly): a direct submission claiming a live pipeline's id
        # would adopt its FIFO anchor (queue-jumping every job since),
        # merge foreign spans into its trace, and ride its handoff
        # purge lifetime — strip them at the RPC door
        for key in ("tpumr.pipeline.id", "tpumr.pipeline.node",
                    "tpumr.pipeline.round"):
            conf_dict.pop(key, None)
        verified = str(current_rpc_user()) if current_rpc_verified() \
            else None
        return self._submit_job(conf_dict, splits, verified)

    def _submit_job(self, conf_dict: dict, splits: list,
                    verified: "str | None") -> str:
        """Submission core. ``verified`` is the cryptographically
        authenticated caller, or None — pipeline STAGE submissions pass
        None explicitly: they run on whatever thread advanced the
        pipeline (usually a heartbeat handler, whose rpc identity is
        the TRACKER's), and the owner-binding check already happened
        once at submit_pipeline against the pipeline's submitter."""
        # submit-time queue validation + ACL (≈ JobTracker.submitJob →
        # QueueManager.hasAccess(SUBMIT_JOB)): rejected jobs never enter
        # any scheduler queue
        from tpumr.mapred.queue_manager import DEFAULT_QUEUE, JOB_QUEUE_KEY
        queue = str(conf_dict.get(JOB_QUEUE_KEY, DEFAULT_QUEUE)
                    or DEFAULT_QUEUE)
        user = str(conf_dict.get("user.name", "") or "")
        if verified is not None:
            # the job OWNER is the authenticated caller (the reference
            # binds owner to the RPC UGI): a verified carol cannot
            # submit a job owned by alice
            if user and user != verified:
                raise PermissionError(
                    f"authenticated user {verified!r} cannot submit a "
                    f"job owned by {user!r}")
            user = conf_dict["user.name"] = verified
        self.queue_manager.check_submit(queue, self._acl_caller(user))
        with self.lock:
            self._next_job += 1
            job_id = JobID(self.cluster_id, self._next_job)
        # distributed tracing: one trace per job, id = the job id (file
        # names + grep both read naturally). Minted BEFORE JobInProgress
        # construction so jip.conf carries it to every tracker
        # (get_job_conf) and child process (the task file).
        from tpumr.core.tracing import (ENABLED_KEY, SAMPLE_KEY,
                                        TRACE_ID_KEY, trace_dir_from_conf,
                                        trace_enabled, trace_sample_rate)
        want_trace = self._trace_all or trace_enabled(conf_dict)
        if want_trace:
            # per-job head sampling (tpumr.trace.sample, default 1.0):
            # decided ONCE here — a sampled-out job is simply untraced
            # everywhere (no id minted into its conf), so a cluster can
            # keep tracing on while span volume stays proportional to
            # the sample rate, not the job count. The job conf's rate
            # wins; the master conf supplies the cluster default.
            import random as _random
            rate = trace_sample_rate(
                conf_dict if SAMPLE_KEY in conf_dict else self.conf)
            if self.brownout is not None \
                    and self.brownout.sheds("trace"):
                # brownout level 1+: new jobs go untraced regardless of
                # the configured rate — span buffers and journal I/O
                # are the cheapest deferrable cost on the master
                rate = 0.0
            if rate < 1.0 and _random.random() >= rate:
                want_trace = False
                conf_dict.pop(TRACE_ID_KEY, None)
                self._mreg.incr("traces_sampled_out")
        # the owning pipeline, when this is a stage submission: the
        # stage job anchors its scheduler order and its trace to it
        pipe = self.pipelines.get(
            str(conf_dict.get("tpumr.pipeline.id") or ""))
        pipe_id = str(conf_dict.get("tpumr.pipeline.id") or "")
        if want_trace:
            if pipe is not None and pipe.trace_id:
                # per-STAGE spans live under one pipeline root: every
                # stage job of a traced pipeline shares the pipeline's
                # trace id (one file, one swimlane end-to-end)
                conf_dict[TRACE_ID_KEY] = pipe.trace_id
            elif pipe_id and str(conf_dict.get(TRACE_ID_KEY)
                                 or "") == pipe_id:
                # restart recovery resubmitting a pipeline-traced
                # stage BEFORE _recover_pipelines rebuilt the table
                # (jobs recover first, by design): the journaled conf
                # already carries the pipeline's trace id — keep it,
                # so the merged trace spans both masters
                pass
            else:
                # overwrite, never setdefault: a clone-and-rerun of a
                # finished job's conf carries the OLD job's trace id,
                # which would merge two jobs' spans into one file
                conf_dict[TRACE_ID_KEY] = str(job_id)
            # master-conf-only tracing must still reach trackers and
            # children — they build their tracers from the JOB conf
            conf_dict[ENABLED_KEY] = True
            # ONE authoritative sink for the whole trace: the master's
            # dir when it has one, else the job conf's — stamped into
            # the job conf so trackers/children write exactly where
            # get_job_trace will read
            sink = self.tracer.trace_dir or trace_dir_from_conf(conf_dict)
            if sink:
                conf_dict["tpumr.trace.dir"] = sink
        # JobInProgress construction resolves split racks (may exec the
        # topology script) — built outside the master lock
        jip = JobInProgress(job_id, conf_dict, splits)
        if self.brownout is not None \
                and self.brownout.sheds("speculation"):
            # jobs born while the master is shedding start with
            # speculation paused; released on step-down with the rest
            jip.speculation_hold = True
        if jip.map_cost_key is not None:
            jip.adopt_carried_cost(self._map_costs.get(jip.map_cost_key))
        if jip.traffic_class:
            self._mreg.incr(
                f"class_jobs_submitted|class={jip.traffic_class}")
        if pipe is not None:
            # FIFO anchor: every stage of one pipeline sorts at the
            # PIPELINE's submit time, so a late stage never queues
            # behind independent jobs submitted mid-pipeline
            jip.sched_anchor = pipe.start_time
        if jip.trace_id:
            if not self.tracer.trace_dir:
                self.tracer.trace_dir = trace_dir_from_conf(conf_dict)
            jip.trace_root = self.tracer.start_span(
                "job", jip.trace_id,
                parent=(pipe.trace_root if pipe is not None else None),
                job_id=str(job_id),
                job_name=str(conf_dict.get("mapred.job.name", "")))
            self.tracer.instant(
                "job:submit", jip.trace_id, parent=jip.trace_root,
                num_maps=len(splits),
                num_reduces=int(conf_dict.get("mapred.reduce.tasks", 1)))
        # per-job shuffle/umbilical token ≈ the reference's JobToken
        # (JobTokenSecretManager): task children get THIS, never the
        # cluster secret, so a task can only reach its own job's
        # umbilical + map outputs
        import secrets as _secrets
        jip.job_token = _secrets.token_bytes(32)
        with self.lock:
            self.jobs[str(job_id)] = jip
            self._mreg.incr("jobs_submitted")
            self._bump_jobs_version()
        # history write (serializes conf + splits) outside the master lock
        self.history.job_submitted(jip)
        return str(job_id)

    # -------------------------------------------------- RPC: tokens

    def get_delegation_token(self, renewer: str = "") -> dict:
        """Issue a delegation token for the CALLER's identity
        (≈ JobTracker.getDelegationToken): a verified user gets their
        own token; a cluster-secret caller (operator tooling) gets one
        for its asserted identity. Token-authenticated callers are
        refused — tokens must not mint successors. The wire dict is the
        client credential (tpumr.rpc.token.file)."""
        from tpumr.security.tokens import issue_for_caller
        wire = issue_for_caller(self.token_store, self._rpc_secret,
                                renewer)
        self._mreg.incr("tokens_issued")
        return wire

    def renew_delegation_token(self, wire: dict) -> float:
        """≈ renewDelegationToken: owner/renewer extends the tracked
        expiry by one renew interval (capped at max lifetime)."""
        from tpumr.ipc.rpc import current_rpc_user
        from tpumr.security.tokens import verify_wire
        tok = verify_wire(self._rpc_secret, wire)
        return self.token_store.renew(tok, str(current_rpc_user() or ""))

    def cancel_delegation_token(self, wire: dict) -> bool:
        """≈ cancelDelegationToken: kills the token immediately."""
        from tpumr.ipc.rpc import current_rpc_user
        from tpumr.security.tokens import verify_wire
        tok = verify_wire(self._rpc_secret, wire)
        self.token_store.cancel(tok, str(current_rpc_user() or ""))
        return True

    def list_jobs(self) -> list[str]:
        """All known job ids ≈ JobSubmissionProtocol.jobsToComplete +
        getAllJobs (bin/hadoop job -list)."""
        with self.lock:
            return sorted(self.jobs)

    def get_queue_info(self) -> "list[dict]":
        """Per-queue summary ≈ ``bin/hadoop queue -list`` (JobClient.
        getQueues → JobQueueInfo): name, ACL specs, and job counts
        attributed by each job's ``mapred.job.queue.name``."""
        from tpumr.mapred.queue_manager import DEFAULT_QUEUE, JOB_QUEUE_KEY
        qm = self.queue_manager
        with self.lock:
            per_queue: dict[str, dict] = {}
            for jip in self.jobs.values():
                q = str(jip.conf.get(JOB_QUEUE_KEY, DEFAULT_QUEUE)
                        or DEFAULT_QUEUE)
                c = per_queue.setdefault(q, {"running": 0, "total": 0})
                c["total"] += 1
                # a terminal-but-unfinalized job still counts as
                # running — get_job_status masks that window as RUNNING
                # and the two surfaces must agree about the same job
                if (jip.status_dict()["state"] not in JobState.TERMINAL
                        or not jip.finalized.is_set()):
                    c["running"] += 1
        out = []
        for q in qm.queues():
            counts = per_queue.get(q, {"running": 0, "total": 0})
            out.append({
                "queue": q,
                "acl_submit_job": qm.acl_spec(q, "submit-job"),
                "acl_administer_jobs": qm.acl_spec(q, "administer-jobs"),
                "acls_enabled": qm.acls_enabled,
                "running_jobs": counts["running"],
                "total_jobs": counts["total"],
            })
        return out

    def get_queue_jobs(self, queue: str) -> "list[str]":
        """Job ids submitted to one queue (``queue -info Q -showJobs``)."""
        from tpumr.mapred.queue_manager import DEFAULT_QUEUE, JOB_QUEUE_KEY
        with self.lock:
            return sorted(
                jid for jid, jip in self.jobs.items()
                if str(jip.conf.get(JOB_QUEUE_KEY, DEFAULT_QUEUE)
                       or DEFAULT_QUEUE) == queue)

    def get_queue_acls(self, user: str = "") -> "list[dict]":
        """The CALLER's operations per queue ≈ JobClient.
        getQueueAclsForCurrentUser (``queue -showacls``). Identity
        resolution matches submit/kill: verified rpc identity wins,
        else the asserted name (anonymous under require.verified)."""
        return self.queue_manager.operations_for(self._acl_caller(user))

    def refresh_queues(self, user: str = "") -> "list[str]":
        """Re-read queue names + ACLs without a restart ≈
        AdminOperationsProtocol.refreshQueues (``mradmin``). Gated on
        cluster administrators whenever ACLs are enforced; with ACLs
        off the cluster is open by definition and any caller may
        refresh (same trust stance as every other open-cluster op).
        Raises (so the CLI reports it) if the configured ACL file is
        unreadable — a failed refresh must never half-apply."""
        from tpumr.mapred.queue_manager import QueueManager
        ugi = self._acl_caller(user)
        qm = self.queue_manager
        if qm.acls_enabled and not qm.is_admin(ugi):
            raise PermissionError(
                f"user {ugi.user!r} is not a cluster administrator "
                f"(mapred.cluster.administrators)")
        fresh = QueueManager(self.conf)   # re-reads mapred.queue.acls.file
        with self.lock:
            self.queue_manager = fresh
        return fresh.queues()

    def refresh_service_acl(self) -> dict:
        """≈ RefreshAuthorizationPolicyProtocol.refreshServiceAcl
        (mradmin -refreshServiceAcl) — authorized by
        security.refresh.policy.protocol.acl; refuses when service
        authorization is off, like the reference."""
        from tpumr.security.authorize import ServiceAuthorizationManager
        if self._server.authz is None or not self._server.authz.enabled:
            raise PermissionError(
                "service authorization is disabled "
                "(tpumr.security.authorization)")
        fresh = ServiceAuthorizationManager(
            self.conf, JOBTRACKER_POLICY,
            "security.job.submission.protocol.acl")
        self._server.authz = fresh
        return fresh.acl_specs()

    def _job_acl_allows(self, jip: JobInProgress, op: str, ugi) -> bool:
        """The JobACLsManager ladder (reference src/mapred/.../
        JobACLsManager.java + ACLsManager.checkAccess): owner, cluster
        administrators / queue administer ACL, then the job's own
        ``mapreduce.job.acl-<op>-job`` list — which defaults to ""
        (nobody beyond the above), the reference's closed default."""
        from tpumr.mapred.queue_manager import (DEFAULT_QUEUE,
                                                JOB_QUEUE_KEY,
                                                AccessControlList)
        owner = str(jip.conf.get("user.name", ""))
        if ugi.user == owner:
            return True
        queue = str(jip.conf.get(JOB_QUEUE_KEY, DEFAULT_QUEUE)
                    or DEFAULT_QUEUE)
        if self.queue_manager.has_access(queue, "administer-jobs", ugi):
            return True                  # cluster admins included here
        spec = str(jip.conf.get(f"mapreduce.job.acl-{op}-job", "") or "")
        return AccessControlList(spec).allows(ugi)

    def _check_job_op(self, jip: JobInProgress, op: str) -> None:
        """Job-level VIEW/MODIFY gate for the PERSONAL-CREDENTIAL tier:
        a verified user-key/token caller must pass the JobACLsManager
        ladder. Cluster-secret callers — daemons above all: trackers
        localize job confs and proxy completion events through their
        service client — are the infrastructure tier of the documented
        flat trust domain and are NOT gated here (a secret holder could
        read the history files directly; gating them would only break
        the trackers the moment an operator locks the queue ACLs down).
        The reference draws the same line with service-level
        authorization (hadoop-policy.xml) vs job ACLs."""
        if not self.queue_manager.acls_enabled:
            return
        from tpumr.ipc.rpc import current_rpc_user, current_rpc_verified
        if not current_rpc_verified():
            return
        from tpumr.security import server_side_ugi
        ugi = server_side_ugi(str(current_rpc_user()), self.conf)
        if not self._job_acl_allows(jip, op, ugi):
            owner = str(jip.conf.get("user.name", ""))
            raise PermissionError(
                f"user {ugi.user!r} cannot {op} job {jip.job_id} "
                f"(owner {owner!r}; mapreduce.job.acl-{op}-job)")

    def get_job_status(self, job_id: str) -> dict:
        try:
            jip = self._job(job_id)
        except KeyError:
            # restart survival for FINISHED work too: a job that
            # completed before the crash lives only in history — serve
            # its terminal status from there (≈ the reference's retired
            # jobs) instead of telling a polling client it vanished
            st = self._retired_status(job_id)
            if st is None:
                raise
            return st
        self._check_job_op(jip, "view")
        d = jip.status_dict()
        if d["state"] in JobState.TERMINAL and not jip.finalized.is_set():
            # commit/abort still in flight — don't let a polling client
            # read the output dir before it's promoted
            d["state"] = JobState.RUNNING
        return d

    def _retired_status(self, job_id: str) -> "dict | None":
        """History-backed terminal status, following at most a few
        hops of ``JOB_RECOVERED`` chains from masters before the last
        restart (each hop either lands on a live job or on that
        incarnation's terminal history)."""
        for _ in range(8):
            st = self.history.retired_job_status(job_id)
            if st is None:
                return None
            successor = st.pop("recovered_as", None)
            if not successor:
                # the same job-view ACL ladder the live path enforces,
                # against the submit-time conf history retained — a job
                # must not become world-readable by finishing + restart
                from types import SimpleNamespace
                self._check_job_op(
                    SimpleNamespace(conf=st.pop("_acl_conf", {}) or {},
                                    job_id=job_id), "view")
                return st
            jip = self._resolve_job(successor)
            if jip is not None:
                return self.get_job_status(str(jip.job_id))
            job_id = successor
        return None

    def get_counters(self, job_id: str) -> dict:
        jip = self._job(job_id)
        self._check_job_op(jip, "view")
        return jip.counters.to_dict()

    def get_task_reports(self, job_id: str, kind: str = "map") -> list:
        jip = self._job(job_id)
        self._check_job_op(jip, "view")
        tips = jip.maps if kind == "map" else jip.reduces
        return [{
            "task_id": str(t.task_id), "state": t.report.state,
            "progress": t.report.progress,
            "start_time": t.report.start_time,
            "finish_time": t.report.finish_time,
            "run_on_tpu": t.report.run_on_tpu,
            "tpu_device_id": t.report.tpu_device_id,
            "successful_attempt": t.report.successful_attempt,
        } for t in tips]

    def set_job_priority(self, job_id: str, priority: str,
                         user: str = "") -> str:
        """≈ JobTracker.setJobPriority (hadoop job -set-priority): the
        MODIFY ladder gates it exactly like kill_job (owner / queue
        admin / cluster admin / acl-modify-job); the FIFO queue re-sorts
        on the next heartbeat. Returns the canonical priority set."""
        jip = self._job(job_id)
        p = normalize_priority(priority)   # raises on unknown names
        ugi = self._acl_caller(user)
        if self.queue_manager.acls_enabled and \
                not self._job_acl_allows(jip, "modify", ugi):
            raise PermissionError(
                f"user {ugi.user!r} cannot administer job {jip.job_id}")
        with jip.lock:
            jip.priority = p
            # NOTE: restart survival is handled by the
            # JOB_PRIORITY_CHANGED replay in history.incomplete_jobs()
            # — recovery resubmits the conf serialized at submit time,
            # so mutating jip.conf here could never reach it
        self._bump_jobs_version()   # the FIFO-order cache re-sorts
        self.history.task_event(str(jip.job_id), "JOB_PRIORITY_CHANGED",
                                priority=p, by=ugi.user)
        return p

    def kill_task(self, attempt_id: str, should_fail: bool = False,
                  user: str = "") -> bool:
        """≈ JobTracker.killTask(taskid, shouldFail) — `tpumr job
        -kill-task` / `-fail-task`. Modify-ACL gated like kill_job. The
        tracker running the attempt receives a kill action on its next
        heartbeat; with ``should_fail`` the terminal report counts
        toward the task's attempt limit."""
        try:
            job_id = str(TaskAttemptID.parse(attempt_id).task.job)
        except (ValueError, KeyError, IndexError):
            return False     # malformed id: nothing to kill, not a crash
        jip = self._job(job_id)
        ugi = self._acl_caller(user)
        if self.queue_manager.acls_enabled and \
                not self._job_acl_allows(jip, "modify", ugi):
            raise PermissionError(
                f"user {ugi.user!r} cannot administer job {jip.job_id}")
        ok = jip.request_attempt_kill(attempt_id, fail=should_fail)
        if ok:
            self.history.task_event(
                job_id, "TASK_KILL_REQUESTED", attempt_id=attempt_id,
                should_fail=should_fail, by=ugi.user)
        return ok

    def get_attempt_ids(self, job_id: str, kind: str = "map",
                        state: str = "running") -> "list[str]":
        """≈ `job -list-attempt-ids JOB_ID map|reduce STATE`: attempt
        ids of one task type filtered by state (running/completed)."""
        jip = self._job(job_id)
        self._check_job_op(jip, "view")
        if kind not in ("map", "reduce") \
                or state.lower() not in ("running", "completed"):
            # a typo must be an error, not the OTHER listing with rc=0
            raise ValueError(
                f"kind must be map|reduce and state running|completed "
                f"(got {kind!r}, {state!r})")
        want_running = state.lower() == "running"
        out = []
        with jip.lock:
            tips = jip.maps if kind == "map" else jip.reduces
            for tip in tips:
                for aid, st in tip.attempts.items():
                    if want_running and st.state == TaskState.RUNNING:
                        out.append(aid)
                    elif not want_running \
                            and st.state == TaskState.SUCCEEDED:
                        out.append(aid)
        return sorted(out)

    def get_active_trackers(self) -> "list[str]":
        """≈ `job -list-active-trackers` (ClusterStatus tracker names).
        Unhealthy-but-heartbeating trackers are annotated with their
        NodeHealthChecker ERROR reason — the cause used to be visible
        only on the node itself."""
        out = []
        with self.lock:
            for n in sorted(self.trackers):
                t = self.trackers[n]
                if t.blacklisted:
                    continue
                st = t.status or {}
                if st.get("healthy", True):
                    out.append(n)
                else:
                    reason = st.get("health_report", "") or "unhealthy"
                    out.append(f"{n}\tUNHEALTHY: {reason}")
        return out

    def get_blacklisted_trackers(self) -> "list[str]":
        """≈ `job -list-blacklisted-trackers`."""
        with self.lock:
            return sorted(n for n, t in self.trackers.items()
                          if t.blacklisted)

    def kill_job(self, job_id: str, user: str = "") -> bool:
        jip = self._job(job_id)
        # job-level ACL (≈ JobTracker.killJob → ADMINISTER_JOBS check):
        # owner always may; others need the queue's administer ACL.
        # ``user`` is the caller's asserted simple-auth identity, like
        # the reference's non-Kerberos UGI over the wire. A caller that
        # sends NO identity is treated as an anonymous nobody — never as
        # the daemon's own (usually administrator) identity, which would
        # turn the old 1-arg call signature into an ACL bypass.
        from tpumr.mapred.queue_manager import DEFAULT_QUEUE, JOB_QUEUE_KEY
        queue = str(jip.conf.get(JOB_QUEUE_KEY, DEFAULT_QUEUE)
                    or DEFAULT_QUEUE)
        owner = str(jip.conf.get("user.name", ""))
        ugi = self._acl_caller(user)
        # one MODIFY ladder (owner / queue admin / cluster admin / the
        # job's acl-modify-job list) shared with the view gate — the
        # asserted-identity handling above (anonymous for missing
        # names) is kill_job's long-standing contract
        if self.queue_manager.acls_enabled and \
                not self._job_acl_allows(jip, "modify", ugi):
            raise PermissionError(
                f"user {ugi.user!r} cannot administer job {jip.job_id} "
                f"in queue {queue!r} (owner {owner!r})")
        # kill() no-ops if a concurrent heartbeat already made it terminal
        if not jip.kill():  # ≈ JobTracker.killJob: no-op on finished jobs
            return False
        self._bump_jobs_version()
        self._finalize_job(jip)
        return True

    def _finalize_job(self, jip: JobInProgress) -> None:
        """Job-level output commit/abort + history. The reference runs this
        as a cleanup TASK on a tracker (getSetupAndCleanupTasks,
        JobTracker.java:3398); master-side finalization is a deliberate
        simplification — the output FS is shared, the work is two renames.
        Idempotent: the first caller claims it under jip.lock; later
        callers (kill_job racing a heartbeat-deferred finalize) return."""
        with jip.lock:
            if jip.finalize_started:
                return
            jip.finalize_started = True
        root = jip.trace_root
        fin_span = self.tracer.start_span(
            "job:finalize", jip.trace_id, parent=root) \
            if root is not None else None
        try:
            from tpumr.mapred.output_formats import FileOutputCommitter
            conf = JobConf()
            for k, v in jip.conf.items():
                conf.set(k, v)
            if conf.get("mapred.output.dir"):
                committer = FileOutputCommitter(conf)
                if jip.state == JobState.SUCCEEDED:
                    committer.commit_job()
                else:
                    committer.abort_job()
        except Exception as e:  # noqa: BLE001
            jip.error = jip.error or f"job finalization failed: {e}"
        if jip.map_cost_key is not None \
                and jip.state == JobState.SUCCEEDED:
            carried = jip.cost_to_carry()
            if carried is not None:
                self._map_costs[jip.map_cost_key] = carried
        try:
            self.history.job_finished(jip)
            self._mreg.incr(f"jobs_{jip.state.lower()}")
            if jip.traffic_class:
                # scenario lab: submit→complete latency by traffic
                # class — successful runs only (a fast failure must
                # not flatter the completion SLO), failures counted
                if jip.state == JobState.SUCCEEDED:
                    self._class_observe(
                        "complete", jip.traffic_class,
                        time.monotonic() - jip.submit_mono)
                else:
                    self._mreg.incr(f"class_jobs_failed|class="
                                    f"{jip.traffic_class}")
            # per-job stats rollup (metrics-<jobid>.json next to the
            # history log): counters + latency percentiles + the
            # TPU/CPU task-time split — what `tpumr job stats` prints
            # and what a future affinity/critical-path scheduler reads
            try:
                self.history.write_job_metrics(jip)
            except Exception:  # noqa: BLE001 — the rollup is auxiliary;
                pass           # its I/O must not fail job finalization
        finally:
            if root is not None:
                # the root span closes with the job and every master
                # span hits disk BEFORE clients can observe the terminal
                # state — a trace pulled right after completion is whole
                if fin_span is not None:
                    self.tracer.finish(fin_span.set(state=jip.state))
                jip.trace_root = None
                jip.trace_sched.clear()
                self.tracer.finish(root.set(state=jip.state,
                                            error=jip.error or ""))
                self.tracer.flush()
            # even when history I/O fails the job must become observable
            # as finished — a stuck RUNNING mask would hang clients
            jip.finalized.set()

    def get_map_completion_events(self, job_id: str, from_index: int = 0,
                                  max_events: int = 10_000) -> list:
        jip = self._job(job_id)
        self._check_job_op(jip, "view")   # own task children pass by scope
        # LOCK-FREE: the feed is append-only (CompletionEventFeed), so
        # reducer polls never queue behind the status fold appending
        # under the job lock — at fleet scale these polls outnumber
        # heartbeats and used to serialize on the same locks
        events, pending = jip.completion_events.read(int(from_index),
                                                     int(max_events))
        # completion-event feed lag: the backlog REMAINING after this
        # poll was served (0 = fully caught up). A growing distribution
        # means pollers can't drain the feed — they fall behind the map
        # completion rate, or can't get through a saturated master. The
        # volume a poll catches up on fine is deliberately NOT counted:
        # that grows with job width, not with saturation.
        self._event_lag.observe(pending)
        return events

    def get_job_conf(self, job_id: str) -> dict:
        jip = self._job(job_id)
        self._check_job_op(jip, "view")
        return dict(jip.conf)

    def get_job_trace(self, job_id: str) -> dict:
        """Merged distributed trace of one traced job: every daemon's
        flushed span files under the trace dir plus the master's own
        buffer, as raw span dicts (the CLI/HTTP layers convert to Chrome
        trace-event format / compute the critical path)."""
        jip = self._job(job_id)
        self._check_job_op(jip, "view")
        from tpumr.core import tracing
        if not jip.trace_id:
            return {"trace_id": "", "spans": [],
                    "error": f"job {job_id} was not traced "
                             f"(set tpumr.trace.enabled=true at submit)"}
        self.tracer.flush()
        # read from the JOB's stamped sink (submit_job made it the
        # authoritative dir every daemon writes to), falling back to the
        # master's own — writers and readers must resolve one place
        read_dir = tracing.trace_dir_from_conf(jip.conf) \
            or self.tracer.trace_dir
        spans = tracing.read_trace_files(read_dir, jip.trace_id) \
            if read_dir else []
        root = jip.trace_root
        if root is not None:
            # still running: ship the open root (end = now) so partial
            # traces anchor correctly in viewers
            d = root.to_dict()
            d["end"] = time.time()
            d["attributes"] = {**d["attributes"], "in_flight": True}
            spans.append(d)
        return {"trace_id": jip.trace_id, "spans": spans}

    def get_job_token(self, job_id: str) -> bytes:
        """Per-job token for trackers localizing the job (cluster-secret
        callers only — the RPC layer rejects token-scoped frames at the
        master, so a task child can never mint or read tokens)."""
        return getattr(self._job(job_id), "job_token", b"") or b""

    def _job(self, job_id: str) -> JobInProgress:
        # lock-free: the job table is insert-only and dict reads are
        # GIL-atomic — completion-event polls and status RPCs must not
        # queue on the global lock just to look up their job. Follows
        # the restart-recovery alias: a pre-restart id serves the
        # resubmitted job (status_dict carries the NEW id, so clients
        # can rebind).
        jip = self._resolve_job(job_id)
        if jip is None:
            raise KeyError(f"unknown job {job_id}")
        return jip

    # --------------------------------------------------- RPC: pipelines

    def submit_pipeline(self, graph_dict: dict) -> str:
        """Admit one validated :class:`~tpumr.pipeline.graph.JobGraph`
        atomically: the whole DAG lands in one RPC, the master owns
        every stage submission from here (split computation included) —
        an N-stage chain costs one client round trip instead of N
        submit/poll/resubmit cycles. Source stages submit before this
        returns, so the client's first status poll already sees them."""
        from tpumr.ipc.rpc import current_rpc_user, current_rpc_verified
        from tpumr.mapred.queue_manager import DEFAULT_QUEUE, JOB_QUEUE_KEY
        from tpumr.pipeline.graph import JobGraph
        from tpumr.pipeline.pipeline_in_progress import PipelineInProgress
        graph = JobGraph.from_dict(dict(graph_dict or {}))
        graph.validate()   # clients lie — reject before admitting
        # ...and they leak: strip client-local credentials server-side
        # too — the graph goes VERBATIM into the history journal and
        # every stage job conf (the submit path's _wire_conf stance)
        from tpumr.mapred.job_client import scrub_credentials
        graph.conf = scrub_credentials(graph.conf)
        for n in graph.nodes.values():
            n["conf"] = scrub_credentials(n["conf"])
        user = str(graph.conf.get("user.name", "") or "")
        if current_rpc_verified():
            verified = str(current_rpc_user())
            if user and user != verified:
                raise PermissionError(
                    f"authenticated user {verified!r} cannot submit a "
                    f"pipeline owned by {user!r}")
            user = graph.conf["user.name"] = verified
        # one submit-ACL check per distinct stage queue, up front — a
        # stage the submitter may not queue must fail the WHOLE graph
        # now, not strand a half-run pipeline later
        ugi = self._acl_caller(user)
        queues = {str(n["conf"].get(JOB_QUEUE_KEY,
                                    graph.conf.get(JOB_QUEUE_KEY,
                                                   DEFAULT_QUEUE))
                      or DEFAULT_QUEUE)
                  for n in graph.nodes.values()}
        for q in sorted(queues):
            self.queue_manager.check_submit(q, ugi)
        # conf hooks execute IN THIS PROCESS at stage submit: only
        # operator-allowlisted module prefixes may run (mapper/reducer
        # names resolve on trackers; this is the one seam where a
        # client string executes in the master itself)
        allowed = [s.strip() for s in str(confkeys.get(
            self.conf, "tpumr.pipeline.conf.hooks.allowed")
            or "").split(",") if s.strip()]
        for nid, n in graph.nodes.items():
            hook = n.get("conf_hook")
            if hook and not any(str(hook).startswith(p)
                                for p in allowed):
                raise PermissionError(
                    f"node {nid!r}: conf_hook {hook!r} is not under "
                    f"an allowed prefix ({', '.join(allowed)}) — "
                    f"hooks run in the master; extend "
                    f"tpumr.pipeline.conf.hooks.allowed to admit it")
        with self.lock:
            self._next_pipe += 1
            pid = f"pipe_{self.cluster_id}_{self._next_pipe:04d}"
        pip = PipelineInProgress(pid, graph, user=user)
        # distributed tracing: ONE root for the whole pipeline; stage
        # jobs share its trace id and parent their job roots to it, so
        # /pipelinetrace renders submit→stage→stage end-to-end
        from tpumr.core.tracing import (ENABLED_KEY, TRACE_ID_KEY,
                                        trace_dir_from_conf,
                                        trace_enabled)
        if self._trace_all or trace_enabled(graph.conf):
            pip.trace_id = pid
            graph.conf[TRACE_ID_KEY] = pid
            graph.conf[ENABLED_KEY] = True
            sink = self.tracer.trace_dir or trace_dir_from_conf(graph.conf)
            if sink:
                graph.conf["tpumr.trace.dir"] = sink
                if not self.tracer.trace_dir:
                    self.tracer.trace_dir = sink
            pip.trace_root = self.tracer.start_span(
                "pipeline", pid, pipeline_id=pid,
                pipeline_name=graph.name, nodes=len(graph.nodes))
        with self._pipe_lock:
            self.pipelines[pid] = pip
        self._mreg.incr("pipelines_submitted")
        # full graph into the journal BEFORE any stage submits: restart
        # recovery replays submission order (≈ job_submitted's stance)
        self.history.task_event(pid, "PIPELINE_SUBMITTED",
                                pipeline_id=pid, user=user,
                                graph=graph.to_dict())
        self._advance_pipeline(pip)
        return pid

    def get_pipeline_status(self, pipeline_id: str) -> dict:
        pip = self.pipelines.get(pipeline_id)
        if pip is None:
            raise KeyError(f"unknown pipeline {pipeline_id}")
        with self._pipe_lock:
            return pip.status_dict()

    def list_pipelines(self) -> "list[dict]":
        with self._pipe_lock:
            return [self.pipelines[pid].status_dict()
                    for pid in sorted(self.pipelines)]

    def kill_pipeline(self, pipeline_id: str, user: str = "") -> bool:
        """Kill the pipeline and every in-flight stage job. MODIFY
        gate: the pipeline's submitter, or a cluster/queue
        administrator (same ladder kill_job walks, at pipeline
        granularity)."""
        pip = self.pipelines.get(pipeline_id)
        if pip is None:
            raise KeyError(f"unknown pipeline {pipeline_id}")
        ugi = self._acl_caller(user)
        qm = self.queue_manager
        if qm.acls_enabled and ugi.user != pip.user \
                and not qm.is_admin(ugi):
            raise PermissionError(
                f"user {ugi.user!r} cannot kill pipeline {pipeline_id} "
                f"(owner {pip.user!r})")
        with self._pipe_lock:
            was_terminal = pip.state in ("SUCCEEDED", "FAILED",
                                         "KILLED")
            victims = pip.kill()
        for jid in victims:
            jip = self.jobs.get(jid)
            if jip is not None and jip.kill():
                self._bump_jobs_version()
                self._finalize_job(jip)
        self._finish_pipeline(pip)
        # ≈ kill_job's contract: False for an already-finished target
        return not was_terminal

    def get_handoff_completion_events(self, job_id: str,
                                      from_index: int = 0,
                                      max_events: int = 10_000) -> list:
        """Streamed-handoff announcements of one upstream stage job —
        the completion-event protocol verbatim, second feed: LOCK-FREE
        cursor reads off the append-only ``handoff_events``, OBSOLETE
        tombstones for withdrawn copies, alias-following lookups for
        pre-restart stage ids."""
        jip = self._job(job_id)
        self._check_job_op(jip, "view")
        events, _pending = jip.handoff_events.read(int(from_index),
                                                   int(max_events))
        return events

    def handoff_purgeable(self, job_id: str) -> bool:
        """May a tracker drop its streamed-handoff copies for
        ``job_id``? Only once the OWNING PIPELINE is over — a finished
        upstream stage keeps serving live downstream stages (job
        cleanup must not eat the intermediates mid-pipeline). Unknown
        jobs (recovery off, alias horizon passed) are purgeable: the
        committed DFS artifact is the fallback truth either way."""
        jip = self._resolve_job(job_id)
        if jip is not None:
            if jip.state not in JobState.TERMINAL:
                return False
            pid = str(jip.conf.get("tpumr.pipeline.id") or "")
        else:
            st = self.history.retired_job_status(job_id)
            if st is None:
                return True
            pid = str((st.get("_acl_conf") or {})
                      .get("tpumr.pipeline.id", "") or "")
        if not pid:
            return True
        pip = self.pipelines.get(pid)
        return pip is None or pip.state in ("SUCCEEDED", "FAILED",
                                            "KILLED")

    def get_pipeline_trace(self, pipeline_id: str) -> dict:
        """The merged end-to-end trace of a traced pipeline: every
        stage job's spans plus the pipeline root, one file (they share
        the pipeline's trace id)."""
        pip = self.pipelines.get(pipeline_id)
        if pip is None:
            raise KeyError(f"unknown pipeline {pipeline_id}")
        from tpumr.core import tracing
        if not pip.trace_id:
            return {"trace_id": "", "spans": [],
                    "error": f"pipeline {pipeline_id} was not traced"}
        self.tracer.flush()
        read_dir = self.tracer.trace_dir \
            or tracing.trace_dir_from_conf(pip.graph.conf)
        spans = tracing.read_trace_files(read_dir, pip.trace_id) \
            if read_dir else []
        root = pip.trace_root
        if root is not None:
            d = root.to_dict()
            d["end"] = time.time()
            d["attributes"] = {**d["attributes"], "in_flight": True}
            spans.append(d)
        return {"trace_id": pip.trace_id, "spans": spans}

    # ------------------------------------------------ pipeline engine

    def _advance_pipelines(self) -> None:
        """One advancement sweep over the running pipelines. Called
        from the heartbeat's DEFERRED phase and the expiry loop — the
        caller holds NO locks; each pipeline's plan/record transitions
        take the pipeline lock briefly, all I/O runs between."""
        for pip in list(self.pipelines.values()):
            if pip.state == "RUNNING":
                self._advance_pipeline(pip)

    def _advance_pipeline(self, pip: Any) -> None:
        # bounded: each iteration either submits stages, resolves
        # history-only stage outcomes, or stops; a loop node chains
        # rounds one fold per iteration
        for _ in range(len(pip.nodes) * 4 + 8):
            with self._pipe_lock:
                plans, unresolved = pip.plan_locked(self)
            if not plans and not unresolved:
                break
            for nid, rnd in plans:
                self._submit_stage(pip, nid, rnd)
            if unresolved:
                # stage jobs only history remembers (finished before a
                # restart): the file reads happen HERE, outside the
                # pipeline lock; verdicts feed back under it
                verdicts = [(nid, pip._retired_state(self, jid))
                            for nid, jid in unresolved]
                with self._pipe_lock:
                    for nid, st in verdicts:
                        pip.apply_retired(nid, st)
                if all(st == "RUNNING" for _, st in verdicts) \
                        and not plans:
                    break   # nothing actionable yet — next beat retries
        if pip.state in ("SUCCEEDED", "FAILED", "KILLED"):
            self._finish_pipeline(pip)

    def _finish_pipeline(self, pip: Any) -> None:
        """Terminal bookkeeping, exactly once (idempotent claim under
        the pipeline lock; the I/O runs outside it). A FAILED pipeline
        kills its still-running sibling stages — half a diamond must
        not burn slots for a join that can never run."""
        with self._pipe_lock:
            if getattr(pip, "finished_recorded", False):
                return
            pip.finished_recorded = True
            victims = []
            if pip.state in ("FAILED", "KILLED"):
                for n in pip.nodes.values():
                    if n.state == "RUNNING":
                        # settle the sibling observably: advancement
                        # stops on terminal pipelines, nothing would
                        # ever fold this node again
                        if n.job_id:
                            victims.append(n.job_id)
                        n.state = "FAILED"
                        n.error = n.error or "killed with pipeline"
        for jid in victims:
            jip = self.jobs.get(jid)
            if jip is not None and jip.kill():
                self._bump_jobs_version()
                self._finalize_job(jip)
        self._mreg.incr(f"pipelines_{pip.state.lower()}")
        self.history.task_event(
            pip.pipeline_id, "PIPELINE_FINISHED", state=pip.state,
            error=pip.error,
            wall_time=(pip.finish_time or time.time()) - pip.start_time,
            nodes={nid: n.state for nid, n in pip.nodes.items()})
        root = pip.trace_root
        if root is not None:
            pip.trace_root = None
            self.tracer.finish(root.set(state=pip.state,
                                        error=pip.error or ""))
            self.tracer.flush()

    def _submit_stage(self, pip: Any, nid: str, rnd: int) -> None:
        """Build and submit one stage job (NO pipeline lock held: conf
        hooks, split computation, and the submission's history write
        all block). The node was marked SUBMITTING under the lock, so
        concurrent advances cannot double-submit."""
        import json as _json
        node = pip.nodes[nid]
        graph = pip.graph
        try:
            conf = node.round_conf(graph.conf, rnd)
            conf.setdefault("user.name", pip.user)
            conf["tpumr.pipeline.id"] = pip.pipeline_id
            conf["tpumr.pipeline.node"] = nid
            conf["tpumr.pipeline.round"] = rnd
            conf.setdefault(
                "mapred.job.name",
                f"{graph.name or pip.pipeline_id}:{nid}"
                + (f"@r{rnd}" if node.is_loop else ""))
            if any(e["stream"] for e in graph.downstreams(nid)):
                conf["tpumr.pipeline.stream.handoff"] = True
            ins = graph.upstreams(nid)
            ups = {e["src"]: pip.nodes[e["src"]] for e in ins}
            ups_info = {src: {"job_id": up.job_id,
                              "output_dir": up.output_dir,
                              "num_reduces": up.num_reduces}
                        for src, up in ups.items()}
            handoff_splits = None
            if ins and all(e["stream"] for e in ins):
                # streamed input: one map per upstream reduce
                # partition, fetched over the shuffle wire — splits are
                # built HERE, no DFS listing, no client round trip
                from tpumr.pipeline.handoff import build_handoff_splits
                conf["mapred.input.format.class"] = \
                    "tpumr.pipeline.handoff.PipelineHandoffInputFormat"
                conf["tpumr.pipeline.handoff.upstream"] = _json.dumps(
                    sorted({i["job_id"] for i in ups_info.values()}))
                handoff_splits = []
                for src in sorted(ups):
                    up = ups[src]
                    serving = self._handoff_serving(up.job_id)
                    handoff_splits.extend(build_handoff_splits(
                        up.job_id, up.num_reduces, up.output_dir,
                        serving))
            elif ins and not str(conf.get("mapred.input.dir") or ""):
                # dfs wiring: the committed upstream output dirs
                conf["mapred.input.dir"] = ",".join(
                    ups_info[src]["output_dir"] for src in sorted(ups))
            hook = node.spec.get("conf_hook")
            if hook:
                # a FUNCTION by dotted name (resolve_class insists on
                # classes): the master-side prep seam for work that
                # needs upstream output to exist (partition sampling)
                import importlib
                mod_name, _, attr = str(hook).rpartition(".")
                getattr(importlib.import_module(mod_name),
                        attr)(conf, ups_info)
            if handoff_splits is not None:
                splits_wire = [s.to_dict() for s in handoff_splits]
            else:
                # the client's submission prep, master-side — the ONE
                # shared helper (job_client.build_submission), so the
                # client and pipeline submit paths can never drift
                # (this is the latency the sequential chain pays per
                # stage)
                from tpumr.mapred.job_client import build_submission
                jc = JobConf()
                for k, v in conf.items():
                    jc.set(k, v)
                conf, splits_wire = build_submission(jc)
            job_id = self._submit_job(conf, splits_wire, verified=None)
            jip = self.jobs[job_id]
            out_dir = str(conf.get("mapred.output.dir") or "")
            with self._pipe_lock:
                accepted = pip.record_submitted(nid, rnd, job_id,
                                                out_dir,
                                                jip.num_reduces)
            if not accepted:
                # the pipeline was killed/failed while this submission
                # was in flight — reap the just-submitted job now, or
                # nothing ever would (advancement stops on terminal
                # pipelines)
                if jip.kill():
                    self._bump_jobs_version()
                    self._finalize_job(jip)
            self._mreg.incr("pipeline_stages_submitted")
            self.history.task_event(
                pip.pipeline_id, "PIPELINE_STAGE_SUBMITTED", node=nid,
                round=rnd, stage_job_id=job_id, output_dir=out_dir,
                num_reduces=jip.num_reduces)
            if pip.trace_root is not None:
                self.tracer.instant(
                    "pipeline:stage_submit", pip.trace_id,
                    parent=pip.trace_root, node=nid, round=rnd,
                    job_id=job_id)
        except Exception as e:  # noqa: BLE001 — a stage that cannot
            # submit fails the pipeline observably, never silently
            with self._pipe_lock:
                pip.record_submit_failed(
                    nid, f"{type(e).__name__}: {e}")
            self._mreg.incr("pipeline_stage_submit_failed")
            self.history.task_event(
                pip.pipeline_id, "PIPELINE_STAGE_SUBMIT_FAILED",
                node=nid, round=rnd, error=f"{type(e).__name__}: {e}")

    def _handoff_serving(self, job_id: str) -> "dict[int, str]":
        """partition -> serving shuffle_addr of one upstream stage's
        already-committed handoff copies (locality hints for the
        downstream splits; lock-free feed iteration)."""
        jip = self._resolve_job(job_id)
        if jip is None:
            return {}
        return {e["map_index"]: e["shuffle_addr"]
                for e in jip.handoff_events
                if e.get("status") == "SUCCEEDED"}

    def _recover_pipelines(self) -> None:
        """Restart recovery for in-flight pipelines: replay each
        journal's graph + stage submissions, following the job-recovery
        alias for stage jobs the restart resubmitted. Completed
        upstream stages are adopted terminal from history — a master
        kill mid-pipeline must never re-run finished stages."""
        from tpumr.pipeline.pipeline_in_progress import PipelineInProgress
        for rec in self.history.incomplete_pipelines():
            pid = rec["pipeline_id"]
            try:
                pip = PipelineInProgress.from_recovery(
                    pid, rec["graph"], rec["stages"], self,
                    user=rec.get("user", ""))
            except Exception as e:  # noqa: BLE001 — recovery is
                self._mreg.incr("pipelines_recovery_failed")  # best-
                self.history.task_event(                      # effort
                    pid, "PIPELINE_RECOVERY_FAILED", error=str(e))
                continue
            with self._pipe_lock:
                self.pipelines[pid] = pip
            self._mreg.incr("pipelines_recovered")
            self.history.task_event(pid, "PIPELINE_RECOVERED",
                                    pipeline_id=pid)

    # ------------------------------------------------------------ RPC: commit

    def can_commit(self, task_id: str, attempt_id: str) -> bool:
        """First asker wins (≈ the single CommitTaskAction per task). Grants
        are revoked when the granted attempt fails or its tracker is lost,
        so re-runs can commit. An attempt the master already settled
        terminally is refused outright: a reaped zombie thread asking
        AFTER its FAILED status was folded (and any prior grant revoked)
        must not capture a fresh grant it would hold forever, denying
        every re-run."""
        jip = None
        try:
            job_id = str(TaskAttemptID.parse(attempt_id).task.job)
        except (ValueError, IndexError):
            pass   # unparseable id: no job to consult, legacy grant path
        else:
            jip = self._resolve_job(job_id)   # lock-free lookup
        if jip is not None:
            with jip.lock:
                tip = jip._tip_of_attempt(attempt_id)
                st = tip.attempts.get(attempt_id) if tip is not None \
                    else None
                if st is not None and st.state in TaskState.TERMINAL:
                    return False
        with self.lock:
            granted = self._commit_grants.setdefault(task_id, attempt_id)
            return granted == attempt_id

    def _revoke_commit(self, task_id: str, attempt_id: str) -> None:
        with self.lock:
            if self._commit_grants.get(task_id) == attempt_id:
                del self._commit_grants[task_id]

    # ------------------------------------------------------------ RPC: heartbeat

    def _instructed_interval_s(self) -> float:
        """The heartbeat interval the master currently asks trackers to
        keep: ``max(floor, fleet_size / target_rate)``, optionally
        capped. Lock-free (``approx_len``) — called per beat under the
        tracker's ``hb_lock``, the bottom of the lock order, where no
        shard stripe may be taken."""
        rate = self._hb_target_rate
        if rate <= 0:
            s = self._hb_interval_s
        else:
            s = max(self._hb_interval_s,
                    self.trackers.approx_len() / rate)
            if self._hb_interval_max_s > 0:
                # a floor above the cap means the operator pinned the
                # cadence — the floor wins (adaptation never speeds
                # beats up)
                s = min(s, max(self._hb_interval_max_s,
                               self._hb_interval_s))
        if self.brownout is not None:
            # brownout level 2+: stretch the instructed cadence toward
            # the adaptive max — the whole fleet beats slower and the
            # fold/assign path breathes (lock-free, one int read)
            s = self.brownout.stretch_interval(
                s, max(self._hb_interval_max_s, self._hb_interval_s))
        return s

    def _class_observe(self, kind: str, cls: str,
                       seconds: float) -> None:
        """Per-traffic-class latency fold (scenario lab):
        ``class_assign_seconds`` / ``class_complete_seconds`` labeled
        by class. Get-or-create is registry-locked and idempotent; the
        local dict probe keeps repeat observations allocation-free."""
        h = self._class_hists.get((kind, cls))
        if h is None:
            h = self._mreg.histogram(
                f"class_{kind}_seconds|class={cls}")
            self._class_hists[(kind, cls)] = h
        h.observe(max(0.0, seconds))

    def brownout_tick(self, pressure: bool) -> None:
        """One flight-recorder tick's pressure bit → the brownout state
        machine, plus the side effects a level change implies (the
        speculation hold is per-job state, flipped here on transitions
        so the scheduler's lock-free prechecks see it)."""
        b = self.brownout
        if b is None:
            return
        was_holding = b.sheds("speculation")
        b.on_tick(pressure)
        holding = b.sheds("speculation")
        if was_holding != holding:
            for jip in list(self.jobs.values()):
                jip.speculation_hold = holding

    def heartbeat(self, status: dict, initial_contact: bool,
                  ask_for_new_task: bool, response_id: int) -> dict:
        name = status["tracker_name"]
        self._mreg.incr("heartbeats")
        t0 = time.monotonic()
        from tpumr.utils.fi import fires
        if fires("jt.heartbeat.slow", self.conf):
            # BEHAVIORAL observability seam: handling crawls for
            # tpumr.fi.jt.heartbeat.slow.ms, breaching the windowed
            # heartbeat p99 SLO — the flight recorder's forcing function
            time.sleep(confkeys.get_int(
                self.conf, "tpumr.fi.jt.heartbeat.slow.ms") / 1000.0)
        # the tracker's PR-2 heartbeat span context (shipped only when
        # the tracker traces its daemon loop): master-side phase work
        # records as sub-spans on that same trace, so one swimlane shows
        # where a slow heartbeat's time went. Popped so the stored
        # tracker status never carries it.
        hb_trace = status.pop("trace", None)
        # history appends + job finalization are file I/O — deferred past
        # all locks so disk latency never serializes the control plane;
        # task events flush BEFORE finalization so the per-job log
        # stays causally ordered (TASK_* precede JOB_FINISHED)
        deferred_events: list[tuple[str, str, dict]] = []
        deferred_final: list[JobInProgress] = []
        try:
            return self._heartbeat(status, initial_contact,
                                   ask_for_new_task, response_id,
                                   name, deferred_events,
                                   deferred_final, hb_trace, t0)
        finally:
            t_io = time.monotonic()
            t_io_wall = time.time()
            for job_id, event, fields in deferred_events:
                try:
                    self.history.task_event(job_id, event, **fields)
                except Exception:  # noqa: BLE001 — history I/O best-effort
                    pass
            for jip in deferred_final:
                try:
                    self._finalize_job(jip)
                except Exception:  # noqa: BLE001
                    jip.error = jip.error or "finalization failed"
                    jip.finalized.set()
            if deferred_events or deferred_final:
                self._hb_phase["deferred_io"].observe(
                    time.monotonic() - t_io)
                self._phase_span(hb_trace, "heartbeat:deferred_io",
                                 t_io_wall,
                                 events=len(deferred_events),
                                 finalized=len(deferred_final))
            if self.pipelines:
                # DAG advancement must NEVER run on a heartbeat
                # handler thread (stage submission blocks on DFS
                # listings and conf hooks — it would silence this
                # tracker's beats): the deferred phase just WAKES the
                # dedicated pipeline-advance thread, which picks the
                # fold's consequences up within microseconds. The
                # guard is a lock-free dict-truthiness read, so
                # pipeline-less clusters pay nothing here.
                self._pipe_wake.set()
            # handling latency INCLUDING the deferred history/finalize
            # I/O: that work serializes this handler thread (and with it
            # this tracker's next heartbeat), so it is part of the
            # latency an operator must see
            self._hb_seconds.observe(time.monotonic() - t0)

    def heartbeat_batch(self, beats: list) -> list:
        """Many co-located trackers' beats in ONE RPC, sparing the
        syscall + dispatch overhead of a round-trip per tracker (only
        ``SimFleet(batch=...)`` sends it). Each member is ``[status,
        initial_contact, ask_for_new_task, response_id]`` and is folded
        through the normal :meth:`heartbeat` path — the per-tracker
        replay cache, hb_lock, delta decode, and deferred phase all
        apply PER MEMBER, so a resent batch replays stored actions
        instead of double-folding any tracker. Members fail
        independently: a bad member yields ``{"error": ...}`` in its
        slot and the rest of the batch proceeds. Deliberately NOT a
        reactor fast method — a batch does real work and belongs on
        the handler pool."""
        self._mreg.incr("heartbeat_batches")
        self._hb_batch_size.observe(len(beats))
        out = []
        for member in beats:
            try:
                status, initial_contact, ask, response_id = member
                out.append(self.heartbeat(status, bool(initial_contact),
                                          bool(ask), int(response_id)))
            except Exception as e:  # noqa: BLE001 — member-isolated
                out.append({"error": f"{type(e).__name__}: {e}"})
        return out

    def _phase_span(self, hb_trace: "dict | None", name: str,
                    start_wall: float, **attrs: Any) -> None:
        """Record one already-elapsed heartbeat phase as a sub-span of
        the tracker's heartbeat span (no-op when the tracker didn't ship
        trace context — the zero-overhead-off contract)."""
        if hb_trace is None:
            return
        s = self.tracer.start_span(name, hb_trace.get("trace_id", ""),
                                   parent=hb_trace, **attrs)
        self.tracer.finish(s.backdate(start_wall))

    def _heartbeat(self, status: dict, initial_contact: bool,
                   ask_for_new_task: bool, response_id: int,
                   name: str, deferred_events: list,
                   deferred_final: list,
                   hb_trace: "dict | None" = None,
                   t0: float = 0.0) -> dict:
        # ---- phase: registry — the ONLY synchronization here is the
        # tracker registry's shard stripe; the global lock is never
        # taken on the heartbeat fast path
        is_delta = bool(status.get("delta"))
        adopted = False
        restarted_info: "_TrackerInfo | None" = None
        shard_lock, shard = self.trackers.shard_of(name)
        with shard_lock:
            info = shard.get(name)
            # host screening first (≈ DisallowedTaskTrackerException)
            # whenever the beat names its host — excluded trackers get
            # "disallowed", never "reinit". A delta that omits the host
            # is screened against the stored status; an UNKNOWN delta
            # can't be screened here and is asked for a full re-send
            # (which gets screened).
            host = status.get("host") if "host" in status \
                or not status.get("delta") \
                else info.status.get("host", "") if info is not None \
                else None
            host_ok = host is None or self._host_allowed(host or "")
            if not host_ok:
                registered = info is not None
            elif info is None and is_delta:
                # no baseline to apply this delta to (master restarted,
                # or the tracker was evicted): ask for a FULL status.
                # Unlike the old blanket reinit, nothing is killed — the
                # full beat that follows is adopted below, in-flight
                # tasks and all.
                return {"response_id": response_id, "actions":
                        [{"type": "resend_full"}]}
            elif info is not None and initial_contact and not is_delta:
                # full INITIAL-contact beat from a tracker this master
                # already knows: the tracker PROCESS restarted under
                # its old name (cold re-registration — crash + rejoin
                # faster than the expiry sweep), or its registration
                # response was lost and this is the re-send. Either
                # way the OLD incarnation's believed-running attempts
                # never ran to completion there, and its replay-cache
                # entry would feed the new process a response meant
                # for the dead one. Swap in a fresh registration here;
                # the stale work is requeued below, outside the shard
                # lock (≈ JobTracker.java's lostTaskTracker on a known
                # tracker's initialContact).
                restarted_info = info
                status.pop("delta", None)
                info = shard[name] = _TrackerInfo(status)
            elif info is not None:
                if not initial_contact:
                    # heartbeat LAG: how far past its scheduled interval
                    # this tracker's beat arrived — judged against the
                    # interval the master last INSTRUCTED it to keep
                    # (adaptive cadence), not the configured floor.
                    # Climbing lag p99 with flat handling latency =
                    # trackers (or the network/handler pool) can't keep
                    # schedule — the first saturation tell. Observed for
                    # replayed beats too.
                    gap = time.monotonic() - info.seen_mono
                    self._hb_lag.observe(max(
                        0.0,
                        gap - (info.interval_s or self._hb_interval_s)))
                # delta beats reconstruct against the stored status
                # (heartbeat.py); full beats replace it wholesale
                status = info.fold_status(status)
            else:
                # full status from an unknown tracker: a true initial
                # contact registers; a NON-initial full beat is a
                # RE-JOIN (this master restarted, or the tracker was
                # expired while partitioned away) — register it and
                # ADOPT its in-flight work in the fold below instead of
                # answering reinit (which would kill healthy tasks)
                adopted = not initial_contact
                status.pop("delta", None)
                info = shard[name] = _TrackerInfo(status)
        if not host_ok:
            # ≈ DisallowedTaskTrackerException: the tracker's host is
            # excluded (or absent from a configured include list) —
            # refuse it; the NodeRunner shuts itself down on this
            if registered:
                self._evict_tracker(name)
            return {"response_id": response_id, "actions":
                    [{"type": "disallowed"}]}

        if restarted_info is not None:
            self._requeue_restarted(name, restarted_info, status)

        # ---- per-tracker serialization: one beat of one tracker at a
        # time. A retry racing its own lost original folds after it and
        # hits the replay cache — it can never double-assign. Trackers
        # never contend here (rank tracker-beat, bottom of the order).
        with info.hb_lock:
            # eviction (expiry/exclusion) may have raced the registry
            # phase above: it pops the entry, then requeues the running
            # set under THIS lock. A beat that loses that race must not
            # fold/assign onto the orphaned info — work assigned there
            # would never be requeued (pre-decomposition the global
            # lock made evict-vs-beat atomic). GIL-atomic dict read;
            # `is` distinguishes a concurrent fresh re-registration.
            # The tracker re-ships a full status and is adopted on its
            # next beat — no reinit, nothing killed.
            if shard.get(name) is not info:
                return {"response_id": response_id, "actions":
                        [{"type": "resend_full"}]}
            return self._heartbeat_fold_and_assign(
                status, info, initial_contact, ask_for_new_task,
                response_id, name, deferred_events, deferred_final,
                hb_trace, t0, is_delta, adopted)

    def _heartbeat_fold_and_assign(self, status: dict, info: _TrackerInfo,
                                   initial_contact: bool,
                                   ask_for_new_task: bool,
                                   response_id: int, name: str,
                                   deferred_events: list,
                                   deferred_final: list,
                                   hb_trace: "dict | None",
                                   t0: float,
                                   is_delta: bool = False,
                                   adopted: bool = False) -> dict:
        """Fold + replay-check + assign for one beat (caller holds the
        tracker's ``hb_lock`` and NOTHING else — every acquisition below
        is rank-ascending: scheduler → global → trackers → job).
        ``adopted`` marks a re-join beat (full status from a tracker
        this master doesn't know): RUNNING attempts are bound to their
        (possibly recovered) TIPs; attempts no live job will claim are
        killed INDIVIDUALLY, never via blanket reinit."""
        t_fold = time.monotonic()
        t_fold_wall = time.time() if hb_trace is not None else 0.0
        # fold the piggybacked tracker metrics into the cluster
        # registry — cumulative state, so replayed heartbeats are
        # idempotent (no seq protocol needed, unlike task statuses);
        # delta beats omit an UNCHANGED piggyback entirely, so idle
        # trackers skip this merge altogether
        self.cluster_agg.merge(name, status.get("metrics"))

        # Fold in task statuses FIRST — even when this turns out to be a
        # replayed heartbeat. The tracker drops terminal statuses after
        # any delivered response, so a completion carried on a retry
        # would otherwise be lost forever. Each status folds under ITS
        # job's lock only; the job table read is lock-free
        # (insert-only dict under the GIL).
        shuffle_addr = status.get("shuffle_addr") or \
            f"{status.get('host', '')}:{status.get('shuffle_port', 0)}"
        statuses = status.get("task_statuses") or []
        if not is_delta:
            # a FULL beat's status list is the tracker's complete
            # running set (delta beats may suppress unchanged RUNNING
            # statuses — they only ever add/remove incrementally below)
            info.running = {sd["attempt_id"] for sd in statuses
                            if sd.get("state") == TaskState.RUNNING}
        # group by job: a beat's statuses overwhelmingly belong to few
        # jobs, and taking each job's lock ONCE per beat (not once per
        # status) halves the lock round trips on the fold fast path
        by_job: "dict[str, list] | None" = None
        if statuses:
            by_job = {}
            for sd in statuses:
                ts = TaskStatus.from_dict(sd)
                aid = str(ts.attempt_id)
                if ts.state == TaskState.RUNNING:
                    info.running.add(aid)
                elif ts.state in TaskState.TERMINAL:
                    info.running.discard(aid)
                by_job.setdefault(str(ts.attempt_id.task.job),
                                  []).append(ts)
        #: attempts a re-join beat carried that no live job adopted —
        #: killed individually in THIS response
        adopt_kills: "list[str]" = []
        attempts_adopted = 0
        for job_id, group in (by_job or {}).items():
            jip = self._resolve_job(job_id)
            if jip is None:
                if adopted:
                    # the job died with the old master (or recovery is
                    # off / failed): these survivors have no home
                    for ts in group:
                        if ts.state not in TaskState.TERMINAL:
                            adopt_kills.append(str(ts.attempt_id))
                            info.running.discard(str(ts.attempt_id))
                continue
            revoke: "list[tuple[str, str]]" = []
            with jip.lock:
                before = jip.state
                for ts in group:
                    aid = str(ts.attempt_id)
                    if adopted and ts.state not in TaskState.TERMINAL:
                        # bind the in-flight attempt to its TIP (the
                        # recovered job's, or this job's after an
                        # eviction re-join) — any non-terminal state
                        # counts as in flight; rejects are zombies,
                        # their task already succeeded elsewhere
                        if jip.adopt_running_attempt(ts):
                            attempts_adopted += 1
                        else:
                            adopt_kills.append(aid)
                            info.running.discard(aid)
                            continue
                    jip.update_task_status(ts, shuffle_addr)
                    if ts.state in TaskState.TERMINAL \
                            and aid not in jip.history_logged:
                        # replayed heartbeats re-deliver terminal
                        # statuses; log each attempt's outcome once
                        jip.history_logged.add(aid)
                        if jip.trace_root is not None:
                            # WHEN the master learnt the attempt ended:
                            # against the end of the tracker's
                            # task:launch (joined on attempt_id) this is
                            # the report lag the heartbeat imposes
                            self.tracer.instant(
                                "task:done", jip.trace_id,
                                parent=jip.trace_sched.pop(aid, None)
                                or jip.trace_root,
                                backend="tpu" if ts.is_map
                                and ts.run_on_tpu else "cpu",
                                attempt_id=aid, state=ts.state,
                                is_map=ts.is_map, tracker=name)
                        if ts.state == TaskState.FAILED \
                                and ts.failure_class == "timeout":
                            # a tracker reaped this attempt for progress
                            # silence (counted once per attempt — this
                            # dedup block — because a lost response
                            # replays the same terminal status); the
                            # FAILED fold below also charges the tracker
                            # a blacklist fault, like any task failure
                            from tpumr.core.counters import JobCounter
                            self._mreg.incr("tasks_reaped_timeout")
                            jip.counters.incr(
                                JobCounter.GROUP,
                                JobCounter.TASKS_REAPED_TIMEOUT)
                        event = {TaskState.SUCCEEDED: "TASK_FINISHED",
                                 TaskState.KILLED: "TASK_KILLED"}.get(
                            ts.state, "TASK_FAILED")
                        deferred_events.append((str(jip.job_id), event,
                                                dict(
                            attempt_id=aid, is_map=ts.is_map,
                            run_on_tpu=ts.run_on_tpu,
                            tpu_device_id=ts.tpu_device_id,
                            runtime=ts.runtime, tracker=name,
                            # where a successful map's output is served
                            # from — restart recovery re-feeds it into
                            # the resubmitted job's completion events.
                            # Streamed-handoff stages record it for
                            # REDUCES too: recovery re-announces the
                            # surviving handoff copies to downstream
                            # pipeline stages
                            shuffle_addr=(shuffle_addr
                                          if (ts.is_map
                                              or jip.stream_handoff)
                                          and ts.state
                                          == TaskState.SUCCEEDED
                                          else ""),
                            # per-attempt counters make the history
                            # file self-sufficient for post-hoc
                            # diagnosis (tools.vaidya) ≈ the reference
                            # history's COUNTERS field
                            counters=ts.counters or {})))
                    if ts.state in (TaskState.FAILED, TaskState.KILLED):
                        # a dead attempt must not keep the commit
                        # grant — otherwise its re-run is denied commit
                        # and output is silently lost (revoked after
                        # the job lock drops: global < job in the rank
                        # order, so the grant table must not be touched
                        # while a job lock is held)
                        revoke.append((str(ts.attempt_id.task), aid))
                    if ts.state == "FAILED":
                        if info.charge_fault(self.blacklist_faults):
                            self._blacklisted += 1
                job_done = (before == JobState.RUNNING
                            and jip.state in JobState.TERMINAL)
            if jip.has_accel_events():
                self._drain_accel_events(jip, str(jip.job_id), name,
                                         deferred_events)
            for task_id, aid in revoke:
                self._revoke_commit(task_id, aid)
            if job_done:
                self._bump_jobs_version()
                deferred_final.append(jip)
        if adopted:
            # the re-join itself is the observable event (acceptance:
            # trackers survive a master restart without reinit)
            self._mreg.incr("trackers_adopted")
            if attempts_adopted:
                self._mreg.incr("attempts_adopted", attempts_adopted)

        # Fetch-failure reports (the "too many fetch failures"
        # protocol): reducers on this tracker found a completed
        # map's output unfetchable while its tracker still
        # heartbeats. Folded BEFORE replay detection for the same
        # reason as task statuses: the tracker only drops reports
        # once a response is delivered, so a retried heartbeat
        # re-carries them (distinct-reducer counting makes the
        # re-delivery harmless).
        for ff in status.get("fetch_failures") or []:
            self._fetch_failure(ff, deferred_events, deferred_final)
        self._hb_phase["fold"].observe(time.monotonic() - t_fold)
        self._phase_span(
            hb_trace, "heartbeat:fold", t_fold_wall,
            statuses=len(statuses))

        # Normal case: the tracker echoes the response id we last sent
        # (last[0] == response_id). A MISMATCH means our response was
        # lost in flight — replay the stored actions rather than
        # assigning duplicate work (JobTracker.java:3336-3375). The
        # cache read is lock-free (GIL-atomic dict get of an immutable
        # tuple; hb_lock excludes same-tracker writers).
        last = self._last_response.get(name)
        if last is not None and last[0] != response_id \
                and not initial_contact:
            # replayed beats observe the phase + lag series uniformly
            # (lag landed in the registry phase above) — distinguishable
            # from first-delivery beats by the phase=replay label
            self._hb_phase["replay"].observe(
                time.monotonic() - (t0 or t_fold))
            self._phase_span(hb_trace, "heartbeat:replay",
                             time.time() if hb_trace is not None else 0.0,
                             response_id=last[0])
            # a tracker whose response was lost still needs the cadence
            # instruction — replays re-carry the CURRENT interval
            nxt = self._instructed_interval_s()
            info.interval_s = nxt
            return {"response_id": last[0], "actions": last[1],
                    "next_interval_ms": int(nxt * 1000 + 0.5)}

        actions: list[dict] = []
        if adopted:
            # individually kill the survivors no job would claim, and
            # teach the tracker any job id rebindings (it re-keys the
            # recovered jobs' served map outputs so NEW-id reducers can
            # fetch outputs produced under the OLD id)
            for aid in adopt_kills:
                actions.append({"type": "kill_task", "attempt_id": aid})
            for old, new in self._recovered.items():
                actions.append({"type": "recover_job",
                                "old": old, "new": new})
        # scheduler observation hook BEFORE the kill scan and
        # independent of free slots: a saturated cluster (no tracker
        # ever asks for work) is exactly when fair-share preemption
        # must still run, and marks made here produce kill actions in
        # THIS response for victims on this tracker. Skipped entirely
        # for schedulers that don't override the hook — no reason to
        # serialize every beat on the scheduler lock for a no-op.
        if self._sched_observes:
            with self.sched_lock:
                try:
                    self.scheduler.before_heartbeat(status)
                except Exception:  # noqa: BLE001 — observation must not
                    pass           # break heartbeats
        # kill actions: tasks of dead jobs + marked attempts
        # (speculative-race losers, preemptions, operator kills) — over
        # the tracker's BELIEVED running set (delta beats may suppress
        # an unchanged RUNNING status, and a speculative loser whose
        # progress report was suppressed must still die). The whole scan
        # is lock-free: job state and the kill-mark set are plain reads
        # (marks are maintained at the points where an attempt becomes
        # a kill candidate — job_in_progress._kill_marked)
        for aid in list(info.running):
            # attempt_<cluster>_<nnnn>_... → job_<cluster>_<nnnn>
            # (sliced, not parsed: this runs per running attempt per
            # beat and TaskAttemptID.parse was profiling-visible).
            # Alias-resolved: adopted pre-restart attempts must still
            # be killable when their (recovered) job dies.
            parts = aid.split("_", 3)
            jip = self._resolve_job(f"job_{parts[1]}_{parts[2]}")
            if jip is None:
                continue
            if jip.state in JobState.TERMINAL or jip.kill_marked(aid):
                actions.append({"type": "kill_task", "attempt_id": aid})

        want_task = (ask_for_new_task and not info.blacklisted
                     and status.get("healthy", True))
        if want_task and not self.sched_lock.acquire(blocking=False):
            # TRY-lock, never queue: with thousands of asking trackers,
            # beats waiting in line for the one-at-a-time scheduler
            # pass were the post-decomposition wall (sched-lock wait
            # p99 tracked heartbeat p99 exactly like the old global
            # lock did). A beat that loses the race simply assigns
            # nothing — the tracker re-asks next interval, and
            # assignment throughput is bounded by pass cost, not by
            # contention. Counted so a hot scheduler is visible.
            self._mreg.incr("assign_skipped_busy")
        elif want_task:
            t_assign = time.monotonic()
            t_assign_wall = time.time() if hb_trace is not None else 0.0
            try:
                assigned = self.scheduler.assign_tasks(status)
            finally:
                self.sched_lock.release()
            for task in assigned:
                if not task.is_map:
                    self._mreg.incr("reduces_launched")
                elif task.run_on_tpu:
                    self._mreg.incr("maps_launched_tpu")
                else:
                    self._mreg.incr("maps_launched_cpu")
                tjip = self.jobs.get(str(task.attempt_id.task.job))
                if tjip is not None and tjip.first_assign_mono is None:
                    # first assignment for this job — the scheduling-
                    # responsiveness half of the per-class SLO (the
                    # assign pass is serialized by sched_lock, so the
                    # None check can't race itself)
                    tjip.first_assign_mono = time.monotonic()
                    if tjip.traffic_class:
                        self._class_observe(
                            "assign", tjip.traffic_class,
                            tjip.first_assign_mono - tjip.submit_mono)
                if tjip is not None and tjip.trace_root is not None:
                    # scheduling decision span; its context rides the
                    # launch action so the tracker/child parent their
                    # spans to it (submit→schedule→launch→run chain)
                    sched = self.tracer.instant(
                        "schedule", tjip.trace_id,
                        parent=tjip.trace_root,
                        backend=("tpu" if task.run_on_tpu else "cpu")
                        if task.is_map else "cpu",
                        attempt_id=str(task.attempt_id), tracker=name)
                    task.trace = {"trace_id": tjip.trace_id,
                                  "span_id": sched.span_id}
                    tjip.trace_sched[str(task.attempt_id)] = sched.span_id
                # the believed-running set learns launches immediately:
                # a launched-but-never-yet-reported attempt must still
                # be requeued if this tracker is lost, and killed if
                # its job dies before the first status arrives
                info.running.add(str(task.attempt_id))
                actions.append({"type": "launch",
                                "job_id": str(task.attempt_id.task.job),
                                "task": task.to_dict()})
                # assignment-time event: gives the history timeline
                # true start stamps + placement (≈ JobHistory
                # Task.START_TIME; rendered by the history server's
                # /jobtasks view, the TaskGraphServlet role). Display-
                # only — the history server derives a start stamp when
                # it's absent — so brownout level 3 sheds the append
                # and its deferred file I/O.
                if self.brownout is not None \
                        and self.brownout.sheds("history"):
                    self.brownout.events_shed += 1
                else:
                    deferred_events.append((
                        str(task.attempt_id.task.job), "TASK_STARTED",
                        dict(attempt_id=str(task.attempt_id),
                             is_map=task.is_map,
                             run_on_tpu=task.run_on_tpu,
                             tpu_device_id=task.tpu_device_id,
                             tracker=name)))
            # the scheduler pass plus per-assignment bookkeeping —
            # observed only when the pass actually ran, so the
            # distribution isn't drowned by no-ask heartbeats
            self._hb_phase["assign"].observe(
                time.monotonic() - t_assign)
            self._phase_span(hb_trace, "heartbeat:assign",
                             t_assign_wall)

        response_id += 1
        self._last_response[name] = (response_id, actions)
        # adaptive cadence: every response tells the tracker when to
        # come back (TaskTracker honors HeartbeatResponse's interval in
        # the reference; ours is the same contract)
        nxt = self._instructed_interval_s()
        info.interval_s = nxt
        return {"response_id": response_id, "actions": actions,
                "next_interval_ms": int(nxt * 1000 + 0.5)}

    def _drain_accel_events(self, jip: JobInProgress, job_id: str,
                            tracker: str, deferred_events: list) -> None:
        """Demotion/quarantine decisions made inside update_task_status:
        meter them, history-log them, and drop trace instants on the job
        timeline (takes only the job lock; history I/O is deferred)."""
        for ev in jip.drain_accel_events():
            kind = ev.pop("kind")
            ev["tracker"] = tracker
            if kind == "tip_demoted":
                self._mreg.incr("tpu_demotions")
                deferred_events.append((job_id, "TIP_TPU_DEMOTED", ev))
                instant = "tpu:demote_tip"
            else:
                self._mreg.incr("jobs_tpu_quarantined")
                deferred_events.append((job_id, "JOB_TPU_QUARANTINED", ev))
                instant = "tpu:job_quarantine"
            if jip.trace_root is not None:
                self.tracer.instant(instant, jip.trace_id,
                                    parent=jip.trace_root, **ev)

    def _fetch_failure(self, ff: dict, deferred_events: list,
                       deferred_final: list) -> None:
        """Apply one reducer fetch-failure report (job-lock work only —
        the global lock is touched just to revoke the burned attempt's
        commit grant). The job counts distinct reporting reducers; once
        it withdraws the map output the master-side effects land here:
        the burned attempt's commit grant is revoked (the re-run must be
        able to commit), a fault is charged to the tracker that SERVED
        the lost output — a lame-but-heartbeating shuffle server walks
        toward blacklisting exactly like a task-failing tracker — and
        the re-execution is metered + history-logged."""
        map_attempt = str(ff.get("map_attempt", ""))
        reduce_attempt = str(ff.get("reduce_attempt", ""))
        try:
            task_id = TaskAttemptID.parse(map_attempt).task
        except (ValueError, IndexError):
            return
        jip = self._resolve_job(str(task_id.job))
        if jip is None:
            return
        before = jip.state
        res = jip.fetch_failure_notification(map_attempt, reduce_attempt)
        if res is None:
            return   # stale (already withdrawn) — not a counted report
        self._mreg.incr("fetch_failures_reported")
        if jip.trace_root is not None:
            # per-map fetch-failure recovery on the job timeline: report
            # marks are sub-threshold; a withdrawal is the re-execution
            # decision itself
            self.tracer.instant(
                "fetch_failure:withdraw" if res["withdrawn"]
                else "fetch_failure:report",
                jip.trace_id, parent=jip.trace_root,
                map_attempt=map_attempt, reduce_attempt=reduce_attempt,
                reports=res.get("reports", 0),
                reexecuted=res["reexecuted"])
        if res["withdrawn"]:
            self._revoke_commit(str(task_id), map_attempt)
            if res["reexecuted"]:
                self._mreg.incr("maps_reexecuted_fetch_failure")
            addr = res.get("shuffle_addr", "")
            info = self._tracker_by_shuffle_addr(addr)
            if info is not None and \
                    info.charge_fault(self.blacklist_faults):
                self._blacklisted += 1
            deferred_events.append((str(task_id.job), "MAP_OUTPUT_LOST",
                                    dict(attempt_id=map_attempt,
                                         shuffle_addr=addr,
                                         reports=res.get("reports", 0),
                                         reexecuted=res["reexecuted"])))
        if before == JobState.RUNNING and jip.state in JobState.TERMINAL:
            self._bump_jobs_version()
            deferred_final.append(jip)

    def _tracker_by_shuffle_addr(self, addr: str) -> "_TrackerInfo | None":
        """The registered tracker serving map outputs at ``addr``
        (registry-striped scan)."""
        if not addr:
            return None
        for info in self.trackers.values():
            st = info.status
            a = st.get("shuffle_addr") or \
                f"{st.get('host', '')}:{st.get('shuffle_port', 0)}"
            if a == addr:
                return info
        return None

    # ------------------------------------------------------------ expiry

    def _evict_tracker(self, name: str) -> None:
        """Remove one tracker and re-queue everything it owned (running
        attempts AND completed maps whose outputs lived there) —
        ≈ JobTracker.lostTaskTracker. Takes the registry shard lock for
        the pop only; the requeue work runs under per-job locks (a slow
        eviction must not stall other trackers' heartbeats)."""
        info = self.trackers.pop(name)
        if info is None:
            return
        if info.blacklisted:
            self._blacklisted = max(0, self._blacklisted - 1)
        self._last_response.pop(name, None)
        self.cluster_agg.forget(name)
        # the BELIEVED running set, not the last beat's status list: a
        # delta beat may have suppressed (rate-limited) an unchanged
        # RUNNING status, and a launched-but-never-reported attempt
        # only exists here. Snapshot under the tracker's hb_lock: an
        # in-flight beat that won the lock first finishes its
        # fold/assign and its launches land in the snapshot; one that
        # loses sees the popped registry entry and aborts with reinit
        # (the membership re-check in _heartbeat) — either way nothing
        # can be assigned to this tracker after the snapshot.
        with info.hb_lock:
            attempts = list(info.running) or \
                [sd["attempt_id"] for sd in
                 info.status.get("task_statuses", [])]
        addr = (f"{info.status.get('host', '')}:"
                f"{info.status.get('shuffle_port', 0)}")
        self._requeue_tracker_work(attempts, addr)

    def _requeue_restarted(self, name: str, old: "_TrackerInfo",
                           status: dict) -> None:
        """Cold re-registration cleanup (caller just swapped the
        registry entry; holds no locks): requeue what the OLD
        incarnation owned — minus any attempt the new status still
        carries, per the wire contract, though a cold process never
        carries one — and drop its replay-cache entry so a stale
        response id can never replay the dead process's actions into
        the new one."""
        self._mreg.incr("trackers_restarted")
        self._last_response.pop(name, None)
        carried = {sd.get("attempt_id")
                   for sd in status.get("task_statuses", [])}
        with old.hb_lock:
            attempts = [a for a in old.running if a not in carried]
        addr = (f"{old.status.get('host', '')}:"
                f"{old.status.get('shuffle_port', 0)}")
        self._requeue_tracker_work(attempts, addr)

    def _requeue_tracker_work(self, attempts: "list[str]",
                              addr: str) -> None:
        """Requeue a dead tracker incarnation's work: running attempts
        back to pending, completed map outputs it served withdrawn,
        streamed-handoff announcements tombstoned, commit grants
        revoked. Per-job locks only — shared by eviction and cold
        re-registration."""
        for jip in list(self.jobs.values()):
            with jip.lock:
                # OBSOLETE entries are tombstones of already-withdrawn
                # outputs — only live events name outputs this tracker
                # still owed the shuffle
                owned = [e["attempt_id"]
                         for e in jip.completion_events
                         if e["shuffle_addr"] == addr
                         and e.get("status") != "OBSOLETE"]
            withdrawn = jip.requeue_lost_attempts(attempts + owned)
            for aid in withdrawn:
                # journal the withdrawal: restart recovery replays the
                # history file and must not adopt outputs this master
                # already declared gone with their tracker
                self.history.task_event(
                    str(jip.job_id), "MAP_OUTPUT_LOST", attempt_id=aid,
                    shuffle_addr=addr, reason="tracker_lost")
            # streamed-handoff copies this tracker served die with it:
            # tombstone their announcements (downstream readers evict
            # the location and fall back to the committed part files —
            # the PR-1 withdrawal dialect, one feed over)
            lost_handoff = jip.withdraw_handoff_at(addr)
            if lost_handoff:
                self._mreg.incr("handoff_outputs_lost", lost_handoff)
                self.history.task_event(
                    str(jip.job_id), "HANDOFF_OUTPUT_LOST",
                    shuffle_addr=addr, partitions=lost_handoff,
                    reason="tracker_lost")
        for aid in attempts:
            self._revoke_commit(str(TaskAttemptID.parse(aid).task), aid)

    def _expire_loop(self) -> None:
        while not self._stop.wait(min(1.0, self.expiry_s / 3)):
            now = time.monotonic()
            self.token_store.purge_expired()
            lost = [n for n, t in self.trackers.items()
                    if now - t.seen_mono > self.expiry_s]
            for name in lost:
                self._evict_tracker(name)

    def _pipeline_loop(self) -> None:
        """THE advancement thread: woken by heartbeat folds (the
        deferred phase sets the event when a pipeline may have moved)
        with a 500ms poll backstop for quiet clusters — e.g.
        resubmitting stages right after a restart while the fleet
        re-joins. Isolated here so blocking stage-submission I/O can
        never wedge eviction or heartbeats."""
        while not self._stop.is_set():
            self._pipe_wake.wait(0.5)
            self._pipe_wake.clear()
            if self._stop.is_set():
                return
            if self.pipelines:
                try:
                    self._advance_pipelines()
                except Exception:  # noqa: BLE001
                    self._mreg.incr("pipeline_advance_errors")
