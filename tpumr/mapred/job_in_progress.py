"""Per-job task bookkeeping + per-backend runtime profiling.

≈ ``org.apache.hadoop.mapred.JobInProgress`` (reference: src/mapred/org/
apache/hadoop/mapred/JobInProgress.java, 3713 LoC). The pieces that matter
to the hybrid scheduler are carried exactly:

- ``finishedCPUMapTasks`` / ``finishedGPUMapTasks`` counters
  (JobInProgress.java:115-116, incremented :2779-2784) →
  :attr:`finished_cpu_maps` / :attr:`finished_tpu_maps`;
- ``getCPUMapTaskMeanTime()`` / ``getGPUMapTaskMeanTime()``
  (:527-565) → :meth:`cpu_map_mean_time` / :meth:`tpu_map_mean_time` —
  kept as RUNNING sums + EWMA instead of the reference's per-heartbeat
  O(tasks) recomputation over all TaskReports (the control-plane hot-loop
  cost called out in SURVEY.md §3.2; semantics preserved, cost O(1));
- locality caches (node → pending maps) feeding
  ``obtainNewNodeLocalMapTask`` / ``obtainNewNonLocalMapTask``;
- the reference decrements BOTH backend counters on a failed map
  (JobInProgress.java:3156-3159) — a quirk, not intent; here a failure
  decrements only the backend the attempt ran on (divergence documented).
"""

from __future__ import annotations

import re
import threading
import time
from dataclasses import dataclass, field, replace
from typing import Any

from tpumr.core.counters import Counters
from tpumr.mapred.ids import JobID, TaskAttemptID, TaskID
from tpumr.mapred.task import (Task, TaskPhase, TaskReport, TaskState,
                               TaskStatus)
from tpumr.core import confkeys
from tpumr.mapred.map_cost import (TWIN_TURNS, CarriedCost, CpuCost,
                                   MapCostEstimate, TurnCost,
                                   map_cost_key)
from tpumr.metrics.locks import RANK_JOB, InstrumentedRLock


class CompletionEventFeed:
    """Append-only completion-event feed with LOCK-FREE reads.

    Writers — the master's status fold, under the job lock — only ever
    ``append()`` or flip an existing event's ``status`` value in place
    (the OBSOLETE withdrawal mark); events are never removed or
    reordered, so an index, once served, names the same event forever.
    Readers slice by cursor WITHOUT any lock: under CPython's GIL a
    list slice concurrent with appends returns a consistent prefix, and
    an in-place ``status`` overwrite is a single atomic value store on
    a dict whose shape never changes. A reader racing a withdrawal sees
    either SUCCEEDED (and later the appended tombstone at a higher
    index) or OBSOLETE directly — both orderings the PR-1 protocol
    already handles. This is what lets ``get_map_completion_events``
    serve reducer polls while the fold appends, with neither touching
    the job lock (PR 8's lock decomposition).
    """

    __slots__ = ("_events",)

    def __init__(self) -> None:
        self._events: "list[dict]" = []

    def append(self, event: dict) -> None:
        self._events.append(event)

    def read(self, from_index: int, max_events: int) -> "tuple[list, int]":
        """One cursor-based incremental poll: up to ``max_events``
        events from ``from_index``, plus the backlog REMAINING after
        this batch (0 when the poll fully caught up — the lag series
        must measure what a poller couldn't drain, not the volume it
        drained fine, or it grows with job width forever)."""
        total = len(self._events)
        frm = max(0, int(from_index))
        if frm > total:
            # a cursor minted against a PREVIOUS incarnation of this
            # job's feed (master restart → the resubmitted job re-feeds
            # recovered events from 0): an append-only feed can never be
            # shorter than a cursor it issued, so serve the WHOLE feed —
            # client folds are idempotent, and a stale cursor must never
            # silently skip recovered or fresh events
            frm = 0
        events = self._events[frm:frm + max(0, int(max_events))]
        return events, max(0, total - frm - len(events))

    def __len__(self) -> int:
        return len(self._events)

    def __getitem__(self, i: Any) -> Any:
        return self._events[i]

    def __iter__(self) -> Any:
        return iter(self._events)


class JobState:
    PREP = "PREP"
    RUNNING = "RUNNING"
    SUCCEEDED = "SUCCEEDED"
    FAILED = "FAILED"
    KILLED = "KILLED"
    TERMINAL = {SUCCEEDED, FAILED, KILLED}


#: ≈ mapred/JobPriority.java — ordinal order is scheduling order
JOB_PRIORITIES = ("VERY_HIGH", "HIGH", "NORMAL", "LOW", "VERY_LOW")


def normalize_priority(value: Any) -> str:
    """Validate/canonicalize a priority name (case-insensitive; the
    reference's JobPriority.valueOf raises on unknowns — so do we)."""
    p = str(value).upper()
    if p not in JOB_PRIORITIES:
        raise ValueError(f"unknown job priority {value!r}; one of "
                         f"{', '.join(JOB_PRIORITIES)}")
    return p


def priority_rank(priority: str) -> int:
    """Sort key: lower rank schedules first."""
    return JOB_PRIORITIES.index(priority)


@dataclass
class TaskInProgress:
    """≈ mapred/TaskInProgress.java (condensed): one logical task, its
    attempts and state."""

    task_id: TaskID
    partition: int
    split: dict | None = None
    state: str = "pending"            # pending | running | succeeded | failed
    attempts: dict[str, TaskStatus] = field(default_factory=dict)
    next_attempt: int = 0
    failures: int = 0
    #: device/compile-classed failures of TPU attempts — the TPU→CPU
    #: demotion ledger (counted separately from ``failures`` because a
    #: demoted TIP keeps its normal attempt budget for the CPU re-runs)
    tpu_failures: int = 0
    successful_attempt: str = ""
    report: TaskReport = None  # type: ignore[assignment]
    # --- scheduling feedback (master-local, MONOTONIC domain — never
    # --- mixed with the wall stamps the client-visible report carries) ---
    #: monotonic stamp of the current incarnation's first dispatch; 0.0
    #: until assigned (and again after a requeue re-pends the TIP)
    dispatch_mono: float = 0.0
    #: EWMA of progress units per second, folded from heartbeat statuses
    rate_ewma: float = 0.0
    #: best progress seen across the incarnation's attempts, and when
    last_progress: float = 0.0
    last_progress_mono: float = 0.0

    def __post_init__(self) -> None:
        if self.report is None:
            self.report = TaskReport(self.task_id)

    def new_attempt(self) -> TaskAttemptID:
        a = TaskAttemptID(self.task_id, self.next_attempt)
        self.next_attempt += 1
        return a

    def reset_feedback(self) -> None:
        """Requeue: the next dispatch starts a fresh incarnation whose
        age and progress rate must not inherit the dead attempt's."""
        self.dispatch_mono = 0.0
        self.rate_ewma = 0.0
        self.last_progress = 0.0
        self.last_progress_mono = 0.0

    @property
    def is_map(self) -> bool:
        return self.task_id.is_map

    def running_attempts(self) -> list[TaskStatus]:
        return [s for s in self.attempts.values()
                if s.state == TaskState.RUNNING]


class JobInProgress:
    def __init__(self, job_id: JobID, conf_dict: dict, splits: list[dict],
                 tracker_addr_of: Any = None) -> None:
        self.job_id = job_id
        self.conf = dict(conf_dict)
        self.num_reduces = confkeys.get_int(self.conf,
                                            "mapred.reduce.tasks")
        self.state = JobState.RUNNING
        self.start_time = time.time()
        self.finish_time = 0.0
        self.counters = Counters()
        # rank-ordered (metrics/locks.py): the job lock is the BOTTOM of
        # the master's lock order — the status fold and the scheduler's
        # obtain calls take it while holding nothing above it, and
        # nothing acquired under it may reach back up (scheduler → job,
        # never the reverse; asserted in debug mode)
        self.lock = InstrumentedRLock(name=f"job-{job_id}", rank=RANK_JOB)
        self.max_map_attempts = confkeys.get_int(
            self.conf, "mapred.map.max.attempts")
        self.max_reduce_attempts = confkeys.get_int(
            self.conf, "mapred.reduce.max.attempts")
        #: distinct reducers that must report a map attempt's output
        #: unfetchable before the master re-executes the map
        #: (≈ JobInProgress.fetchFailureNotification's
        #: MAX_FETCH_FAILURES_NOTIFICATIONS)
        self.max_fetch_failures_per_map = confkeys.get_int(
            self.conf, "mapred.max.fetch.failures.per.map")
        self.slowstart = confkeys.get_float(
            self.conf, "mapred.reduce.slowstart.completed.maps")
        self.speculative = confkeys.get_boolean(
            self.conf, "mapred.speculative.execution")
        #: lazily memoized has_kernel() answer (kernel conf is submit-fixed)
        self._has_kernel: "bool | None" = None
        # ≈ mapred.reduce.tasks.speculative.execution: reduces speculate
        # too (JobInProgress.java:257,739,2320 hasSpeculativeReduces /
        # findSpeculativeTask) — a straggling reduce ends every job, so
        # it needs the same mitigation maps get. Defaults to the global
        # switch; the dedicated key turns one side off independently.
        spec_reduces = confkeys.get_boolean(
            self.conf, "mapred.reduce.speculative.execution")
        self.speculative_reduces = self.speculative \
            if spec_reduces is None else spec_reduces
        # ≈ JobPriority (mapred/JobPriority.java) — FIFO scheduling
        # sorts by (priority, start time); mutable at runtime via
        # JobMaster.set_job_priority (hadoop job -set-priority)
        self.priority = normalize_priority(
            confkeys.get(self.conf, "mapred.job.priority"))
        # scenario lab: a job tagged with a traffic class gets per-class
        # submit→first-assignment / submit→complete latency series on
        # the master, which the flight recorder windows into per-class
        # SLO verdicts. Sanitized: the tag becomes a metric label.
        cls = str(confkeys.get(self.conf, "tpumr.scenario.class") or "")
        self.traffic_class = re.sub(r"[^a-z0-9_]", "_",
                                    cls.lower())[:24]
        self.submit_mono = time.monotonic()
        self.first_assign_mono: "float | None" = None
        #: master brownout: True pauses speculative scans for this job
        #: (stamped at submit while shedding, flipped on running jobs
        #: at level transitions; speculation is pure opportunism and
        #: the first deferrable scheduler cost)
        self.speculation_hold = False
        self.error = ""

        self.maps = [TaskInProgress(TaskID(job_id, True, i), i, split=s)
                     for i, s in enumerate(splits)]
        self.reduces = [TaskInProgress(TaskID(job_id, False, r), r)
                        for r in range(self.num_reduces)]
        # locality caches ≈ nonRunningMapCache: host -> splits and
        # rack -> splits (the rack tier of obtainNewNodeOrRackLocalMapTask)
        from tpumr.net import DEFAULT_RACK, resolver_from_conf
        self._rack_resolver = resolver_from_conf(self.conf)
        self._default_rack = DEFAULT_RACK
        self.host_cache: dict[str, set[int]] = {}
        self.rack_cache: dict[str, set[int]] = {}
        for i, s in enumerate(splits):
            for h in (s or {}).get("locations", []) or []:
                self.host_cache.setdefault(h, set()).add(i)
                rack = self._rack_resolver(h)
                if rack != DEFAULT_RACK:
                    self.rack_cache.setdefault(rack, set()).add(i)
        self._pending_maps = set(range(len(self.maps)))
        self._pending_reduces = set(range(self.num_reduces))
        self.finished_maps = 0
        self.finished_reduces = 0
        #: attempts whose terminal outcome is already in the history log
        #: (heartbeat replays re-deliver terminal statuses)
        self.history_logged: set[str] = set()
        self.speculative_map_tasks = 0
        self.speculative_reduce_tasks = 0
        # --- scheduling feedback: targeted (LATE-style) speculation ---
        #: False = legacy blanket twins (the reference's age-only rule)
        self.speculative_targeted = confkeys.get_boolean(
            self.conf, "tpumr.speculative.targeted")
        #: concurrent speculative attempts allowed in flight per job
        self.speculative_cap = max(1, confkeys.get_int(
            self.conf, "tpumr.speculative.cap"))
        #: critical-path membership: a TIP whose remaining estimate is
        #: within this fraction of the job's longest remaining estimate
        self._spec_cp_fraction = confkeys.get_float(
            self.conf, "tpumr.speculative.critical.fraction")
        #: per-TIP progress-rate EWMA weight
        self._rate_alpha = confkeys.get_float(
            self.conf, "tpumr.speculative.rate.ewma")
        #: outcome counters: launched at obtain time; won/wasted settle
        #: when the speculative attempt reaches a terminal state
        self.speculative_launched = 0
        self.speculative_won = 0
        self.speculative_wasted = 0
        #: speculative attempts not yet terminal (the in-flight gauge);
        #: mutated only under ``lock``, len() read lock-free by gauges
        self._spec_attempts: set[str] = set()
        #: memoized devcache_tags() answer (side-input conf is
        #: submit-fixed; the affinity scheduler asks per TPU pass)
        self._devcache_tags: "tuple[str, ...] | None" = None
        #: running sum of successful reduce runtimes — the speculation
        #: threshold's mean (reduces have no per-backend split: they
        #: always run on CPU slots)
        self._reduce_time_sum = 0.0
        #: set by the master once job-level output commit/abort completed —
        #: clients must not observe a terminal state before the output is
        #: actually promoted (finalization runs outside the heartbeat lock)
        self.finalized = threading.Event()
        #: atomic claim (under ``lock``) that finalization is running —
        #: kill_job racing a heartbeat-deferred finalize must not run
        #: commit/abort twice or duplicate JOB_FINISHED history events
        self.finalize_started = False
        #: attempts a scheduler marked for preemption (kill-not-fail);
        #: cleared when the attempt's terminal status arrives
        self._preempt_requested: set[str] = set()
        #: RUNNING attempts with a kill pending (speculative-race
        #: losers, preemptions, operator kills) — maintained at the
        #: points where an attempt BECOMES a kill candidate so the
        #: heartbeat kill scan is a lock-free set probe instead of a
        #: per-attempt job-lock round trip re-deriving it every beat
        self._kill_marked: set[str] = set()
        #: attempts whose operator kill must count as FAILED (-fail-task)
        self._fail_requested: set[str] = set()
        # --- per-backend profiling (running sums, O(1) per update) ---
        self.finished_cpu_maps = 0
        self.finished_tpu_maps = 0
        self._cpu_time_sum = 0.0
        self._tpu_time_sum = 0.0
        self._ewma_alpha = confkeys.get_float(self.conf,
                                              "tpumr.profile.ewma")
        self._cpu_ewma = 0.0
        self._tpu_ewma = 0.0
        #: what a CPU map and a TPU slot's turn cost in this job, from
        #: running and killed attempts and the job before as well as
        #: from finished maps (map_cost.py): what the scheduler's CPU
        #: share and the twin on an idle chip decide by
        self.map_cost = MapCostEstimate()
        #: what the master carries the estimate under from job to job;
        #: None for a job with no device kernel
        self.map_cost_key = map_cost_key(self.conf, splits)
        # completion events for reduce fetchers (≈ TaskCompletionEvents).
        # APPEND-ONLY: consumers read incrementally by cursor, so a
        # withdrawn map output is marked status=OBSOLETE in place AND
        # re-announced as a tombstone event — never removed (removal
        # would shift indices under every live cursor). The feed object
        # makes reducer polls lock-free against the appending fold.
        self.completion_events = CompletionEventFeed()
        #: map attempt -> distinct reduce attempts reporting its output
        #: unfetchable (the "too many fetch failures" ledger)
        self._fetch_failures: dict[str, set[str]] = {}
        # --- pipeline streamed handoff (DAG engine) ---
        #: does this stage tee reduce output into IFile framing served
        #: over the shuffle wire for a downstream stage? Gated off for
        #: run shapes whose trackers never REGISTER a tee (process
        #: isolation drops the child's payload; device-shuffle reduces
        #: bypass run_reduce_task) — announcing addresses nothing
        #: serves would have every downstream map burn doomed fetch
        #: RPCs until the DFS fallback appears
        from tpumr.mapred.device_shuffle import DEVICE_SHUFFLE_KEY
        self.stream_handoff = (
            confkeys.get_boolean(self.conf,
                                 "tpumr.pipeline.stream.handoff")
            and str(self.conf.get("tpumr.task.isolation")
                    or "thread") != "process"
            and not bool(self.conf.get(DEVICE_SHUFFLE_KEY)))
        #: reduce-commit announcements for downstream stages — the SAME
        #: append-only feed class (and OBSOLETE-withdrawal dialect) the
        #: map completion events use, with ``map_index`` carrying the
        #: reduce PARTITION; served lock-free by
        #: get_handoff_completion_events
        self.handoff_events = CompletionEventFeed()
        #: scheduler FIFO anchor: normally the submit time, but stage
        #: jobs of a pipeline inherit the PIPELINE's submit time so a
        #: late stage never queues behind independent jobs submitted
        #: mid-pipeline (the master stamps it at submit)
        self.sched_anchor = self.start_time
        # --- accelerator fault tolerance (tentpole PR 4) ---
        #: device/compile-classed failures a TIP may take before it is
        #: pinned CPU-only (≈ "how many TPU retries does a sick kernel
        #: placement get"); ≥1 — 0 would demote before any failure
        self.tpu_attempt_retries = max(1, int(self.conf.get(
            "tpumr.tpu.attempt.retries", 1)))
        #: distinct device-failing TIPs before the whole JOB's TPU pass
        #: is quarantined off
        self.tpu_quarantine_tips = max(1, int(self.conf.get(
            "tpumr.tpu.job.quarantine.tips", 3)))
        #: job-level TPU quarantine flag: the scheduler's TPU pass and
        #: the optional-scheduling starvation gate both honor it (the
        #: gate MUST, or a quarantined job deadlocks with zero CPU
        #: budget and an ineligible TPU pass)
        self.tpu_disabled = False
        #: map partitions pinned CPU-only after repeated device-classed
        #: failures — the TPU obtain path skips them
        self._cpu_only_maps: set[int] = set()
        #: distinct TIPs that ever took a device-classed TPU failure
        #: (the job-quarantine threshold counts TIPs, not attempts)
        self._tpu_failed_tips: set[int] = set()
        #: demotion/quarantine decisions made inside update_task_status,
        #: drained by the master's heartbeat for metrics + history +
        #: trace instants (the JIP has no tracer/history of its own)
        self._accel_events: list[dict] = []
        #: per-assignment backend placement: (seconds-since-submit, 'T'|'c')
        #: appended at every map assignment — the raw series behind the
        #: hybrid scheduler's convergence curve, so ANY run's status or
        #: history doubles as the convergence artifact (SURVEY §5: backend
        #: placement is a first-class metric). Bounded; overflow counted.
        self.placement_series: list = []
        self.placement_dropped = 0
        #: raw successful-attempt runtimes, kept verbatim for the
        #: per-job stats rollup (metrics-<jobid>.json): the profile
        #: sums above are means the SCHEDULER needs (and unwind on
        #: quarantine); the rollup wants exact percentiles over what
        #: actually ran, quarantined or not. Bounded; overflow counted.
        self.map_runtimes: "list[tuple[float, bool]]" = []  # (s, on_tpu)
        self.reduce_runtimes: "list[float]" = []
        self.runtimes_dropped = 0
        #: distributed tracing (core/tracing.py): the job's trace id and
        #: the open root span, set by the master at submit for traced
        #: jobs only ("" / None keeps every trace check a cheap miss)
        self.trace_id: str = str(self.conf.get("tpumr.trace.id", "") or "")
        self.trace_root: Any = None
        #: attempt id -> span id of its ``schedule`` instant, for the
        #: attempts of a traced job that have not ended yet: what the
        #: master's ``task:done`` is parented to (popped there)
        self.trace_sched: dict[str, str] = {}
        # --- master restart survival (attempt-level recovery) ---
        #: the interrupted job this one was recovered from (None for a
        #: normal submission): attempt ids carrying the OLD job id are
        #: accepted as this job's own — recovered completion events,
        #: adopted in-flight attempts, and their fetch-failure reports
        #: all name old-id attempts
        self.recovered_from: "str | None" = None
        #: monotonic deadline before which the scheduler must NOT hand
        #: out this job's tasks (obtain_* return None): the recovery
        #: grace window. A restarted master sees pending TIPs whose
        #: attempts are still RUNNING on trackers that have not
        #: re-joined yet — assigning them would duplicate in-flight
        #: work (≈ the reference RecoveryManager waiting for trackers
        #: to report back before scheduling resumes)
        self.schedule_hold_until = 0.0

    # ------------------------------------------------------------ queries

    @property
    def num_maps(self) -> int:
        return len(self.maps)

    def pending_map_count(self) -> int:
        return len(self._pending_maps)

    def pending_reduce_count(self) -> int:
        return len(self._pending_reduces)

    def running_map_count(self) -> int:
        """Maps assigned and not yet finished (scheduler's usage signal)."""
        return max(0, len(self.maps) - self.finished_maps
                   - self.pending_map_count())

    def running_reduce_count(self) -> int:
        return max(0, len(self.reduces) - self.finished_reduces
                   - self.pending_reduce_count())

    def has_kernel(self) -> bool:
        """≈ the hadoop.pipes.gpu.executable gate
        (JobQueueTaskScheduler.java:342-347): only jobs with a device kernel
        OR a TPU pipes executable are eligible for TPU slots. Memoized —
        the kernel conf is fixed at submit, and the scheduler consults
        this per job per pass on the heartbeat fast path."""
        v = self._has_kernel
        if v is None:
            v = self._has_kernel = bool(
                self.conf.get("tpumr.map.kernel")
                or self.conf.get("tpumr.pipes.tpu.executable"))
        return v

    def tpu_eligible(self) -> bool:
        """May the scheduler's TPU pass offer this job work? The kernel
        gate plus the job-level accelerator quarantine."""
        return self.has_kernel() and not self.tpu_disabled

    def cpu_pinned_pending_count(self) -> int:
        """Pending maps that can ONLY run on CPU (demoted TIPs) — the
        optional-scheduling starvation gate must not zero the CPU budget
        while any of these exist, or they can never be assigned."""
        with self.lock:
            return len(self._pending_maps & self._cpu_only_maps)

    def has_accel_events(self) -> bool:
        """Lock-free emptiness hint so the heartbeat fold can skip the
        drain's lock round trip on the (overwhelmingly common) beat
        with no demotion/quarantine decisions. May be stale by one
        beat; the next fold drains whatever it missed."""
        return bool(self._accel_events)

    def drain_accel_events(self) -> "list[dict]":
        """Demotion/quarantine decisions since the last drain (consumed
        by the master heartbeat for metrics, history, and traces)."""
        with self.lock:
            out, self._accel_events = self._accel_events, []
            return out

    def cpu_map_mean_time(self) -> float:
        """Mean CPU map runtime (0.0 when no data — matching the reference's
        'returns 0 until first completion' behavior that makes the scheduler
        fall back to unconditional assignment)."""
        if self._ewma_alpha and self._cpu_ewma:
            return self._cpu_ewma
        return self._cpu_time_sum / self.finished_cpu_maps \
            if self.finished_cpu_maps else 0.0

    def tpu_map_mean_time(self) -> float:
        if self._ewma_alpha and self._tpu_ewma:
            return self._tpu_ewma
        return self._tpu_time_sum / self.finished_tpu_maps \
            if self.finished_tpu_maps else 0.0

    def acceleration_factor(self) -> float:
        """What a CPU map costs over what a turn of a TPU slot costs, by
        the job's estimate (≈ cpuMean / tpuMean,
        JobQueueTaskScheduler.java:175-178, which knows nothing until a
        map of each kind has finished); 1.0 while either is unknown —
        and again after a job-level TPU quarantine (the unwound sums
        must not resurrect via in-flight TPU completions trickling in
        post-quarantine)."""
        if self.tpu_disabled:
            return 1.0
        cpu, t_tpu = self.map_costs()
        if cpu.seconds > 0 and t_tpu.alone > 0:
            return cpu.seconds / t_tpu.alone
        return 1.0

    def map_costs(self) -> "tuple[CpuCost, TurnCost]":
        """The estimate now: what a CPU map costs (and where the number
        is from) and what one turn of a TPU slot costs, alone and beside
        a CPU map of the job, in seconds; 0.0 where nothing says."""
        with self.lock:
            return (self.map_cost.t_cpu(time.monotonic(),
                                        self.finished_cpu_maps,
                                        self.cpu_map_mean_time()),
                    self.map_cost.t_tpu(self.tpu_map_mean_time()))

    def tpu_serving(self) -> bool:
        """Is a TPU attempt of this job running: is a chip serving it,
        and not another job ahead of it in the queue."""
        return self.map_cost.tpu_serving()

    def adopt_carried_cost(self, carried: "CarriedCost | None") -> None:
        """Start from what the job before with this job's
        ``map_cost_key`` ended with (the master, at submit)."""
        with self.lock:
            self.map_cost.carried = carried

    def cost_to_carry(self) -> "CarriedCost | None":
        with self.lock:
            if self.tpu_disabled:
                return None
            return self.map_cost.to_carry(
                time.monotonic(), self.finished_cpu_maps,
                self.cpu_map_mean_time(), self.tpu_map_mean_time())

    def note_cpu_maps_withheld(self) -> None:
        """One ask at which a free CPU slot got no map of this job
        because the estimate says the chip ends it sooner."""
        from tpumr.core.counters import JobCounter
        with self.lock:
            self.counters.incr(JobCounter.GROUP,
                               JobCounter.CPU_MAPS_WITHHELD)

    def map_progress(self) -> float:
        if not self.maps:
            return 1.0
        running = sum(max((s.progress for s in t.running_attempts()),
                          default=0.0)
                      for t in self.maps if t.state == "running")
        return min(1.0, (self.finished_maps + running) / len(self.maps))

    def reduce_progress(self) -> float:
        if not self.reduces:
            return 1.0
        return self.finished_reduces / len(self.reduces)

    # ------------------------------------------- scheduling feedback model

    def devcache_tags(self) -> "tuple[str, ...]":
        """Side-input devcache tags this job's device tasks stage
        (``tpumr.devcache.required.tags``, or derived from the kernels'
        known side-input confs) — the affinity scheduler matches these
        against tracker-piggybacked inventories. The derivation is
        string-level coupling with ops/kmeans.device_centroids and
        ops/matmul: the tag IS ``family:path``, so the conf that names
        the side input names the tag."""
        v = self._devcache_tags
        if v is None:
            explicit = str(confkeys.get(
                self.conf, "tpumr.devcache.required.tags") or "")
            tags = [t.strip() for t in explicit.split(",") if t.strip()]
            if not tags:
                c = self.conf.get("tpumr.kmeans.centroids")
                if c:
                    tags.append(f"kmeans-centroids:{c}")
                b = self.conf.get("tpumr.matmul.b")
                if b:
                    tags.append(f"matmul-b:{b}")
            v = self._devcache_tags = tuple(tags)
        return v

    def speculative_in_flight(self) -> int:
        """Speculative attempts launched and not yet terminal — the
        scheduler gauge's per-job term. Lock-free: len() of a set only
        mutated under the job lock; one beat of staleness is fine."""
        return len(self._spec_attempts)

    def _fold_progress(self, tip: TaskInProgress,
                       status: TaskStatus) -> None:
        """Fold one RUNNING status into the TIP's progress-rate EWMA.
        Master-local monotonic stamps only — the status' own wall
        clocks never enter the math (cross-host skew). A beat with no
        progress advance leaves the anchor alone, so the next advance
        averages over the whole stall. Caller holds ``self.lock``."""
        now = time.monotonic()
        if tip.dispatch_mono == 0.0:
            tip.dispatch_mono = now   # adopted/recovered attempt
        p = min(1.0, max(0.0, status.progress))
        if p <= tip.last_progress:
            return
        base = tip.last_progress_mono or tip.dispatch_mono
        dt = now - base
        if dt <= 0.0:
            return
        rate = (p - tip.last_progress) / dt
        a = self._rate_alpha
        tip.rate_ewma = rate if not tip.rate_ewma \
            else a * rate + (1 - a) * tip.rate_ewma
        tip.last_progress = p
        tip.last_progress_mono = now

    @staticmethod
    def _tip_remaining_s(tip: TaskInProgress, now: float,
                         mean_hint: float) -> float:
        """Estimated seconds until a RUNNING tip finishes: rate EWMA
        when it reports progress; elapsed-proportional fallback before
        the first EWMA fold; a full mean runtime when it has shown no
        progress at all — a silent tip must look LONG, never
        nearly-done (stalls are exactly what speculation targets)."""
        p = tip.last_progress
        if tip.rate_ewma > 0.0:
            return max(0.0, (1.0 - p) / tip.rate_ewma)
        elapsed = now - (tip.dispatch_mono or now)
        if p > 0.0 and elapsed > 0.0:
            return elapsed * (1.0 - p) / p
        return max(0.0, mean_hint)

    def _remaining_locked(self, tips: "list[TaskInProgress]", now: float,
                          mean_hint: float) -> "dict[int, float]":
        return {t.partition: self._tip_remaining_s(t, now, mean_hint)
                for t in tips if t.state == "running"}

    def _map_mean_locked(self) -> float:
        done = self.finished_cpu_maps + self.finished_tpu_maps
        return ((self._cpu_time_sum + self._tpu_time_sum) / done) \
            if done else 0.0

    def map_remaining_estimates(self) -> "dict[int, float]":
        """partition → estimated seconds remaining, for RUNNING maps."""
        with self.lock:
            return self._remaining_locked(self.maps, time.monotonic(),
                                          self._map_mean_locked())

    def critical_path_maps(self) -> "set[int]":
        """Running map partitions on the estimated critical path: those
        whose remaining estimate is within
        ``tpumr.speculative.critical.fraction`` of the longest."""
        est = self.map_remaining_estimates()
        if not est:
            return set()
        mx = max(est.values())
        if mx <= 0.0:
            return set(est)
        return {p for p, r in est.items()
                if r >= self._spec_cp_fraction * mx}

    def longest_remaining_path_s(self) -> float:
        """Live longest-remaining-path estimate: the slowest running
        map's remaining (pending maps contribute at least one mean
        runtime — they haven't even started) plus the same term for the
        reduce phase. An estimate of the floor on job completion, not a
        promise; the targeted speculation pass and the /job page read
        it."""
        with self.lock:
            now = time.monotonic()
            m_mean = self._map_mean_locked()
            m_est = self._remaining_locked(self.maps, now, m_mean)
            path = max(m_est.values(), default=0.0)
            if self._pending_maps:
                path = max(path, m_mean)
            r_mean = self._reduce_time_sum / self.finished_reduces \
                if self.finished_reduces else 0.0
            r_est = self._remaining_locked(self.reduces, now, r_mean)
            rpath = max(r_est.values(), default=0.0)
            if self._pending_reduces:
                rpath = max(rpath, r_mean)
            return path + rpath

    def _note_spec_launch(self, attempt: TaskAttemptID) -> None:
        """Account one speculative twin launch (caller holds the lock)."""
        self.speculative_launched += 1
        self._spec_attempts.add(str(attempt))

    def _settle_speculative(self, aid: str, won: bool) -> None:
        """A speculative attempt reached a terminal state: move it from
        in-flight to won/wasted. No-op for non-speculative attempts.
        Caller holds ``self.lock``."""
        if aid in self._spec_attempts:
            self._spec_attempts.discard(aid)
            if won:
                self.speculative_won += 1
            else:
                self.speculative_wasted += 1

    # ------------------------------------------------------------ obtain

    def obtain_new_map_task(self, host: str, run_on_tpu: bool,
                            tpu_device_id: int = -1,
                            rack: "str | None" = None,
                            tracker: str = "") -> Task | None:
        """Locality-preferring map assignment ≈ obtainNewNodeLocalMapTask →
        obtainNewNonLocalMapTask (selection path of
        JobQueueTaskScheduler.java:306-317). ``tracker`` names the
        asking tracker: with the device id it is the TPU slot whose
        turns the cost estimate times (``host`` where it is not
        given)."""
        slot = (tracker or host, tpu_device_id)
        with self.lock:
            if self.state != JobState.RUNNING:
                return None
            if self.schedule_hold_until \
                    and time.monotonic() < self.schedule_hold_until:
                return None  # recovery grace: re-joining trackers first
            if run_on_tpu and self.tpu_disabled:
                return None  # job-level accelerator quarantine
            # demoted TIPs never land on TPU again; the CPU pass sees
            # all, those first that can run nowhere else (the floor of
            # CPU slots the scheduler keeps for them is theirs)
            eligible = (self._pending_maps - self._cpu_only_maps
                        if run_on_tpu else
                        (self._pending_maps & self._cpu_only_maps)
                        or self._pending_maps)
            if run_on_tpu and not eligible:
                # a free device and no map it may take: a CPU map the
                # chip would end sooner is done over on it
                twin = self._obtain_tpu_twin(slot)
                if twin is not None:
                    return twin
            if not self._pending_maps:
                return self._obtain_speculative_map(run_on_tpu, slot)
            if not eligible:
                return None  # pending work exists but none TPU-eligible
            # tiers: node-local → rack-local → any (≈ obtainNewNodeLocal /
            # rack-local / NonLocal MapTask). The tracker reports its own
            # rack (resolved tracker-side); resolving here is the fallback
            # for local/direct callers only — it may exec the topology
            # script, which must not happen on the scheduling path.
            local = self.host_cache.get(host, set()) & eligible
            if not local:
                if rack is None:
                    rack = self._rack_resolver(host)
                if rack != self._default_rack:
                    local = self.rack_cache.get(rack, set()) & eligible
            idx = min(local) if local else min(eligible)
            self._pending_maps.discard(idx)
            tip = self.maps[idx]
            tip.state = "running"
            tip.dispatch_mono = tip.dispatch_mono or time.monotonic()
            self._record_placement(run_on_tpu)
            attempt = tip.new_attempt()
            tip.report.state = TaskState.RUNNING
            tip.report.start_time = tip.report.start_time or time.time()
            # stamp placement on the report ≈ JobTracker.java:3414-3433
            tip.report.run_on_tpu = run_on_tpu
            tip.report.tpu_device_id = tpu_device_id
            self._note_map_launch(attempt, run_on_tpu, slot,
                                  still_pending=len(eligible) > 1)
            return Task(attempt, partition=idx, num_reduces=self.num_reduces,
                        split=tip.split, num_maps=len(self.maps),
                        run_on_tpu=run_on_tpu, tpu_device_id=tpu_device_id,
                        memory_mb=self.map_memory_mb())

    def _note_map_launch(self, attempt: TaskAttemptID, run_on_tpu: bool,
                         slot: tuple, still_pending: bool) -> None:
        """Tell the cost estimate of one map launch (caller holds the
        lock). ``still_pending``: a map the device may take is left."""
        now = time.monotonic()
        if run_on_tpu:
            self.map_cost.tpu_launched(str(attempt), slot, now,
                                       still_pending)
        else:
            self.map_cost.cpu_launched(str(attempt), now)

    def _launch_twin(self, tip: TaskInProgress, run_on_tpu: bool,
                     slot: tuple) -> Task:
        """A duplicate attempt of a running map; first completion wins
        (the loser is killed by the master). Caller holds the lock."""
        attempt = tip.new_attempt()
        self.speculative_map_tasks += 1
        self._note_spec_launch(attempt)
        self._record_placement(run_on_tpu)
        tip.report.run_on_tpu = run_on_tpu
        tip.report.tpu_device_id = slot[1]
        self._note_map_launch(attempt, run_on_tpu, slot,
                              still_pending=False)
        return Task(attempt, partition=tip.partition,
                    num_reduces=self.num_reduces, split=tip.split,
                    num_maps=len(self.maps), run_on_tpu=run_on_tpu,
                    tpu_device_id=slot[1],
                    memory_mb=self.map_memory_mb())

    def _obtain_tpu_twin(self, slot: tuple) -> Task | None:
        """The twin on an idle chip: a device of the asking tracker is
        free and no map it may take is pending, so a RUNNING CPU map
        (not CPU-pinned, not yet twinned) is done over on it as soon as
        what the CPU attempt has left exceeds what the chip needs to do
        it over, ``TWIN_TURNS`` turns as THIS job measured them. The
        floor and lag factor of ``_obtain_speculative_map`` were written
        to keep CPU attempts from twinning each other too early, not to
        keep a chip that would end the map in a tenth of a second from
        taking it; they keep governing every other speculation. The
        speculation switch, the brownout hold and the cap hold here
        too. Caller holds self.lock."""
        cost = self.map_cost
        if not cost.cpu_running or not self.speculative \
                or self.speculation_hold or self.tpu_disabled \
                or len(self._spec_attempts) >= self.speculative_cap:
            return None
        turn = cost.t_tpu_own(self.tpu_map_mean_time())
        if turn <= 0:
            return None
        now = time.monotonic()
        cpu, _ = self.map_costs()
        worst, worst_left = None, TWIN_TURNS * turn
        for aid, started in cost.cpu_running.items():
            tip = self._tip_of_attempt(aid)
            if tip is None or tip.state != "running" \
                    or tip.next_attempt != 1 \
                    or tip.partition in self._cpu_only_maps:
                continue
            left = self._tip_remaining_s(
                tip, now, cpu.left_after(now - started))
            if left > worst_left:
                worst, worst_left = tip, left
        if worst is None:
            return None
        from tpumr.core.counters import JobCounter
        self.counters.incr(JobCounter.GROUP,
                           JobCounter.TPU_TWINS_OF_CPU_MAPS)
        return self._launch_twin(worst, True, slot)

    def _obtain_speculative_map(self, run_on_tpu: bool,
                                slot: tuple) -> Task | None:
        """Straggler mitigation ≈ JobInProgress.hasSpeculativeMap /
        speculativeMapTasks (JobInProgress.java:2777): when all maps are
        assigned but some lag, issue a duplicate attempt; first
        completion wins (the loser is killed by the master).

        Two modes. Blanket (``tpumr.speculative.targeted=false``): the
        reference's age-only rule — any running TIP older than
        max(floor, factor·mean) twins. Targeted (default), LATE-style:
        a TIP is speculated only when its ESTIMATED FINISH (elapsed +
        estimated remaining, from the per-TIP progress-rate EWMA) lags
        the job's completed-runtime distribution AND it sits on the
        estimated critical path, under a concurrent-speculation cap.
        Caller holds self.lock."""
        if not self.speculative or self.speculation_hold:
            return None
        if run_on_tpu and self.tpu_disabled:
            return None
        # denominator matches the sums: a TPU quarantine unwinds both
        # finished_tpu_maps and _tpu_time_sum, so using finished_maps
        # here would deflate the mean and over-speculate exactly when
        # the job just lost its accelerator capacity
        done = self.finished_cpu_maps + self.finished_tpu_maps
        if done == 0:
            return None
        mean = ((self._cpu_time_sum + self._tpu_time_sum) / done)
        factor = confkeys.get_float(
            self.conf, "mapred.speculative.lag.factor")
        # minimum runtime before a task can be speculated — ≈ the
        # reference's SPECULATIVE_LAG (60s); without a floor, short-task
        # jobs speculate everything instantly
        floor = confkeys.get_float(
            self.conf, "mapred.speculative.min.runtime.s")
        targeted = self.speculative_targeted
        if targeted and len(self._spec_attempts) >= self.speculative_cap:
            return None  # concurrent-speculation cap
        now = time.monotonic()
        est: "dict[int, float]" = {}
        max_rem = 0.0
        if targeted:
            est = self._remaining_locked(self.maps, now, mean)
            max_rem = max(est.values(), default=0.0)
        for tip in self.maps:
            if tip.state != "running":
                continue
            if tip.next_attempt != 1:
                continue  # already speculated (or restarted) — one dup max
            if run_on_tpu and tip.partition in self._cpu_only_maps:
                continue  # a demoted TIP's twin must not land on TPU
            # master-local monotonic age: the dispatch stamp lives in the
            # same clock domain as ``now``, so no wall arithmetic here
            elapsed = now - (tip.dispatch_mono or now)
            if targeted:
                if elapsed <= floor:
                    continue
                remaining = est.get(tip.partition, 0.0)
                if elapsed + remaining <= factor * mean:
                    continue  # estimated finish within the distribution
                if max_rem > 0.0 \
                        and remaining < self._spec_cp_fraction * max_rem:
                    continue  # lagging, but not on the critical path
            elif elapsed <= max(floor, factor * mean):
                continue
            return self._launch_twin(tip, run_on_tpu, slot)
        return None

    def should_kill_attempt(self, attempt_id: str) -> bool:
        """True when this RUNNING attempt lost a speculative race — its TIP
        already succeeded through a different attempt (≈ the reference
        killing the slower speculative twin) — or a scheduler marked it for
        preemption (≈ FairScheduler.preemptTasksIfNecessary)."""
        from tpumr.mapred.ids import TaskAttemptID
        with self.lock:
            if attempt_id in self._preempt_requested:
                return True
            tip = self._tip_of(TaskAttemptID.parse(attempt_id).task)
            return (tip is not None and tip.state == "succeeded"
                    and tip.successful_attempt != attempt_id)

    def kill_marked(self, attempt_id: str) -> bool:
        """Lock-free kill-scan probe (see ``_kill_marked``); a mark set
        mid-probe is caught on the next beat."""
        return attempt_id in self._kill_marked

    def request_preempt(self, attempt_id: str) -> None:
        """Mark a RUNNING attempt for preemption: the next heartbeat of its
        tracker carries a kill action; the KILLED report requeues the TIP
        without counting a failure (fair-scheduler min-share restoration —
        the reference kills tasks of over-share pools the same way)."""
        with self.lock:
            self._preempt_requested.add(attempt_id)
            self._kill_marked.add(attempt_id)

    def request_attempt_kill(self, attempt_id: str,
                             fail: bool = False) -> bool:
        """Operator-driven attempt kill ≈ JobTracker.killTask(taskid,
        shouldFail) — `job -kill-task` / `-fail-task`. ``fail=True``
        makes the attempt count toward the task's attempt limit (the
        -fail-task semantics); plain kill re-queues without burning an
        attempt. Returns False when the attempt is unknown or already
        terminal."""
        with self.lock:
            tip = self._tip_of_attempt(attempt_id)
            if tip is None:
                return False
            st = tip.attempts.get(attempt_id)
            if st is None or st.state in TaskState.TERMINAL:
                # unknown to the master, or already finished — nothing
                # to kill (the reference's killTask returns false too)
                return False
            self._preempt_requested.add(attempt_id)
            self._kill_marked.add(attempt_id)
            if fail:
                self._fail_requested.add(attempt_id)
            return True

    def _tip_of_attempt(self, attempt_id: str) -> "TaskInProgress | None":
        from tpumr.mapred.ids import TaskAttemptID
        try:
            return self._tip_of(TaskAttemptID.parse(attempt_id).task)
        except (ValueError, KeyError, IndexError):
            return None

    def preempt_pending(self) -> set[str]:
        """Attempts marked but not yet observed terminal (so the scheduler
        does not double-count in-flight preemptions when sizing the next
        round of kills)."""
        with self.lock:
            return set(self._preempt_requested)

    def running_map_attempts(self) -> "list[tuple[str, float]]":
        """(attempt_id, start_time) for every RUNNING map attempt — the
        fair scheduler's victim candidates (newest first is the caller's
        sort)."""
        with self.lock:
            out = []
            for tip in self.maps:
                for aid, st in tip.attempts.items():
                    if st.state == TaskState.RUNNING:
                        out.append((aid, st.start_time))
            return out

    def map_memory_mb(self) -> int:
        """Declared per-map memory demand (mapred.job.map.memory.mb, 0 =
        undeclared) — the capacity scheduler's memory-matching input
        (≈ CapacityTaskScheduler's memory checks)."""
        return confkeys.get_int(self.conf, "mapred.job.map.memory.mb")

    def reduce_memory_mb(self) -> int:
        return confkeys.get_int(self.conf,
                                "mapred.job.reduce.memory.mb")

    def obtain_new_reduce_task(self, host: str) -> Task | None:
        with self.lock:
            if self.state != JobState.RUNNING:
                return None
            if self.schedule_hold_until \
                    and time.monotonic() < self.schedule_hold_until:
                return None  # recovery grace: re-joining trackers first
            if not self._pending_reduces:
                return self._obtain_speculative_reduce()
            # slowstart gate ≈ JobInProgress.scheduleReduces
            if self.finished_maps < self.slowstart * max(1, len(self.maps)):
                return None
            idx = min(self._pending_reduces)
            self._pending_reduces.discard(idx)
            tip = self.reduces[idx]
            tip.state = "running"
            tip.dispatch_mono = tip.dispatch_mono or time.monotonic()
            attempt = tip.new_attempt()
            tip.report.state = TaskState.RUNNING
            tip.report.start_time = tip.report.start_time or time.time()
            return Task(attempt, partition=idx, num_reduces=self.num_reduces,
                        num_maps=len(self.maps),
                        memory_mb=self.reduce_memory_mb())

    def _obtain_speculative_reduce(self) -> Task | None:
        """Straggler mitigation for the phase that ends every job ≈
        JobInProgress.hasSpeculativeReduces / findSpeculativeTask
        (JobInProgress.java:257,739,2320): when all reduces are assigned
        but one runs much longer than the completed mean, issue a
        duplicate attempt; first completion wins (the loser is killed by
        the master via should_kill_attempt, and the output committer's
        promote-on-commit makes the race safe). Same progress-gap rule
        as maps (and the same targeted/blanket split as the map pass).
        Caller holds ``self.lock``."""
        if not self.speculative_reduces or self.speculation_hold \
                or self.finished_reduces == 0:
            return None
        mean = self._reduce_time_sum / self.finished_reduces
        factor = confkeys.get_float(
            self.conf, "mapred.speculative.lag.factor")
        floor = confkeys.get_float(
            self.conf, "mapred.speculative.min.runtime.s")
        targeted = self.speculative_targeted
        if targeted and len(self._spec_attempts) >= self.speculative_cap:
            return None  # concurrent-speculation cap (shared with maps)
        now = time.monotonic()
        est: "dict[int, float]" = {}
        max_rem = 0.0
        if targeted:
            est = self._remaining_locked(self.reduces, now, mean)
            max_rem = max(est.values(), default=0.0)
        for tip in self.reduces:
            if tip.state != "running":
                continue
            if tip.next_attempt != 1:
                continue  # already speculated (or restarted) — one dup max
            # master-local monotonic age, as in the map pass above
            elapsed = now - (tip.dispatch_mono or now)
            if targeted:
                if elapsed <= floor:
                    continue
                remaining = est.get(tip.partition, 0.0)
                if elapsed + remaining <= factor * mean:
                    continue
                if max_rem > 0.0 \
                        and remaining < self._spec_cp_fraction * max_rem:
                    continue
            elif elapsed <= max(floor, factor * mean):
                continue
            attempt = tip.new_attempt()
            self.speculative_reduce_tasks += 1
            self._note_spec_launch(attempt)
            return Task(attempt, partition=tip.partition,
                        num_reduces=self.num_reduces,
                        num_maps=len(self.maps),
                        memory_mb=self.reduce_memory_mb())
        return None

    # ------------------------------------------------------------ updates

    def update_task_status(self, status: TaskStatus,
                           tracker_shuffle_addr: str = "") -> None:
        with self.lock:
            tip = self._tip_of(status.attempt_id.task)
            if tip is None:
                return
            aid_s = str(status.attempt_id)
            prev = tip.attempts.get(aid_s)
            if prev is not None and prev.state in (TaskState.FAILED,
                                                   TaskState.KILLED):
                # the master already terminally settled this attempt
                # (withdrawn output, lost tracker, -fail-task): a
                # replayed tracker status must neither resurrect a dead
                # attempt (a re-delivered SUCCEEDED would re-publish a
                # withdrawn shuffle address and re-increment
                # finished_maps while the tip sits in _pending_maps) nor
                # double-count its failure
                return
            if status.state in TaskState.TERMINAL:
                self._preempt_requested.discard(aid_s)
                self._kill_marked.discard(aid_s)
                if status.state == TaskState.KILLED \
                        and aid_s in self._fail_requested:
                    # -fail-task: the tracker reports the kill as KILLED;
                    # the operator asked for FAILED semantics (burn an
                    # attempt) — rewrite before accounting
                    status = replace(status, state=TaskState.FAILED,
                                     diagnostics=(status.diagnostics
                                                  or "failed by operator "
                                                     "(-fail-task)"))
                # any terminal outcome clears the fail mark (an attempt
                # that FAILED or SUCCEEDED on its own must not leak a
                # stale entry for the life of the job)
                self._fail_requested.discard(aid_s)
                self.map_cost.attempt_ended(
                    aid_s, time.monotonic(),
                    killed=status.state == TaskState.KILLED)
            tip.attempts[str(status.attempt_id)] = status
            tip.report.progress = max(tip.report.progress, status.progress)
            if status.state == TaskState.RUNNING \
                    and tip.state == "running":
                # the feedback model's input: per-TIP progress-rate EWMA
                # folded here, under the job lock only (off the
                # heartbeat fast path per the PR-8 lock ranks)
                self._fold_progress(tip, status)
            if status.state == TaskState.RUNNING \
                    and tip.state == "succeeded" \
                    and tip.successful_attempt != aid_s:
                # a speculative loser reporting progress after its twin
                # already won (possibly its FIRST report): mark it so
                # the kill scan catches it without re-deriving the race
                self._kill_marked.add(aid_s)
            if status.state == TaskState.SUCCEEDED:
                self._on_success(tip, status, tracker_shuffle_addr)
            elif status.state in (TaskState.FAILED, TaskState.KILLED):
                self._on_failure(tip, status)

    def _tip_of(self, task_id: TaskID) -> TaskInProgress | None:
        arr = self.maps if task_id.is_map else self.reduces
        return arr[task_id.id] if task_id.id < len(arr) else None

    def _on_success(self, tip: TaskInProgress, status: TaskStatus,
                    shuffle_addr: str) -> None:
        aid = str(status.attempt_id)
        if tip.state == "succeeded":
            # a speculative duplicate — first completion wins (and this
            # late finisher's work is by definition wasted)
            self._settle_speculative(aid, won=False)
            return
        tip.state = "succeeded"
        tip.successful_attempt = aid
        self._settle_speculative(aid, won=True)
        # the losing speculative twins (any other attempt still RUNNING)
        # get their kill marks NOW — the heartbeat kill scan reads the
        # mark set lock-free instead of re-deriving the race per beat
        for other_aid, other in tip.attempts.items():
            if other_aid != tip.successful_attempt \
                    and other.state == TaskState.RUNNING:
                self._kill_marked.add(other_aid)
        tip.report.state = TaskState.SUCCEEDED
        tip.report.progress = 1.0
        tip.report.finish_time = status.finish_time or time.time()
        tip.report.successful_attempt = str(status.attempt_id)
        if status.counters:
            self.counters.merge(Counters.from_dict(status.counters))
        # a completion may fold for a tip the master believed PENDING: a
        # restarted master recovers in-flight tasks as pending, and the
        # re-joining tracker's first beat can carry the attempt's
        # (undelivered) terminal status directly — the tip must leave
        # the pending set or the scheduler re-assigns finished work
        if tip.is_map:
            self._pending_maps.discard(tip.partition)
        else:
            self._pending_reduces.discard(tip.partition)
        if tip.is_map:
            self.finished_maps += 1
            runtime = status.runtime
            self._record_runtime(runtime, is_map=True,
                                 on_tpu=bool(status.run_on_tpu))
            if status.run_on_tpu:
                # post-quarantine TPU completions (in-flight attempts
                # finishing after tpu_disabled) are excluded from BOTH
                # backends' profiles: the unwound TPU sums must not
                # resurrect, and folding TPU runtimes into the CPU mean
                # would skew it just as badly
                if not self.tpu_disabled:
                    self.finished_tpu_maps += 1
                    self._tpu_time_sum += runtime
                    if self._ewma_alpha:
                        a = self._ewma_alpha
                        self._tpu_ewma = (
                            runtime if not self._tpu_ewma
                            else a * runtime + (1 - a) * self._tpu_ewma)
            else:
                self.finished_cpu_maps += 1
                self._cpu_time_sum += runtime
                if self._ewma_alpha:
                    a = self._ewma_alpha
                    self._cpu_ewma = (runtime if not self._cpu_ewma
                                      else a * runtime + (1 - a) * self._cpu_ewma)
            self.completion_events.append({
                "map_index": tip.partition,
                "attempt_id": str(status.attempt_id),
                "shuffle_addr": shuffle_addr,
                "status": "SUCCEEDED",
                # tracker-stamped map-output size: reducers order their
                # fetch queues largest-first on it (size-aware shuffle)
                "output_bytes": int(getattr(status, "output_bytes", 0)
                                    or 0),
            })
        else:
            self.finished_reduces += 1
            self._reduce_time_sum += status.runtime
            self._record_runtime(status.runtime, is_map=False)
            if self.stream_handoff:
                # announce the committed reduce partition to downstream
                # pipeline stages (their HandoffSplit readers poll this
                # feed through the same MapLocator the shuffle uses)
                self.handoff_events.append({
                    "map_index": tip.partition,
                    "attempt_id": str(status.attempt_id),
                    "shuffle_addr": shuffle_addr,
                    "status": "SUCCEEDED",
                })
        if (self.finished_maps == len(self.maps)
                and self.finished_reduces == len(self.reduces)):
            self.state = JobState.SUCCEEDED
            self.finish_time = time.time()

    _MAX_RUNTIME_SAMPLES = 65536

    def _record_runtime(self, runtime: float, is_map: bool,
                        on_tpu: bool = False) -> None:
        """Keep one successful attempt's runtime for the stats rollup
        (caller holds ``self.lock`` via update_task_status)."""
        if len(self.map_runtimes) + len(self.reduce_runtimes) \
                >= self._MAX_RUNTIME_SAMPLES:
            self.runtimes_dropped += 1
            return
        if is_map:
            self.map_runtimes.append((float(runtime), on_tpu))
        else:
            self.reduce_runtimes.append(float(runtime))

    def _on_failure(self, tip: TaskInProgress, status: TaskStatus) -> None:
        # a FAILED/KILLED speculative twin settles as wasted whether or
        # not its TIP already succeeded through the other attempt
        self._settle_speculative(str(status.attempt_id), won=False)
        if tip.state == "succeeded":
            return
        if status.state == TaskState.FAILED:
            # KILLED attempts (lost trackers, job kills, lost commit races)
            # do NOT count toward the attempt limit — only real failures do
            # (Hadoop excludes killed attempts the same way)
            tip.failures += 1
            from tpumr.mapred.task import FailureClass
            if (tip.is_map and status.run_on_tpu
                    and status.failure_class in FailureClass.ACCELERATOR):
                self._note_tpu_failure(tip, status)
        limit = self.max_map_attempts if tip.is_map else self.max_reduce_attempts
        if status.state == TaskState.FAILED and tip.failures >= limit:
            self.state = JobState.FAILED
            self.finish_time = time.time()
            self.error = (f"task {tip.task_id} failed {tip.failures} times; "
                          f"last: {status.diagnostics}")
            return
        # if a twin attempt (speculative, or not-yet-reaped) is still
        # running, don't re-queue — a third concurrent attempt would waste
        # a slot and the live twin may still succeed
        aid = str(status.attempt_id)
        if any(s.state == TaskState.RUNNING and str(s.attempt_id) != aid
               for s in tip.attempts.values()):
            tip.state = "running"
            return
        # re-queue (≈ lost/failed task re-execution)
        tip.state = "pending"
        tip.reset_feedback()
        if tip.is_map:
            self._pending_maps.add(tip.partition)
        else:
            self._pending_reduces.add(tip.partition)

    def _note_tpu_failure(self, tip: TaskInProgress,
                          status: TaskStatus) -> None:
        """One device/compile-classed TPU failure: walk the TIP toward
        CPU-only pinning and the job toward TPU quarantine. Caller holds
        ``self.lock`` (via update_task_status)."""
        from tpumr.core.counters import JobCounter
        tip.tpu_failures += 1
        self._tpu_failed_tips.add(tip.partition)
        if (tip.partition not in self._cpu_only_maps
                and tip.tpu_failures >= self.tpu_attempt_retries):
            # ≈ the reference re-landing a deterministically-crashing
            # kernel on the same backend until the job dies — instead
            # the TIP's remaining attempts are pinned to the CPU pass
            self._cpu_only_maps.add(tip.partition)
            self.counters.incr(JobCounter.GROUP, JobCounter.TPU_DEMOTIONS)
            self._accel_events.append({
                "kind": "tip_demoted", "task_id": str(tip.task_id),
                "attempt_id": str(status.attempt_id),
                "failure_class": status.failure_class,
                "tpu_failures": tip.tpu_failures})
        if (not self.tpu_disabled
                and len(self._tpu_failed_tips) >= self.tpu_quarantine_tips):
            # enough DISTINCT tasks indicted the accelerator path: the
            # fault is the job's kernel (or the fleet's devices), not
            # one unlucky split — stop offering this job TPU work at all
            self.tpu_disabled = True
            # unwind the TPU profile sums so acceleration_factor → 1.0:
            # a poisoned factor would keep the optional-scheduling gate
            # starving the CPU pass, deadlocking the job it just demoted
            self.finished_tpu_maps = 0
            self._tpu_time_sum = 0.0
            self._tpu_ewma = 0.0
            self.map_cost.forget_tpu()
            self._accel_events.append({
                "kind": "job_tpu_quarantined",
                "failed_tips": len(self._tpu_failed_tips),
                "attempt_id": str(status.attempt_id)})

    def _obsolete_map_output(self, tip: TaskInProgress, aid: str) -> str:
        """Withdraw a published map output: mark its completion event(s)
        OBSOLETE in place (late consumers replaying from cursor 0 see
        SUCCEEDED→OBSOLETE in order) AND append a tombstone event so
        consumers whose cursor is already past the original learn of the
        withdrawal. Returns the shuffle address that served the output
        ("" when it was never published). Caller holds ``self.lock``."""
        addr = ""
        for e in self.completion_events:
            if e["attempt_id"] == aid and e.get("status") != "OBSOLETE":
                addr = e.get("shuffle_addr", "")
                e["status"] = "OBSOLETE"
        self.completion_events.append({
            "map_index": tip.partition, "attempt_id": aid,
            "shuffle_addr": addr, "status": "OBSOLETE"})
        return addr

    def _unwind_finished_map(self, tip: TaskInProgress,
                             st: "TaskStatus | None") -> None:
        """Take one completed map back out of the books: completion
        count AND the per-backend profile sums, so the hybrid
        scheduler's means aren't poisoned by a re-run being
        double-counted. Caller holds ``self.lock``."""
        self.finished_maps -= 1
        if st is not None and st.is_map:
            if st.run_on_tpu:
                self.finished_tpu_maps -= 1
                self._tpu_time_sum -= st.runtime
            else:
                self.finished_cpu_maps -= 1
                self._cpu_time_sum -= st.runtime

    def fetch_failure_notification(self, map_attempt: str,
                                   reduce_attempt: str) -> "dict | None":
        """A reducer reports ``map_attempt``'s output unfetchable
        (≈ JobInProgress.fetchFailureNotification, reached via
        ReduceTask's umbilical → heartbeat). Distinct reporting reducers
        are counted per map attempt; at ``mapred.max.fetch.failures.per.
        map`` (or once EVERY live reduce is reporting — a 1-reduce job
        could never reach 3) the still-"successful" attempt is failed:
        its output is withdrawn (OBSOLETE completion events), the hybrid
        profile sums are unwound, and the map re-queues for re-execution
        while the reporting reduces stay alive in their penalty-box
        retry loops. Returns None for stale/unknown reports, else a dict
        with ``reexecuted`` and the serving ``shuffle_addr`` (so the
        master can charge a fault to the lame tracker)."""
        try:
            attempt = TaskAttemptID.parse(map_attempt)
            reducer = TaskAttemptID.parse(reduce_attempt)
        except (ValueError, IndexError):
            return None
        with self.lock:
            if self.state != JobState.RUNNING or not attempt.task.is_map:
                return None
            tip = self._tip_of(attempt.task)
            if tip is None:
                return None
            # the reporter must be a real, running reduce attempt of
            # THIS job (≈ the reference trusting only its own umbilical
            # children): forged reducer names must not be able to
            # manufacture "distinct reducers" and kill healthy maps.
            # Attempts adopted from the job this one was recovered from
            # (master restart) carry the OLD job id and count as ours.
            if reducer.task.is_map or (
                    reducer.task.job != self.job_id
                    and str(reducer.task.job) != (self.recovered_from
                                                  or "")):
                return None
            rtip = self._tip_of(reducer.task)
            rst = rtip.attempts.get(reduce_attempt) \
                if rtip is not None else None
            if rst is None or rst.state != TaskState.RUNNING:
                return None
            if tip.state != "succeeded" \
                    or tip.successful_attempt != map_attempt:
                # stale: the output was already withdrawn (lost tracker
                # or an earlier notification) — the reducer just hasn't
                # refreshed its events yet
                return None
            reporters = self._fetch_failures.setdefault(map_attempt, set())
            # keyed by reduce TASK, not attempt: a speculative twin is
            # the same reducer corroborating nothing new
            reporters.add(str(reducer.task))
            n_reports = len(reporters)
            live_reduces = max(1, len(self.reduces) - self.finished_reduces)
            threshold = min(self.max_fetch_failures_per_map, live_reduces)
            if n_reports < threshold:
                return {"withdrawn": False, "reexecuted": False,
                        "shuffle_addr": "", "reports": n_reports}
            del self._fetch_failures[map_attempt]
            addr = self._obsolete_map_output(tip, map_attempt)
            st = tip.attempts.get(map_attempt)
            if st is not None:
                st.state = TaskState.FAILED
                st.diagnostics = (
                    f"Too many fetch failures: {n_reports} reducer(s) "
                    f"could not fetch this attempt's output from {addr}")
            # the attempt is burned (≈ failedTask for fetch failures): a
            # map whose output keeps vanishing eventually fails the job
            # like any other repeatedly-failing task
            tip.failures += 1
            tip.state = "pending"
            tip.successful_attempt = ""
            tip.reset_feedback()
            self._unwind_finished_map(tip, st)
            self._pending_maps.add(tip.partition)
            if tip.failures >= self.max_map_attempts:
                self.state = JobState.FAILED
                self.finish_time = time.time()
                self.error = (f"map {tip.task_id} lost its output to "
                              f"fetch failures {tip.failures} times")
                return {"withdrawn": True, "reexecuted": False,
                        "shuffle_addr": addr, "reports": n_reports}
            return {"withdrawn": True, "reexecuted": True,
                    "shuffle_addr": addr, "reports": n_reports}

    def fetch_failure_pending_count(self) -> int:
        """Map attempts with outstanding (sub-threshold) fetch-failure
        reports — the master's penalty-ledger gauge."""
        with self.lock:
            return len(self._fetch_failures)

    def requeue_lost_attempts(self, attempt_ids: list[str]) -> "list[str]":
        """Tracker lost (≈ JobTracker.lostTaskTracker): running attempts on
        it are killed and their tasks re-queued; completed MAPS are also
        re-queued because their outputs lived on the lost tracker — unless
        the job has no reduces (reference semantics). Returns the attempt
        ids whose published map outputs were withdrawn, so the caller can
        journal MAP_OUTPUT_LOST events (restart recovery must not adopt
        outputs the master already declared gone)."""
        withdrawn: "list[str]" = []
        with self.lock:
            for aid in attempt_ids:
                attempt = TaskAttemptID.parse(aid)
                tip = self._tip_of(attempt.task)
                if tip is None:
                    continue
                # a lost attempt is terminal either way — a pending preempt
                # mark must not linger as a phantom in-flight kill
                self._preempt_requested.discard(aid)
                self._kill_marked.discard(aid)
                st = tip.attempts.get(aid)
                if st is None and attempt.task.job == self.job_id \
                        and attempt.attempt < tip.next_attempt:
                    # THIS job launched it (the caller passes a
                    # tracker's attempts of every job to each), in a
                    # response the tracker did not live to read: no
                    # status ever arrived, yet the task sits `running`
                    # on it — lost like any other running attempt
                    st = tip.attempts[aid] = TaskStatus(
                        attempt_id=attempt, is_map=tip.is_map)
                if st is not None and st.state == TaskState.RUNNING:
                    # honor a pending -fail-task even when the tracker
                    # died before delivering the kill: the operator asked
                    # for a burned attempt, not a free requeue
                    if aid in self._fail_requested:
                        st.state = TaskState.FAILED
                        st.diagnostics = (st.diagnostics or
                                          "failed by operator (-fail-task)")
                    else:
                        st.state = TaskState.KILLED
                    # (not as killed: when it stopped running nobody
                    # knows, so its age proves nothing of a map's cost)
                    self.map_cost.attempt_ended(aid, time.monotonic(),
                                                killed=False)
                    self._on_failure(tip, st)
                elif (tip.is_map and tip.state == "succeeded"
                      and tip.successful_attempt == aid
                      and self.num_reduces > 0
                      and self.state == JobState.RUNNING):
                    tip.state = "pending"
                    tip.successful_attempt = ""
                    tip.reset_feedback()
                    # unwind the backend profile so the re-run isn't
                    # double-counted in the hybrid scheduler's means
                    self._unwind_finished_map(tip, st)
                    self._pending_maps.add(tip.partition)
                    self._obsolete_map_output(tip, aid)
                    self._fetch_failures.pop(aid, None)
                    withdrawn.append(aid)
                # lost = terminal for this attempt whatever branch ran:
                # never leak a -fail-task mark for the life of the job
                self._fail_requested.discard(aid)
        return withdrawn

    def withdraw_handoff_at(self, addr: str) -> int:
        """The tracker serving streamed-handoff reduce output at
        ``addr`` is gone: tombstone its announcements (OBSOLETE in
        place + appended, the PR-1 withdrawal dialect) so downstream
        readers evict the location and fall back to the COMMITTED part
        file — the reduce itself never re-runs for this (its DFS output
        survived the tracker). Runs for terminal jobs too: a finished
        upstream stage keeps serving a live pipeline. Returns the
        number of partitions withdrawn."""
        if not self.stream_handoff:
            return 0
        with self.lock:
            # snapshot before appending tombstones: the feed grows
            # under this very loop otherwise
            live = [e for e in self.handoff_events
                    if e.get("shuffle_addr") == addr
                    and e.get("status") != "OBSOLETE"]
            for e in live:
                e["status"] = "OBSOLETE"
                self.handoff_events.append({
                    "map_index": e["map_index"],
                    "attempt_id": e["attempt_id"],
                    "shuffle_addr": addr, "status": "OBSOLETE"})
        return len(live)

    # ------------------------------------------------------------ recovery

    def recover_attempts(self, state: dict, old_job_id: str) -> int:
        """Replay an interrupted job's completed attempts (from
        ``JobHistory.recovered_attempt_state``) into this resubmitted
        job: completed maps are marked SUCCEEDED with their ORIGINAL
        attempt ids and their completion events re-fed into the
        append-only feed (reducers fetch the surviving outputs instead
        of waiting for re-runs); completed reduces are simply counted
        done (their output is already committed). A recovered output
        that turns out to be gone re-executes through the normal
        fetch-failure protocol. Returns the number of attempts adopted
        from history."""
        n = 0
        with self.lock:
            self.recovered_from = old_job_id
            for idx, rec in sorted((state.get("maps") or {}).items()):
                idx = int(idx)
                if idx >= len(self.maps):
                    continue
                if self.num_reduces > 0 and not rec.get("shuffle_addr"):
                    # no recorded serving address (pre-upgrade history):
                    # reducers could never fetch it — re-run instead
                    continue
                self._recover_one(self.maps[idx], rec)
                if self.num_reduces > 0:
                    self.completion_events.append({
                        "map_index": idx,
                        "attempt_id": rec["attempt_id"],
                        "shuffle_addr": rec["shuffle_addr"],
                        "status": "SUCCEEDED",
                    })
                n += 1
            for idx, rec in sorted((state.get("reduces") or {}).items()):
                idx = int(idx)
                if idx >= len(self.reduces):
                    continue
                self._recover_one(self.reduces[idx], rec)
                if self.stream_handoff and rec.get("shuffle_addr"):
                    # re-announce the surviving streamed handoff copy:
                    # downstream readers' cursors rewind on the shorter
                    # post-restart feed (MapLocator's starvation rewind)
                    # and re-fold idempotently
                    self.handoff_events.append({
                        "map_index": idx,
                        "attempt_id": rec["attempt_id"],
                        "shuffle_addr": rec["shuffle_addr"],
                        "status": "SUCCEEDED",
                    })
                n += 1
            if (self.finished_maps == len(self.maps)
                    and self.finished_reduces == len(self.reduces)):
                # the crash fell between the last completion and
                # finalization — the caller finalizes, nothing re-runs
                self.state = JobState.SUCCEEDED
                self.finish_time = time.time()
        return n

    def _recover_one(self, tip: TaskInProgress, rec: dict) -> None:
        """Adopt one history-recovered successful attempt into its TIP.
        Caller holds ``self.lock``."""
        aid = rec["attempt_id"]
        finish = rec.get("ts") or time.time()
        runtime = float(rec.get("runtime", 0.0) or 0.0)
        status = TaskStatus(
            attempt_id=TaskAttemptID.parse(aid), is_map=tip.is_map,
            state=TaskState.SUCCEEDED, progress=1.0,
            phase=TaskPhase.MAP if tip.is_map else TaskPhase.REDUCE,
            start_time=finish - runtime, finish_time=finish,
            run_on_tpu=bool(rec.get("run_on_tpu", False)),
            tpu_device_id=int(rec.get("tpu_device_id", -1)))
        tip.attempts[aid] = status
        tip.next_attempt = max(tip.next_attempt,
                               int(rec.get("attempt", 0)) + 1)
        tip.state = "succeeded"
        tip.successful_attempt = aid
        tip.report.state = TaskState.SUCCEEDED
        tip.report.progress = 1.0
        tip.report.start_time = status.start_time
        tip.report.finish_time = finish
        tip.report.successful_attempt = aid
        self.history_logged.add(aid)
        if rec.get("counters"):
            self.counters.merge(Counters.from_dict(rec["counters"]))
        if tip.is_map:
            self._pending_maps.discard(tip.partition)
            self.finished_maps += 1
            self._record_runtime(runtime, is_map=True,
                                 on_tpu=status.run_on_tpu)
            tip.report.run_on_tpu = status.run_on_tpu
            tip.report.tpu_device_id = status.tpu_device_id
            # feed the hybrid profile so the recovered job's scheduler
            # means start where the interrupted job's left off
            if status.run_on_tpu:
                self.finished_tpu_maps += 1
                self._tpu_time_sum += runtime
            else:
                self.finished_cpu_maps += 1
                self._cpu_time_sum += runtime
        else:
            self._pending_reduces.discard(tip.partition)
            self.finished_reduces += 1
            self._reduce_time_sum += runtime
            self._record_runtime(runtime, is_map=False)

    def adopt_running_attempt(self, status: TaskStatus) -> bool:
        """A re-joining tracker reports ``status`` RUNNING and the
        master has no record of launching it (master restart, or the
        tracker was expired and re-contacted). Bind it to its TIP —
        in-flight work survives the restart — or return False: the
        caller kills the attempt individually (its task already
        succeeded through another attempt, was settled terminally, or
        the job is over). A blanket ``reinit`` never happens here."""
        with self.lock:
            if self.state != JobState.RUNNING:
                return False
            tip = self._tip_of(status.attempt_id.task)
            if tip is None:
                return False
            aid = str(status.attempt_id)
            if tip.state == "succeeded":
                # only the recorded winner survives; an unknown twin of
                # a finished task is a zombie to kill
                return tip.successful_attempt == aid
            prev = tip.attempts.get(aid)
            if prev is not None and prev.state in TaskState.TERMINAL:
                return False   # the master already settled it
            tip.attempts[aid] = status
            tip.next_attempt = max(tip.next_attempt,
                                   status.attempt_id.attempt + 1)
            # age anchor for the feedback model: adoption time is the
            # best master-local stand-in for the unknown dispatch time
            tip.dispatch_mono = tip.dispatch_mono or time.monotonic()
            if tip.state == "pending":
                tip.state = "running"
                if tip.is_map:
                    self._pending_maps.discard(tip.partition)
                else:
                    self._pending_reduces.discard(tip.partition)
            tip.report.state = TaskState.RUNNING
            tip.report.start_time = (tip.report.start_time
                                     or status.start_time or time.time())
            if tip.is_map:
                tip.report.run_on_tpu = status.run_on_tpu
                tip.report.tpu_device_id = status.tpu_device_id
            return True

    def kill(self) -> bool:
        """Transition to KILLED; returns True only for the caller that
        actually performed the transition (False if already terminal)."""
        with self.lock:
            if self.state in JobState.TERMINAL:
                return False
            self.state = JobState.KILLED
            self.finish_time = time.time()
            return True

    # ------------------------------------------------------------ wire

    _PLACEMENT_CAP = 50_000

    def _record_placement(self, run_on_tpu: bool) -> None:
        """One map assignment's backend, time-stamped relative to submit.
        Caller holds ``self.lock``."""
        if len(self.placement_series) >= self._PLACEMENT_CAP:
            self.placement_dropped += 1
            return
        self.placement_series.append(
            # offsets from the submit WALL stamp — the same zero the
            # history/trace timeline uses
            (round(time.time() - self.start_time, 3),  # tpulint: disable=clock-arith
             "T" if run_on_tpu else "c"))

    def placement_timeline(self) -> dict:
        """The convergence curve the hybrid scheduler is judged on
        (≈ JobQueueTaskScheduler.java:290-327 starvation rule observed
        from outside): the full assignment sequence ('TcccTTcT…') plus
        per-assignment timestamps, so a plot falls out of any finished
        run's history. Cumulative counts are derivable from ``seq`` in
        one pass — deliberately NOT serialized (a 50k-map job's history
        event would triple in size for redundant data)."""
        with self.lock:
            series = list(self.placement_series)
        return {"seq": "".join(b for _, b in series),
                "t": [t for t, _ in series],
                "dropped": self.placement_dropped}

    def status_dict(self) -> dict:
        with self.lock:
            return {
                "job_id": str(self.job_id),
                "state": self.state,
                "priority": self.priority,
                "map_progress": self.map_progress(),
                "reduce_progress": self.reduce_progress(),
                "finished_maps": self.finished_maps,
                "finished_tpu_maps": self.finished_tpu_maps,
                "finished_cpu_maps": self.finished_cpu_maps,
                "num_maps": len(self.maps),
                "num_reduces": len(self.reduces),
                "cpu_map_mean_time": self.cpu_map_mean_time(),
                "tpu_map_mean_time": self.tpu_map_mean_time(),
                "acceleration_factor": self.acceleration_factor(),
                # scheduling feedback: the live remaining-work model and
                # the targeted-speculation ledger (the "/job page's one
                # map is dragging this job" answer)
                "longest_remaining_path_s": round(
                    self.longest_remaining_path_s(), 3),
                "speculative_launched": self.speculative_launched,
                "speculative_won": self.speculative_won,
                "speculative_wasted": self.speculative_wasted,
                "speculative_in_flight": len(self._spec_attempts),
                # placement TAIL only: status_dict rides every polled
                # get_job_status RPC (clients poll at 5 Hz), so it must
                # stay small on 50k-map jobs; the full timeline ships
                # once, in the JOB_FINISHED history event
                "placement_seq": "".join(
                    b for _, b in self.placement_series[-512:]),
                # accelerator fault tolerance: demoted TIPs + the job-
                # level quarantine flag (the /job page's "why did my TPU
                # job go CPU" answer)
                "tpu_disabled": self.tpu_disabled,
                "tpu_demoted_tips": len(self._cpu_only_maps),
                # pipeline stage identity ("which stage/round is this
                # job", the /job page's link back to its /pipeline)
                "pipeline": str(confkeys.get(
                    self.conf, "tpumr.pipeline.id") or ""),
                "pipeline_node": str(confkeys.get(
                    self.conf, "tpumr.pipeline.node") or ""),
                "pipeline_round": confkeys.get_int(
                    self.conf, "tpumr.pipeline.round"),
                "error": self.error,
            }
