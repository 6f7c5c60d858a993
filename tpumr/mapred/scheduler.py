"""Task schedulers: the pluggable SPI + the hybrid CPU/TPU scheduler.

≈ ``org.apache.hadoop.mapred.TaskScheduler`` (SPI) and the GPU-modified
``JobQueueTaskScheduler`` (reference: src/mapred/org/apache/hadoop/mapred/
JobQueueTaskScheduler.java, 628 LoC — the Shirahata et al. hybrid
scheduler, SURVEY.md §2.1). The algorithm is ported faithfully:

- per-job CPU/TPU map costs → ``accelerationFactor = cpuMean/tpuMean``
  (:127-178); here the job's ESTIMATE (map_cost.py), which also reads
  running and killed attempts and the job before, so that it says
  something before a map of each kind has finished;
- **the CPU share of a hybrid job, one rule**: the reference's
  commented-out load-split minimization ``f(x,y) =
  max(⌈x/n_cpu⌉·t_cpu, ⌈y/n_tpu⌉·t_tpu)`` (:181-219), fed with the
  estimate: a free CPU slot gets a map of a hybrid job only where that
  shortens the job. It stands where the reference's optional scheduling
  stood (:78, :290-291: skip the CPU pass once ``pendingMapLoad <
  accelFactor × tpuCapacity × numTrackers``), which was off by default
  and blind in a job whose CPU maps never finish. With no estimate (a
  first job's first beat) it is the full share;
- the TPU pass requires the job to have a device kernel (≈ the
  ``hadoop.pipes.gpu.executable`` gate :342-347) and assigns a concrete free
  device id per task (:355-361), consuming device availability locally
  within the same heartbeat (:373-378); a free device with no pending
  map to take twins a running CPU map the chip would end sooner
  (job_in_progress.py ``_obtain_tpu_twin``);
- at most ONE reduce task per heartbeat (:527-560).
"""

from __future__ import annotations

from typing import Any, Protocol

from tpumr.core import confkeys
from tpumr.mapred import map_cost
from tpumr.mapred.job_in_progress import (JobInProgress, JobState,
                                          priority_rank)
from tpumr.mapred.task import Task


class TaskTrackerManager(Protocol):
    """What a scheduler needs from the master (≈ mapred/TaskTrackerManager
    interface — the seam the reference's scheduler unit tests fake)."""

    def running_jobs(self) -> list[JobInProgress]: ...
    def num_trackers(self) -> int: ...
    def total_slots(self) -> dict: ...   # {"cpu": n, "tpu": n, "reduce": n}
    # optional: monotonically bumped when the running-job set (or a job
    # priority) changes — lets the FIFO order cache skip its re-sort.
    # Fakes without it just lose the caching (getattr-guarded).
    # def jobs_version(self) -> int: ...
    # optional: tag -> live tracker names whose piggybacked devcache
    # inventory holds the tag (the affinity pass's cross-tracker view).
    # Fakes without it just lose deferral (getattr-guarded).
    # def devcache_tag_index(self) -> dict[str, set[str]]: ...


class TaskScheduler:
    """SPI ≈ mapred/TaskScheduler.java — pluggable via
    ``mapred.jobtracker.taskScheduler``."""

    def __init__(self) -> None:
        self.manager: TaskTrackerManager | None = None
        self.conf: Any = None
        #: optional MetricsRegistry wired by the master: scheduling is a
        #: per-heartbeat decision on the control plane's critical path,
        #: so its wall time is a first-class distribution
        #: (``assign_seconds``) and its output a per-backend counter set
        self.metrics: Any = None

    def set_manager(self, manager: TaskTrackerManager) -> None:
        self.manager = manager

    def configure(self, conf: Any) -> None:
        self.conf = conf

    def assign_tasks(self, tracker_status: dict) -> list[Task]:
        raise NotImplementedError

    def before_heartbeat(self, tracker_status: dict) -> None:
        """Observation hook run on EVERY heartbeat, before kill-action
        generation and regardless of free slots (assign_tasks only runs
        when the tracker asks for work — a fully saturated cluster never
        does, which is precisely when preemption logic must still fire)."""


def _free_tpu_devices(tracker_status: dict) -> list[int]:
    """Free physical device ids, recomputed from running task statuses each
    heartbeat (≈ TaskTrackerStatus.availableGPUDevices(),
    TaskTrackerStatus.java:536-550 — inferred, not leased)."""
    avail = tracker_status.get("available_tpu_devices")
    if avail is None:
        avail = [True] * int(tracker_status.get("max_tpu_map_slots", 0))
    return [i for i, free in enumerate(avail) if free]


def _priority_fifo(jobs: list[JobInProgress]) -> list[JobInProgress]:
    """The reference's FIFO queue order (JobQueueJobInProgressListener.
    FIFO_JOB_QUEUE_COMPARATOR): priority first, then submit time, then
    job id — so ``job -set-priority`` reorders the queue live.

    Submit time is the job's ``sched_anchor``: normally its own submit
    stamp, but pipeline STAGE jobs inherit their pipeline's submit time
    — a chain's late stages keep the chain's queue position instead of
    re-queueing behind every job submitted while the early stages ran
    (start_time stays the tiebreak so stages still order among
    themselves)."""
    return sorted(jobs, key=lambda j: (priority_rank(j.priority),
                                       getattr(j, "sched_anchor",
                                               j.start_time),
                                       j.start_time, str(j.job_id)))


class HybridQueueScheduler(TaskScheduler):
    """FIFO job queue + Shirahata hybrid CPU/TPU map placement.

    Subclass seams: ``_map_job_order`` / ``_reduce_job_order`` decide which
    job is offered the next free slot — the fair and capacity schedulers
    (tpumr.contrib) override only these, inheriting the hybrid CPU/TPU
    passes (an upgrade over the reference, whose contrib schedulers were
    GPU-blind — SURVEY.md §1 L5)."""

    #: FIFO-order cache state: (manager jobs_version, len(jobs)) → sorted
    #: list. The order hooks run PER FREE SLOT per heartbeat (contract
    #: below), which at fleet scale meant thousands of identical
    #: O(jobs log jobs) sorts per second; priority and submit time only
    #: change when the master bumps its jobs_version, so the sorted
    #: order is reused until it does. Subclass overrides (fair/capacity
    #: recompute shares per slot) are unaffected — the cache lives in
    #: the base implementation only.
    _fifo_key: "tuple | None" = None
    _fifo_cache: "list[JobInProgress]" = []

    def __init__(self) -> None:
        super().__init__()
        # --- devcache-affinity placement state ---
        #: job id → TPU passes its maps were held back waiting for a
        #: tag-warm tracker's heartbeat; reset on a warm hit, pinned at
        #: the budget once spent (the job then places cold anywhere)
        self._affinity_defers: "dict[str, int]" = {}
        #: (enabled, defer budget) — conf is master-fixed; parsed once
        self._affinity_conf: "tuple[bool, int] | None" = None
        # per-heartbeat state (the passes run per free slot)
        self._beat_local_tags: "frozenset[str]" = frozenset()
        self._beat_tag_index: "dict[str, Any] | None" = None
        self._beat_affinity: "dict[str, bool]" = {}

    def _priority_fifo_cached(self,
                              jobs: list[JobInProgress]) -> list[JobInProgress]:
        ver_fn = getattr(self.manager, "jobs_version", None)
        if ver_fn is None:
            return _priority_fifo(jobs)
        key = (ver_fn(), len(jobs))
        if key != self._fifo_key:
            self._fifo_cache = _priority_fifo(jobs)
            self._fifo_key = key
        return self._fifo_cache

    def _map_job_order(self, jobs: list[JobInProgress]) -> list[JobInProgress]:
        return self._priority_fifo_cached(jobs)

    def _reduce_job_order(self,
                          jobs: list[JobInProgress]) -> list[JobInProgress]:
        return self._priority_fifo_cached(jobs)

    def _begin_assignment(self, tts: dict) -> None:
        """Called once per heartbeat before the passes — subclasses cache
        heartbeat-invariant state here (the order hooks run per free slot)."""

    # ------------------------------------------ devcache-affinity placement

    def _begin_affinity(self, tts: dict) -> None:
        """Per-heartbeat affinity context: the asking tracker's
        piggybacked devcache tag inventory, the master's cross-tracker
        tag index (getattr-guarded — fakes without it lose deferral,
        not correctness), and a fresh per-job decision memo so the
        per-slot inner loops charge each job's defer budget at most
        once per heartbeat. Lives in ``_assign_tasks`` rather than
        ``_begin_assignment`` because contrib subclasses override the
        latter without chaining up."""
        if self._affinity_conf is None:
            if self.conf is None:
                self._affinity_conf = (True, 3)
            else:
                self._affinity_conf = (
                    confkeys.get_boolean(self.conf,
                                         "tpumr.scheduler.affinity"),
                    max(0, confkeys.get_int(
                        self.conf,
                        "tpumr.scheduler.affinity.defer.passes")))
        self._beat_affinity = {}
        self._beat_local_tags = frozenset(tts.get("devcache_tags") or ())
        self._beat_tag_index = None
        if self._affinity_conf[0]:
            index_fn = getattr(self.manager, "devcache_tag_index", None)
            if index_fn is not None:
                self._beat_tag_index = index_fn()

    def _affinity_defer(self, job: JobInProgress) -> bool:
        """Should the TPU pass hold this job's maps back from the asking
        tracker this heartbeat? True only when the job names side-input
        tags, this tracker's devcache is cold on all of them, some OTHER
        live tracker is warm, and the job still has defer budget — a
        bounded wait for the warm tracker's next heartbeat, never
        starvation (the budget pins once spent and the job places cold).
        FIFO/priority order is never reordered, only deferred."""
        jid = str(job.job_id)
        memo = self._beat_affinity
        if jid in memo:
            return memo[jid]
        memo[jid] = d = self._affinity_defer_uncached(job, jid)
        return d

    def _affinity_defer_uncached(self, job: JobInProgress,
                                 jid: str) -> bool:
        enabled, budget = self._affinity_conf or (True, 3)
        if not enabled:
            return False
        tags_fn = getattr(job, "devcache_tags", None)
        tags = tags_fn() if tags_fn is not None else ()
        if not tags:
            return False
        reg = self.metrics
        if any(t in self._beat_local_tags for t in tags):
            # warm here: assign here (and forgive any defer history)
            self._affinity_defers.pop(jid, None)
            if reg is not None:
                reg.incr("affinity_warm_hits")
            return False
        index = self._beat_tag_index
        if not index or not any(index.get(t) for t in tags):
            return False   # nobody warm anywhere — no reason to wait
        spent = self._affinity_defers.get(jid, 0)
        if spent >= budget:
            if reg is not None:
                reg.incr("affinity_cold_assigns")
            return False   # budget pinned: place cold rather than starve
        self._affinity_defers[jid] = spent + 1
        if reg is not None:
            reg.incr("affinity_defers")
        return True

    def assign_tasks(self, tts: dict) -> list[Task]:
        reg = self.metrics
        if reg is None:
            return self._assign_tasks(tts)
        with reg.histogram("assign_seconds").time():
            assigned = self._assign_tasks(tts)
        for task in assigned:
            if not task.is_map:
                reg.incr("assigned_reduces")
            elif task.run_on_tpu:
                reg.incr("assigned_tpu_maps")
            else:
                reg.incr("assigned_cpu_maps")
        return assigned

    def _assign_tasks(self, tts: dict) -> list[Task]:
        assert self.manager is not None
        jobs = [j for j in self.manager.running_jobs()
                if j.state == JobState.RUNNING]
        if not jobs:
            return []
        self._begin_assignment(tts)
        self._begin_affinity(tts)
        n_trackers = max(1, self.manager.num_trackers())
        host = tts.get("host", "")

        max_cpu = int(tts.get("max_cpu_map_slots", 0))
        max_tpu = int(tts.get("max_tpu_map_slots", 0))
        max_red = int(tts.get("max_reduce_slots", 0))
        run_cpu = int(tts.get("count_cpu_map_tasks", 0))
        run_tpu = int(tts.get("count_tpu_map_tasks", 0))
        run_red = int(tts.get("count_reduce_tasks", 0))
        free_cpu = max(0, max_cpu - run_cpu)
        free_tpu = max(0, max_tpu - run_tpu)
        free_red = max(0, max_red - run_red)
        free_devices = _free_tpu_devices(tts)
        # memory matching (≈ CapacityTaskScheduler): a tracker reporting
        # finite memory only receives tasks whose declared demand fits;
        # consumed locally as this heartbeat assigns. -1 / absent = off.
        mem_left = int(tts.get("available_memory_mb", -1))

        def fits(demand_mb: int) -> bool:
            return mem_left < 0 or demand_mb <= mem_left

        assigned: list[Task] = []

        # ---- per-JOB CPU budgets (a starved hybrid job must not block CPU
        # slots for kernel-less jobs that can only ever run on CPU).
        # Computed LAZILY on first visit: the passes walk the job order
        # front-to-first-assignable, so a wide queue's tail — the common
        # case at fleet scale, where this ran per asking heartbeat —
        # never pays the estimate's arithmetic.
        cpu_budget: dict[str, int] = {}

        def budget_of(job: JobInProgress) -> int:
            jid = str(job.job_id)
            b = cpu_budget.get(jid)
            if b is not None:
                return b
            b = free_cpu
            if job.has_kernel() and not job.tpu_disabled:
                # (quarantined jobs keep the full budget: the TPU pass
                # skips them entirely, so the rule may not zero their
                # CPU share — that combination would deadlock the job
                # with pending maps no pass can assign.) The optimum
                # may put everything on TPU — demoted (CPU-pinned) TIPs
                # still need a floor of CPU slots
                b = max(self._cpu_share(job, free_cpu,
                                        max_tpu * n_trackers),
                        min(free_cpu, job.cpu_pinned_pending_count()))
                if b < min(free_cpu, job.pending_map_count()):
                    job.note_cpu_maps_withheld()
            cpu_budget[jid] = b
            return b

        # ---- TPU pass first (reference order fills GPU after CPU; filling
        # the scarcer, faster pool first avoids giving a map to a CPU slot
        # that a free device could have taken in the same heartbeat)
        for _ in range(free_tpu):
            if not free_devices:
                break
            task = None
            for job in self._map_job_order(jobs):
                if not job.tpu_eligible():
                    # ≈ gpu-executable gate (:342-347), plus the job-
                    # level accelerator quarantine
                    continue
                if job.pending_map_count() == 0 \
                        and not (job.speculative
                                 and not job.speculation_hold):
                    # lock-free precheck (len of a set, stale by at most
                    # a beat): obtain re-checks under the job lock, this
                    # just skips the lock round trip for drained jobs
                    # (a brownout speculation hold drains them too)
                    continue
                if not fits(job.map_memory_mb()):
                    continue
                if self._affinity_defer(job):
                    # this tracker's devcache is cold on the job's side
                    # inputs and a warm tracker is live — hold the maps
                    # for its heartbeat (bounded by the defer budget)
                    continue
                device = free_devices[0]
                task = job.obtain_new_map_task(
                    host, run_on_tpu=True, tpu_device_id=device,
                    rack=tts.get("rack"),
                    tracker=str(tts.get("tracker_name") or ""))
                if task is not None:
                    free_devices.pop(0)  # consume locally (:373-378)
                    break
            if task is None:
                break
            assigned.append(task)
            if mem_left >= 0:
                mem_left -= task.memory_mb

        # ---- CPU pass (:290-327)
        for _ in range(free_cpu):
            task = None
            for job in self._map_job_order(jobs):
                if job.pending_map_count() == 0 \
                        and not (job.speculative
                                 and not job.speculation_hold):
                    continue   # lock-free precheck, same as TPU pass
                if budget_of(job) <= 0:
                    continue
                if not fits(job.map_memory_mb()):
                    continue
                task = job.obtain_new_map_task(host, run_on_tpu=False,
                                               rack=tts.get("rack"))
                if task is not None:
                    cpu_budget[str(job.job_id)] -= 1
                    break
            if task is None:
                break
            assigned.append(task)
            if mem_left >= 0:
                mem_left -= task.memory_mb

        # ---- reduce pass: at most one per heartbeat (:527-560)
        if free_red > 0:
            for job in self._reduce_job_order(jobs):
                if job.pending_reduce_count() == 0 \
                        and not (job.speculative_reduces
                                 and not job.speculation_hold):
                    # lock-free precheck: most jobs in a wide queue have
                    # their (few) reduces already placed — without this,
                    # every heartbeat's reduce pass took every job's
                    # lock just to hear "nothing pending"
                    continue
                if not fits(job.reduce_memory_mb()):
                    continue
                task = job.obtain_new_reduce_task(host)
                if task is not None:
                    assigned.append(task)
                    break

        return assigned

    def _cpu_share(self, job: JobInProgress, n_cpu: int,
                   n_tpu_total: int) -> int:
        """How many of ``n_cpu`` free CPU slots are worth a map of this
        hybrid job now: those that shorten it by the job's estimate
        (``map_cost.cpu_share`` over its pending maps); 0 when the chip
        ends them all sooner. The full share while no chip is serving
        the job (none in the cluster, or another job ahead of it holds
        them): the rule's TPU side would be a promise nobody keeps."""
        if not job.tpu_serving():
            return n_cpu
        cpu, t_tpu = job.map_costs()
        return map_cost.cpu_share(job.pending_map_count(), n_cpu,
                                  n_tpu_total, cpu.seconds, t_tpu)


class FifoScheduler(HybridQueueScheduler):
    """Plain FIFO: hybrid logic off — every map is a CPU map unless the
    tracker has TPU slots and the job a kernel (every free CPU slot gets
    a map whatever the estimate). Mirrors stock JobQueueTaskScheduler
    behavior."""

    def _cpu_share(self, job: JobInProgress, n_cpu: int,
                   n_tpu_total: int) -> int:
        return n_cpu
