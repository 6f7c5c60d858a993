"""Hybrid scheduler unit tests against fakes — the seam the reference tests
the same way (TestJobQueueTaskScheduler.java:33 drives the scheduler against
FakeTaskTrackerManager :114; SURVEY.md §4.1). Deterministic: no daemons, no
clocks — runtimes injected via TaskStatus timestamps."""

import time

import pytest

from tpumr.mapred.ids import JobID
from tpumr.mapred.job_in_progress import JobInProgress
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.scheduler import HybridQueueScheduler
from tpumr.mapred.task import TaskState, TaskStatus


class FakeManager:
    """≈ FakeTaskTrackerManager."""

    def __init__(self, jobs, n_trackers=1):
        self._jobs = jobs
        self._n = n_trackers

    def running_jobs(self):
        return self._jobs

    def num_trackers(self):
        return self._n

    def total_slots(self):
        return {"cpu": 3 * self._n, "tpu": 1 * self._n, "reduce": 2 * self._n}


def make_job(n_maps=8, n_reduces=1, kernel=True, job_num=1, hosts=None):
    conf = {"mapred.reduce.tasks": n_reduces,
            "mapred.reduce.slowstart.completed.maps": 0.0}
    if kernel:
        conf["tpumr.map.kernel"] = "kmeans-assign"
    splits = [{"locations": (hosts or [])} for _ in range(n_maps)]
    return JobInProgress(JobID("test", job_num), conf, splits)


def tracker_status(cpu=3, tpu=1, reduce=2, run_cpu=0, run_tpu=0, run_red=0,
                   devices=None, host="host0"):
    return {
        "tracker_name": "tracker_0", "host": host, "shuffle_port": 0,
        "max_cpu_map_slots": cpu, "max_tpu_map_slots": tpu,
        "max_reduce_slots": reduce,
        "count_cpu_map_tasks": run_cpu, "count_tpu_map_tasks": run_tpu,
        "count_reduce_tasks": run_red,
        "available_tpu_devices": devices if devices is not None
        else [True] * tpu,
    }


def make_scheduler(jobs, n_trackers=1, **conf_kv):
    sched = HybridQueueScheduler()
    conf = JobConf()
    for k, v in conf_kv.items():
        conf.set(k, v)
    sched.configure(conf)
    sched.set_manager(FakeManager(jobs, n_trackers))
    return sched


def finish_map(job, task, runtime, on_tpu):
    now = time.time()
    st = TaskStatus(attempt_id=task.attempt_id, is_map=True,
                    state=TaskState.SUCCEEDED, start_time=now - runtime,
                    finish_time=now, run_on_tpu=on_tpu,
                    tpu_device_id=task.tpu_device_id)
    job.update_task_status(st, "h:0")


def test_fills_both_pools_with_device_ids():
    job = make_job(n_maps=8)
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status(cpu=3, tpu=2,
                                              devices=[True, True]))
    tpu_tasks = [t for t in tasks if t.run_on_tpu]
    cpu_tasks = [t for t in tasks if t.is_map and not t.run_on_tpu]
    reduce_tasks = [t for t in tasks if not t.is_map]
    assert len(tpu_tasks) == 2
    assert sorted(t.tpu_device_id for t in tpu_tasks) == [0, 1]
    assert len(cpu_tasks) == 3
    assert len(reduce_tasks) == 1  # at most one reduce per heartbeat


def test_kernel_gate_blocks_tpu_assignment():
    """Jobs without a device kernel never get TPU slots
    (≈ hadoop.pipes.gpu.executable gate, JobQueueTaskScheduler.java:342-347)."""
    job = make_job(kernel=False)
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status())
    assert all(not t.run_on_tpu for t in tasks)
    assert len([t for t in tasks if t.is_map]) == 3  # CPU pass still runs


def test_no_free_device_no_tpu_task():
    job = make_job()
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status(tpu=1, devices=[False]))
    assert all(not t.run_on_tpu for t in tasks)


def profile(job, cpu_s, tpu_s):
    """One finished map a backend: what the estimate stands on until a
    slot has turned (the TPU map's runtime then stands for its turn)."""
    for on_tpu, runtime in [(False, cpu_s), (True, tpu_s)]:
        t = job.obtain_new_map_task("host0", run_on_tpu=on_tpu,
                                    tpu_device_id=0 if on_tpu else -1)
        finish_map(job, t, runtime, on_tpu)


def cpu_maps(tasks):
    return [t for t in tasks if t.is_map and not t.run_on_tpu]


def test_rule_starves_cpu_when_chip_ends_the_job_sooner():
    """The paper's claim (:290-291, give the CPU slots nothing once the
    accelerator is far ahead), by the one rule: 17 pending maps are 9
    turns of two TPU slots at 1 s, sooner than ONE CPU map at 10 s."""
    job = make_job(n_maps=20)
    profile(job, cpu_s=10.0, tpu_s=1.0)
    assert job.acceleration_factor() == 10.0

    sched = make_scheduler([job], n_trackers=2)
    tasks = sched.assign_tasks(tracker_status())
    assert [t.run_on_tpu for t in tasks if t.is_map] == [True]

    # with no estimate at all (a first job, first beat): the full share
    fresh = make_job(n_maps=20, job_num=2)
    sched2 = make_scheduler([fresh], n_trackers=2)
    assert len(cpu_maps(sched2.assign_tasks(tracker_status()))) == 3


def test_rule_keeps_cpu_under_heavy_load():
    job = make_job(n_maps=500)
    profile(job, cpu_s=10.0, tpu_s=1.0)
    sched = make_scheduler([job], n_trackers=2)
    # 497 pending are 249 s of two TPU slots: every CPU wave shortens it
    assert len(cpu_maps(sched.assign_tasks(tracker_status()))) == 3


def test_rule_puts_everything_on_tpu_when_faster():
    """The f(x,y) minimization (reference's commented-out :181-219): 7
    pending maps, TPU 10× faster, 1 TPU slot → optimum is x=0 CPU tasks
    (7×1s on TPU beats any CPU share at 10s each)."""
    job = make_job(n_maps=10)
    profile(job, cpu_s=10.0, tpu_s=1.0)
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status())
    assert [t.run_on_tpu for t in tasks if t.is_map] == [True]

    # inverse profile: CPU faster → CPU pass fills all slots
    job2 = make_job(n_maps=10, job_num=2)
    profile(job2, cpu_s=1.0, tpu_s=10.0)
    sched2 = make_scheduler([job2])
    assert len(cpu_maps(sched2.assign_tasks(tracker_status()))) == 3


def test_locality_preference():
    job = make_job(n_maps=4, hosts=["far"])
    job.host_cache = {"host0": {2}, "far": {0, 1, 3}}
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status(cpu=1, tpu=0, host="host0"))
    assert tasks[0].partition == 2  # node-local split chosen first


def test_fifo_across_jobs():
    j1 = make_job(n_maps=2, job_num=1, kernel=False)
    j2 = make_job(n_maps=8, job_num=2, kernel=False)
    sched = make_scheduler([j1, j2])
    tasks = sched.assign_tasks(tracker_status(cpu=4, tpu=0))
    # j1 exhausted first, then j2
    jobs_in_order = [str(t.attempt_id.task.job) for t in tasks if t.is_map]
    assert jobs_in_order[:2] == ["job_test_0001"] * 2
    assert all(j == "job_test_0002" for j in jobs_in_order[2:])


def test_failure_requeues_and_eventually_fails_job():
    job = make_job(n_maps=1, kernel=False)
    for attempt in range(4):
        t = job.obtain_new_map_task("h", run_on_tpu=False)
        assert t is not None and t.attempt_id.attempt == attempt
        st = TaskStatus(attempt_id=t.attempt_id, is_map=True,
                        state=TaskState.FAILED, diagnostics="boom")
        job.update_task_status(st, "h:0")
    assert job.state == "FAILED"
    assert "4 times" in job.error


def test_speculative_duplicate_success_ignored():
    job = make_job(n_maps=1, n_reduces=0, kernel=False)
    t0 = job.obtain_new_map_task("h", run_on_tpu=False)
    # second (speculative) attempt of same task
    tip = job.maps[0]
    a1 = tip.new_attempt()
    finish_map(job, t0, 1.0, False)
    assert job.finished_maps == 1
    st = TaskStatus(attempt_id=a1, is_map=True, state=TaskState.SUCCEEDED)
    job.update_task_status(st, "h:0")
    assert job.finished_maps == 1  # not double counted
    assert job.state == "SUCCEEDED"


def test_lost_tracker_requeues_completed_maps():
    job = make_job(n_maps=2, n_reduces=1, kernel=False)
    t0 = job.obtain_new_map_task("h", run_on_tpu=False)
    finish_map(job, t0, 1.0, False)
    assert job.finished_maps == 1
    aid = job.maps[0].successful_attempt
    job.requeue_lost_attempts([aid])
    assert job.finished_maps == 0
    assert job.pending_map_count() == 2
    # the event feed is append-only (cursor-based consumers): the lost
    # output's event is OBSOLETE-marked + tombstoned, never removed
    assert not [e for e in job.completion_events
                if e.get("status") != "OBSOLETE"]
    assert any(e["attempt_id"] == aid and e.get("status") == "OBSOLETE"
               for e in job.completion_events)


@pytest.mark.parametrize("is_map", [True, False], ids=["map", "reduce"])
def test_lost_tracker_requeues_attempt_it_never_reported(is_map):
    """An attempt launched in a response its tracker did not live to
    read has no status on the master yet. The tracker's loss must hand
    the task out again all the same (found by the churn_storm mix: a
    tracker killed between send and receive left a map `running` for
    good, and the job never finished)."""
    job = make_job(n_maps=1, n_reduces=1, kernel=False)
    task = job.obtain_new_map_task("h", run_on_tpu=False) if is_map \
        else job.obtain_new_reduce_task("h")
    pending = job.pending_map_count if is_map else job.pending_reduce_count
    tip = (job.maps if is_map else job.reduces)[0]
    aid = str(task.attempt_id)
    assert pending() == 0 and aid not in tip.attempts
    job.requeue_lost_attempts([aid])
    assert pending() == 1 and tip.state == "pending"
    # settled KILLED: it burns no attempt of the task's budget, and a
    # late status of the dead attempt cannot resurrect it
    assert tip.attempts[aid].state == TaskState.KILLED
    assert tip.failures == 0
    job.update_task_status(TaskStatus(
        attempt_id=task.attempt_id, is_map=is_map,
        state=TaskState.SUCCEEDED), "h:0")
    assert pending() == 1 and tip.state == "pending"
    again = job.obtain_new_map_task("h2", run_on_tpu=False) if is_map \
        else job.obtain_new_reduce_task("h2")
    assert again.attempt_id.attempt == 1
    # the master hands a lost tracker's attempts of EVERY job to each
    # job: another job's must not pass for this one's
    other = make_job(n_maps=1, n_reduces=1, kernel=False, job_num=2)
    other.obtain_new_map_task("h3", run_on_tpu=False)
    other.obtain_new_reduce_task("h3")
    other.requeue_lost_attempts([aid])
    for tip in (other.maps[0], other.reduces[0]):
        assert tip.state == "running" and not tip.attempts


def test_rule_adapts_per_job_to_what_it_measures():
    """One cluster, one rule, no key: two jobs in one queue get the CPU
    share their OWN measured costs give them (a factor of 10 starves,
    a factor of 1.5 over many maps does not)."""
    far = make_job(n_maps=10)
    profile(far, cpu_s=10.0, tpu_s=1.0)
    near = make_job(n_maps=40, job_num=2)
    profile(near, cpu_s=1.5, tpu_s=1.0)
    sched = make_scheduler([far, near])
    tasks = sched.assign_tasks(tracker_status())
    by_job = {str(j.job_id): [t for t in tasks if t.is_map
                              and t.attempt_id.task.job == j.job_id]
              for j in (far, near)}
    # the TPU slot went to the head of the queue; its CPU share is 0
    assert [t.run_on_tpu for t in by_job[str(far.job_id)]] == [True]
    # the job behind it holds no chip: the rule's TPU side would be a
    # promise nobody keeps, so the free CPU slots are its to use
    assert len(cpu_maps(by_job[str(near.job_id)])) == 3
    counters = far.counters
    from tpumr.core.counters import JobCounter
    assert counters.value(JobCounter.GROUP,
                          JobCounter.CPU_MAPS_WITHHELD) == 1
    assert near.counters.value(JobCounter.GROUP,
                               JobCounter.CPU_MAPS_WITHHELD) == 0

    # alone, with a chip of its own, the near job still gets CPU maps:
    # 37 pending at 1 s a turn are longer than waves of 1.5 s
    near2 = make_job(n_maps=40, job_num=3)
    profile(near2, cpu_s=1.5, tpu_s=1.0)
    sched2 = make_scheduler([near2])
    assert len(cpu_maps(sched2.assign_tasks(tracker_status()))) == 3


def test_within_job_convergence_timeline():
    """The convergence clause end-to-end at the scheduler level: a many-
    map job starts with no estimate (both pools fill); once both costs
    are known and the pending maps are fewer turns of the chip than a
    CPU map takes, the CPU pass stops and the TAIL of the job is
    all-TPU."""
    job = make_job(n_maps=24)
    sched = make_scheduler([job], n_trackers=2)
    placements = []
    for _hb in range(100):
        if job.pending_map_count() == 0:
            break
        tasks = [t for t in sched.assign_tasks(tracker_status())
                 if t.is_map]
        for t in tasks:
            placements.append(t.run_on_tpu)
            # every map "runs" instantly: CPU maps 10s, TPU maps 1s
            finish_map(job, t, 10.0 if not t.run_on_tpu else 1.0,
                       t.run_on_tpu)
    assert job.pending_map_count() == 0
    # early waves used the CPU pool (TPU pass runs first, so the first
    # heartbeat is 1 TPU + 3 CPU maps), and the tail converged to all-TPU
    assert not all(placements[:4])
    assert placements[-1] and placements[-2]
    tail = 0
    for b in reversed(placements):
        if not b:
            break
        tail += 1
    # accel=10, two TPU slots -> no CPU share from pending<=20: nearly
    # the whole job after the first profiled wave goes TPU
    assert tail >= 10, (placements, tail)


def test_priority_reorders_fifo_queue():
    """≈ JobQueueJobInProgressListener's FIFO comparator: priority
    outranks submit order, and set_job_priority reorders a live queue
    (hadoop job -set-priority)."""
    j1 = make_job(n_maps=2, job_num=1, kernel=False)
    j2 = make_job(n_maps=2, job_num=2, kernel=False)
    j2.priority = "HIGH"
    sched = make_scheduler([j1, j2])
    tasks = sched.assign_tasks(tracker_status(cpu=4, tpu=0))
    order = [str(t.attempt_id.task.job) for t in tasks if t.is_map]
    # HIGH j2 drains before NORMAL j1 despite submitting second
    assert order[:2] == ["job_test_0002"] * 2
    assert all(j == "job_test_0001" for j in order[2:])


def test_priority_from_conf_and_validation():
    import pytest

    from tpumr.mapred.job_in_progress import normalize_priority
    j = make_job(job_num=3)
    assert j.priority == "NORMAL"
    conf = {"mapred.reduce.tasks": 0, "mapred.job.priority": "very_low"}
    jlow = JobInProgress(JobID("test", 4), conf, [{"locations": []}])
    assert jlow.priority == "VERY_LOW"
    with pytest.raises(ValueError, match="unknown job priority"):
        normalize_priority("URGENT")
