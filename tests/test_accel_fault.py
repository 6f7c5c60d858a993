"""Accelerator fault tolerance — failure-classified retries, TPU→CPU
demotion, job-level TPU quarantine, per-device tracker quarantine, and
hung-task reaping (≈ mapred.task.timeout + TaskTracker's
markUnresponsiveTasks; demotion/quarantine are new capabilities over the
reference, which re-lands a deterministically-crashing kernel on the
same backend until the job dies).

The two mini-cluster chaos e2es at the bottom are the acceptance runs:
persistent injected TPU execute failures must complete byte-identically
to a CPU-only run via the demotion path, and an injected hung map must
be reaped within ``mapred.task.timeout`` with the job finishing
byte-correct. ``TPUMR_FI_SEED`` pins the fault-injection RNG (the CI
chaos-smoke job sets it)."""

import os
import time
from collections import Counter

import pytest

from tpumr.core.counters import JobCounter
from tpumr.mapred.ids import JobID, TaskAttemptID
from tpumr.mapred.job_in_progress import JobInProgress, JobState
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.node_health import TpuDeviceHealth
from tpumr.mapred.task import (FailureClass, TaskState, TaskStatus,
                               classify_accelerator_exception,
                               classify_exception, tag_failure)
from tpumr.utils import fi

FI_SEED = os.environ.get("TPUMR_FI_SEED", "20260804")


def _conf(**kv):
    conf = JobConf()
    for k, v in kv.items():
        conf.set(k, v)
    return conf


# ------------------------------------------------------- classification


class TestFailureClassification:
    def test_site_tag_wins(self):
        e = tag_failure(RuntimeError("boom"), FailureClass.DEVICE)
        assert classify_exception(e) == "device"
        # first stamp wins — a later tag cannot reclassify
        tag_failure(e, FailureClass.USER)
        assert classify_exception(e) == "device"

    def test_memory_errors_are_oom(self):
        assert classify_exception(MemoryError()) == "oom"
        assert classify_exception(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                         "to allocate")) == "oom"

    def test_default_is_user(self):
        assert classify_exception(TypeError("unhashable")) == "user"

    def test_cold_compile_text_classes_compile(self):
        e = RuntimeError("Mosaic lowering failed: unsupported op")
        assert classify_accelerator_exception(
            e, compile_cold=True) == "compile"
        # the same error on a WARM dispatch is not a compile failure
        assert classify_accelerator_exception(
            e, compile_cold=False) == "user"

    def test_xla_errors_are_device(self):
        e = RuntimeError("INTERNAL: XLA stream executor failure")
        assert classify_accelerator_exception(e) == "device"

    def test_injected_fault_carries_class(self):
        fi.reset()
        conf = _conf(**{"tpumr.fi.classed.point.probability": 1.0})
        with pytest.raises(fi.InjectedFault) as ei:
            fi.maybe_fail("classed.point", conf,
                          failure_class=FailureClass.DEVICE)
        assert classify_exception(ei.value) == "device"
        fi.reset()


class TestFiresSeam:
    def setup_method(self):
        fi.reset()

    def teardown_method(self):
        fi.reset()

    def test_fires_honors_probability_and_limit(self):
        conf = _conf(**{"tpumr.fi.behave.probability": 1.0,
                        "tpumr.fi.behave.max.failures": 2})
        assert [fi.fires("behave", conf) for _ in range(4)] == \
            [True, True, False, False]
        assert fi.fired("behave") == 2
        assert fi.fires("behave", None) is False
        assert fi.fires("unconfigured", conf) is False

    def test_fires_and_maybe_fail_share_determinism(self):
        a = _conf(**{"tpumr.fi.det.probability": 0.5,
                     "tpumr.fi.seed": FI_SEED})
        seq = [fi.fires("det", a) for _ in range(64)]
        fi.reset()
        seq2 = []
        for _ in range(64):
            try:
                fi.maybe_fail("det", a)
                seq2.append(False)
            except fi.InjectedFault:
                seq2.append(True)
        assert seq == seq2 and 0 < sum(seq) < 64


# ------------------------------------------ JIP demotion / quarantine


def _job(n_maps=2, n_reduces=1, **conf):
    base = {"mapred.reduce.tasks": n_reduces,
            "mapred.speculative.execution": False,
            "mapred.reduce.slowstart.completed.maps": 0.0,
            "tpumr.map.kernel": "sleep"}
    base.update(conf)
    return JobInProgress(JobID("af", 1),
                         splits=[{"locations": []} for _ in range(n_maps)],
                         conf_dict=base)


def _fail_attempt(job, task, failure_class="", on_tpu=True, runtime=1.0):
    now = time.time()
    job.update_task_status(TaskStatus(
        attempt_id=task.attempt_id, is_map=task.is_map, run_on_tpu=on_tpu,
        tpu_device_id=task.tpu_device_id, state=TaskState.FAILED,
        failure_class=failure_class, start_time=now - runtime,
        finish_time=now), "t:0")


def _finish(job, task, runtime=1.0, on_tpu=False):
    now = time.time()
    job.update_task_status(TaskStatus(
        attempt_id=task.attempt_id, is_map=task.is_map, run_on_tpu=on_tpu,
        state=TaskState.SUCCEEDED, start_time=now - runtime,
        finish_time=now), "t:0")


class TestTipDemotion:
    def test_device_failure_pins_tip_cpu_only(self):
        job = _job(n_maps=1)
        t = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        _fail_attempt(job, t, FailureClass.DEVICE)
        # the re-queued TIP is invisible to the TPU pass, visible to CPU
        assert job.obtain_new_map_task("h", run_on_tpu=True,
                                       tpu_device_id=0) is None
        cpu = job.obtain_new_map_task("h", run_on_tpu=False)
        assert cpu is not None and not cpu.run_on_tpu
        assert job.counters.value(JobCounter.GROUP,
                                  JobCounter.TPU_DEMOTIONS) == 1
        events = job.drain_accel_events()
        assert [e["kind"] for e in events] == ["tip_demoted"]
        assert events[0]["failure_class"] == "device"
        assert job.drain_accel_events() == []   # drained exactly once
        assert job.status_dict()["tpu_demoted_tips"] == 1

    def test_compile_failures_demote_too(self):
        job = _job(n_maps=1)
        t = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        _fail_attempt(job, t, FailureClass.COMPILE)
        assert job.obtain_new_map_task("h", run_on_tpu=True) is None

    def test_user_and_unclassified_failures_do_not_demote(self):
        for fc in (FailureClass.USER, FailureClass.OOM,
                   FailureClass.TIMEOUT, ""):
            job = _job(n_maps=1)
            t = job.obtain_new_map_task("h", run_on_tpu=True,
                                        tpu_device_id=0)
            _fail_attempt(job, t, fc)
            again = job.obtain_new_map_task("h", run_on_tpu=True,
                                            tpu_device_id=0)
            assert again is not None, f"class {fc!r} must not demote"
            assert job.counters.value(JobCounter.GROUP,
                                      JobCounter.TPU_DEMOTIONS) == 0

    def test_cpu_failures_never_demote(self):
        job = _job(n_maps=1)
        t = job.obtain_new_map_task("h", run_on_tpu=False)
        _fail_attempt(job, t, FailureClass.DEVICE, on_tpu=False)
        assert job.obtain_new_map_task("h", run_on_tpu=True,
                                       tpu_device_id=0) is not None

    def test_retries_knob_allows_more_tpu_attempts(self):
        job = _job(n_maps=1, **{"tpumr.tpu.attempt.retries": 2})
        t = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        _fail_attempt(job, t, FailureClass.DEVICE)
        t2 = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        assert t2 is not None          # one more TPU try allowed
        _fail_attempt(job, t2, FailureClass.DEVICE)
        assert job.obtain_new_map_task("h", run_on_tpu=True,
                                       tpu_device_id=0) is None
        assert job.maps[0].tpu_failures == 2

    def test_demoted_tip_keeps_attempt_budget_for_cpu(self):
        """Demotion must not eat into mapred.map.max.attempts beyond the
        failures that actually happened."""
        job = _job(n_maps=1, **{"mapred.map.max.attempts": 3})
        t = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        _fail_attempt(job, t, FailureClass.DEVICE)
        assert job.state == JobState.RUNNING
        assert job.maps[0].failures == 1
        cpu = job.obtain_new_map_task("h", run_on_tpu=False)
        _finish(job, cpu)
        assert job.maps[0].state == "succeeded"


class TestJobTpuQuarantine:
    def _quarantine(self, job, n_tips=3):
        for _ in range(n_tips):
            t = job.obtain_new_map_task("h", run_on_tpu=True,
                                        tpu_device_id=0)
            assert t is not None
            _fail_attempt(job, t, FailureClass.DEVICE)

    def test_distinct_tips_disable_the_tpu_pass(self):
        job = _job(n_maps=4, **{"tpumr.tpu.job.quarantine.tips": 3})
        self._quarantine(job)
        assert job.tpu_disabled
        assert not job.tpu_eligible()
        assert job.obtain_new_map_task("h", run_on_tpu=True,
                                       tpu_device_id=0) is None
        # the 4th (never-TPU-failed) map still runs on CPU
        assert job.obtain_new_map_task("h", run_on_tpu=False) is not None
        kinds = [e["kind"] for e in job.drain_accel_events()]
        assert kinds.count("job_tpu_quarantined") == 1
        assert job.status_dict()["tpu_disabled"] is True

    def test_one_tip_failing_repeatedly_is_not_a_job_quarantine(self):
        job = _job(n_maps=4, **{"tpumr.tpu.job.quarantine.tips": 3,
                                "tpumr.tpu.attempt.retries": 10,
                                "mapred.map.max.attempts": 20})
        for _ in range(5):
            t = job.obtain_new_map_task("h", run_on_tpu=True,
                                        tpu_device_id=0)
            _fail_attempt(job, t, FailureClass.DEVICE)
        assert not job.tpu_disabled   # one tip, many failures: not 3 TIPs

    def test_profile_sums_unwound_and_factor_reset(self):
        job = _job(n_maps=5, **{"tpumr.tpu.job.quarantine.tips": 3})
        # profile data on both backends first: TPU looks 4x faster
        t = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        _finish(job, t, runtime=1.0, on_tpu=True)
        c = job.obtain_new_map_task("h", run_on_tpu=False)
        _finish(job, c, runtime=4.0, on_tpu=False)
        assert job.acceleration_factor() == pytest.approx(4.0)
        self._quarantine(job)
        assert job.tpu_disabled
        assert job.finished_tpu_maps == 0
        assert job._tpu_time_sum == pytest.approx(0.0)
        assert job.acceleration_factor() == 1.0
        # an in-flight TPU completion trickling in post-quarantine must
        # not resurrect the poisoned factor (still counts as a finished
        # map — the work is real)
        finished = job.finished_maps
        straggler = job.maps[4]
        aid = TaskAttemptID(straggler.task_id, 7)
        now = time.time()
        job.update_task_status(TaskStatus(
            attempt_id=aid, is_map=True, run_on_tpu=True,
            state=TaskState.SUCCEEDED, start_time=now - 0.5,
            finish_time=now), "t:0")
        assert job.finished_maps == finished + 1
        assert job.finished_tpu_maps == 0
        assert job.acceleration_factor() == 1.0
        # ...and it must not be misattributed to the CPU profile either
        assert job.finished_cpu_maps == 1
        assert job._cpu_time_sum == pytest.approx(4.0)


class TestSchedulerQuarantineInteraction:
    def test_cpu_share_deadlock_broken_by_quarantine(self):
        """The regression this PR exists for: a quarantined job whose
        estimate gave it a zero CPU budget used to keep it while the TPU
        pass skipped it — pending maps no pass could ever assign."""
        from test_scheduler import (finish_map, make_job, make_scheduler,
                                    tracker_status)
        job = make_job(n_maps=8)
        sched = make_scheduler([job])
        # both costs known, so the rule is live (TPU 10x faster; the
        # pending maps are fewer turns than one CPU map)
        t = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        finish_map(job, t, runtime=0.1, on_tpu=True)
        c = job.obtain_new_map_task("h", run_on_tpu=False)
        finish_map(job, c, runtime=1.0, on_tpu=False)
        # no CPU share: the CPU pass assigns nothing (only the TPU pass
        # places work)
        before = sched.assign_tasks(tracker_status(cpu=3, tpu=1,
                                                   reduce=0))
        assert before and all(x.run_on_tpu for x in before)
        job.tpu_disabled = True
        tasks = sched.assign_tasks(tracker_status(cpu=3, tpu=1,
                                                  reduce=0))
        assert tasks, "quarantined job must fall back to the CPU pass"
        assert all(not x.run_on_tpu for x in tasks)

    def test_tpu_pass_skips_quarantined_job_for_next_in_queue(self):
        from test_scheduler import make_job, make_scheduler, tracker_status
        quarantined = make_job(n_maps=4, job_num=1)
        quarantined.tpu_disabled = True
        healthy = make_job(n_maps=4, job_num=2)
        sched = make_scheduler([quarantined, healthy])
        tasks = sched.assign_tasks(tracker_status(cpu=0, tpu=1, reduce=0))
        assert len(tasks) == 1 and tasks[0].run_on_tpu
        assert tasks[0].attempt_id.task.job == healthy.job_id


# ------------------------------------------------------- device health


class TestTpuDeviceHealth:
    def test_consecutive_threshold_and_streak_reset(self):
        dh = TpuDeviceHealth(2, threshold=3, probe=lambda d: None,
                             probe_interval_s=3600)
        try:
            assert not dh.record_failure(0)
            assert not dh.record_failure(0)
            dh.record_success(0)            # streak broken
            assert not dh.record_failure(0)
            assert not dh.record_failure(0)
            assert dh.record_failure(0)     # third consecutive: bad
            assert dh.quarantined() == [0]
            assert dh.is_quarantined(0) and not dh.is_quarantined(1)
            # further failures on a quarantined device are not new events
            assert not dh.record_failure(0)
            assert dh.quarantine_events == 1
        finally:
            dh.stop()

    def test_probe_restores_and_backs_off_capped(self):
        sick = [True]
        probes = []

        def probe(d):
            probes.append(d)
            if sick[0]:
                raise RuntimeError("still dead")

        dh = TpuDeviceHealth(1, threshold=1, probe=probe,
                             probe_interval_s=1.0, probe_max_interval_s=4.0)
        try:
            assert dh.record_failure(0)
            now = time.monotonic()
            # deterministic probe driving: each failed probe doubles the
            # backoff up to the cap (1 → 2 → 4 → 4)
            deadlines = []
            for _ in range(4):
                at, backoff = dh._quarantined[0]
                deadlines.append(backoff)
                assert dh.probe_once(now=at) == []
            assert deadlines == [1.0, 2.0, 4.0, 4.0]
            assert dh.quarantined() == [0]
            sick[0] = False               # the injected fault clears
            at, _ = dh._quarantined[0]
            assert dh.probe_once(now=at) == [0]
            assert dh.quarantined() == []
            assert dh.restore_events == 1
            assert len(probes) == 5
            # requarantine works after a restore
            assert dh.record_failure(0)
        finally:
            dh.stop()

    def test_zero_threshold_disables(self):
        dh = TpuDeviceHealth(1, threshold=0, probe=lambda d: None)
        assert not dh.record_failure(0)
        assert dh.quarantined() == []
        dh.stop()


class TestTrackerDeviceQuarantine:
    def test_quarantine_shrinks_heartbeat_slots_and_probe_restores(self):
        """Acceptance: quarantine observably shrinks the tracker's
        advertised TPU slots on heartbeat; the probe restores them once
        the fault clears."""
        from tpumr.mapred.mini_cluster import MiniMRCluster
        base = JobConf()
        base.set("tpumr.tpu.device.quarantine.failures", 2)
        with MiniMRCluster(num_trackers=1, conf=base, cpu_slots=1,
                           tpu_slots=2, tpu_devices_per_tracker=2) as c:
            tracker = c.trackers[0]
            dh = tracker.device_health
            assert dh is not None and dh.threshold == 2
            sick = [True]

            def probe(d):
                if sick[0]:
                    raise RuntimeError("injected device fault")

            dh.probe = probe
            dh.record_failure(1)
            assert dh.record_failure(1)          # 2 consecutive: bad
            st = tracker._status_dict()
            assert st["max_tpu_map_slots"] == 1  # 2 - 1 quarantined
            assert st["quarantined_tpu_devices"] == [1]
            assert st["available_tpu_devices"][1] is False

            # the master sees the shrunken pool on the next heartbeat
            deadline = time.time() + 5
            while time.time() < deadline:
                with c.master.lock:
                    infos = list(c.master.trackers.values())
                if infos and infos[0].status.get(
                        "quarantined_tpu_devices") == [1]:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("master never saw the quarantined device")
            assert c.master.total_slots()["tpu"] == 1
            snap = c.master.metrics.snapshot()["jobtracker"]
            assert snap["tpu_devices_quarantined"] == 1

            # fault clears → the probe re-admits the device
            sick[0] = False
            at, _ = dh._quarantined[1]
            assert dh.probe_once(now=at) == [1]
            st = tracker._status_dict()
            assert st["max_tpu_map_slots"] == 2
            assert st["quarantined_tpu_devices"] == []
            assert st["available_tpu_devices"][1] is True


# ------------------------------------------- health-report visibility


class TestHealthReportSurfaced:
    def test_unhealthy_reason_in_active_trackers_and_page(self):
        """Satellite: the NodeHealthChecker ERROR reason reaches the
        cluster-wide surfaces (`-list-active-trackers` output and the
        JT /trackers page), not just the node itself."""
        from tpumr.mapred.jobtracker import JobMaster
        jm = JobMaster(_conf())
        try:
            def beat(name, healthy, report=""):
                jm.heartbeat({
                    "tracker_name": name, "host": "127.0.0.1",
                    "shuffle_port": 0, "max_cpu_map_slots": 1,
                    "max_tpu_map_slots": 0, "max_reduce_slots": 1,
                    "count_cpu_map_tasks": 0, "count_tpu_map_tasks": 0,
                    "count_reduce_tasks": 0, "task_statuses": [],
                    "healthy": healthy, "health_report": report,
                }, True, False, 0)

            beat("tr_ok", True)
            beat("tr_sick", False, "ERROR disk full on /scratch")
            active = jm.get_active_trackers()
            assert "tr_ok" in active
            sick = [a for a in active if a.startswith("tr_sick")]
            assert sick and "ERROR disk full on /scratch" in sick[0]
        finally:
            jm.stop()


# ------------------------------------------------- recovery satellites


class TestRecoveryFailurePaths:
    def _master(self, tmp_path):
        from tpumr.mapred.jobtracker import JobMaster
        conf = JobConf()
        conf.set("tpumr.history.dir", str(tmp_path))
        conf.set("mapred.jobtracker.restart.recover", True)
        return JobMaster(conf)

    def _write_submitted(self, tmp_path, job_id, **extra):
        import json
        ev = {"event": "JOB_SUBMITTED", "job_id": job_id,
              "conf": {"mapred.job.name": "wreck",
                       "mapred.reduce.tasks": 0},
              "conf_dropped": [], "splits": [{"locations": []}]}
        ev.update(extra)
        with open(os.path.join(str(tmp_path), f"{job_id}.jsonl"),
                  "a") as f:
            f.write(json.dumps(ev) + "\n")

    def _events(self, tmp_path, job_id):
        from tpumr.mapred.history import JobHistory
        return JobHistory.read(os.path.join(str(tmp_path),
                                            f"{job_id}.jsonl"))

    def test_conf_dropped_skips_and_flags(self, tmp_path):
        self._write_submitted(tmp_path, "job_x_0001",
                              conf_dropped=["mapred.mapper.class"])
        jm = self._master(tmp_path).start()
        try:
            assert jm.jobs == {}   # NOT resubmitted broken
            snap = jm.metrics.snapshot()["jobtracker"]
            assert snap["jobs_recovery_failed"] == 1
            assert snap.get("jobs_recovered", 0) == 0
        finally:
            jm.stop()
        evs = self._events(tmp_path, "job_x_0001")
        failed = [e for e in evs if e["event"] == "JOB_RECOVERY_FAILED"]
        assert len(failed) == 1
        assert "mapred.mapper.class" in failed[0]["error"]
        # the failure marker is terminal: a second restart doesn't retry
        jm2 = self._master(tmp_path).start()
        try:
            assert jm2.metrics.snapshot()["jobtracker"].get(
                "jobs_recovery_failed", 0) == 0
        finally:
            jm2.stop()

    def test_submit_raise_flags_and_continues(self, tmp_path):
        # splits that blow up JobInProgress construction inside submit_job
        self._write_submitted(tmp_path, "job_x_0001", splits=17)
        self._write_submitted(tmp_path, "job_x_0002")   # healthy sibling
        jm = self._master(tmp_path).start()
        try:
            snap = jm.metrics.snapshot()["jobtracker"]
            assert snap["jobs_recovery_failed"] == 1
            assert snap["jobs_recovered"] == 1   # the sibling made it
            assert len(jm.jobs) == 1
        finally:
            jm.stop()
        evs = self._events(tmp_path, "job_x_0001")
        assert [e["event"] for e in evs
                if e["event"].startswith("JOB_RECOVERY")] \
            == ["JOB_RECOVERY_FAILED"]


# ------------------------------------------------------------ e2e chaos


def _register_faultcount_kernel():
    """A wordcount-style kernel whose TPU and CPU batch paths emit
    identical records — the byte-identity contract the demotion e2e
    asserts. Registered in-process (the mini-cluster shares this
    interpreter)."""
    from tpumr.ops.registry import KernelMapper, register_kernel

    def _count(batch):
        counts = Counter()
        for _k, v in batch:
            counts.update(bytes(v).split())
        return sorted(counts.items())

    class FaultCountKernel(KernelMapper):
        name = "faultcount"

        def map_batch(self, batch, conf, task):
            return _count(batch)

        map_batch_cpu = staticmethod(lambda batch, conf, task:
                                     _count(batch))

    return register_kernel(FaultCountKernel())


def _run_wordcount_job(cluster, fs, in_path, out_path, kernel=None,
                       **conf_kv):
    from tpumr.mapred.job_client import JobClient
    conf = cluster.create_job_conf()
    conf.set_input_paths(in_path)
    conf.set_output_path(out_path)
    conf.set("mapred.mapper.class", "tpumr.mapred.lib.TokenCountMapper")
    conf.set("mapred.reducer.class", "tpumr.examples.basic.LongSumReducer")
    conf.set("mapred.map.tasks", 4)
    conf.set_num_reduce_tasks(1)
    if kernel:
        conf.set_map_kernel(kernel)
    for k, v in conf_kv.items():
        conf.set(k, v)
    return JobClient(conf).run_job(conf)


def _output_bytes(fs, out_dir):
    return b"".join(fs.read_bytes(st.path)
                    for st in sorted(fs.list_status(out_dir),
                                     key=lambda s: str(s.path))
                    if "part-" in str(st.path))


def _write_input(fs, path, n=2000):
    fs.write_bytes(path, b"".join(b"w%02d x\n" % (i % 23)
                                  for i in range(n)))


class TestEndToEndDemotionChaos:
    def test_persistent_tpu_faults_complete_via_cpu_demotion(self, tmp_path):
        """Acceptance: with tpumr.fi injecting PERSISTENT TPU execute
        failures, the job completes byte-identically to a CPU-only run,
        TPU_DEMOTIONS > 0, and the job never fails. Also exports the
        merged job trace for the CI chaos-smoke artifact."""
        fi.reset()
        from tpumr.fs import FileSystem, get_filesystem
        from tpumr.mapred.mini_cluster import MiniMRCluster
        _register_faultcount_kernel()
        try:
            fs = get_filesystem("mem:///")
            _write_input(fs, "/af/in.txt")

            # control: CPU-only cluster (no TPU slots at all)
            with MiniMRCluster(num_trackers=2, cpu_slots=2,
                               tpu_slots=0) as c:
                control = _run_wordcount_job(c, fs, "mem:///af/in.txt",
                                             "mem:///af/out-cpu",
                                             kernel="faultcount")
                assert control.successful
                want = _output_bytes(fs, "/af/out-cpu")
            assert want  # the control run must actually produce bytes

            # chaos: every TPU execution fails, persistently, classed
            # device — the demotion path is the only road to completion
            base = JobConf()
            base.set("tpumr.fi.tpu.execute.probability", 1.0)
            base.set("tpumr.fi.seed", FI_SEED)
            base.set("tpumr.trace.enabled", True)
            base.set("tpumr.history.dir", str(tmp_path))
            with MiniMRCluster(num_trackers=2, conf=base, cpu_slots=2,
                               tpu_slots=1) as c:
                result = _run_wordcount_job(
                    c, fs, "mem:///af/in.txt", "mem:///af/out-chaos",
                    kernel="faultcount",
                    **{"tpumr.tpu.job.quarantine.tips": 3})
                assert result.successful, \
                    "persistent TPU faults must demote, not fail the job"
                got = _output_bytes(fs, "/af/out-chaos")
                assert got == want, "demotion path must be byte-identical"

                jip = c.master.jobs[str(result.job_id)]
                assert jip.counters.value(
                    JobCounter.GROUP, JobCounter.TPU_DEMOTIONS) > 0
                assert fi.fired("tpu.execute") > 0
                # every demoted attempt failed classed `device`
                classes = {s.failure_class
                           for tip in jip.maps
                           for s in tip.attempts.values()
                           if s.state == TaskState.FAILED}
                assert classes == {"device"}
                snap = c.master.metrics.snapshot()["jobtracker"]
                assert snap["tpu_demotions"] > 0
                # history carries the decisions
                evs = [e["event"] for e in c.master.history.read(
                    os.path.join(str(tmp_path),
                                 f"{result.job_id}.jsonl"))]
                assert "TIP_TPU_DEMOTED" in evs

                # CI artifact: the merged chaos-run job trace
                from tpumr.core import tracing
                trace = c.master.get_job_trace(str(result.job_id))
                assert trace["spans"], "chaos run must be traced"
                import json
                with open("/tmp/tpumr-chaos-trace.json", "w") as f:
                    json.dump(tracing.to_chrome_trace(trace["spans"]), f)
        finally:
            fi.reset()
            FileSystem.clear_cache()


class TestEndToEndHungTaskReap:
    def test_hung_map_is_reaped_and_job_completes(self):
        """Acceptance: an injected hung map (stops reporting progress
        mid-map) is reaped within mapred.task.timeout with
        failure_class=timeout; the re-run completes the job
        byte-correct."""
        fi.reset()
        from tpumr.fs import FileSystem, get_filesystem
        from tpumr.mapred.mini_cluster import MiniMRCluster
        base = JobConf()
        base.set("mapred.task.timeout", 1500)   # ms, Hadoop-compatible
        base.set("tpumr.fi.task.hang.m0.probability", 1.0)
        base.set("tpumr.fi.task.hang.m0.max.failures", 1)
        base.set("tpumr.fi.seed", FI_SEED)
        try:
            fs = get_filesystem("mem:///")
            _write_input(fs, "/reap/in.txt")
            with MiniMRCluster(num_trackers=2, conf=base, cpu_slots=2,
                               tpu_slots=0) as c:
                t0 = time.monotonic()
                result = _run_wordcount_job(c, fs, "mem:///reap/in.txt",
                                            "mem:///reap/out")
                wall = time.monotonic() - t0
                assert result.successful, "the reaped map must re-run"
                counts = dict(line.split(b"\t") for line in
                              _output_bytes(fs, "/reap/out").splitlines())
                assert counts[b"x"] == b"2000"
                assert fi.fired("task.hang.m0") == 1

                jip = c.master.jobs[str(result.job_id)]
                reaped = [s for tip in jip.maps
                          for s in tip.attempts.values()
                          if s.state == TaskState.FAILED]
                assert len(reaped) == 1
                assert reaped[0].failure_class == "timeout"
                assert "failed to report status" in reaped[0].diagnostics
                # reaped within the timeout (plus reaper granularity +
                # retry wall time — generous bound, but far below the
                # 600s a timeout-less attempt would burn)
                assert wall < 30
                snap = c.master.metrics.snapshot()["jobtracker"]
                assert snap["tasks_reaped_timeout"] == 1
                assert jip.counters.value(
                    JobCounter.GROUP, JobCounter.TASKS_REAPED_TIMEOUT) == 1
                t_snaps = [t.metrics.snapshot()[t.name].get(
                    "tasks_reaped_timeout", 0) for t in c.trackers]
                assert sum(t_snaps) == 1
                # the hung attempt burned one attempt, like Hadoop's
                # "failed to report status ... Killing!"
                assert sum(t.failures for t in jip.maps) == 1
        finally:
            fi.reset()
            FileSystem.clear_cache()

    def test_hung_isolated_child_is_sigkilled_and_reaped(self, tmp_path):
        """Process-isolation variant: the hung child keeps its umbilical
        ping and 1 Hz status push alive (neither counts as progress), is
        reaped at the timeout, and its whole process tree is SIGKILLed
        via _kill_tree; the re-run completes the job. Local files, not
        mem:// — isolated children live in their own process and cannot
        see this process's in-memory filesystem."""
        fi.reset()
        from tpumr.fs import FileSystem
        from tpumr.mapred.mini_cluster import MiniMRCluster
        base = JobConf()
        # well above a child's start-up: the clock runs from launch, and
        # an isolated child spends seconds importing (its mapper's module
        # pulls in jax) before its first report — at 2 s every HEALTHY
        # child was reaped too and the job never finished. No twin: past
        # mapred.speculative.min.runtime.s a speculative attempt would
        # win and the hung one would be KILLED, not reaped
        base.set("mapred.task.timeout", 9000)
        base.set("mapred.speculative.execution", False)
        base.set("tpumr.task.isolation", "process")
        # the hang comes from the sleep example's attempt-aware mode,
        # not the fi seam: fi's max.failures ledger is per-process, and
        # each isolated attempt is a FRESH process — the seam would
        # hang every re-run too
        in_path = tmp_path / "in.txt"
        in_path.write_bytes(b"0\n1\n2\n")
        try:
            with MiniMRCluster(num_trackers=1, conf=base, cpu_slots=2,
                               tpu_slots=0) as c:
                from tpumr.examples.sleep import SleepMapper, SleepReducer
                from tpumr.mapred.input_formats import NLineInputFormat
                from tpumr.mapred.job_client import JobClient
                conf = c.create_job_conf()
                conf.set_input_paths(str(in_path))
                conf.set_output_path(str(tmp_path / "out"))
                conf.set_input_format(NLineInputFormat)
                conf.set("mapred.line.input.format.linespermap", 1)
                conf.set_mapper_class(SleepMapper)
                conf.set_reducer_class(SleepReducer)
                conf.set("tpumr.sleep.map.ms", 20)
                # map 1's FIRST attempt hangs (attempt-aware, so the
                # re-run — a fresh child process — runs clean)
                conf.set("tpumr.sleep.hang.map", 1)
                result = JobClient(conf).run_job(conf)
                assert result.successful
                jip = c.master.jobs[str(result.job_id)]
                reaped = [s for tip in jip.maps
                          for s in tip.attempts.values()
                          if s.state == TaskState.FAILED]
                assert len(reaped) == 1
                assert reaped[0].failure_class == "timeout"
                snap = c.master.metrics.snapshot()["jobtracker"]
                assert snap["tasks_reaped_timeout"] == 1
        finally:
            fi.reset()
            FileSystem.clear_cache()

    def test_healthy_tasks_survive_a_tight_timeout(self):
        """Counter-case: a normally-progressing job with the same tight
        timeout is never reaped — progress observation keeps live
        attempts alive."""
        fi.reset()
        from tpumr.fs import FileSystem, get_filesystem
        from tpumr.mapred.mini_cluster import MiniMRCluster
        base = JobConf()
        base.set("mapred.task.timeout", 1500)
        try:
            fs = get_filesystem("mem:///")
            _write_input(fs, "/ok/in.txt")
            with MiniMRCluster(num_trackers=1, conf=base, cpu_slots=2,
                               tpu_slots=0) as c:
                result = _run_wordcount_job(c, fs, "mem:///ok/in.txt",
                                            "mem:///ok/out")
                assert result.successful
                snap = c.master.metrics.snapshot()["jobtracker"]
                assert snap.get("tasks_reaped_timeout", 0) == 0
        finally:
            fi.reset()
            FileSystem.clear_cache()
