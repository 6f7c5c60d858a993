"""Lost-map-output recovery — the "too many fetch failures" protocol
(≈ ReduceTask fetch-failure notification → JobInProgress.
fetchFailureNotification → TaskCompletionEvent OBSOLETE): copier penalty
box + reporting, master-side distinct-reducer counting and map
re-execution, append-only OBSOLETE completion events, and the
end-to-end chaos run over a live mini-cluster."""

import threading
import time

import pytest

from tpumr.mapred.ids import JobID, TaskAttemptID
from tpumr.mapred.job_in_progress import JobInProgress, JobState
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.shuffle_copier import PenaltyBox, ShuffleCopier
from tpumr.mapred.task import TaskState, TaskStatus
from tpumr.utils import fi

from test_shuffle_copier import SpillChunkSource, make_spill, records_for


# --------------------------------------------------------------- copier


class FlakySource(SpillChunkSource):
    """A chunk source whose map 0 is unfetchable until a fetch-failure
    report arrives — then it 'relocates' (as if the map re-ran) and
    serves fine. Duck-types the locator hooks of RemoteChunkSource."""

    def __init__(self, spills):
        super().__init__(spills)
        self.addr = {m: f"t0:{m}" for m in range(len(spills))}
        self.attempts = {m: f"attempt_x_0001_m_{m:06d}_0"
                         for m in range(len(spills))}
        self.recovered = threading.Event()
        self.invalidated = []

    def addr_of(self, m):
        return self.addr.get(m, "")

    def attempt_of(self, m):
        return self.attempts.get(m, "")

    def invalidate(self, m):
        self.invalidated.append(m)
        # the "re-run" publishes a new location + attempt
        self.addr[m] = f"t1:{m}"
        self.attempts[m] = f"attempt_x_0001_m_{m:06d}_1"
        self.recovered.set()

    def __call__(self, map_index, partition, offset):
        if map_index == 0 and not self.recovered.is_set():
            raise ConnectionError("output gone (disk lost)")
        return super().__call__(map_index, partition, offset)


def _conf(**kv):
    conf = JobConf()
    for k, v in kv.items():
        conf.set(k, v)
    return conf


class TestCopierRecovery:
    def test_report_then_reresolve_instead_of_failing(self, tmp_path):
        """A persistently-failing source must NOT fail the reduce when a
        report callback is wired: the copier reports, invalidates, and
        picks up the new location mid-shuffle."""
        spills = [make_spill(records_for(100, b"m%d" % i))
                  for i in range(3)]
        src = FlakySource(spills)
        reports = []
        conf = _conf(**{"tpumr.shuffle.copy.backoff.ms": 1,
                        "tpumr.shuffle.copy.backoff.max.ms": 5,
                        "tpumr.shuffle.fetch.retries.per.source": 2})
        copier = ShuffleCopier(conf, src, 3, 0, str(tmp_path),
                               on_fetch_failure=lambda m, a:
                               reports.append((m, a)))
        segs = copier.copy_all()
        assert len(segs) == 3
        assert reports == [(0, "attempt_x_0001_m_000000_0")]
        assert src.invalidated == [0]
        assert copier.fetch_failures >= 2     # per-source threshold hit
        assert copier.fetch_failures_reported == 1
        for s in segs:
            s.close()

    def test_without_callback_failure_stays_terminal(self, tmp_path):
        """Legacy contract preserved: no callback → local retries then
        raise (a LocalJobRunner reduce has no master to report to)."""
        class DeadSource:
            chunk_bytes = 1 << 20

            def __call__(self, m, p, o):
                raise ConnectionError("gone")

        conf = _conf(**{"tpumr.shuffle.copy.retries": 1,
                        "tpumr.shuffle.copy.backoff.ms": 1})
        with pytest.raises(RuntimeError, match="failed after 2 attempts"):
            ShuffleCopier(conf, DeadSource(), 1, 0,
                          str(tmp_path)).copy_all()

    def test_max_failures_ceiling_is_terminal_even_with_callback(
            self, tmp_path):
        class DeadSource:
            chunk_bytes = 1 << 20

            def __call__(self, m, p, o):
                raise ConnectionError("gone")

        conf = _conf(**{"tpumr.shuffle.copy.backoff.ms": 1,
                        "tpumr.shuffle.copy.backoff.max.ms": 2,
                        "tpumr.shuffle.fetch.retries.per.source": 2,
                        "tpumr.shuffle.fetch.max.failures": 5})
        copier = ShuffleCopier(conf, DeadSource(), 1, 0, str(tmp_path),
                               on_fetch_failure=lambda m, a: None)
        with pytest.raises(ConnectionError):
            copier.copy_all()
        assert copier.fetch_failures == 5

    def test_penalty_box_backoff_capped_and_jittered(self):
        box = PenaltyBox(base_s=1.0, cap_s=4.0)
        delays = [box.punish("t0") for _ in range(6)]
        # nominal 1,2,4,4,4,4 jittered into [0.5, 1.0) of nominal
        for d, nominal in zip(delays, [1, 2, 4, 4, 4, 4]):
            assert 0.5 * nominal <= d <= nominal
        assert box.active() == 1
        # hold-offs are MONOTONIC stamps (clock-step immunity, this
        # PR's deadline sweep) — compare against the monotonic clock
        assert box.until("t0") > time.monotonic()
        box.clear("t0")
        assert box.active() == 0
        # strikes reset: next punishment starts from the base again
        assert box.punish("t0") <= 1.0

    def test_local_backoff_jitter_and_cap(self, tmp_path):
        conf = _conf(**{"tpumr.shuffle.copy.backoff.ms": 100,
                        "tpumr.shuffle.copy.backoff.max.ms": 400})
        copier = ShuffleCopier(conf, lambda m, p, o: {}, 1, 0,
                               str(tmp_path))
        for attempt, nominal in [(0, 0.1), (1, 0.2), (2, 0.4), (8, 0.4)]:
            for _ in range(8):
                d = copier._local_backoff_s(attempt)
                assert 0.5 * nominal <= d <= nominal


# --------------------------------------------------------- master state


def _job(n_maps=2, n_reduces=2, **conf):
    base = {"mapred.reduce.tasks": n_reduces,
            "mapred.speculative.execution": False,
            "mapred.reduce.slowstart.completed.maps": 0.0}
    base.update(conf)
    return JobInProgress(JobID("ff", 1),
                         splits=[{"locations": []}
                                 for _ in range(n_maps)],
                         conf_dict=base)


def _finish_map(job, task, runtime=1.0, on_tpu=False, addr="t0:1"):
    now = time.time()
    job.update_task_status(TaskStatus(
        attempt_id=task.attempt_id, is_map=True, run_on_tpu=on_tpu,
        state=TaskState.SUCCEEDED, start_time=now - runtime,
        finish_time=now), addr)


def _running_reduce(job):
    """Obtain a reduce and fold its RUNNING heartbeat status — reports
    are only accepted from reducers the master knows are running."""
    t = job.obtain_new_reduce_task("h")
    job.update_task_status(TaskStatus(
        attempt_id=t.attempt_id, is_map=False,
        state=TaskState.RUNNING), "t:0")
    return str(t.attempt_id)


class TestFetchFailureNotification:
    def test_distinct_reducers_until_threshold(self):
        job = _job(n_maps=1, n_reduces=3,
                   **{"mapred.max.fetch.failures.per.map": 2})
        t = job.obtain_new_map_task("h", run_on_tpu=False)
        _finish_map(job, t, addr="t0:9")
        aid = job.maps[0].successful_attempt
        r0, r1, r2 = (_running_reduce(job) for _ in range(3))
        # same reducer reporting twice counts ONCE
        res = job.fetch_failure_notification(aid, r0)
        assert res == {"withdrawn": False, "reexecuted": False,
                       "shuffle_addr": "", "reports": 1}
        assert job.fetch_failure_notification(aid, r0)["reports"] == 1
        # a speculative TWIN of the same reduce corroborates nothing new
        twin = TaskAttemptID(TaskAttemptID.parse(r0).task, 99)
        job.update_task_status(TaskStatus(
            attempt_id=twin, is_map=False,
            state=TaskState.RUNNING), "t:0")
        assert job.fetch_failure_notification(aid,
                                              str(twin))["reports"] == 1
        assert job.fetch_failure_pending_count() == 1
        res = job.fetch_failure_notification(aid, r1)
        assert res["withdrawn"] and res["reexecuted"]
        assert res["shuffle_addr"] == "t0:9"
        assert res["reports"] == 2
        # the map is back in the pending pool, attempt burned
        assert job.pending_map_count() == 1
        assert job.finished_maps == 0
        assert job.maps[0].failures == 1
        assert job.maps[0].successful_attempt == ""
        assert job.fetch_failure_pending_count() == 0
        # events: original mutated OBSOLETE + tombstone appended
        obs = [e for e in job.completion_events
               if e.get("status") == "OBSOLETE"]
        assert len(obs) == 2 and all(e["attempt_id"] == aid for e in obs)
        # stale report after withdrawal is a no-op
        assert job.fetch_failure_notification(aid, r2) is None

    def test_single_reduce_job_triggers_below_default_threshold(self):
        """A 1-reduce job can never produce 3 distinct reporters — once
        EVERY live reduce is complaining, nothing can progress and the
        map must re-execute."""
        job = _job(n_maps=1, n_reduces=1)   # default threshold 3
        t = job.obtain_new_map_task("h", run_on_tpu=False)
        _finish_map(job, t)
        r0 = _running_reduce(job)
        aid = job.maps[0].successful_attempt
        res = job.fetch_failure_notification(aid, r0)
        assert res["withdrawn"] and res["reexecuted"]

    def test_profile_sums_unwound_exactly(self):
        job = _job(n_maps=2, n_reduces=1)
        t0 = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        t1 = job.obtain_new_map_task("h", run_on_tpu=False)
        _finish_map(job, t0, runtime=4.0, on_tpu=True)
        _finish_map(job, t1, runtime=8.0, on_tpu=False)
        assert job.finished_tpu_maps == 1 and job.finished_cpu_maps == 1
        tpu_sum, cpu_sum = job._tpu_time_sum, job._cpu_time_sum
        r0 = _running_reduce(job)
        aid = job.maps[t0.partition].successful_attempt
        res = job.fetch_failure_notification(aid, r0)
        assert res["withdrawn"]
        # the TPU books are restored exactly; CPU books untouched
        assert job.finished_tpu_maps == 0
        assert job._tpu_time_sum == pytest.approx(tpu_sum - 4.0)
        assert job.finished_cpu_maps == 1
        assert job._cpu_time_sum == pytest.approx(cpu_sum)
        assert job.tpu_map_mean_time() == 0.0

    def test_repeated_output_loss_fails_the_job(self):
        job = _job(n_maps=1, n_reduces=1,
                   **{"mapred.map.max.attempts": 2})
        r0 = _running_reduce(job)
        for round_no in range(2):
            t = job.obtain_new_map_task("h", run_on_tpu=False)
            _finish_map(job, t)
            aid = job.maps[0].successful_attempt
            res = job.fetch_failure_notification(aid, r0)
            assert res["withdrawn"]
        assert res["reexecuted"] is False
        assert job.state == JobState.FAILED
        assert "fetch failures" in job.error

    def test_unknown_and_reduce_attempts_ignored(self):
        job = _job(n_maps=1, n_reduces=1)
        r0 = _running_reduce(job)
        assert job.fetch_failure_notification("garbage", r0) is None
        assert job.fetch_failure_notification(
            "attempt_ff_0001_r_000000_0", r0) is None
        # a map that is still running (not succeeded) can't be withdrawn
        t = job.obtain_new_map_task("h", run_on_tpu=False)
        assert job.fetch_failure_notification(
            str(t.attempt_id), r0) is None

    def test_replayed_success_cannot_resurrect_withdrawn_attempt(self):
        """The wedged-but-heartbeating tracker this protocol targets can
        re-deliver the map's terminal SUCCEEDED on every beat (statuses
        fold before replay detection): it must not re-publish the
        withdrawn output or re-increment finished_maps."""
        job = _job(n_maps=1, n_reduces=1)
        t = job.obtain_new_map_task("h", run_on_tpu=False)
        _finish_map(job, t, addr="t0:9")
        r0 = _running_reduce(job)
        aid = job.maps[0].successful_attempt
        assert job.fetch_failure_notification(aid, r0)["withdrawn"]
        now = time.time()
        job.update_task_status(TaskStatus(
            attempt_id=TaskAttemptID.parse(aid), is_map=True,
            state=TaskState.SUCCEEDED, start_time=now - 1,
            finish_time=now), "t0:9")
        assert job.finished_maps == 0             # not resurrected
        assert job.pending_map_count() == 1
        assert job.maps[0].successful_attempt == ""
        assert not [e for e in job.completion_events
                    if e.get("status") != "OBSOLETE"]

    def test_forged_or_finished_reporters_ignored(self):
        """Reports count only from reduce attempts the master knows are
        RUNNING in THIS job — a job-token child inventing reducer names
        (or a finished reduce) cannot manufacture corroboration."""
        job = _job(n_maps=1, n_reduces=2)
        t = job.obtain_new_map_task("h", run_on_tpu=False)
        _finish_map(job, t)
        aid = job.maps[0].successful_attempt
        # never-launched (forged) reducer
        assert job.fetch_failure_notification(
            aid, "attempt_ff_0001_r_000001_7") is None
        # another job's reducer
        assert job.fetch_failure_notification(
            aid, "attempt_other_0002_r_000000_0") is None
        # a finished reduce no longer corroborates
        r0 = _running_reduce(job)
        now = time.time()
        job.update_task_status(TaskStatus(
            attempt_id=TaskAttemptID.parse(r0), is_map=False,
            state=TaskState.SUCCEEDED, start_time=now - 1,
            finish_time=now), "t:0")
        assert job.fetch_failure_notification(aid, r0) is None
        assert job.fetch_failure_pending_count() == 0


class TestRequeueLostAttemptsUnwind:
    def test_hybrid_profile_unwound_exactly_on_lost_tracker(self):
        """Satellite: a completed map on a lost tracker must restore
        finished_tpu_maps/_tpu_time_sum (and the CPU twins) EXACTLY, so
        the hybrid scheduler's means stay unpoisoned."""
        job = _job(n_maps=3, n_reduces=1)
        t0 = job.obtain_new_map_task("h", run_on_tpu=True, tpu_device_id=0)
        t1 = job.obtain_new_map_task("h", run_on_tpu=False)
        t2 = job.obtain_new_map_task("h", run_on_tpu=False)
        _finish_map(job, t0, runtime=2.0, on_tpu=True, addr="lost:1")
        _finish_map(job, t1, runtime=6.0, on_tpu=False, addr="lost:1")
        _finish_map(job, t2, runtime=10.0, on_tpu=False, addr="ok:2")
        assert (job.finished_tpu_maps, job.finished_cpu_maps) == (1, 2)
        lost = [job.maps[t0.partition].successful_attempt,
                job.maps[t1.partition].successful_attempt]
        job.requeue_lost_attempts(lost)
        assert job.finished_maps == 1
        assert job.finished_tpu_maps == 0
        assert job._tpu_time_sum == pytest.approx(0.0)
        assert job.finished_cpu_maps == 1
        assert job._cpu_time_sum == pytest.approx(10.0)
        assert job.cpu_map_mean_time() == pytest.approx(10.0)
        assert job.tpu_map_mean_time() == 0.0
        assert job.pending_map_count() == 2
        # the survivor's event is still live; the lost ones tombstoned
        live = [e for e in job.completion_events
                if e.get("status") != "OBSOLETE"]
        assert [e["map_index"] for e in live] == [t2.partition]


# --------------------------------------------------------------- locator


class TestMapLocatorReresolution:
    def _feed(self, events):
        return lambda cursor: events[cursor:]

    def test_obsolete_evicts_and_rerun_replaces(self):
        from tpumr.mapred.tasktracker import make_map_locator
        events = [{"map_index": 0, "attempt_id": "a0",
                   "shuffle_addr": "127.0.0.1:7001",
                   "status": "SUCCEEDED"}]
        loc = make_map_locator(self._feed(events), None, poll_s=0.01,
                               timeout_s=2.0)
        cli = loc(0)
        assert (cli.host, cli.port) == ("127.0.0.1", 7001)
        assert loc.attempt_of(0) == "a0"
        assert loc.addr_of(0) == "127.0.0.1:7001"
        # the master withdraws a0 and a re-run publishes a new address
        events.append({"map_index": 0, "attempt_id": "a0",
                       "shuffle_addr": "127.0.0.1:7001",
                       "status": "OBSOLETE"})
        events.append({"map_index": 0, "attempt_id": "a1",
                       "shuffle_addr": "127.0.0.1:7002",
                       "status": "SUCCEEDED"})
        loc.invalidate(0)
        cli = loc(0)
        assert (cli.host, cli.port) == ("127.0.0.1", 7002)
        assert loc.attempt_of(0) == "a1"

    def test_invalidate_falls_back_to_stale_until_replaced(self):
        """An invalidated location the master never withdraws (the fault
        may be OUR network path, not the output) must stay usable: the
        cursor-based feed never re-serves the original event, so without
        the stale fallback the reducer would block to the full shuffle
        timeout and report empty attempt ids forever."""
        from tpumr.mapred.tasktracker import make_map_locator
        events = [{"map_index": 0, "attempt_id": "a0",
                   "shuffle_addr": "127.0.0.1:7001",
                   "status": "SUCCEEDED"}]
        loc = make_map_locator(self._feed(events), None, poll_s=0.01,
                               timeout_s=5.0)
        assert loc(0).port == 7001
        loc.invalidate(0)
        # reports keep naming the real attempt while demoted
        assert loc.attempt_of(0) == "a0"
        t0 = time.time()
        assert loc(0).port == 7001          # falls back, does NOT block
        assert time.time() - t0 < 2.0
        # once the master withdraws it, the fallback dies with it and
        # the re-run's fresh event wins
        loc.invalidate(0)
        events.append({"map_index": 0, "attempt_id": "a0",
                       "shuffle_addr": "127.0.0.1:7001",
                       "status": "OBSOLETE"})
        events.append({"map_index": 0, "attempt_id": "a1",
                       "shuffle_addr": "127.0.0.1:7002",
                       "status": "SUCCEEDED"})
        assert loc(0).port == 7002
        assert loc.attempt_of(0) == "a1"

    def test_tombstone_for_uncached_attempt_is_inert(self):
        """A late joiner replaying SUCCEEDED→OBSOLETE→SUCCEEDED from
        cursor 0 must land on the re-run's address."""
        from tpumr.mapred.tasktracker import make_map_locator
        events = [
            {"map_index": 0, "attempt_id": "a0",
             "shuffle_addr": "127.0.0.1:7001", "status": "SUCCEEDED"},
            {"map_index": 0, "attempt_id": "a0",
             "shuffle_addr": "127.0.0.1:7001", "status": "OBSOLETE"},
            {"map_index": 0, "attempt_id": "a1",
             "shuffle_addr": "127.0.0.1:7002", "status": "SUCCEEDED"},
        ]
        loc = make_map_locator(self._feed(events), None, poll_s=0.01,
                               timeout_s=2.0)
        assert loc(0).port == 7002


# ------------------------------------------------------- fi determinism


class TestSeededFaultInjection:
    def setup_method(self):
        fi.reset()

    def _sequence(self, conf, n=64):
        out = []
        for _ in range(n):
            try:
                fi.maybe_fail("seeded.point", conf)
                out.append(0)
            except fi.InjectedFault:
                out.append(1)
        return out

    def test_same_seed_replays_bit_identically(self):
        conf = _conf(**{"tpumr.fi.seeded.point.probability": 0.5,
                        "tpumr.fi.seed": 1234})
        first = self._sequence(conf)
        fi.reset()   # fresh process-equivalent
        assert self._sequence(conf) == first
        assert 0 < sum(first) < 64   # actually probabilistic

    def test_different_seeds_diverge(self):
        a = _conf(**{"tpumr.fi.seeded.point.probability": 0.5,
                     "tpumr.fi.seed": 1})
        b = _conf(**{"tpumr.fi.seeded.point.probability": 0.5,
                     "tpumr.fi.seed": 2})
        sa = self._sequence(a)
        fi.reset()
        sb = self._sequence(b)
        assert sa != sb


# ------------------------------------------------- tracker heartbeat


def _bare_noderunner(interval_s=0.2):
    """A NodeRunner shell for heartbeat-loop tests — no daemon
    bring-up, just the fields the loop touches."""
    from tpumr.mapred.tasktracker import NodeRunner
    from tpumr.metrics.core import MetricsRegistry
    nr = object.__new__(NodeRunner)
    nr._stop = threading.Event()
    nr._wake = threading.Event()
    nr.heartbeat_s = nr._heartbeat_floor_s = interval_s
    nr.tracer = None                     # tracing off (the default)
    nr.master_unreachable = False
    nr._master_failures = 0
    nr._last_master_contact = time.monotonic()
    nr._lost_master_backoff_max_s = 15.0
    nr._mreg = MetricsRegistry("t")
    return nr


class TestHeartbeatErrorBackoff:
    def test_lost_master_backs_off_and_honors_stop(self):
        """Master-unreachable beats enter the lost-master state: capped
        jittered exponential backoff (never below one interval), the
        master_unreachable flag raised, retries forever, and _stop
        still interrupts the wait promptly."""
        nr = _bare_noderunner(interval_s=0.1)
        beats = []
        nr._heartbeat_once = lambda **kw: (beats.append(time.time()),
                                          (_ for _ in ()).throw(
                                              ConnectionError("down")))
        t = threading.Thread(target=nr._heartbeat_loop, daemon=True)
        t.start()
        time.sleep(1.0)
        assert nr.master_unreachable, \
            "transport failure must raise the lost-master flag"
        nr._stop.set()
        nr._wake.set()               # as NodeRunner.stop() does
        t.join(timeout=1.0)
        assert not t.is_alive(), "stop must interrupt the backoff wait"
        assert len(beats) >= 2, "must keep retrying through the outage"
        gaps = [b - a for a, b in zip(beats, beats[1:])]
        # jittered exponential: every gap within [interval, cap], and
        # the SECOND retry gap is never shorter than half the first's
        # ceiling — it backs off rather than hammering a restarting
        # master at a fixed cadence
        assert all(0.09 <= g <= 15.0 for g in gaps), gaps
        assert nr._master_failures == len(beats)

    def test_application_rpc_error_keeps_cadence_and_charges_nothing(self):
        """An RPC-level error (the master answered, unhappily) is NOT a
        lost master: normal interval, no unreachable flag, no backoff."""
        from tpumr.ipc.rpc import RpcError
        nr = _bare_noderunner(interval_s=0.1)
        beats = []
        nr._heartbeat_once = lambda **kw: (beats.append(time.time()),
                                          (_ for _ in ()).throw(
                                              RpcError("handler raised")))
        t = threading.Thread(target=nr._heartbeat_loop, daemon=True)
        t.start()
        time.sleep(0.55)
        nr._stop.set()
        nr._wake.set()               # as NodeRunner.stop() does
        t.join(timeout=1.0)
        assert not nr.master_unreachable
        assert nr._master_failures == 0
        assert len(beats) >= 3, "application errors keep the cadence"
        gaps = [b - a for a, b in zip(beats, beats[1:])]
        assert all(g < 0.25 for g in gaps), \
            f"no lost-master backoff for application errors (gaps={gaps})"


# ------------------------------------------------------------ end to end


class TestEndToEndChaos:
    def test_lost_map_output_recovers_without_failing_reduces(self):
        """Acceptance: tpumr.fi.shuffle.serve injects persistent fetch
        failures for one completed map's output (its tracker keeps
        heartbeating). The job must finish with byte-correct output:
        the map re-executes, reducers pick the new location up from
        OBSOLETE/refreshed completion events, no reduce attempt fails,
        and maps_reexecuted_fetch_failure == 1."""
        fi.reset()
        from tpumr.fs import FileSystem, get_filesystem
        from tpumr.mapred.job_client import JobClient
        from tpumr.mapred.mini_cluster import MiniMRCluster

        base = JobConf()
        # every serve of an ATTEMPT-0 map output fails, persistently —
        # the tracker itself stays healthy and heartbeating; the re-run
        # (attempt 1) serves fine wherever it lands
        base.set("tpumr.fi.shuffle.serve.a0.probability", 1.0)
        base.set("tpumr.shuffle.fetch.retries.per.source", 1)
        base.set("tpumr.shuffle.copy.backoff.ms", 10)
        base.set("tpumr.shuffle.copy.backoff.max.ms", 100)
        base.set("mapred.max.fetch.failures.per.map", 2)
        try:
            with MiniMRCluster(num_trackers=2, conf=base) as c:
                fs = get_filesystem("mem:///")
                fs.write_bytes("/ff/in.txt",
                               b"".join(b"w%02d x\n" % (i % 31)
                                        for i in range(3000)))
                conf = c.create_job_conf()
                conf.set_input_paths("mem:///ff/in.txt")
                conf.set_output_path("mem:///ff/out")
                conf.set("mapred.mapper.class",
                         "tpumr.mapred.lib.TokenCountMapper")
                conf.set("mapred.reducer.class",
                         "tpumr.examples.basic.LongSumReducer")
                conf.set("mapred.map.tasks", 1)
                conf.set_num_reduce_tasks(2)
                result = JobClient(conf).run_job(conf)
                assert result.successful, \
                    "job must survive the lost map output"
                out = b"".join(fs.read_bytes(st.path)
                               for st in fs.list_status("/ff/out")
                               if "part-" in str(st.path))
                counts = dict(line.split(b"\t")
                              for line in out.splitlines())
                assert counts[b"x"] == b"3000"
                assert counts[b"w00"] == b"97"     # 3000/31 → 97
                # the protocol ran: exactly one map re-executed, faults
                # were reported, and NO reduce attempt was failed
                snap = c.master.metrics.snapshot()["jobtracker"]
                assert snap["maps_reexecuted_fetch_failure"] == 1
                assert snap["fetch_failures_reported"] >= 2
                jip = c.master.jobs[str(result.job_id)]
                for tip in jip.reduces:
                    assert tip.failures == 0
                    assert not [s for s in tip.attempts.values()
                                if s.state == TaskState.FAILED]
                # the lost attempt itself was burned, once
                assert sum(t.failures for t in jip.maps) == 1
                assert fi.fired("shuffle.serve.a0") >= 1
        finally:
            fi.reset()
            FileSystem.clear_cache()
