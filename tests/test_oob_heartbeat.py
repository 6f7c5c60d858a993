"""The out-of-band heartbeat: a tracker beats when one of its attempts
goes terminal, so the master hears of the completion and refills the
slot then and not on the next tick (≈ TaskTracker.notifyTTAboutTask-
Completion / mapreduce.tasktracker.outofband.heartbeat, with no
switch). The unit tests drive a real NodeRunner's loop against a fake
master that only records beats; the last test runs a real job on a
mini-cluster at the shipped 1000 ms interval."""

import threading
import time

import pytest

from tpumr.ipc.rpc import RpcServer
from tpumr.mapred.ids import TaskAttemptID
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.jobtracker import PROTOCOL_VERSION
from tpumr.mapred.task import TaskState, TaskStatus
from tpumr.mapred.tasktracker import NodeRunner

JOB = "job_202610010000_0001"


class FakeMaster:
    """Answers the two calls a tracker's loop makes and keeps every beat
    with the time it arrived. ``instruct_ms`` is the next_interval_ms of
    every response; ``hold`` (an Event) parks a beat inside the handler,
    which is a beat whose RPC is in flight."""

    def __init__(self, instruct_ms: int) -> None:
        self.instruct_ms = instruct_ms
        self.beats: "list[tuple[float, dict]]" = []
        self.hold: "threading.Event | None" = None
        self.entered = threading.Event()
        self._lock = threading.Lock()
        self._response_id = 0

    def get_protocol_version(self) -> int:
        return PROTOCOL_VERSION

    def heartbeat(self, status: dict, initial_contact: bool,
                  ask_for_new_task: bool, response_id: int) -> dict:
        with self._lock:
            self.beats.append((time.monotonic(), status))
            self._response_id += 1
            rid = self._response_id
        self.entered.set()
        hold = self.hold
        if hold is not None:
            hold.wait(5)
        return {"response_id": rid, "actions": [],
                "next_interval_ms": self.instruct_ms}

    # ---- what the tests read

    def states_of(self, aid: str) -> "list[str]":
        """The states this attempt was reported in, beat by beat."""
        with self._lock:
            beats = list(self.beats)
        return [sd["state"] for _, st in beats
                for sd in st.get("task_statuses", [])
                if sd["attempt_id"] == aid]

    def n_beats(self) -> int:
        with self._lock:
            return len(self.beats)


class Rig:
    def __init__(self, interval_ms: int, instruct_ms: "int | None" = None,
                 start: bool = True, conf: "dict | None" = None) -> None:
        self.master = FakeMaster(instruct_ms if instruct_ms is not None
                                 else interval_ms)
        self.server = RpcServer(self.master)
        self.server.start()
        jc = JobConf()
        jc.set("tpumr.heartbeat.interval.ms", interval_ms)
        jc.set("tpumr.heartbeat.delta", False)   # every beat is whole
        jc.set("mapred.tasktracker.map.tpu.tasks.maximum", 0)
        for k, v in (conf or {}).items():
            jc.set(k, v)
        self.nr = NodeRunner("127.0.0.1", self.server.port, jc,
                             name="tt0")
        if start:
            self.nr.start()
            self.wait_beats(1)

    def close(self) -> None:
        if self.master.hold is not None:
            self.master.hold.set()
        self.nr.stop()
        self.server.stop()

    def wait_beats(self, n: int, timeout: float = 5.0) -> None:
        deadline = time.monotonic() + timeout
        while self.master.n_beats() < n and time.monotonic() < deadline:
            time.sleep(0.005)
        assert self.master.n_beats() >= n

    def run_attempt(self, i: int) -> str:
        """A RUNNING map attempt in the tracker's table, as _launch
        leaves one (no thread: the tests end it themselves)."""
        aid = f"attempt_{JOB[4:]}_m_{i:06d}_0"
        st = TaskStatus(attempt_id=TaskAttemptID.parse(aid), is_map=True,
                        state=TaskState.RUNNING)
        with self.nr.lock:
            self.nr.running[aid] = st
        return aid

    def finish(self, aid: str) -> float:
        """End an attempt the way _run_task does: terminal under the
        lock, then the wake. Returns when."""
        with self.nr.lock:
            self.nr.running[aid].state = TaskState.SUCCEEDED
        self.nr._slot_freed()
        return time.monotonic()

    def reported_at(self, aid: str, timeout: float = 5.0) -> float:
        """When the master first saw this attempt terminal."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self.master._lock:
                beats = list(self.master.beats)
            for t, st in beats:
                for sd in st.get("task_statuses", []):
                    if sd["attempt_id"] == aid \
                            and sd["state"] in TaskState.TERMINAL:
                        return t
            time.sleep(0.005)
        raise AssertionError(f"{aid} never reported terminal")

    def oob_count(self, settle_s: float = 0.25) -> int:
        """heartbeats_out_of_band once it has stopped moving (it is
        counted when a beat's answer arrives, a moment after the master
        saw the beat)."""
        def read() -> int:
            return int(self.nr.metrics.snapshot()["tt0"].get(
                "heartbeats_out_of_band", 0))
        n, deadline = read(), time.monotonic() + settle_s
        while time.monotonic() < deadline:
            time.sleep(0.02)
            if read() != n:
                n, deadline = read(), time.monotonic() + settle_s
        return n


@pytest.fixture
def rig():
    rigs = []

    def make(*a, **kw) -> Rig:
        r = Rig(*a, **kw)
        rigs.append(r)
        return r

    yield make
    for r in rigs:
        r.close()


class TestEarlyBeat:
    def test_finished_attempt_reported_within_200ms_at_5s_interval(self, rig):
        r = rig(5000)
        aid = r.run_attempt(0)
        time.sleep(0.1)              # well clear of the first beat
        n0 = r.master.n_beats()
        done = r.finish(aid)
        assert r.reported_at(aid) - done < 0.2
        assert r.master.n_beats() == n0 + 1
        assert r.oob_count() == 1
        # delivered, so dropped: the next timer beat does not repeat it
        assert aid not in r.nr.running

    def test_three_finishing_together_cost_at_most_two_beats(self, rig):
        r = rig(5000)
        aids = [r.run_attempt(i) for i in range(3)]
        time.sleep(0.1)
        n0 = r.master.n_beats()
        for aid in aids:
            r.finish(aid)
        for aid in aids:
            r.reported_at(aid)
        time.sleep(0.3)              # anything more would have come by now
        assert 1 <= r.master.n_beats() - n0 <= 2
        assert r.oob_count() == r.master.n_beats() - n0

    def test_completion_during_inflight_rpc_gets_its_own_early_beat(self, rig):
        r = rig(5000)
        a, b = r.run_attempt(0), r.run_attempt(1)
        time.sleep(0.1)
        r.master.hold = threading.Event()
        r.master.entered.clear()
        r.finish(a)
        assert r.master.entered.wait(2)      # a's beat is in the handler
        done_b = r.finish(b)                 # reported RUNNING in that beat
        time.sleep(0.05)
        r.master.hold.set()
        r.master.hold = None
        assert r.reported_at(b) - done_b < 0.5
        assert r.master.states_of(b)[-2:] == [TaskState.RUNNING,
                                              TaskState.SUCCEEDED]
        assert r.oob_count() == 2

    def test_early_beats_keep_their_spacing(self, rig):
        """A stream of instant tasks cannot spin the loop: each early
        beat keeps _OOB_MIN_GAP_S from the beat before it."""
        r = rig(5000)
        time.sleep(0.1)
        n0 = r.master.n_beats()
        t_end = time.monotonic() + 0.5
        i = 0
        while time.monotonic() < t_end:
            r.finish(r.run_attempt(i))
            i += 1
            time.sleep(0.002)
        time.sleep(0.2)
        with r.master._lock:
            ts = [t for t, _ in r.master.beats[n0:]]
        assert len(ts) <= 0.7 / NodeRunner._OOB_MIN_GAP_S + 1
        assert len(ts) < i, "tasks that finish together share a beat"
        gaps = [b - a for a, b in zip(ts, ts[1:])]
        assert all(g >= NodeRunner._OOB_MIN_GAP_S * 0.9 for g in gaps), gaps
        assert not r.nr.running, "every completion was delivered"

    @pytest.mark.parametrize("end", ["umbilical_done", "umbilical_fail",
                                     "reaper"])
    def test_every_terminal_path_wakes_the_loop(self, rig, end):
        r = rig(5000, conf={"mapred.task.timeout": 600_000})
        aid = r.run_attempt(0)
        time.sleep(0.1)
        t0 = time.monotonic()
        if end == "umbilical_done":
            r.nr.umbilical_done(aid, {"state": TaskState.SUCCEEDED}, JOB,
                                0, "", {})
        elif end == "umbilical_fail":
            r.nr.umbilical_fail(aid, TaskState.FAILED, "boom")
        else:
            assert r.nr._reap_one(aid, 601.0, 600.0)
        assert r.reported_at(aid) - t0 < 0.2
        assert r.oob_count() == 1


class TestWithheld:
    def test_no_early_beat_while_master_stretches_the_interval(self, rig):
        """A master that instructs a slower cadence than the tracker's
        own is shedding load: the completion waits for the timer."""
        r = rig(300, instruct_ms=900)
        r.wait_beats(2)              # the instruction has arrived
        assert r.nr.heartbeat_s == pytest.approx(0.9)
        aid = r.run_attempt(0)
        n0 = r.master.n_beats()
        t_last = r.master.beats[-1][0]
        r.finish(aid)
        assert r.reported_at(aid) - t_last >= 0.85, \
            "reported by the timer beat, not early"
        assert r.master.n_beats() == n0 + 1
        assert r.oob_count() == 0

    def test_early_beats_resume_when_the_instruction_returns_to_floor(
            self, rig):
        r = rig(300, instruct_ms=900)
        r.wait_beats(2)
        r.master.instruct_ms = 300
        r.wait_beats(r.master.n_beats() + 1)
        time.sleep(0.05)
        assert r.nr.heartbeat_s == pytest.approx(0.3)
        aid = r.run_attempt(0)
        done = r.finish(aid)
        assert r.reported_at(aid) - done < 0.15
        assert r.oob_count() == 1

    def test_no_early_beat_while_master_unreachable(self, rig):
        """A wake must not defeat the lost-master backoff: the loop's
        sleep runs its whole length."""
        r = rig(5000, start=False)
        aid = r.run_attempt(0)
        r.nr.master_unreachable = True
        r.finish(aid)
        t0 = time.monotonic()
        assert r.nr._await_next_beat(0.4) is False
        assert time.monotonic() - t0 >= 0.39
        # the same wake with the master reachable is an early beat
        r.nr.master_unreachable = False
        r.nr._slot_freed()
        t0 = time.monotonic()
        assert r.nr._await_next_beat(0.4) is True
        assert time.monotonic() - t0 < 0.2

    def test_wake_with_nothing_to_tell_is_not_a_beat(self, rig):
        """An isolated child wakes the loop twice (its done report, then
        its babysitter's release); the second finds nothing waiting."""
        r = rig(5000, start=False)
        r.nr._slot_freed()
        t0 = time.monotonic()
        assert r.nr._await_next_beat(0.3) is False
        assert time.monotonic() - t0 >= 0.29


class TestTimer:
    def test_idle_tracker_still_beats_on_the_timer(self, rig):
        r = rig(200)
        n0 = r.master.n_beats()
        time.sleep(1.1)
        n = r.master.n_beats() - n0
        assert 4 <= n <= 6, n
        assert r.oob_count() == 0

    def test_timer_restarts_from_an_early_beat(self, rig):
        r = rig(600)
        r.wait_beats(2)
        time.sleep(0.3)              # mid-interval
        aid = r.run_attempt(0)
        r.finish(aid)
        early = r.reported_at(aid)
        n = r.master.n_beats()
        r.wait_beats(n + 1)
        assert r.master.beats[n][0] - early == pytest.approx(0.6, abs=0.15)

    def test_finished_job_sweep_is_off_the_beats_thread(self, rig):
        """The sweep of finished jobs (every 20 INTERVALS, seconds long
        on a busy tracker) runs on its own thread: early beats do not
        bring it round sooner, and a completion that lands while it
        runs is still told at once."""
        r = rig(100)
        sweeps = []
        sweeping = threading.Event()

        def slow_sweep():
            sweeps.append((time.monotonic(), threading.current_thread()))
            sweeping.set()
            time.sleep(0.6)

        r.nr._cleanup_finished_jobs = slow_sweep
        for i in range(15):          # 15 early beats in about 1.2 s
            aid = r.run_attempt(i)
            r.finish(aid)
            r.reported_at(aid)
            time.sleep(0.06)
        assert len(sweeps) <= 1, "20 intervals are 2 s, whatever the beats"
        assert sweeping.wait(3)
        aid = r.run_attempt(99)
        done = r.finish(aid)
        assert r.reported_at(aid) - done < 0.2
        assert time.monotonic() - sweeps[-1][0] < 0.6, "the sweep still ran"
        assert sweeps[0][1] is r.nr._cleanup_thread

    def test_stop_interrupts_the_sleep(self, rig):
        r = rig(5000)
        t0 = time.monotonic()
        r.nr.stop()
        r.nr._hb_thread.join(2)
        assert not r.nr._hb_thread.is_alive()
        assert time.monotonic() - t0 < 2


class TestObservability:
    def test_counter_counts_what_was_sent(self, rig):
        r = rig(5000)
        time.sleep(0.1)
        n0 = r.master.n_beats()
        for i in range(4):
            aid = r.run_attempt(i)
            r.finish(aid)
            r.reported_at(aid)
            time.sleep(0.08)
        assert r.master.n_beats() - n0 == 4
        assert r.oob_count() == 4

    def test_heartbeat_span_says_oob_and_how_many_freed(self, rig, tmp_path):
        r = rig(5000, conf={"tpumr.trace.enabled": True,
                            "tpumr.trace.dir": str(tmp_path)})
        a, b = r.run_attempt(0), r.run_attempt(1)
        time.sleep(0.1)
        with r.nr.lock:              # both terminal before the one wake
            r.nr.running[a].state = TaskState.SUCCEEDED
            r.nr.running[b].state = TaskState.KILLED
        r.nr._slot_freed()
        r.reported_at(a)
        deadline = time.monotonic() + 2
        spans = []
        while time.monotonic() < deadline and len(spans) < 2:
            spans = [s for s in r.nr.tracer.pending()
                     if s.name == "heartbeat"]
            time.sleep(0.01)
        assert [s.attributes.get("oob") for s in spans] == [None, True]
        assert spans[1].attributes["freed"] == 2
        assert "freed" not in spans[0].attributes


# ------------------------------------------------------------ end to end


class ShortMapper:
    def configure(self, conf):
        pass

    def map(self, key, value, output, reporter):
        time.sleep(0.05)
        for w in value.split():
            output.collect(w, 1)

    def close(self):
        pass


class SumReducer:
    def configure(self, conf):
        pass

    def reduce(self, key, values, output, reporter):
        output.collect(key, sum(values))

    def close(self):
        pass


def test_short_maps_at_the_shipped_interval_do_not_wait_for_the_tick():
    """12 short maps on 2 CPU slots at the shipped 1000 ms beat. On the
    fixed cadence a slot is refilled once a beat: six waves, six beats,
    6 s and more before the reduce can end. With the beat on completion
    the map phase is a few hundred milliseconds; every map still runs
    exactly once."""
    from tpumr.fs import get_filesystem
    from tpumr.mapred.job_client import JobClient
    from tpumr.mapred.mini_cluster import MiniMRCluster

    conf = JobConf()
    conf.set("tpumr.heartbeat.interval.ms", 1000)
    conf.set("tpumr.tracker.expiry.ms", 30_000)
    fs = get_filesystem("mem:///")
    for i in range(12):
        fs.write_bytes(f"/oob/in/part-{i:02d}.txt", b"alpha beta\n")
    with MiniMRCluster(num_trackers=1, conf=conf, cpu_slots=2,
                       tpu_slots=0) as c:
        jc = c.create_job_conf()
        jc.set_input_paths("mem:///oob/in")
        jc.set_output_path("mem:///oob/out")
        jc.set_class("mapred.mapper.class", ShortMapper)
        jc.set_class("mapred.reducer.class", SumReducer)
        jc.set_num_reduce_tasks(1)
        t0 = time.monotonic()
        result = JobClient(jc).run_job(jc)
        took = time.monotonic() - t0
        assert result.successful
        tracker = c.trackers[0]
        snap = tracker.metrics.snapshot()[tracker.name]
        assert snap["cpu_maps_launched"] == 12, "every map exactly once"
        assert snap["reduces_launched"] == 1
        assert snap["heartbeats_out_of_band"] >= 6
        out = fs.read_bytes("mem:///oob/out/part-00000").decode()
        assert dict(l.split("\t") for l in out.splitlines()) == {
            "alpha": "12", "beta": "12"}
        assert took < 4.0, took
