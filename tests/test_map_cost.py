"""The hybrid scheduler's estimate (mapred/map_cost.py) and its two
readers: the CPU share of a hybrid job (scheduler.py ``budget_of``) and
the twin on an idle chip (job_in_progress.py ``_obtain_tpu_twin``).
Stamps are handed in or written onto the estimate: no sleeping except in
the mini-cluster job at the end."""

import math
import threading
import time
from collections import Counter

import pytest

from test_scheduler import (cpu_maps, finish_map, make_job, make_scheduler,
                            profile, tracker_status)
from tpumr.core.counters import JobCounter
from tpumr.mapred import map_cost
from tpumr.mapred.history import job_metrics_rollup
from tpumr.mapred.map_cost import (FEW, CarriedCost, CpuCost,
                                   MapCostEstimate, TurnCost, map_cost_key)
from tpumr.mapred.task import TaskState, TaskStatus

SLOT = ("tracker_0", 0)


def _brute_cpu_share(pending, n_cpu, n_tpu, t_cpu, t_tpu):
    best_x, best_f = 0, math.inf
    for x in range(pending + 1):
        f = max(math.ceil(x / n_cpu) * t_cpu,
                math.ceil((pending - x) / n_tpu)
                * (t_tpu.beside if x else t_tpu.alone))
        if f < best_f:
            best_x, best_f = x, f
    return min(n_cpu, best_x)


def both(t):
    return TurnCost(t, t)


@pytest.mark.parametrize("t_cpu,t_tpu", [
    (10.0, both(1.0)), (1.0, both(10.0)), (2.73, both(0.135)),
    (16.0, both(0.13)), (1.5, both(1.0)), (1.0, both(1.0)),
    (0.3, both(0.29)), (2.8, TurnCost(0.054, 0.128)),
    (2.8, TurnCost(0.2, 0.128)), (1.2, TurnCost(0.054, 0.128))])
def test_cpu_share_is_the_first_argmin_of_f(t_cpu, t_tpu):
    for pending in (1, 2, 3, 4, 7, 19, 24, 25, 100, 501):
        for n_cpu in (1, 2, 3, 8):
            for n_tpu in (1, 2, 4):
                assert map_cost.cpu_share(pending, n_cpu, n_tpu, t_cpu,
                                          t_tpu) == _brute_cpu_share(
                    pending, n_cpu, n_tpu, t_cpu, t_tpu), \
                    (pending, n_cpu, n_tpu)


@pytest.mark.parametrize("pending,n_cpu,n_tpu,t_cpu,t_tpu", [
    (0, 3, 1, 10.0, 1.0), (5, 3, 0, 10.0, 1.0), (5, 3, 1, 0.0, 1.0),
    (5, 3, 1, 10.0, 0.0)])
def test_cpu_share_is_full_with_a_cost_unknown(pending, n_cpu, n_tpu,
                                               t_cpu, t_tpu):
    assert map_cost.cpu_share(pending, n_cpu, n_tpu, t_cpu,
                              both(t_tpu)) == n_cpu


def test_cpu_share_reads_the_turn_of_the_regime_it_weighs():
    """`kmeans-100m.rounds` on the chip: a turn is 0.054 s with no CPU
    map beside it and 0.128 s beside three. By the turn beside alone a
    CPU wave pays (2.8 s against 24 x 0.128 = 3.1 s) and keeps paying,
    round after round; by both it never did (24 x 0.054 = 1.3 s)."""
    assert map_cost.cpu_share(24, 3, 1, 2.8, both(0.128)) == 3
    assert map_cost.cpu_share(24, 3, 1, 2.8, TurnCost(0.054, 0.128)) == 0
    # at any length of the job: the chip alone does 18 maps a second,
    # the chip beside three CPU maps 8 and they 1 between them
    assert map_cost.cpu_share(2400, 3, 1, 2.8, TurnCost(0.054, 0.128)) == 0
    # where CPU maps cost the chip little, their waves keep paying
    assert map_cost.cpu_share(240, 3, 1, 2.8, TurnCost(0.12, 0.128)) == 3


# ----------------------------------------------------------- the estimate


def test_t_cpu_from_running_attempts_alone():
    """No CPU map has finished and none may ever: the longest time one
    has RUN is what a CPU map costs at least."""
    est = MapCostEstimate()
    assert est.t_cpu(100.0, 0, 0.0) == CpuCost(0.0, "none", True)
    est.cpu_launched("a", 100.0)
    est.cpu_launched("b", 103.0)
    assert est.t_cpu(110.0, 0, 0.0) == CpuCost(10.0, "running", True)
    # it grows with the clock, with nothing reported
    assert est.t_cpu(116.0, 0, 0.0).seconds == 16.0


def test_t_cpu_from_a_killed_attempts_last_report():
    """An attempt that ran 16 s and was killed proves a CPU map costs
    over 16 s; one that FINISHED proves nothing of the kind."""
    est = MapCostEstimate()
    est.cpu_launched("a", 100.0)
    est.cpu_launched("b", 100.0)
    est.attempt_ended("a", 116.0, killed=True)
    est.attempt_ended("b", 109.0, killed=False)
    assert not est.cpu_running
    assert est.t_cpu(500.0, 0, 0.0) == CpuCost(16.0, "running", True)


def test_t_cpu_is_the_mean_once_a_few_have_finished():
    est = MapCostEstimate()
    est.cpu_launched("slow", 0.0)
    # one or two finished: the mean, but never under what one has run
    assert est.t_cpu(9.0, 1, 2.0) == CpuCost(9.0, "running", False)
    assert est.t_cpu(1.0, 2, 2.0) == CpuCost(2.0, "job", False)
    # a few: the mean alone (one straggler does not set the cost)
    assert est.t_cpu(9.0, FEW, 2.0) == CpuCost(2.0, "job", False)


def test_turn_is_launch_to_next_launch_with_maps_pending():
    est = MapCostEstimate()
    est.tpu_launched("m0", SLOT, 10.0, still_pending=True)
    est.tpu_launched("m1", SLOT, 10.5, still_pending=True)   # cold turn
    est.tpu_launched("m2", SLOT, 10.7, still_pending=False)  # last map
    assert est.t_tpu_own(0.0) == pytest.approx((0.5 + 0.2) / 2)
    # the chain ended at 10.7: what follows is idleness, not a turn
    est.tpu_launched("twin", SLOT, 18.0, still_pending=False)
    assert est.t_tpu_own(0.0) == pytest.approx(0.35)
    # a requeued map later: the interval from the twin holds the drain
    est.tpu_launched("m1r", SLOT, 30.0, still_pending=True)
    est.tpu_launched("m3", SLOT, 30.3, still_pending=False)
    assert est.t_tpu_own(0.0) == pytest.approx((0.5 + 0.2 + 0.3) / 3)


def test_turn_is_per_slot_and_falls_back_to_the_mean_runtime():
    est = MapCostEstimate()
    assert est.t_tpu_own(0.0) == 0.0
    assert est.t_tpu_own(0.09) == 0.09      # no slot has turned yet
    est.tpu_launched("a", ("tracker_0", 0), 0.0, True)
    est.tpu_launched("b", ("tracker_1", 0), 0.05, True)  # another chip
    assert est.t_tpu_own(0.09) == 0.09
    est.tpu_launched("c", ("tracker_0", 0), 0.2, True)
    assert est.t_tpu_own(0.09) == pytest.approx(0.2)
    assert est.tpu_serving()
    for aid in "abc":
        est.attempt_ended(aid, 1.0, killed=False)
    assert not est.tpu_serving()


def test_own_evidence_replaces_a_carried_number():
    est = MapCostEstimate()
    est.carried = CarriedCost(t_cpu=8.0, cpu_is_bound=True,
                              t_tpu=TurnCost(0.9, 0.0))
    # a kind of turn nobody has measured reads as the other
    assert est.t_tpu(0.0) == TurnCost(0.9, 0.9)
    assert est.t_cpu(0.0, 0, 0.0) == CpuCost(8.0, "carried", True)
    # t_tpu after its first few turns
    for i in range(FEW + 1):
        assert est.t_tpu(0.0).alone == 0.9
        est.tpu_launched(f"m{i}", SLOT, 0.1 * i, still_pending=True)
    assert est.t_tpu(0.0) == TurnCost(pytest.approx(0.1),
                                      pytest.approx(0.1))
    # t_cpu: a running attempt past the carried bound, then a finish
    est.cpu_launched("c", 0.0)
    assert est.t_cpu(5.0, 0, 0.0).source == "carried"
    assert est.t_cpu(12.0, 0, 0.0) == CpuCost(12.0, "running", True)
    assert est.t_cpu(12.0, 1, 3.0) == CpuCost(12.0, "running", False)
    est.attempt_ended("c", 13.0, killed=False)
    assert est.t_cpu(13.0, 1, 3.0) == CpuCost(3.0, "job", False)


def test_turns_alone_and_beside_a_cpu_map_are_kept_apart():
    est = MapCostEstimate()
    est.tpu_launched("m0", SLOT, 0.0, True)
    est.cpu_launched("c0", 0.0)
    est.tpu_launched("m1", SLOT, 0.6, True)       # beside
    est.tpu_launched("m2", SLOT, 0.73, True)      # beside
    est.attempt_ended("c0", 0.8, killed=False)
    est.tpu_launched("m3", SLOT, 0.86, True)      # it ended inside: beside
    est.tpu_launched("m4", SLOT, 0.91, True)      # alone
    est.tpu_launched("m5", SLOT, 0.97, True)      # alone
    t = est.t_tpu(0.0)
    assert t.beside == pytest.approx((0.6 + 0.13 + 0.13) / 3)
    assert t.alone == pytest.approx((0.05 + 0.06) / 2)
    assert est.t_tpu_own(0.0) == pytest.approx(0.97 / 5)
    carried = est.to_carry(1.0, 1, 0.8, 0.0)
    assert carried.t_tpu == t
    # the next job, all on the chip, keeps the carried turn beside
    nxt = MapCostEstimate()
    nxt.carried = carried
    for i in range(FEW + 1):
        nxt.tpu_launched(f"m{i}", SLOT, 0.05 * i, True)
    assert nxt.t_tpu(0.0) == TurnCost(pytest.approx(0.05), t.beside)
    assert nxt.to_carry(1.0, 0, 0.0, 0.0).t_tpu == nxt.t_tpu(0.0)


def test_to_carry_hands_on_what_the_job_ends_with():
    est = MapCostEstimate()
    assert est.to_carry(0.0, 0, 0.0, 0.0) is None
    # a job that never turned a slot: its finished TPU maps' mean
    assert est.to_carry(0.0, 1, 4.0, 1.0) == CarriedCost(
        4.0, False, TurnCost(1.0, 0.0))
    # a job that launched no CPU map hands the carried CPU cost on
    est.carried = CarriedCost(9.0, True, TurnCost(0.5, 0.7))
    est.tpu_launched("a", SLOT, 0.0, True)
    est.tpu_launched("b", SLOT, 0.2, True)
    assert est.to_carry(1.0, 0, 0.0, 0.1) == CarriedCost(
        9.0, True, TurnCost(pytest.approx(0.2), 0.7))
    # a mean is handed on as a mean
    got = est.to_carry(1.0, FEW, 2.5, 0.1)
    assert (got.t_cpu, got.cpu_is_bound) == (2.5, False)


def test_quarantine_forgets_the_tpu_side():
    est = MapCostEstimate()
    est.carried = CarriedCost(9.0, True, both(0.5))
    est.tpu_launched("a", SLOT, 0.0, True)
    est.tpu_launched("b", SLOT, 0.2, True)
    est.forget_tpu()
    assert est.t_tpu(0.0) == both(0.0) and not est.tpu_serving()


def test_what_a_silent_cpu_attempt_has_left():
    mean = CpuCost(2.73, "carried", False)
    assert mean.left_after(2.6) == pytest.approx(0.13)
    assert mean.left_after(5.0) == 0.0      # the floor's case
    bound = CpuCost(6.0, "running", True)
    assert bound.left_after(6.0) == 6.0     # as long again
    assert bound.left_after(1.0) == 5.0     # no less than the bound


def test_key_is_what_fixes_a_maps_cost():
    conf = {"tpumr.map.kernel": "kmeans-assign",
            "mapred.input.dir": "file:///d/points.npy",
            "mapred.input.format.class": "x.DenseInputFormat",
            "tpumr.kmeans.centroids": "file:///out/iter3.in.npy",
            "tpumr.kmeans.use.pallas": False,
            "mapred.job.name": "kmeans-iter-3"}
    splits = [{"num_rows": 500_000, "row_bytes": 512}] * 20
    key = map_cost_key(conf, splits)
    # the next round: another name, another centroid file, the same key
    nxt = dict(conf, **{"tpumr.kmeans.centroids": "file:///out/iter4.in.npy",
                        "mapred.job.name": "kmeans-iter-4"})
    assert map_cost_key(nxt, splits) == key
    # another kernel knob, input, split length or kernel: another key
    assert map_cost_key(dict(conf, **{"tpumr.kmeans.use.pallas": True}),
                        splits) != key
    assert map_cost_key(dict(conf, **{"mapred.input.dir": "file:///e"}),
                        splits) != key
    assert map_cost_key(conf, [{"num_rows": 250_000,
                                "row_bytes": 512}]) != key
    assert map_cost_key(conf, [{"split_length": 256_000_000}]) == key
    assert map_cost_key(dict(conf, **{"tpumr.map.kernel": "matmul-block"}),
                        splits) != key
    assert map_cost_key({"mapred.input.dir": "x"}, splits) is None


# ------------------------------------------------- reader 1: the CPU share


def _running_cpu_maps(job, n, started_ago):
    """``n`` CPU maps launched ``started_ago`` seconds ago, still
    running."""
    tasks = [job.obtain_new_map_task("host0", run_on_tpu=False)
             for _ in range(n)]
    for t in tasks:
        job.map_cost.cpu_running[str(t.attempt_id)] -= started_ago
        job.maps[t.partition].dispatch_mono -= started_ago
        job.update_task_status(TaskStatus(
            attempt_id=t.attempt_id, is_map=True,
            state=TaskState.RUNNING), "h:0")     # silent: no progress
    return tasks


def _tpu_turns(job, n, turn_s):
    """``n`` TPU maps launched and finished ``turn_s`` apart on one
    slot, ending now; returns the last task."""
    now = time.monotonic()
    est = job.map_cost
    task = None
    for i in range(n):
        task = job.obtain_new_map_task("host0", run_on_tpu=True,
                                       tpu_device_id=0,
                                       tracker="tracker_0")
        finish_map(job, task, turn_s * 0.6, True)
    # re-time the launches: n launches, turn_s apart, the last one now
    est._turns[False] = [0.0, 0]
    est._turns[True] = [0.0, 0]
    est._turns[bool(est.cpu_running)] = [turn_s * (n - 1), n - 1]
    est._last_launch[SLOT] = now
    return task


def test_running_attempts_alone_starve_the_cpu_share():
    """The SIFT round's case with NO carried estimate: two CPU maps have
    run 16 s without finishing, the chip turns in 0.13 s. A freed CPU
    slot gets nothing."""
    job = make_job(n_maps=20)
    _running_cpu_maps(job, 2, started_ago=16.0)
    _tpu_turns(job, 4, 0.13)
    cpu, t_tpu = job.map_costs()
    assert cpu.source == "running" and cpu.seconds >= 16.0
    assert t_tpu == both(pytest.approx(0.13))
    assert job.acceleration_factor() > 100
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status(run_cpu=2))
    assert [t.run_on_tpu for t in tasks if t.is_map] == [True]
    assert job.counters.value(JobCounter.GROUP,
                              JobCounter.CPU_MAPS_WITHHELD) == 1


def test_a_lost_trackers_attempt_proves_nothing_of_a_maps_cost():
    """When it stopped running nobody knows: only a KILLED report of the
    tracker itself counts as a run that did not finish."""
    job = make_job(n_maps=4)
    lost, killed = _running_cpu_maps(job, 2, started_ago=50.0)
    job.requeue_lost_attempts([str(lost.attempt_id)])
    job.update_task_status(TaskStatus(
        attempt_id=killed.attempt_id, is_map=True, state=TaskState.KILLED),
        "h:0")
    assert not job.map_cost.cpu_running
    cpu, _ = job.map_costs()
    assert cpu.source == "running" and 50.0 <= cpu.seconds < 51.0
    # the lost one alone would have left nothing
    job2 = make_job(n_maps=4, job_num=2)
    (lost2,) = _running_cpu_maps(job2, 1, started_ago=50.0)
    job2.requeue_lost_attempts([str(lost2.attempt_id)])
    assert job2.map_costs()[0] == CpuCost(0.0, "none", True)


def test_carried_estimate_starves_the_first_beat_of_the_next_round():
    job = make_job(n_maps=20)
    job.adopt_carried_cost(CarriedCost(16.0, True, both(0.13)))
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status())
    assert [t.run_on_tpu for t in tasks if t.is_map] == [True]
    assert job.map_costs()[0].source == "carried"
    # ...and at a factor of 20 over 25 maps one CPU wave still pays
    near = make_job(n_maps=25, job_num=2)
    near.adopt_carried_cost(CarriedCost(2.73, False, both(0.135)))
    sched2 = make_scheduler([near])
    assert len(cpu_maps(sched2.assign_tasks(tracker_status()))) == 3
    # but no second wave: with 2 maps pending the chain is 0.3 s
    for _ in range(19):
        near.obtain_new_map_task("host0", run_on_tpu=True, tpu_device_id=0)
    assert near.pending_map_count() == 2
    assert cpu_maps(sched2.assign_tasks(
        tracker_status(run_cpu=2, devices=[False]))) == []


def test_round_after_a_cold_job_reads_the_turn_alone():
    """`kmeans-100m.rounds`, round 2, by the cold job's own numbers (my
    chip run, PR 31): a CPU map 6.3 s, a turn 0.42 s beside CPU maps and
    0.30 s once they had ended. By one mean of the turns the round hands
    three maps to the CPU slots again (24 x 0.39 s against 6.3 s), and by
    the turns it then measures beside them, every round after it."""
    job = make_job(n_maps=25)
    job.adopt_carried_cost(CarriedCost(6.3, False, TurnCost(0.30, 0.42)))
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status())
    assert [t.run_on_tpu for t in tasks if t.is_map] == [True]
    one_mean = make_job(n_maps=25, job_num=2)
    one_mean.adopt_carried_cost(CarriedCost(6.3, False, both(0.39)))
    assert len(cpu_maps(make_scheduler([one_mean]).assign_tasks(
        tracker_status()))) == 3


def test_cpu_pinned_maps_keep_their_floor_of_cpu_slots():
    job = make_job(n_maps=10)
    profile(job, cpu_s=10.0, tpu_s=1.0)
    with job.lock:
        job._cpu_only_maps.update(sorted(job._pending_maps)[-2:])
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status())
    # the rule alone says 0; the two demoted maps can run nowhere else
    assert len(cpu_maps(tasks)) == 2
    assert {t.partition for t in cpu_maps(tasks)} <= job._cpu_only_maps


def test_quarantined_job_keeps_the_full_share_and_carries_nothing():
    job = make_job(n_maps=10)
    profile(job, cpu_s=10.0, tpu_s=1.0)
    job.tpu_disabled = True
    sched = make_scheduler([job])
    tasks = sched.assign_tasks(tracker_status())
    assert len(cpu_maps(tasks)) == 3 and all(
        not t.run_on_tpu for t in tasks if t.is_map)
    assert job.cost_to_carry() is None
    assert job.acceleration_factor() == 1.0


def test_fifo_scheduler_keeps_giving_the_full_share():
    from tpumr.mapred.jobconf import JobConf
    from tpumr.mapred.scheduler import FifoScheduler
    from test_scheduler import FakeManager
    job = make_job(n_maps=10)
    profile(job, cpu_s=10.0, tpu_s=1.0)
    sched = FifoScheduler()
    sched.configure(JobConf())
    sched.set_manager(FakeManager([job]))
    assert len(cpu_maps(sched.assign_tasks(tracker_status()))) == 3


# ------------------------------------------- reader 2: the twin on the chip


def _twin_job(n_maps=6, **conf):
    job = make_job(n_maps=n_maps, n_reduces=0)
    job.conf.update(conf)
    return job


def _drain_on_tpu(job, turn_s=0.1):
    n = job.pending_map_count()
    return _tpu_turns(job, n, turn_s)


def _ask_tpu(job):
    return job.obtain_new_map_task("host0", run_on_tpu=True,
                                   tpu_device_id=0, tracker="tracker_0")


def test_idle_chip_twins_a_cpu_map_that_cannot_finish():
    """The cold job's case: nothing carried, no CPU map ever finished.
    When the chip's chain ends the CPU maps have run 3 s, far under the
    10 s floor, and the chip would do each over in 0.1 s."""
    job = _twin_job()
    cpu = _running_cpu_maps(job, 2, started_ago=3.0)
    _drain_on_tpu(job)
    assert job.pending_map_count() == 0
    twin = _ask_tpu(job)
    assert twin is not None and twin.run_on_tpu
    assert twin.partition in {t.partition for t in cpu}
    assert twin.attempt_id.attempt == 1
    assert job.counters.value(JobCounter.GROUP,
                              JobCounter.TPU_TWINS_OF_CPU_MAPS) == 1
    assert job.speculative_in_flight() == 1
    # the other one next (one twin a map: the first is not twinned again)
    twin2 = _ask_tpu(job)
    assert twin2 is not None and twin2.partition != twin.partition
    assert _ask_tpu(job) is None
    # first completion wins, the loser is killed: exactly as now
    finish_map(job, twin, 0.1, True)
    loser = next(t for t in cpu if t.partition == twin.partition)
    assert job.kill_marked(str(loser.attempt_id))
    assert job.speculative_won == 1
    # the killed attempt's run is what the next job starts from
    job.update_task_status(TaskStatus(
        attempt_id=loser.attempt_id, is_map=True, state=TaskState.KILLED),
        "h:0")
    carried = job.cost_to_carry()
    assert carried.cpu_is_bound and carried.t_cpu >= 3.0
    assert carried.t_tpu == TurnCost(0.0, pytest.approx(0.1))
    roll = job_metrics_rollup(job)
    assert roll["estimate_from"] == "running"
    assert roll["t_cpu_estimate_s"] >= 3.0
    assert roll["t_tpu_turn_s"] == pytest.approx(0.1)
    assert roll["t_tpu_turn_beside_cpu_s"] == pytest.approx(0.1)
    assert roll["acceleration_factor_profiled"] >= 30.0


def test_cpu_maps_that_have_almost_finished_get_no_twin():
    """`kmeans-100m.rounds`: a CPU map costs 2.73 s by the round before,
    these have run 2.6 s when the chain ends: 0.13 s left is under
    three turns of 0.135 s."""
    job = _twin_job()
    job.adopt_carried_cost(CarriedCost(2.73, False, both(0.135)))
    _running_cpu_maps(job, 2, started_ago=2.6)
    _drain_on_tpu(job, turn_s=0.135)
    assert _ask_tpu(job) is None
    assert job.counters.value(JobCounter.GROUP,
                              JobCounter.TPU_TWINS_OF_CPU_MAPS) == 0
    # a CPU map that REPORTS how far it is goes by its own rate
    slow = job.maps[0]
    slow.rate_ewma, slow.last_progress = 0.01, 0.2     # 80 s left
    twin = _ask_tpu(job)
    assert twin is not None and twin.partition == 0


@pytest.mark.parametrize("why", ["speculation_off", "hold", "cap",
                                 "cpu_pinned", "no_turn_of_its_own",
                                 "quarantined"])
def test_twin_is_withheld(why):
    conf = {}
    if why == "speculation_off":
        conf["mapred.speculative.execution"] = False
    if why == "cap":
        conf["tpumr.speculative.cap"] = 1
    job = _twin_job(**conf)
    # (the constructor read the conf before the update)
    job.speculative = why != "speculation_off"
    job.speculative_cap = 1 if why == "cap" else 2
    cpu = _running_cpu_maps(job, 2, started_ago=3.0)
    if why == "no_turn_of_its_own":
        # carried numbers do not do: the chip's cost is measured on
        # THIS job
        job.adopt_carried_cost(CarriedCost(16.0, True, both(0.1)))
        while job.pending_map_count():
            job.obtain_new_map_task("host0", run_on_tpu=False)
    else:
        _drain_on_tpu(job)
    if why == "hold":
        job.speculation_hold = True
    if why == "cpu_pinned":
        job._cpu_only_maps.update(t.partition for t in cpu)
    if why == "quarantined":
        job.tpu_disabled = True
    if why == "cap":
        assert _ask_tpu(job) is not None       # the one the cap allows
    assert _ask_tpu(job) is None


def test_floor_still_governs_a_cpu_twin_of_a_cpu_map():
    """The rule is the chip's alone: a free CPU slot asking for the same
    job waits out mapred.speculative.min.runtime.s as before."""
    job = _twin_job()
    _running_cpu_maps(job, 2, started_ago=3.0)
    _drain_on_tpu(job)
    assert job.obtain_new_map_task("host0", run_on_tpu=False) is None
    for tip in job.maps:
        if tip.state == "running":
            tip.dispatch_mono -= 100.0
    assert job.obtain_new_map_task("host0", run_on_tpu=False) is not None


def test_pending_maps_all_cpu_pinned_still_let_the_chip_twin():
    job = _twin_job(n_maps=4)
    cpu = _running_cpu_maps(job, 1, started_ago=3.0)
    last = _tpu_turns(job, 2, 0.1)
    assert last is not None and job.pending_map_count() == 1
    job._cpu_only_maps.update(job._pending_maps)
    twin = _ask_tpu(job)
    assert twin is not None and twin.partition == cpu[0].partition


# ------------------------------------------------------- the master carries


def _submit(master, name, **conf):
    base = {"mapred.reduce.tasks": 0, "tpumr.map.kernel": "kmeans-assign",
            "mapred.input.dir": "mem:///pts.npy", "mapred.job.name": name}
    base.update(conf)
    jid = master.submit_job(base, [{"num_rows": 100, "row_bytes": 64,
                                    "locations": []} for _ in range(4)])
    return master.jobs[jid]


def test_master_carries_to_the_same_key_and_not_to_another():
    from tpumr.mapred.jobconf import JobConf
    from tpumr.mapred.jobtracker import JobMaster
    master = JobMaster(JobConf())
    try:
        first = _submit(master, "round-0",
                        **{"tpumr.kmeans.centroids": "mem:///c0.npy"})
        assert first.map_cost.carried is None
        assert first.map_costs()[0].source == "none"
        _running_cpu_maps(first, 1, started_ago=5.0)
        for t in iter(lambda: first.obtain_new_map_task(
                "h", run_on_tpu=True, tpu_device_id=0, tracker="t0"),
                None):
            finish_map(first, t, 0.05, True)
            if first.pending_map_count() == 0:
                break
        twin = first.obtain_new_map_task("h", run_on_tpu=True,
                                         tpu_device_id=0, tracker="t0")
        finish_map(first, twin, 0.05, True)
        assert first.state == "SUCCEEDED"
        master._finalize_job(first)
        # the same key (another round's name and centroid file)
        second = _submit(master, "round-1",
                         **{"tpumr.kmeans.centroids": "mem:///c1.npy"})
        got = second.map_cost.carried
        assert got is not None and got.cpu_is_bound and got.t_cpu >= 5.0
        assert second.map_costs()[0].source == "carried"
        # another key: from nothing, as before
        other = _submit(master, "elsewhere",
                        **{"mapred.input.dir": "mem:///other.npy"})
        assert other.map_cost.carried is None
        plain = master.jobs[master.submit_job(
            {"mapred.reduce.tasks": 0}, [{"locations": []}])]
        assert plain.map_cost_key is None and plain.map_cost.carried is None
        # a job that did not succeed hands nothing on
        master._map_costs.clear()
        second.kill()
        master._finalize_job(second)
        assert not master._map_costs
    finally:
        master.stop()


# ------------------------------------------------- a real cluster, two rounds

_release = threading.Event()


def _register_slowcpu_kernel():
    """Word counts whose CPU batch path waits far past the TPU path's
    time (until released): a map a CPU slot cannot finish."""
    from tpumr.ops.registry import KernelMapper, register_kernel

    def _count(batch):
        counts = Counter()
        for _k, v in batch:
            counts.update(bytes(v).split())
        return sorted(counts.items())

    def _slow(batch, conf, task):
        _release.wait(timeout=30.0)
        return _count(batch)

    class SlowCpuKernel(KernelMapper):
        name = "slowcpu"

        def map_batch(self, batch, conf, task):
            return _count(batch)

        map_batch_cpu = staticmethod(_slow)

    return register_kernel(SlowCpuKernel())


def _round(cluster, fs, n, maps=6):
    from tpumr.mapred.job_client import JobClient
    conf = cluster.create_job_conf()
    conf.set_input_paths("mem:///mc/in.txt")
    conf.set_output_path(f"mem:///mc/out{n}")
    conf.set_job_name(f"round-{n}")
    conf.set("mapred.mapper.class", "tpumr.mapred.lib.TokenCountMapper")
    conf.set("mapred.reducer.class", "tpumr.examples.basic.LongSumReducer")
    conf.set("mapred.map.tasks", maps)
    conf.set("mapred.min.split.size", 1)
    conf.set_num_reduce_tasks(1)
    conf.set_map_kernel("slowcpu")
    t0 = time.monotonic()
    result = JobClient(conf).run_job(conf)
    return result, time.monotonic() - t0


def test_cluster_job_does_not_wait_out_the_floor_and_round_two_learns():
    from tpumr.fs import get_filesystem
    from tpumr.mapred.mini_cluster import MiniMRCluster
    _register_slowcpu_kernel()
    _release.clear()
    fs = get_filesystem("mem:///")
    fs.write_bytes("/mc/in.txt", b"".join(b"w%02d x\n" % (i % 23)
                                          for i in range(3000)))
    with MiniMRCluster(num_trackers=1, cpu_slots=2, tpu_slots=1) as c:
        try:
            first, took = _round(c, fs, 0)
            assert first.successful
            jip = c.master.jobs[str(first.job_id)]
            n_maps = len(jip.maps)
            # the first beat gave the CPU slots maps they cannot end;
            # the chip did them over when its chain ended, not
            # mapred.speculative.min.runtime.s (10 s) after their launch
            assert took < 8.0, took
            seq = jip.placement_timeline()["seq"]
            assert seq.count("c") == 2 and seq.count("T") == n_maps, seq
            assert jip.counters.value(
                JobCounter.GROUP, JobCounter.TPU_TWINS_OF_CPU_MAPS) == 2
            assert jip.finished_cpu_maps == 0
            assert jip.finished_tpu_maps == n_maps
            roll = job_metrics_rollup(jip)
            assert roll["estimate_from"] == "running"
            assert roll["acceleration_factor_profiled"] > 1.0
            want = _read_counts(fs, "/mc/out0")
        finally:
            _release.set()        # the killed attempts end; slots free
        deadline = time.monotonic() + 10.0
        while c.trackers[0]._status_dict()["count_cpu_map_tasks"] \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        _release.clear()
        try:
            second, _ = _round(c, fs, 1)
            assert second.successful
            jip2 = c.master.jobs[str(second.job_id)]
            # round 2 knows what round 1 learned: no CPU map is launched
            assert set(jip2.placement_timeline()["seq"]) == {"T"}
            assert jip2.counters.value(
                JobCounter.GROUP, JobCounter.CPU_MAPS_WITHHELD) > 0
            assert jip2.counters.value(
                JobCounter.GROUP, JobCounter.TPU_TWINS_OF_CPU_MAPS) == 0
            roll2 = job_metrics_rollup(jip2)
            assert roll2["estimate_from"] == "carried"
            assert _read_counts(fs, "/mc/out1") == want
        finally:
            _release.set()


def _read_counts(fs, out_dir):
    out = {}
    for st in fs.list_files(f"mem://{out_dir}"):
        if st.path.name.startswith("part-"):
            for line in fs.read_bytes(st.path).decode().splitlines():
                k, v = line.split("\t")
                out[k] = int(v)
    assert out
    return out
