"""Scenario lab (tpumr/scale/scenario.py) + master brownout
(tpumr/mapred/brownout.py): spec validation, deterministic trace
planning, per-class windowed SLO verdicts, the brownout step-up/step-
down state machine, the tracker-churn chaos seams, and two end-to-end
mixes (acceptance: churn completes every job with adoption counters
moving; overload engages the brownout, interactive recovers WHILE it
is active, and it fully steps down after the pressure clears)."""

import json
import os
import time
import types

import pytest

from tpumr.mapred.brownout import LEVELS, MAX_LEVEL, BrownoutController
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.jobtracker import JobMaster
from tpumr.metrics.flightrec import FlightRecorder
from tpumr.metrics.histogram import Histogram
from tpumr.scale import SimTracker
from tpumr.scale.scenario import (BUILTIN_SCENARIOS, ScenarioError,
                                  load_spec, plan, run_named,
                                  validate_spec)
from tpumr.utils import fi


def _spec(**over):
    base = {
        "name": "t",
        "classes": [{"name": "interactive", "jobs": 2, "maps": 2}],
    }
    base.update(over)
    return base


# ------------------------------------------------------------ specs


class TestSpecValidation:
    def test_minimal_spec_normalizes_with_defaults(self):
        out = validate_spec(_spec())
        assert out["fleet"]["trackers"] == 8
        assert out["master"]["expiry_ms"] == 60_000
        assert out["classes"][0]["priority"] == "NORMAL"
        assert out["classes"][0]["slo_assign_ms"] is None
        assert out["chaos"] == []

    def test_validate_is_idempotent(self):
        once = validate_spec(_spec())
        assert validate_spec(once) == once

    def test_unknown_keys_rejected_at_every_level(self):
        with pytest.raises(ScenarioError, match="unknown top-level"):
            validate_spec(_spec(typo=1))
        with pytest.raises(ScenarioError, match="unknown keys"):
            validate_spec(_spec(fleet={"trackerz": 4}))
        with pytest.raises(ScenarioError, match="unknown keys"):
            validate_spec(_spec(classes=[{"name": "a", "jbos": 2}]))

    @pytest.mark.parametrize("over, match", [
        ({"master": {"shards": 2}}, r"master has unknown keys \['shards'\]"),
        ({"chaos": [{"kind": "shard_kill", "at_ms": 0}]},
         r"chaos\[0\]\.kind must be one of"),
    ])
    def test_one_master_only(self, over, match):
        # no special case: a sharded master's spec fields fall to the
        # rule that rejects any unknown key or chaos kind
        with pytest.raises(ScenarioError, match=match):
            validate_spec(_spec(**over))
        assert "shard_kill" not in BUILTIN_SCENARIOS

    def test_classes_required_and_named(self):
        with pytest.raises(ScenarioError, match="non-empty"):
            validate_spec({"name": "t", "classes": []})
        with pytest.raises(ScenarioError, match="identifier"):
            validate_spec(_spec(classes=[{"name": "no spaces!"}]))

    def test_bad_priority_and_negative_numbers_rejected(self):
        with pytest.raises(ScenarioError, match="priority"):
            validate_spec(_spec(
                classes=[{"name": "a", "priority": "URGENT"}]))
        with pytest.raises(ScenarioError, match="non-negative"):
            validate_spec(_spec(
                classes=[{"name": "a", "period_ms": -5}]))

    def test_chaos_kinds_and_fi_points_screened(self):
        with pytest.raises(ScenarioError, match="kind"):
            validate_spec(_spec(chaos=[{"kind": "meteor", "at_ms": 0}]))
        # fi points are bare seam names; the tpumr.fi. prefix is added
        # by the runner
        with pytest.raises(ScenarioError, match="bare seam"):
            validate_spec(_spec(chaos=[
                {"kind": "fi", "at_ms": 0,
                 "point": "tpumr.fi.task.slow", "probability": 0.5}]))
        with pytest.raises(ScenarioError, match="probability"):
            validate_spec(_spec(chaos=[
                {"kind": "fi", "at_ms": 0, "point": "task.slow",
                 "probability": 1.5}]))

    def test_builtins_all_validate(self):
        for name, spec in BUILTIN_SCENARIOS.items():
            out = validate_spec(spec)
            assert out["name"] == name
            assert out["classes"]


class TestPlanDeterminism:
    def test_same_seed_same_plan(self):
        for name in BUILTIN_SCENARIOS:
            spec = dict(BUILTIN_SCENARIOS[name], seed=1337)
            assert plan(spec) == plan(spec), name

    def test_plan_is_time_sorted_and_jitter_is_seeded(self):
        spec = _spec(classes=[{"name": "a", "jobs": 8, "maps": 1,
                               "period_ms": 100, "jitter_ms": 500}])
        p1 = plan(dict(spec, seed=1))
        assert [e["t_s"] for e in p1] == sorted(e["t_s"] for e in p1)
        assert p1 != plan(dict(spec, seed=2))

    def test_default_chaos_targets_drawn_from_seed(self):
        spec = _spec(chaos=[{"kind": "tracker_crash", "at_ms": 100,
                             "count": 2}])
        crash = [e for e in plan(dict(spec, seed=3))
                 if e["kind"] == "tracker_crash"]
        assert len(crash) == 1 and len(crash[0]["targets"]) == 2
        assert crash == [e for e in plan(dict(spec, seed=3))
                         if e["kind"] == "tracker_crash"]


class TestTomlSpecs:
    def _toml(self):
        try:
            import tomllib  # noqa: F401
        except ImportError:
            pytest.importorskip(
                "tomli", reason="TOML specs need py3.11+ or tomli")

    def test_load_spec_from_scenario_dir(self, tmp_path):
        self._toml()
        (tmp_path / "mini.toml").write_text(
            'seed = 9\n'
            '[fleet]\ntrackers = 3\n'
            '[[classes]]\nname = "quick"\njobs = 1\nmaps = 2\n'
            'slo_assign_ms = 5000\n')
        spec = load_spec("mini", scenario_dir=str(tmp_path))
        assert spec["name"] == "mini" and spec["seed"] == 9
        assert spec["classes"][0]["slo_assign_ms"] == 5000

    def test_bad_toml_is_a_scenario_error(self, tmp_path):
        self._toml()
        (tmp_path / "broken.toml").write_text("= not toml =")
        with pytest.raises(ScenarioError, match="bad TOML"):
            load_spec("broken", scenario_dir=str(tmp_path))

    def test_unknown_name_lists_builtins(self):
        with pytest.raises(ScenarioError, match="churn_storm"):
            load_spec("no_such_mix")


# ------------------------------------------------------------ brownout


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now

    def tick(self, s=1.0):
        self.now += s


def _ctrl(**over):
    clock = FakeClock()
    kw = dict(engage_ticks=3, release_ticks=2, dwell_s=5.0,
              cadence_factor=3.0, clock=clock)
    kw.update(over)
    return BrownoutController(**kw), clock


class TestBrownoutStateMachine:
    def test_engages_only_after_consecutive_pressure(self):
        b, clock = _ctrl()
        for _ in range(2):
            b.on_tick(True)
            clock.tick()
        assert b.level == 0
        b.on_tick(True)
        assert b.level == 1 and b.step_ups == 1

    def test_clear_tick_resets_the_run(self):
        b, clock = _ctrl()
        b.on_tick(True); clock.tick()
        b.on_tick(True); clock.tick()
        b.on_tick(False); clock.tick()   # run broken
        b.on_tick(True); clock.tick()
        b.on_tick(True); clock.tick()
        assert b.level == 0

    def test_dwell_rate_limits_step_ups(self):
        b, clock = _ctrl(dwell_s=10.0)
        for _ in range(3):
            b.on_tick(True); clock.tick()
        assert b.level == 1
        for _ in range(5):               # pressure continues, < dwell
            b.on_tick(True); clock.tick()
        assert b.level == 1
        clock.tick(10.0)
        for _ in range(3):
            b.on_tick(True); clock.tick()
        assert b.level == 2

    def test_release_steps_down_one_level_per_dwell(self):
        b, clock = _ctrl(dwell_s=1.0)
        for _ in range(3):
            b.on_tick(True); clock.tick(2.0)
        for _ in range(3):
            b.on_tick(True); clock.tick(2.0)
        assert b.level == 2
        downs = 0
        for _ in range(10):
            b.on_tick(False); clock.tick(2.0)
            downs = max(downs, b.step_downs)
            if b.level == 0:
                break
        assert b.level == 0 and b.step_downs == 2
        # transitions journaled (old, new) with the fake clock's stamps
        trans = [(t[1], t[2]) for t in b.transitions]
        assert trans == [(0, 1), (1, 2), (2, 1), (1, 0)]

    def test_caps_at_max_level(self):
        b, clock = _ctrl(dwell_s=0.0)
        for _ in range(MAX_LEVEL * 3 + 9):
            b.on_tick(True); clock.tick()
        assert b.level == MAX_LEVEL == len(LEVELS)

    def test_shed_ranking_is_graceful(self):
        # the ranked steps: trace sampling first, cadence second,
        # speculation + history I/O last — never the reverse
        b, _ = _ctrl()
        assert not b.sheds("trace")
        b._change(1, 0.0)
        assert b.sheds("trace") and not b.sheds("cadence")
        b._change(2, 0.0)
        assert b.sheds("cadence") and not b.sheds("speculation")
        b._change(3, 0.0)
        assert b.sheds("speculation") and b.sheds("history") \
            and b.sheds("trace")

    def test_stretch_interval_only_while_shedding_cadence(self):
        b, _ = _ctrl(cadence_factor=3.0)
        assert b.stretch_interval(0.1, 1.0) == pytest.approx(0.1)
        b._change(2, 0.0)
        assert b.stretch_interval(0.1, 1.0) == pytest.approx(0.3)
        # capped at the instructed max...
        assert b.stretch_interval(0.5, 1.0) == pytest.approx(1.0)
        # ...but never stretched BELOW the current interval when the
        # configured max is smaller than it
        assert b.stretch_interval(0.5, 0.2) == pytest.approx(0.5)

    def test_from_conf_disabled_by_default(self):
        conf = JobConf()
        assert BrownoutController.from_conf(conf) is None
        conf.set("tpumr.brownout.enabled", True)
        conf.set("tpumr.brownout.engage.ticks", 7)
        b = BrownoutController.from_conf(conf)
        assert b is not None and b.engage_ticks == 7

    def test_snapshot_shape(self):
        b, clock = _ctrl(dwell_s=0.0)
        for _ in range(3):
            b.on_tick(True); clock.tick()
        snap = b.snapshot()
        assert snap["level"] == 1 and snap["step_ups"] == 1
        assert snap["sheds"] == ["trace"]
        assert snap["transitions"][-1]["to"] == 1


# ------------------------------------------------------------ per-class fold


def _recorder(tmp_path, conf=None):
    master = types.SimpleNamespace(
        _hb_seconds=Histogram("heartbeat_seconds"),
        _hb_lag=Histogram("heartbeat_lag_seconds"),
        _class_hists={}, _mreg=None, brownout=None,
        scenario_name="unit")
    rec = FlightRecorder(master, None, slo_ms=250, cooldown_ms=0,
                         incident_dir=str(tmp_path), conf=conf)
    return master, rec


class TestPerClassWindows:
    def test_fold_windows_deltas_not_cumulative(self, tmp_path):
        conf = JobConf()
        conf.set("tpumr.scenario.slo.web.assign.ms", 100)
        master, rec = _recorder(tmp_path, conf)
        h = Histogram("class_assign_seconds|class=web")
        master._class_hists[("assign", "web")] = h
        h.observe(0.5)                       # breach (slo 100ms)
        rows = rec._fold_classes()
        assert rows == [("web", "assign", pytest.approx(rows[0][2]),
                         0.1, True)]
        assert rows[0][2] > 0.1
        st = rec._class_state["web"]
        assert st["assign_windows"] == 1
        assert st["assign_breach_windows"] == 1
        assert st["assign_ok"] is False
        # next window: only NEW observations count — fast ones now
        for _ in range(50):
            h.observe(0.01)
        rows = rec._fold_classes()
        assert rows[0][4] is False           # windowed p99 recovered
        assert rec._class_state["web"]["assign_ok"] is True
        # an empty window leaves the verdict state untouched
        assert rec._fold_classes() == []
        assert rec._class_state["web"]["assign_windows"] == 2

    def test_class_without_slo_observed_never_judged(self, tmp_path):
        master, rec = _recorder(tmp_path, JobConf())
        h = Histogram("class_complete_seconds|class=bulk")
        master._class_hists[("complete", "bulk")] = h
        h.observe(99.0)
        rec._fold_classes()
        report = rec.class_report()
        assert report["bulk"]["complete"]["ok"] is None
        assert report["bulk"]["pass"] is True

    def test_class_report_fails_breaching_class_only(self, tmp_path):
        conf = JobConf()
        conf.set("tpumr.scenario.slo.web.assign.ms", 100)
        conf.set("tpumr.scenario.slo.bulk.complete.ms", 60_000)
        master, rec = _recorder(tmp_path, conf)
        web = Histogram("a"); bulk = Histogram("b")
        master._class_hists[("assign", "web")] = web
        master._class_hists[("complete", "bulk")] = bulk
        web.observe(2.0); bulk.observe(1.0)
        rec._fold_classes()
        report = rec.class_report()
        assert report["web"]["pass"] is False
        assert report["bulk"]["pass"] is True

    def test_window_history_records_level_and_verdict_bits(
            self, tmp_path):
        conf = JobConf()
        conf.set("tpumr.scenario.slo.web.assign.ms", 100)
        master, rec = _recorder(tmp_path, conf)
        h = Histogram("x")
        master._class_hists[("assign", "web")] = h
        h.observe(0.5)
        # the window record is the subject here, not the bundle (the
        # stub master has no metrics system to snapshot)
        rec.write_incident = lambda breaches: None
        rec._tick()
        hist = rec.window_history()
        assert len(hist) == 1
        assert hist[0]["classes"]["web"]["assign_ok"] is False
        assert hist[0]["brownout_level"] == 0


# ------------------------------------------------------------ chaos seams


def _fi_conf(**keys):
    conf = JobConf()
    conf.set("tpumr.fi.seed", 42)
    for k, v in keys.items():
        conf.set(k, v)
    return conf


class TestTrackerCrashSeam:
    def setup_method(self):
        fi.reset()

    def teardown_method(self):
        fi.reset()

    def test_seam_fires_and_hard_kills_mid_beat(self):
        master = JobMaster(JobConf()).start()
        try:
            host, port = master.address
            conf = _fi_conf(**{
                "tpumr.fi.tracker.crash.probability": 1.0,
                "tpumr.fi.tracker.crash.max.failures": 1})
            t = SimTracker("doomed", host, port, fi_conf=conf)
            try:
                assert t.heartbeat_begin() is False
                assert t.crashed and t.stopped
                assert fi.fired("tracker.crash") == 1
                # capped: a fresh tracker under the same conf survives
                t2 = SimTracker("safe", host, port, fi_conf=conf)
                try:
                    assert t2.heartbeat_begin() is True
                    t2.heartbeat_finish()
                    assert not t2.crashed
                finally:
                    t2.close()
            finally:
                t.close()
        finally:
            master.stop()

    def test_targeted_seam_kills_only_its_slot(self):
        master = JobMaster(JobConf()).start()
        try:
            host, port = master.address
            conf = _fi_conf(**{
                "tpumr.fi.tracker.crash.t3.probability": 1.0})
            bystander = SimTracker("t2", host, port, index=2,
                                   fi_conf=conf)
            target = SimTracker("t3", host, port, index=3,
                                fi_conf=conf)
            try:
                assert bystander.heartbeat_begin() is True
                bystander.heartbeat_finish()
                assert target.heartbeat_begin() is False
                assert target.crashed and not bystander.crashed
            finally:
                bystander.close()
                target.close()
        finally:
            master.stop()


class TestColdReRegistration:
    def test_known_tracker_initial_contact_requeues_and_counts(self):
        """A tracker process that dies and comes back under its old
        name FASTER than the expiry sweep: the master must swap in the
        fresh registration, drop the stale replay-cache entry, and
        requeue the old incarnation's work — not feed the new process
        the dead one's actions."""
        conf = JobConf()
        conf.set("tpumr.heartbeat.interval.ms", 50)
        master = JobMaster(conf).start()
        host, port = master.address
        old = SimTracker("phoenix", host, port)
        try:
            old.heartbeat_once()
            assert "phoenix" in master.trackers
            # process dies silently...
            old.crash()
            # ...and the replacement registers under the same name
            # before any eviction sweep notices
            new = SimTracker("phoenix", host, port)
            try:
                new.heartbeat_once()
                jt = master.metrics.snapshot()["jobtracker"]
                assert jt.get("trackers_restarted", 0) == 1
                assert jt.get("trackers_adopted", 0) == 0
                # the new incarnation keeps beating normally (its
                # replay cache entry is its own, not the dead one's)
                new.heartbeat_once()
                assert new.heartbeats == 2
            finally:
                new.close()
        finally:
            old.close()
            master.stop()


# ------------------------------------------------------------ dfs specs


def _dfs_spec(**over):
    base = _spec(dfs={"datanodes": 3, "clients": 2, "files": 2,
                      "file_kb": 16})
    base.update(over)
    return base


class TestDFSSpecValidation:
    def test_dfs_table_normalizes_with_defaults(self):
        out = validate_spec(_dfs_spec())
        assert out["dfs"]["datanodes"] == 3
        assert out["dfs"]["replication_interval_ms"] == 200
        assert out["dfs"]["max_error_fraction"] == 0.02
        assert validate_spec(out) == out          # idempotent
        assert validate_spec(_spec())["dfs"] is None

    def test_storage_chaos_requires_dfs_table(self):
        for kind in ("dn_crash", "dn_partition", "nn_restart",
                     "block_corrupt"):
            with pytest.raises(ScenarioError, match="dfs"):
                validate_spec(_spec(
                    chaos=[{"kind": kind, "at_ms": 0}]))
            validate_spec(_dfs_spec(
                chaos=[{"kind": kind, "at_ms": 0}]))

    def test_out_of_range_targets_rejected(self):
        with pytest.raises(ScenarioError, match="datanode indexes"):
            validate_spec(_dfs_spec(chaos=[
                {"kind": "dn_crash", "at_ms": 0, "targets": [3]}]))
        with pytest.raises(ScenarioError, match="file_index"):
            validate_spec(_dfs_spec(chaos=[
                {"kind": "block_corrupt", "at_ms": 0,
                 "file_index": 2}]))

    def test_too_few_datanodes_rejected(self):
        # the seeded working set writes at replication=2
        with pytest.raises(ScenarioError, match="datanodes"):
            validate_spec(_spec(dfs={"datanodes": 1}))


class TestDFSPlanDeterminism:
    def test_dn_crash_targets_and_corrupt_file_drawn_from_seed(self):
        spec = _dfs_spec(chaos=[
            {"kind": "dn_crash", "at_ms": 100, "count": 2},
            {"kind": "block_corrupt", "at_ms": 200},
            {"kind": "nn_restart", "at_ms": 300, "outage_ms": 250},
            {"kind": "dn_partition", "at_ms": 400,
             "duration_ms": 1500},
        ])
        p1 = plan(dict(spec, seed=7))
        assert p1 == plan(dict(spec, seed=7))
        rows = {e["kind"]: e for e in p1 if e["kind"] != "submit"}
        assert len(rows["dn_crash"]["targets"]) == 2
        assert all(0 <= t < 3 for t in rows["dn_crash"]["targets"])
        assert 0 <= rows["block_corrupt"]["file_index"] < 2
        assert rows["nn_restart"]["outage_s"] == pytest.approx(0.25)
        assert rows["dn_partition"]["duration_s"] == pytest.approx(1.5)


# ------------------------------------------------------------ e2e mixes


_GENEROUS_MS = 600_000


def _generous(name, seed):
    """The built-in mix with every latency SLO it sets widened to ten
    minutes. These tests accept completion, counters and the replay
    plan; ``rep["pass"]`` folds the per-class (and DFS read) p99
    verdicts in, and a runner busy with five other workers must not
    fail an acceptance of adoption on a wall-clock p99. The mixes' own
    SLOs are what ``tpumr simulate -scenario`` gates on."""
    spec = dict(BUILTIN_SCENARIOS[name], seed=seed)
    spec["classes"] = [
        dict(c, **{k: _GENEROUS_MS
                   for k in ("slo_assign_ms", "slo_complete_ms")
                   if c.get(k) is not None})
        for c in spec["classes"]]
    if spec.get("dfs"):
        spec["dfs"] = dict(spec["dfs"], **{
            k: _GENEROUS_MS
            for k in ("slo_read_p99_ms", "slo_meta_p99_ms")
            if spec["dfs"].get(k) is not None})
    return spec


class TestScenarioEndToEnd:
    def test_churn_mix_completes_everything_with_adoption(
            self, tmp_path):
        """Acceptance: trackers hard-killed mid-run, partitioned past
        the expiry, and crash-rejoined inside it — every workload still
        completes and the adoption/restart counters prove each rejoin
        path actually ran."""
        rep = run_named(_generous("churn_storm", 1337),
                        artifacts_dir=str(tmp_path))
        jobs = rep["jobs"]
        assert jobs["failed"] == 0 and jobs["unfinished"] == 0
        assert jobs["succeeded"] == jobs["submitted"] > 0
        chaos = rep["chaos"]
        assert chaos["trackers_crashed"] >= 2
        assert chaos["trackers_respawned"] >= 2
        assert chaos["trackers_adopted"] >= 1
        assert chaos["fi_fired"]["tracker.crash"] >= 1
        assert rep["pass"] is True
        # the replay plan is the determinism surface: re-planning the
        # same (spec, seed) reproduces the exact schedule this run used
        # (the BUILT-IN's: an SLO is no part of a schedule)
        assert rep["plan"] == plan(
            dict(BUILTIN_SCENARIOS["churn_storm"], seed=1337))

    def test_overload_mix_brownout_engages_recovers_releases(
            self, tmp_path):
        """Acceptance: sustained master-side pressure engages the
        brownout; interactive-class SLO recovers WHILE the brownout is
        active (graceful degradation — batch slows, never the
        reverse); after the pressure clears it steps fully down."""
        rep = run_named("overload_brownout", seed=1337,
                        artifacts_dir=str(tmp_path))
        jobs = rep["jobs"]
        assert jobs["failed"] == 0 and jobs["unfinished"] == 0
        assert rep["brownout_max_level"] >= 1
        assert rep["brownout"]["level"] == 0          # fully released
        assert rep["brownout"]["step_downs"] >= 1
        hist = rep["window_history"]
        recovered_under_brownout = any(
            r["brownout_level"] > 0
            and (r["classes"].get("interactive") or {}).get(
                "assign_ok") is True
            for r in hist)
        assert recovered_under_brownout, \
            [(r["brownout_level"],
              (r["classes"].get("interactive") or {}).get("assign_ok"))
             for r in hist]
        assert rep["verdicts"]["interactive"]["pass"] is True
        # an incident bundle was written and carries the workload
        # context: scenario name, brownout state, per-class breakdown
        assert rep["incidents"], "overload must write an incident"
        inc_dir = os.path.join(str(tmp_path), "incidents")
        with open(os.path.join(inc_dir, rep["incidents"][0])) as f:
            doc = json.load(f)
        assert doc["workload"]["scenario"] == "overload_brownout"
        assert "classes" in doc["workload"]
        assert "level" in doc["workload"]["brownout"]

    def test_dfs_churn_mix_heals_and_readers_never_see_rot(
            self, tmp_path):
        """Acceptance: a replica corrupted under live verified reads,
        a datanode hard-killed with a cold rejoin, and a heartbeat
        partition — the MapReduce classes all complete, the verifying
        DFS fleet sees ZERO corrupt reads, and the cluster converges
        to a clean fsck."""
        rep = run_named(_generous("dfs_churn_storm", 20260804),
                        artifacts_dir=str(tmp_path))
        jobs = rep["jobs"]
        assert jobs["failed"] == 0 and jobs["unfinished"] == 0
        dfs = rep["dfs"]
        assert dfs["ops"] > 0
        assert dfs["corrupt_reads"] == 0
        assert dfs["heal"]["healed"] is True
        assert dfs["pass"] is True
        chaos = rep["chaos"]
        assert chaos["datanodes_killed"] == 1
        assert chaos["fi_fired"]["dn.partition"] == 1
        # the corrupted block's targeted seam fired exactly once
        corrupt = [r for r in rep["chaos_log"]
                   if r["kind"] == "block_corrupt"][0]
        assert corrupt["block_id"] is not None
        assert chaos["fi_fired"][
            f"dn.read.corrupt.b{corrupt['block_id']}"] == 1
        assert rep["pass"] is True
        assert rep["plan"] == plan(
            dict(BUILTIN_SCENARIOS["dfs_churn_storm"], seed=20260804))

    def test_dfs_nn_failover_clients_ride_the_outage(self, tmp_path):
        """Acceptance: NameNode SIGKILLed mid-mix and rebound on the
        same port — editlog replay + safemode exit are timed into the
        chaos log, the fleet's error budget holds (safemode refusals
        budgeted separately), and every MapReduce job completes."""
        rep = run_named(_generous("dfs_nn_failover", 20260804),
                        artifacts_dir=str(tmp_path))
        jobs = rep["jobs"]
        assert jobs["failed"] == 0 and jobs["unfinished"] == 0
        assert rep["chaos"]["nn_restarts"] == 1
        restart = [r for r in rep["chaos_log"]
                   if r["kind"] == "nn_restart"][0]
        assert restart["safemode_exited"] is True
        assert restart["safemode_exit_s"] < 10.0
        dfs = rep["dfs"]
        assert dfs["corrupt_reads"] == 0
        assert dfs["verdicts"]["errors_ok"] is True
        assert dfs["heal"]["healed"] is True
        assert rep["pass"] is True
