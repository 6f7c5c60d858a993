"""Control-plane scale harness (tpumr/scale/) + master saturation
observability: the instrumented master lock, RPC inflight accounting,
heartbeat lag/phase series, completion-event feed lag, trace-volume
controls, and the simulated-tracker fleet driving the REAL heartbeat
wire path end-to-end (acceptance: the saturation series render and
validate on a live JobTracker's /metrics/prom)."""

import json
import threading
import time
import urllib.request

import pytest

from tpumr.ipc.rpc import RpcClient, RpcServer
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.jobtracker import JobMaster
from tpumr.metrics.core import MetricsRegistry
from tpumr.metrics.locks import InstrumentedRLock
from tpumr.scale import ScaleDriver, SimFleet, SimTracker


def fetch(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.getcode(), r.read().decode("utf-8")


# ------------------------------------------------------------ lock


class TestInstrumentedRLock:
    def test_wait_and_hold_recorded(self):
        wait = MetricsRegistry("x").histogram("w")
        hold = MetricsRegistry("x").histogram("h")
        lock = InstrumentedRLock(wait, hold)
        with lock:
            time.sleep(0.02)
        assert wait.count == 1 and hold.count == 1
        assert hold.max >= 0.015
        assert wait.max < 0.015  # uncontended: no queueing

        # contention: a second thread must observe real wait time
        def contender():
            with lock:
                pass

        with lock:
            t = threading.Thread(target=contender)
            t.start()
            time.sleep(0.03)
        t.join()
        # main thread's second acquire + the contender's contended one
        assert wait.count == 3
        assert wait.max >= 0.02

    def test_reentrant_acquire_measures_outermost_hold_only(self):
        wait = MetricsRegistry("x").histogram("w")
        hold = MetricsRegistry("x").histogram("h")
        lock = InstrumentedRLock(wait, hold)
        with lock:
            with lock:          # re-entrant: no extra wait/hold sample
                time.sleep(0.01)
        assert wait.count == 1
        assert hold.count == 1
        assert hold.max >= 0.008

    def test_unbound_lock_works_and_binds_later(self):
        lock = InstrumentedRLock()
        with lock:
            pass
        h = MetricsRegistry("x").histogram("h")
        lock.bind(MetricsRegistry("x").histogram("w"), h)
        with lock:
            pass
        assert h.count == 1


# ------------------------------------------------------------ rpc server


class _MixedService:
    def get_protocol_version(self):
        return 1

    def echo(self, x):
        return x

    def slow(self, t):
        time.sleep(t)
        return "ok"


class TestRpcServerConcurrency:
    """Satellite: parallel in-flight requests observe correct
    rpc_inflight accounting, and the per-method latency histograms stay
    bounded to the handler's REAL method surface under concurrent
    mixed-method load (bogus method names must not mint series)."""

    def test_inflight_peak_and_return_to_zero(self):
        reg = MetricsRegistry("rpc")
        srv = RpcServer(_MixedService()).start()
        srv.metrics = reg
        try:
            n = 6
            barrier = threading.Barrier(n)
            errors = []

            def worker(i):
                cli = RpcClient(*srv.address)
                try:
                    barrier.wait(timeout=5)
                    if i % 3 == 0:
                        cli.call("echo", i)
                    cli.call("slow", 0.15)
                    # unknown + private methods error server-side but
                    # must not create latency series
                    with pytest.raises(Exception):
                        cli.call(f"no_such_method_{i}")
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                finally:
                    cli.close()

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=15)
            assert not errors
            # all n slow() calls overlapped on the barrier: the peak saw
            # the parallelism, and everything drained back to zero
            assert srv.inflight_peak() >= n - 1
            snap = reg.snapshot()
            assert snap["rpc_inflight"] == 0
            assert snap["rpc_inflight_peak"] >= n - 1
            # handler-thread gauge tracked the open connections
            assert snap["rpc_handler_threads"] >= 0
            # latency histograms exist ONLY for the real method surface
            hist_names = {name for name, v in snap.items()
                          if isinstance(v, dict) and "p99" in v}
            assert "rpc_slow" in hist_names
            assert "rpc_echo" in hist_names
            assert not [h for h in hist_names if "no_such_method" in h]
            # peak reads with reset=True re-arm the high-water mark
            assert srv.inflight_peak(reset=True) >= n - 1
            assert srv.inflight_peak() == 0
        finally:
            srv.stop()


# ------------------------------------------------ reactor hardening


class TestReactorEdgeCases:
    """Satellite: the selector-reactor transport under hostile/unlucky
    connections — torn frames, resets between request and response,
    oversized frames, and handler-pool saturation. The loop must shrug
    each one off: later connections keep being served, and overload
    answers bounded backpressure instead of queueing without bound."""

    def _reactor_server(self, handler=None, fast=()):
        srv = RpcServer(handler or _MixedService(), reactor=True,
                        fast_methods=set(fast)).start()
        return srv

    def _alive(self, srv):
        cli = RpcClient(*srv.address)
        try:
            assert cli.call("echo", "ping") == "ping"
        finally:
            cli.close()

    def test_mid_frame_disconnect_leaves_server_serving(self):
        import socket
        import struct
        srv = self._reactor_server()
        try:
            host, port = srv.address
            # announce a 1000-byte frame, send 10 bytes, hang up
            s = socket.create_connection((host, port), timeout=5)
            s.sendall(struct.pack(">I", 1000) + b"x" * 10)
            s.close()
            time.sleep(0.1)
            self._alive(srv)
        finally:
            srv.stop()

    def test_reset_between_request_and_response(self):
        import socket
        from tpumr.io.writable import serialize
        import struct
        srv = self._reactor_server()
        try:
            host, port = srv.address
            # a well-formed slow request whose connection dies before
            # the response can be written back
            req = serialize({"id": 1, "method": "slow", "params": [0.2]})
            s = socket.create_connection((host, port), timeout=5)
            s.sendall(struct.pack(">I", len(req)) + req)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         struct.pack("ii", 1, 0))   # RST on close
            s.close()
            time.sleep(0.4)   # the pooled handler writes into the void
            self._alive(srv)
        finally:
            srv.stop()

    def test_oversized_frame_rejected_without_allocation(self):
        import socket
        import struct
        srv = self._reactor_server()
        try:
            host, port = srv.address
            s = socket.create_connection((host, port), timeout=5)
            # length prefix far beyond MAX_FRAME: the reactor must drop
            # the connection on the prefix alone, never buffer toward it
            s.sendall(struct.pack(">I", 0xFFFFFFFE)[:4])
            s.sendall(b"y" * 64)
            time.sleep(0.1)
            # connection observably dead...
            s.settimeout(2)
            assert s.recv(1) == b""
            s.close()
            # ...server observably alive
            self._alive(srv)
        finally:
            srv.stop()

    def test_handler_pool_saturation_returns_backpressure(self):
        from tpumr.ipc.rpc import RpcError, _Reactor
        reg = MetricsRegistry("rpc")
        srv = self._reactor_server()
        srv.metrics = reg
        old_backlog = _Reactor.POOL_BACKLOG
        _Reactor.POOL_BACKLOG = 4
        srv._reactor.POOL_BACKLOG = 4
        try:
            n = 12
            barrier = threading.Barrier(n)
            results = {"ok": 0, "busy": 0, "other": []}
            rlock = threading.Lock()

            def worker():
                cli = RpcClient(*srv.address)
                try:
                    barrier.wait(timeout=5)
                    cli.call("slow", 0.3)
                    with rlock:
                        results["ok"] += 1
                except RpcError as e:
                    with rlock:
                        if "saturated" in str(e):
                            results["busy"] += 1
                        else:
                            results["other"].append(e)
                except Exception as e:  # noqa: BLE001
                    with rlock:
                        results["other"].append(e)
                finally:
                    cli.close()

            threads = [threading.Thread(target=worker) for _ in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=20)
            assert not [t for t in threads if t.is_alive()], \
                "saturation must never deadlock callers"
            assert not results["other"], results["other"]
            # the pool (8 threads, backlog 4) absorbed some, pushed the
            # rest back IMMEDIATELY as busy errors — and nothing hung
            assert results["busy"] >= 1
            assert results["ok"] >= 4
            assert results["ok"] + results["busy"] == n
            assert reg.snapshot()["rpc_pool_saturated"] >= 1
            # after the storm the server serves normally again
            self._alive(srv)
        finally:
            _Reactor.POOL_BACKLOG = old_backlog
            srv.stop()


# ------------------------------------------------------------ fleet e2e


def _master(extra=None):
    conf = JobConf()
    conf.set("tpumr.heartbeat.interval.ms", 50)
    conf.set("tpumr.tracker.expiry.ms", 30_000)
    for k, v in (extra or {}).items():
        conf.set(k, v)
    return JobMaster(conf).start()


def _poll(cond, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.02)


class TestSimFleetEndToEnd:
    def test_fleet_drives_real_wire_heartbeats_and_jobs_complete(self):
        master = _master()
        host, port = master.address
        fleet = SimFleet(host, port, 4, interval_s=0.05, cpu_slots=2,
                         reduce_slots=1, task_time_mean_s=0.05,
                         piggyback_interval_s=0.05).start()
        driver = ScaleDriver(host, port)
        try:
            res = driver.run_workload(2, 8, 2, timeout_s=30)
            assert not res["unfinished"] and not res["failed"], res
            snap = master.metrics.snapshot()
            jt = snap["jobtracker"]
            # master-side saturation series all populated — the lock
            # series are per decomposed lock class since PR 8
            assert jt["heartbeat_seconds"]["count"] > 0
            assert jt["heartbeat_lag_seconds"]["count"] > 0
            for lock in ("global", "trackers", "scheduler"):
                assert jt[f"jt_lock_wait_seconds|lock={lock}"][
                    "count"] > 0, lock
                assert jt[f"jt_lock_hold_seconds|lock={lock}"][
                    "count"] > 0, lock
            assert jt["completion_event_lag"]["count"] > 0
            for phase in ("fold", "assign"):
                assert jt[f"heartbeat_phase_seconds|phase={phase}"][
                    "count"] > 0, phase
            assert snap["scheduler"]["assign_seconds"]["count"] > 0
            # WIRE-LEVEL proof: the transport-side per-method histogram
            # only populates when heartbeats arrive as real RPC frames
            assert snap["rpc"]["rpc_heartbeat"]["count"] > 0
            assert snap["rpc"]["rpc_heartbeat_request_bytes"]["count"] > 0
            assert master._server.inflight_peak() >= 1
            # the sim trackers' metrics piggybacks merged cluster-side
            assert snap["cluster"]["sim_tasks_completed"] > 0
            fl = fleet.stats()
            assert fl["heartbeats"] > 0 and fl["hb_errors"] == 0
            assert fl["tasks_completed"] >= 2 * (8 + 2)
        finally:
            fleet.stop()
            driver.close()
            master.stop()

    def test_fetch_failure_injection_drives_master_protocol(self):
        master = _master()
        host, port = master.address
        fleet = SimFleet(host, port, 3, interval_s=0.05, cpu_slots=2,
                         reduce_slots=1, task_time_mean_s=0.05,
                         fetch_failure_rate=1.0).start()
        driver = ScaleDriver(host, port)
        try:
            res = driver.run_workload(1, 6, 3, timeout_s=45)
            assert not res["failed"], res
            snap = master.metrics.snapshot()["jobtracker"]
            assert snap.get("fetch_failures_reported", 0) >= 1
        finally:
            fleet.stop()
            driver.close()
            master.stop()

    def test_prom_scrape_renders_and_validates_saturation_series(self):
        """Acceptance: jt_lock_wait_seconds, rpc_inflight,
        heartbeat_phase_seconds{phase=...}, heartbeat_lag_seconds render
        and validate on a live JobTracker's /metrics/prom."""
        from tpumr.metrics.prometheus import validate_exposition
        master = _master({"mapred.job.tracker.http.port": 0})
        host, port = master.address
        fleet = SimFleet(host, port, 3, interval_s=0.05, cpu_slots=2,
                         reduce_slots=1, task_time_mean_s=0.04).start()
        driver = ScaleDriver(host, port)
        try:
            res = driver.run_workload(1, 6, 1, timeout_s=30)
            assert not res["unfinished"] and not res["failed"], res
            code, body = fetch(master.http_url + "/metrics/prom")
            assert code == 200
            validate_exposition(body)
            for series in ("tpumr_jt_lock_wait_seconds_bucket",
                           "tpumr_jt_lock_hold_seconds_bucket",
                           "tpumr_heartbeat_lag_seconds_bucket",
                           "tpumr_completion_event_lag_bucket",
                           "tpumr_rpc_inflight{",
                           "tpumr_rpc_inflight_peak{",
                           "tpumr_rpc_handler_threads{"):
                assert series in body, series
            # the phase breakdown is ONE family with phase labels
            assert "# TYPE tpumr_heartbeat_phase_seconds histogram" \
                in body
            assert 'phase="fold"' in body and 'phase="assign"' in body
            # per-lock wait/hold of the decomposed master locks render
            # as ONE labeled family (satellite: the decomposition is
            # observable on /metrics/prom)
            assert "# TYPE tpumr_jt_lock_wait_seconds histogram" in body
            for lock in ("global", "trackers", "scheduler"):
                assert f'lock="{lock}"' in body, lock
        finally:
            fleet.stop()
            driver.close()
            master.stop()

    def test_sim_tracker_rejoins_after_eviction_without_reinit(self):
        master = _master()
        host, port = master.address
        t = SimTracker("solo", host, port, cpu_slots=1, reduce_slots=1)
        try:
            t.heartbeat_once()   # initial contact registers
            assert t.heartbeats == 1
            # master amnesia (eviction/restart): the next DELTA beat is
            # asked for a full re-send — no reinit, nothing dropped —
            # and the full beat after that is ADOPTED
            master._evict_tracker("solo")
            t.heartbeat_once()
            assert t._initial_contact is False, \
                "resend_full must not reset the tracker like reinit"
            assert "solo" not in master.trackers
            t.heartbeat_once()   # full status → adopted
            with master.lock:
                assert "solo" in master.trackers
            assert master.metrics.snapshot()["jobtracker"][
                "trackers_adopted"] == 1
        finally:
            t.close()
            master.stop()

    def test_launch_a_dead_tracker_never_read_is_handed_out_again(self):
        """A tracker killed between send and receive: the master folded
        its beat and launched a map in a response nobody read, so no
        status of that attempt ever arrives. Losing the tracker must
        re-queue the map all the same, or the job never ends (what the
        churn_storm mix hit under load: PR 28)."""
        master = _master()
        host, port = master.address
        dead = SimTracker("dead", host, port, cpu_slots=1, reduce_slots=0)
        live = SimTracker("live", host, port, cpu_slots=1, reduce_slots=0,
                          task_time_mean_s=0.01)
        driver = ScaleDriver(host, port)
        try:
            dead.heartbeat_once()            # registers; nothing to run
            (job_id,) = driver.submit(1, 1, 0)
            assert dead.heartbeat_begin()    # on the wire ...
            jip = master.jobs[job_id]
            _poll(lambda: jip.maps[0].state == "running")
            dead.crash()                     # ... and never read
            assert not jip.maps[0].attempts  # launched, never reported
            master._evict_tracker("dead")
            assert jip.maps[0].state == "pending"

            def done():
                live.heartbeat_once()
                return jip.state == "SUCCEEDED"
            _poll(done)
            assert jip.maps[0].successful_attempt.endswith("_1")
        finally:
            dead.close()
            live.close()
            driver.close()
            master.stop()


# ------------------------------------------------------------ heartbeat spans


def _sim_status(name="t1"):
    return {"tracker_name": name, "host": "h1", "shuffle_addr": "h1:0",
            "shuffle_port": 0, "max_cpu_map_slots": 1,
            "max_tpu_map_slots": 0, "max_reduce_slots": 1,
            "count_cpu_map_tasks": 0, "count_tpu_map_tasks": 0,
            "count_reduce_tasks": 0, "available_tpu_devices": [],
            "task_statuses": [], "fetch_failures": [], "healthy": True}


class TestHeartbeatPhaseSpans:
    def test_master_records_phase_subspans_of_tracker_heartbeat(self):
        master = _master()
        try:
            status = _sim_status()
            status["trace"] = {"trace_id": "daemon-t1", "span_id": "ab12"}
            master.heartbeat(status, True, True, 0)
            spans = [s for s in master.tracer.pending()
                     if s.trace_id == "daemon-t1"]
            names = {s.name for s in spans}
            assert "heartbeat:fold" in names
            assert "heartbeat:assign" in names
            assert all(s.parent_span_id == "ab12" for s in spans)
            # and the context never leaks into the stored status
            with master.lock:
                assert "trace" not in master.trackers["t1"].status
        finally:
            master.stop()

    def test_untraced_heartbeat_records_no_spans(self):
        master = _master()
        try:
            master.heartbeat(_sim_status(), True, True, 0)
            assert master.tracer.pending() == []
        finally:
            master.stop()


# ------------------------------------------------------------ trace volume


class TestTraceVolumeControls:
    def test_sample_zero_mints_no_trace(self):
        master = _master({"tpumr.trace.enabled": True,
                          "tpumr.trace.sample": 0.0})
        try:
            jid = master.submit_job({"mapred.reduce.tasks": 1,
                                     "user.name": "u"}, [{}])
            jip = master.jobs[jid]
            assert jip.trace_id == "" and jip.trace_root is None
            snap = master.metrics.snapshot()["jobtracker"]
            assert snap.get("traces_sampled_out", 0) == 1
        finally:
            master.stop()

    def test_sample_one_traces_and_job_conf_rate_wins(self):
        master = _master({"tpumr.trace.enabled": True,
                          "tpumr.trace.sample": 0.0})
        try:
            # the job conf's explicit rate overrides the master default
            jid = master.submit_job({"mapred.reduce.tasks": 1,
                                     "user.name": "u",
                                     "tpumr.trace.sample": 1.0}, [{}])
            assert master.jobs[jid].trace_id == jid
        finally:
            master.stop()

    def test_sample_rate_parsing(self):
        from tpumr.core.tracing import trace_sample_rate
        assert trace_sample_rate({"tpumr.trace.sample": "0.25"}) == 0.25
        assert trace_sample_rate({}) == 1.0
        assert trace_sample_rate({"tpumr.trace.sample": "bogus"}) == 1.0
        assert trace_sample_rate({"tpumr.trace.sample": 7}) == 1.0
        assert trace_sample_rate({"tpumr.trace.sample": -3}) == 0.0

    def test_span_buffer_high_water_drops_oldest_bounded(self):
        from tpumr.core import tracing
        tracer = tracing.Tracer("t", trace_dir=None)
        tracer._flush_pending = True   # pin the flusher: pure cap test
        total = tracing.MAX_BUFFERED + 57
        for i in range(total):
            tracer.finish(tracer.start_span(f"s{i}", "tid"))
        assert len(tracer.pending()) == tracing.MAX_BUFFERED
        assert tracer.dropped == 57
        # oldest were shed, newest survived
        assert tracer.pending()[-1].name == f"s{total - 1}"


# ------------------------------------------------------------ delta protocol


class TestHeartbeatDelta:
    def test_delta_reconstruction_and_per_beat_keys(self):
        from tpumr.mapred.heartbeat import HeartbeatEncoder
        master = _master()
        try:
            enc = HeartbeatEncoder(True)
            full = _sim_status("d1")
            r = master.heartbeat(enc.encode(dict(full)), True, False, 0)
            enc.delivered()
            assert master.trackers["d1"].status["host"] == "h1"
            # idle beat: near-empty wire dict
            wire = enc.encode(dict(full))
            assert wire.get("delta") is True
            assert set(wire) == {"tracker_name", "delta"}
            r = master.heartbeat(wire, False, False, r["response_id"])
            enc.delivered()
            stored = master.trackers["d1"].status
            # baseline keys inherited; per-beat keys are NOT
            assert stored["host"] == "h1"
            assert stored["max_cpu_map_slots"] == 1
            assert not stored.get("task_statuses")
            # a changed slot count rides the delta (and only it)
            full["max_cpu_map_slots"] = 5
            wire = enc.encode(dict(full))
            assert wire["max_cpu_map_slots"] == 5
            assert "host" not in wire
            master.heartbeat(wire, False, False, r["response_id"])
            enc.delivered()
            assert master.trackers["d1"].status[
                "max_cpu_map_slots"] == 5
        finally:
            master.stop()

    def test_unknown_delta_gets_resend_full(self):
        master = _master()
        try:
            resp = master.heartbeat(
                {"tracker_name": "ghost", "delta": True}, False, True, 7)
            # a baseline-less delta is asked for the full status — the
            # master can't use the delta, but unlike the old reinit
            # nothing on the tracker is killed
            assert resp["actions"] == [{"type": "resend_full"}]
            assert "ghost" not in master.trackers
        finally:
            master.stop()

    def test_failed_delivery_resets_to_full_status(self):
        from tpumr.mapred.heartbeat import HeartbeatEncoder
        enc = HeartbeatEncoder(True)
        full = _sim_status("d2")
        enc.encode(dict(full))
        enc.delivered()
        assert enc.encode(dict(full)).get("delta") is True
        # an RPC failure leaves delivery unknown: next beat must be full
        enc.reset()
        wire = enc.encode(dict(full))
        assert "delta" not in wire and wire["host"] == "h1"

    def test_unchanged_metrics_piggyback_is_omitted(self):
        from tpumr.mapred.heartbeat import HeartbeatEncoder
        enc = HeartbeatEncoder(True)
        full = _sim_status("d3")
        m = {"tasktracker": {"counters": {"x": 1}}}
        first = enc.encode(dict(full), m)
        assert first["metrics"] == m
        enc.delivered()
        assert "metrics" not in enc.encode(dict(full), m)
        # a delivered piggyback-less beat (the common case — piggyback
        # intervals are longer than heartbeat intervals) must not
        # clobber the baseline: the snapshot is STILL unchanged after
        enc.encode(dict(full), None)
        enc.delivered()
        assert "metrics" not in enc.encode(dict(full), m)
        changed = {"tasktracker": {"counters": {"x": 2}}}
        assert enc.encode(dict(full), changed)["metrics"] == changed

    def test_delta_disabled_sends_full_every_beat(self):
        from tpumr.mapred.heartbeat import HeartbeatEncoder
        enc = HeartbeatEncoder(False)
        full = _sim_status("d4")
        for _ in range(2):
            wire = enc.encode(dict(full))
            enc.delivered()
            assert "delta" not in wire and wire["host"] == "h1"


# ------------------------------------------------------------ replay path


class TestReplayObservability:
    def test_replayed_beat_observes_phase_and_lag_series(self):
        """Satellite: a replayed heartbeat (stale response id) lands in
        heartbeat_lag_seconds AND heartbeat_phase_seconds{phase=replay},
        so replays are distinguishable from first deliveries."""
        master = _master()
        try:
            st = _sim_status("r1")
            r1 = master.heartbeat(dict(st), True, True, 0)
            r2 = master.heartbeat(dict(st), False, True,
                                  r1["response_id"])

            def jt():
                return master.metrics.snapshot()["jobtracker"]

            replays = jt().get("heartbeat_phase_seconds|phase=replay",
                               {}).get("count", 0)
            lags = jt()["heartbeat_lag_seconds"]["count"]
            # retry echoing the ALREADY-CONSUMED id: response was lost
            r3 = master.heartbeat(dict(st), False, True,
                                  r1["response_id"])
            assert r3 == r2            # stored actions replayed
            snap = jt()
            assert snap["heartbeat_phase_seconds|phase=replay"][
                "count"] == replays + 1
            assert snap["heartbeat_lag_seconds"]["count"] == lags + 1
        finally:
            master.stop()


# ------------------------------------------------------------ adaptive cadence


class TestAdaptiveCadence:
    def test_interval_scales_with_fleet_floor_and_cap(self):
        """max(floor, fleet/rate), capped: small fleets keep the
        configured floor; the instruction grows with registrations and
        never exceeds the cap."""
        master = _master({"tpumr.heartbeat.beats.per.second": 100,
                          "tpumr.heartbeat.interval.max.ms": 120})
        try:
            first = master.heartbeat(_sim_status("ac000"), True, False, 0)
            # one registered tracker: 1/100 s << the 50 ms floor
            assert first["next_interval_ms"] == 50
            for i in range(1, 20):
                master.heartbeat(_sim_status(f"ac{i:03d}"), True,
                                 False, 0)
            # 20 trackers at 100 beats/s wants 200 ms — the cap wins
            again = master.heartbeat(_sim_status("ac000"), False,
                                     False, first["response_id"])
            assert again["next_interval_ms"] == 120
            assert master._mreg.snapshot()[
                "heartbeat_interval_instructed_ms"] == 120
        finally:
            master.stop()

    def test_rate_zero_always_instructs_the_floor(self):
        master = _master()   # beats.per.second unset -> adaptation off
        try:
            for i in range(8):
                r = master.heartbeat(_sim_status(f"off{i}"), True,
                                     False, 0)
            assert r["next_interval_ms"] == 50
        finally:
            master.stop()

    def test_floor_above_cap_pins_the_cadence(self):
        master = _master({"tpumr.heartbeat.beats.per.second": 1,
                          "tpumr.heartbeat.interval.max.ms": 20})
        try:
            r = master.heartbeat(_sim_status("pin"), True, False, 0)
            # operator pinned a 50 ms floor above the 20 ms cap: the
            # floor wins (adaptation never speeds beats up)
            assert r["next_interval_ms"] == 50
        finally:
            master.stop()

    def test_replay_carries_current_interval(self):
        master = _master({"tpumr.heartbeat.beats.per.second": 2})
        try:
            r1 = master.heartbeat(_sim_status("rp"), True, True, 0)
            # mismatched response id -> the replay path must still
            # instruct the cadence (1 tracker / 2 per s = 500 ms)
            r2 = master.heartbeat(_sim_status("rp"), False, True, 999)
            assert r2["response_id"] == r1["response_id"]
            assert r2["next_interval_ms"] == 500
        finally:
            master.stop()

    def test_sim_tracker_honors_instructed_interval(self):
        master = _master({"tpumr.heartbeat.beats.per.second": 2})
        host, port = master.address
        tracker = SimTracker("ad0001", host, port)
        try:
            tracker.heartbeat_once()
            assert tracker.next_interval_s == 0.5
        finally:
            tracker.close()
            master.stop()

    def test_node_runner_honors_instructed_interval(self):
        """The REAL tracker reschedules its loop from the response —
        two runners at 4 beats/s aggregate settle on 500 ms beats."""
        from tpumr.mapred.mini_cluster import MiniMRCluster
        base = JobConf()
        base.set("tpumr.heartbeat.beats.per.second", 4)
        with MiniMRCluster(num_trackers=2, conf=base) as c:
            deadline = time.monotonic() + 15
            want = [0.5, 0.5]
            while time.monotonic() < deadline and \
                    [t.heartbeat_s for t in c.trackers] != want:
                time.sleep(0.05)
            assert [t.heartbeat_s for t in c.trackers] == want


# ------------------------------------------------------------ lock order


class TestLockOrdering:
    def test_descending_acquisition_raises_in_debug_mode(self):
        from tpumr.metrics import locks
        if not locks.ORDER_CHECK:
            pytest.skip("lock-order checking disabled")
        job = locks.InstrumentedRLock(name="job-x", rank=locks.RANK_JOB)
        sched = locks.InstrumentedRLock(name="scheduler",
                                        rank=locks.RANK_SCHEDULER)
        with sched:      # scheduler -> job: the documented legal order
            with job:
                pass
        with pytest.raises(AssertionError, match="lock-order violation"):
            with job:    # job -> scheduler: the deadlock direction
                with sched:
                    pass
        # the held stack unwound cleanly after the violation
        with sched:
            with job:
                pass

    def test_reentrancy_and_unranked_locks_exempt(self):
        from tpumr.metrics import locks
        job = locks.InstrumentedRLock(name="job-x", rank=locks.RANK_JOB)
        plain = locks.InstrumentedRLock()          # unranked: exempt
        with job:
            with job:      # same-lock re-entrancy always legal
                with plain:
                    pass


# ------------------------------------------------------------ event feed


class TestCompletionEventFeed:
    def test_cursor_reads_and_post_serve_backlog(self):
        from tpumr.mapred.job_in_progress import CompletionEventFeed
        feed = CompletionEventFeed()
        for i in range(10):
            feed.append({"map_index": i, "attempt_id": f"a{i}",
                         "shuffle_addr": "x", "status": "SUCCEEDED"})
        events, pending = feed.read(0, 4)
        assert [e["map_index"] for e in events] == [0, 1, 2, 3]
        assert pending == 6       # backlog AFTER the batch, not before
        events, pending = feed.read(4, 100)
        assert len(events) == 6 and pending == 0
        events, pending = feed.read(10, 5)
        assert events == [] and pending == 0
        events, _ = feed.read(-3, 2)     # clamped, not wrapped
        assert events[0]["map_index"] == 0
        # list-like surface the eviction/withdrawal paths rely on
        assert len(feed) == 10
        assert feed[3]["attempt_id"] == "a3"
        assert [e["map_index"] for e in feed][:3] == [0, 1, 2]


# ------------------------------------------------------------ stress


class TestLockDecompositionStress:
    def test_concurrent_folds_and_polls_no_deadlock_no_lost_status(self):
        """Satellite: N in-process trackers heartbeat concurrently into
        ONE job (half of them speaking delta) while pollers hammer
        get_map_completion_events — no deadlock, no lost terminal
        status, and every poller sees a monotone, self-consistent
        event feed."""
        from tpumr.mapred.heartbeat import HeartbeatEncoder
        from tpumr.mapred.ids import TaskAttemptID
        from tpumr.mapred.task import TaskPhase, TaskState, TaskStatus

        n_maps, n_trackers, n_pollers = 48, 6, 3
        master = _master()
        jid = master.submit_job(
            {"user.name": "stress", "mapred.reduce.tasks": 0,
             "mapred.speculative.execution": False},
            [{} for _ in range(n_maps)])
        jip = master.jobs[jid]
        done = threading.Event()
        errors: list = []

        def tracker(i):
            enc = HeartbeatEncoder(enabled=(i % 2 == 0))
            name, rid, initial = f"st{i}", 0, True
            running: dict = {}
            try:
                deadline = time.monotonic() + 60
                while not done.is_set():
                    if time.monotonic() > deadline:
                        errors.append(f"{name}: never drained")
                        return
                    statuses = []
                    for aid in list(running):
                        a = TaskAttemptID.parse(aid)
                        statuses.append(TaskStatus(
                            attempt_id=a, is_map=True,
                            state=TaskState.SUCCEEDED, progress=1.0,
                            phase=TaskPhase.MAP,
                            finish_time=time.time()).to_dict())
                    full = dict(_sim_status(name), max_cpu_map_slots=2,
                                task_statuses=statuses)
                    resp = master.heartbeat(enc.encode(full), initial,
                                            True, rid)
                    enc.delivered()
                    initial = False
                    rid = resp["response_id"]
                    for sd in statuses:
                        running.pop(sd["attempt_id"], None)
                    for act in resp["actions"]:
                        if act["type"] == "launch":
                            running[act["task"]["attempt_id"]] = act
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        poller_seen = [0] * n_pollers

        def poller(pi):
            cursor, seen = 0, []
            try:
                while not done.is_set():
                    events = master.get_map_completion_events(
                        jid, cursor, 10)
                    # cursor-based serving: batches are contiguous and
                    # an index, once served, never changes identity
                    seen.extend(events)
                    cursor += len(events)
                    poller_seen[pi] = cursor
                    time.sleep(0.001)
                if len(seen) != n_maps:
                    errors.append(f"poller saw {len(seen)}/{n_maps}")
                if sorted(e["map_index"] for e in seen) != \
                        list(range(n_maps)):
                    errors.append("non-monotone/duplicated event feed")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=tracker, args=(i,))
                   for i in range(n_trackers)]
        threads += [threading.Thread(target=poller, args=(pi,))
                    for pi in range(n_pollers)]
        try:
            for t in threads:
                t.start()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if jip.state != "RUNNING" and jip.finalized.is_set():
                    break
                time.sleep(0.01)
            # let every poller drain the tail (deterministically — a
            # fixed sleep flaked under ambient load)
            drain = time.monotonic() + 20
            while time.monotonic() < drain \
                    and min(poller_seen) < n_maps:
                time.sleep(0.01)
            done.set()
            for t in threads:
                t.join(timeout=30)
            assert not [t for t in threads if t.is_alive()], "deadlock"
            assert not errors, errors
            # no lost terminal status: every map completed exactly once
            assert jip.state == "SUCCEEDED"
            assert jip.finished_maps == n_maps
            assert all(t.state == "succeeded" for t in jip.maps)
            assert len(jip.completion_events) == n_maps
        finally:
            done.set()
            master.stop()


# ------------------------------------------------------------ delta e2e


class TestDeltaHeartbeatEndToEnd:
    def test_job_output_byte_identical_delta_on_vs_off(self):
        """Acceptance: wordcount over a real mini-cluster produces
        byte-identical output with delta heartbeats on vs off."""
        from tpumr.fs import FileSystem, get_filesystem
        from tpumr.mapred.job_client import JobClient
        from tpumr.mapred.mini_cluster import MiniMRCluster

        def run(enabled):
            base = JobConf()
            base.set("tpumr.heartbeat.delta", enabled)
            with MiniMRCluster(num_trackers=2, conf=base) as c:
                fs = get_filesystem("mem:///")
                fs.write_bytes("/hd/in.txt",
                               b"".join(b"w%02d x\n" % (i % 23)
                                        for i in range(3000)))
                conf = c.create_job_conf()
                conf.set_input_paths("mem:///hd/in.txt")
                conf.set_output_path(f"mem:///hd/out-{enabled}")
                conf.set("mapred.mapper.class",
                         "tpumr.mapred.lib.TokenCountMapper")
                conf.set("mapred.reducer.class",
                         "tpumr.examples.basic.LongSumReducer")
                conf.set_num_reduce_tasks(2)
                conf.set("mapred.map.tasks", 4)
                conf.set("mapred.min.split.size", 1)
                result = JobClient(conf).run_job(conf)
                assert result.successful
                out = b"".join(
                    fs.read_bytes(st.path)
                    for st in sorted(
                        fs.list_status(f"/hd/out-{enabled}"),
                        key=lambda s: str(s.path))
                    if "part-" in str(st.path))
            FileSystem.clear_cache()
            return out

        assert run(True) == run(False)


# ------------------------------------------------------------ prometheus


class TestLabeledFamilies:
    def test_extra_label_convention_renders_one_family(self):
        from tpumr.metrics.prometheus import (render_exposition,
                                              validate_exposition)
        reg = MetricsRegistry("jt")
        reg.histogram("hb_phase_seconds|phase=fold").observe(0.01)
        reg.histogram("hb_phase_seconds|phase=assign").observe(0.02)
        reg.incr("beats|kind=sim", 3)
        text = render_exposition({"jt": reg.typed_snapshot()})
        validate_exposition(text)
        assert text.count("# TYPE tpumr_hb_phase_seconds histogram") == 1
        assert 'phase="fold"' in text and 'phase="assign"' in text
        assert 'tpumr_beats{source="jt",kind="sim"} 3' in text


# ------------------------------------------------------------ batching


def _history_master(tmp_path):
    return _master({"tpumr.history.dir": str(tmp_path / "history")})


class TestHeartbeatBatch:
    def test_resent_batch_replays_not_refolds(self, tmp_path):
        """A resent batch must not double-fold any member — each
        member rides the per-tracker replay cache exactly like a lone
        resent heartbeat."""
        master = _history_master(tmp_path)
        try:
            host, port = master.address
            tr = SimTracker("batcher_00", host, port)
            args = tr.heartbeat_build()
            assert args is not None
            tr.heartbeat_apply(master.heartbeat_batch([list(args)])[0])
            # second beat (initial contact is over — the replay cache
            # is armed now), delivered twice with the same response_id
            args = tr.heartbeat_build()
            first = master.heartbeat_batch([list(args)])
            again = master.heartbeat_batch([list(args)])
            assert first[0]["response_id"] == again[0]["response_id"]
            assert first[0]["actions"] == again[0]["actions"]
            snap = master.metrics.snapshot()["jobtracker"]
            assert snap["heartbeat_batches"] == 3
            replay = snap.get(
                "heartbeat_phase_seconds|phase=replay", {})
            assert replay.get("count") == 1, \
                "second delivery must take the replay path"
            tr.heartbeat_abort()
            tr.close()
        finally:
            master.stop()

    def test_member_failures_are_isolated(self, tmp_path):
        master = _history_master(tmp_path)
        try:
            host, port = master.address
            tr = SimTracker("batcher_01", host, port)
            args = tr.heartbeat_build()
            out = master.heartbeat_batch(
                [["not-a-status", True, False, 0], list(args)])
            assert "error" in out[0]
            assert "response_id" in out[1], \
                "a bad member must not poison the rest of the batch"
            tr.heartbeat_abort()
            tr.close()
        finally:
            master.stop()

    def test_batched_fleet_drives_a_workload(self, tmp_path):
        master = _history_master(tmp_path)
        fleet = None
        driver = None
        try:
            host, port = master.address
            fleet = SimFleet(host, port, 6, interval_s=0.05,
                             batch=4).start()
            driver = ScaleDriver(host, port)
            res = driver.run_workload(n_jobs=2, maps_per_job=4,
                                      reduces_per_job=1, timeout_s=30)
            assert len(res["succeeded"]) == 2, res
            snap = master.metrics.snapshot()["jobtracker"]
            assert snap.get("heartbeat_batches", 0) > 0
            assert fleet.registry.snapshot().get("hb_errors", 0) == 0
        finally:
            if fleet is not None:
                fleet.stop()
            if driver is not None:
                driver.close()
            master.stop()


# ------------------------------------------------------------ simulate


def _tpumr(capsys, *argv):
    """``tpumr ARGV`` in-process: (rc, stdout, stderr)."""
    from tpumr.cli import main
    capsys.readouterr()
    rc = main(list(argv))
    out = capsys.readouterr()
    return rc, out.out, out.err


def _simulate(capsys, *argv):
    return _tpumr(capsys, "simulate", *argv)


class TestSimulateRow:
    def test_ramp_row_carries_required_series(self, capsys, monkeypatch):
        """The ROW CONTRACT of the tracker ramp an operator runs by
        hand — never a latency: a loaded CI runner must not flake it on
        a wall-clock p99."""
        from tpumr.metrics.core import MetricsSystem
        snaps = []
        real_snapshot = MetricsSystem.snapshot

        def spy(self):
            snaps.append(real_snapshot(self))
            return snaps[-1]

        monkeypatch.setattr(MetricsSystem, "snapshot", spy)
        rc, out, _ = _simulate(
            capsys, "-trackers", "3", "-jobs", "1", "-maps", "6",
            "-reduces", "1", "-interval", "50", "-task-ms", "50",
            "-timeout", "60")
        row = json.loads(out)
        for key in ("heartbeat_p50_s", "heartbeat_p99_s",
                    "heartbeat_lag_p99_s", "lock_wait_p99_s",
                    "lock_wait_trackers_p99_s",
                    "lock_wait_scheduler_p99_s", "assign_p99_s",
                    "completion_event_lag_p99", "rpc_inflight_peak",
                    "interval_instructed_ms", "client_rtt_p99_s",
                    "client_lag_p99_s", "trackers"):
            assert key in row, key
        assert rc == 0 and row["trackers"] == 3
        assert row["jobs_succeeded"] == 1
        assert not row["jobs_failed"] and not row["jobs_unfinished"]
        assert row["tasks_completed"] >= 7 and row["hb_errors"] == 0
        assert row["interval_instructed_ms"] == 50
        # every lock class's wait row reads a series that is live in
        # the snapshot the row was cut from (its count, not its p99:
        # how a histogram interpolates is not this contract)
        jts = [s["jobtracker"] for s in snaps if "jobtracker" in s]
        for key, lock in (("lock_wait_p99_s", "global"),
                          ("lock_wait_trackers_p99_s", "trackers"),
                          ("lock_wait_scheduler_p99_s", "scheduler")):
            name = f"jt_lock_wait_seconds|lock={lock}"
            assert any(jt[name]["count"] > 0
                       and jt[name]["p99"] == row[key]
                       for jt in jts if name in jt), lock


class TestSimulateCli:
    def test_dfs_rung_prints_its_verdict(self, capsys):
        # generous SLO: the verdict's SHAPE is under test, not the box
        rc, out, _ = _tpumr(
            capsys, "-D", "tpumr.dfs.bench.op.slo.ms=60000",
            "-D", "tpumr.dfs.bench.read.slo.ms=60000",
            "simulate", "-dfs", "2", "-seconds", "1.5", "-files", "3")
        row = json.loads(out)
        assert row["clients"] == 2 and row["completed"]
        assert row["slo"] == {"op_slo_s": 60.0, "read_slo_s": 60.0,
                              "pass": True}
        assert rc == 0
        for key in ("nn_op_p99_s", "read_rtt_p99_s", "lag_p99_s",
                    "lock_wait_p99_by_lock", "editlog_sync_p99_s",
                    "read_mb_s", "hot_top1_share"):
            assert key in row, key

    def test_live_master_row_has_the_fleet_side_only(self, capsys):
        """``-jt HOST:PORT``: the fleet joins a master it did not
        start, whose own series are read off its /metrics instead."""
        master = _master()
        try:
            host, port = master.address
            rc, out, _ = _tpumr(
                capsys, "-jt", f"{host}:{port}", "simulate",
                "-trackers", "2", "-jobs", "1", "-maps", "4",
                "-reduces", "0", "-interval", "50", "-task-ms", "50",
                "-timeout", "60")
            row = json.loads(out)
            assert rc == 0 and row["jobs_succeeded"] == 1
            assert row["heartbeats"] > 0 and row["hb_errors"] == 0
            assert "client_rtt_p99_s" in row
            assert "heartbeat_p99_s" not in row
            # the beats really went to THAT master
            jt = master.metrics.snapshot()["jobtracker"]
            assert jt["heartbeat_seconds"]["count"] >= row["heartbeats"]
        finally:
            master.stop()

    def test_scenario_list_names_every_builtin(self, capsys):
        from tpumr.scale import BUILTIN_SCENARIOS
        rc, out, _ = _tpumr(capsys, "scenario", "-list")
        rows = out.splitlines()
        assert rc == 0
        assert [r.split()[0] for r in rows] == sorted(BUILTIN_SCENARIOS)
        assert all("[builtin]" in r and "jobs=" in r and "chaos=" in r
                   for r in rows)

    @pytest.mark.parametrize("name", ["no_such_mix", "shard_kill"])
    def test_unknown_scenario_is_refused_with_the_builtins(
            self, capsys, name):
        from tpumr.scale import BUILTIN_SCENARIOS
        rc, out, err = _simulate(capsys, "-scenario", name)
        assert rc == 2 and not out
        assert f"unknown scenario {name!r}" in err
        for builtin in BUILTIN_SCENARIOS:
            assert builtin in err, builtin

    def test_scenario_report_holds_the_replay_plan(self, capsys,
                                                   tmp_path):
        """The report, not the exit code: the code follows the mix's
        own per-class SLO verdicts, which a loaded box may miss."""
        from tpumr.scale import BUILTIN_SCENARIOS, plan
        report = tmp_path / "report.json"
        rc, out, _ = _simulate(
            capsys, "-scenario", "steady_mix", "-seed", "4242",
            "-report", str(report), "-incidents", str(tmp_path / "a"))
        rep = json.loads(report.read_text())
        assert rep["scenario"] == "steady_mix" and rep["seed"] == 4242
        assert rep["plan"] == plan(
            dict(BUILTIN_SCENARIOS["steady_mix"], seed=4242))
        jobs = rep["jobs"]
        n = jobs["submitted"]
        assert jobs["succeeded"] == n > 0
        assert rc == (0 if rep["pass"] else 1)
        # with -report, stdout is the short verdict summary
        assert f"{n}/{n} jobs" in out and str(report) in out
        for cls_name in ("interactive", "batch", "pipeline"):
            assert f"class {cls_name}: " in out

    @pytest.mark.parametrize("argv", [
        ["stray"],                      # positional, not -name value
        ["-trackers"],                  # a flag without its value
        ["-trackers", "3", "-jobs"],
    ])
    def test_bad_arguments_are_refused(self, capsys, argv):
        with pytest.raises(SystemExit, match="unexpected argument"):
            _simulate(capsys, *argv)
