"""Distributed job tracing (core/tracing.py): span model, cross-daemon
propagation (master → tracker → task → shuffle), Chrome-trace export,
critical-path analysis, and the zero-overhead-off contract."""

import json
import os
import time
import urllib.request

import pytest

from tpumr.core import tracing
from tpumr.fs import FileSystem, get_filesystem
from tpumr.mapred.job_client import JobClient
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.mini_cluster import MiniMRCluster
from tpumr.mapred.task import TaskState
from tpumr.utils import fi


def fetch(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


class WcMapper:
    def configure(self, conf):
        pass

    def map(self, key, value, output, reporter):
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


# ------------------------------------------------------------ unit


class TestTracerUnit:
    def test_span_lifecycle_and_flush_roundtrip(self, tmp_path):
        tr = tracing.Tracer("jobtracker", trace_dir=str(tmp_path))
        root = tr.start_span("job", "job_x_1", job_id="job_x_1")
        child = tr.start_span("schedule", "job_x_1", parent=root,
                              backend="tpu", attempt_id="a0")
        tr.finish(child)
        tr.finish(root)
        assert tr.flush() == 2
        spans = tracing.read_trace_files(str(tmp_path), "job_x_1")
        assert [s["name"] for s in spans] == ["job", "schedule"]
        sched = spans[1]
        assert sched["parent_span_id"] == root.span_id
        assert sched["backend"] == "tpu"
        assert sched["attributes"]["attempt_id"] == "a0"
        assert sched["attributes"]["host"]          # stamped at finish
        assert sched["end"] >= sched["start"] > 0
        # idempotent: nothing left to flush
        assert tr.flush() == 0

    def test_from_conf_disabled_returns_none(self):
        conf = JobConf()
        assert tracing.Tracer.from_conf(conf, "x") is None
        conf.set("tpumr.trace.enabled", True)
        assert tracing.Tracer.from_conf(conf, "x") is not None

    def test_ambient_noop_when_inactive(self):
        # the off fast path: no tracer installed → span yields None and
        # records nothing, instant returns without touching anything
        with tracing.span("anything", foo=1) as s:
            assert s is None
        tracing.instant("marker", bar=2)

    def test_ambient_nesting_and_thread_capture(self, tmp_path):
        import threading
        tr = tracing.Tracer("tasktracker", trace_dir=str(tmp_path))
        run = tr.start_span("task:run", "job_x_2", role="task")
        with tracing.activate(tr, run):
            with tracing.span("map:spill", records=5) as s:
                assert s.parent_span_id == run.span_id
                assert s.role == "task"      # inherited from parent
            cap = tracing.capture()

            def worker():
                with tracing.activate_captured(cap):
                    tracing.instant("shuffle:penalty", delay_s=0.1)

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        tr.finish(run)
        tr.flush()
        spans = tracing.read_trace_files(str(tmp_path), "job_x_2")
        names = {s["name"] for s in spans}
        assert names == {"task:run", "map:spill", "shuffle:penalty"}
        pen = next(s for s in spans if s["name"] == "shuffle:penalty")
        assert pen["parent_span_id"] == run.span_id

    def test_chrome_trace_schema_and_validation(self):
        tr = tracing.Tracer("jobtracker")
        a = tr.start_span("job", "t1")
        tr.finish(a)
        doc = tracing.to_chrome_trace([s.to_dict() for s in tr.pending()])
        assert tracing.validate_chrome_trace(doc) == []
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert len(xs) == 1 and xs[0]["name"] == "job"
        assert any(m["name"] == "process_name" for m in metas)
        assert tracing.validate_chrome_trace({"nope": 1})
        assert tracing.validate_chrome_trace(
            {"traceEvents": [{"ph": "??", "pid": 1, "name": "x"}]})

    def test_critical_path_follows_dependency_chain(self):
        # job(0..10) with a zero-width schedule marker whose task
        # subtree (2..9) dominates, plus a short finalize (9.5..10):
        # the path must pass THROUGH the marker into the task, and the
        # summed durations must cover the makespan
        def span(name, sid, parent, start, end, role="jobtracker"):
            return {"trace_id": "t", "span_id": sid,
                    "parent_span_id": parent, "name": name, "role": role,
                    "backend": "", "start": start, "end": end,
                    "attributes": {}}

        spans = [
            span("job", "r", "", 0.0, 10.0),
            span("schedule", "s", "r", 2.0, 2.0),
            span("task:run", "t", "s", 2.0, 9.0, role="task"),
            span("job:finalize", "f", "r", 9.5, 10.0),
        ]
        cp = tracing.critical_path(spans)
        names = [p["name"] for p in cp["path"]]
        assert names == ["job", "schedule", "task:run", "job:finalize"]
        assert cp["makespan_s"] == pytest.approx(10.0)
        assert cp["total_s"] >= cp["makespan_s"]
        # contributions: the task dominates, and they sum to ~100%
        by = {p["name"]: p for p in cp["path"]}
        assert by["task:run"]["contribution_pct"] > 50
        assert sum(p["contribution_pct"] for p in cp["path"]) == \
            pytest.approx(100.0, abs=0.5)

    def test_swimlane_svg_escapes_and_renders(self):
        spans = [{"trace_id": "t", "span_id": "a", "parent_span_id": "",
                  "name": "<script>x</script>", "role": "task",
                  "backend": "tpu", "start": 0.0, "end": 1.0,
                  "attributes": {"attempt_id": "a1"}}]
        svg = tracing.swimlane_svg(spans)
        assert "<svg" in svg and "<script>x" not in svg
        assert tracing.swimlane_svg([]).startswith("<p")


# ------------------------------------------------------------ cluster


@pytest.fixture(scope="module")
def traced_cluster(tmp_path_factory):
    hist = str(tmp_path_factory.mktemp("trace-hist"))
    conf = JobConf()
    conf.set("tpumr.history.dir", hist)
    conf.set("tpumr.trace.enabled", True)
    conf.set("mapred.job.tracker.http.port", 0)
    with MiniMRCluster(num_trackers=2, cpu_slots=2, tpu_slots=0,
                       conf=conf) as c:
        c.history_dir = hist
        yield c


def run_wc(cluster, name, n_maps=2, n_reduces=1):
    fs = get_filesystem("mem:///")
    fs.write_bytes(f"/tr/{name}.txt", b"alpha beta\nbeta gamma\n" * 100)
    conf = cluster.create_job_conf()
    conf.set_input_paths(f"mem:///tr/{name}.txt")
    conf.set_output_path(f"mem:///tr/{name}-out")
    conf.set_class("mapred.mapper.class", WcMapper)
    conf.set_class("mapred.reducer.class", SumReducer)
    conf.set("mapred.map.tasks", n_maps)
    conf.set("mapred.min.split.size", 1)
    conf.set_num_reduce_tasks(n_reduces)
    result = JobClient(conf).run_job(conf)
    assert result.successful
    return result


def wait_for_spans(cluster, jid, pred, timeout=5.0):
    """Tracker task-thread flushes can land a beat after the client sees
    SUCCEEDED — poll the merged trace briefly."""
    deadline = time.monotonic() + timeout
    while True:
        t = cluster.master.get_job_trace(jid)
        if pred(t["spans"]) or time.monotonic() > deadline:
            return t
        time.sleep(0.05)


class TestMasterOnlyTracing:
    def test_master_flag_propagates_into_job_conf(self, tmp_path):
        """tpumr.trace.enabled on the MASTER conf alone must still
        produce a complete trace: trackers and children build their
        tracers from the job conf, so the master stamps both the trace
        id AND the enabled flag into it at submit."""
        from tpumr.mapred.jobtracker import JobMaster
        conf = JobConf()
        conf.set("tpumr.history.dir", str(tmp_path))
        conf.set("tpumr.trace.enabled", True)
        master = JobMaster(conf)
        try:
            jid = master.submit_job({"mapred.reduce.tasks": 0},
                                    [{"locations": []}])
            jip = master.jobs[jid]
            assert jip.trace_id == jid
            # what get_job_conf ships to every tracker/child
            assert jip.conf["tpumr.trace.enabled"] is True
            assert jip.conf["tpumr.trace.id"] == jid
        finally:
            master.stop()

    def test_sink_converges_and_stale_trace_id_rejected(self, tmp_path):
        """One authoritative trace dir for writers AND readers (the
        master's, stamped into the job conf), and a clone-and-rerun of
        an old job's conf must get a FRESH trace id — never append to
        the previous job's files."""
        from tpumr.mapred.jobtracker import JobMaster
        master_dir = str(tmp_path / "master")
        conf = JobConf()
        conf.set("tpumr.history.dir", master_dir)
        master = JobMaster(conf)
        try:
            jid = master.submit_job(
                {"mapred.reduce.tasks": 0,
                 "tpumr.trace.enabled": True,
                 # a cloned conf carrying another job's id + own dir
                 "tpumr.trace.id": "job_stale_0001",
                 "tpumr.trace.dir": str(tmp_path / "client")},
                [{"locations": []}])
            jip = master.jobs[jid]
            assert jip.trace_id == jid            # fresh, not the clone's
            # master's dir wins and is what trackers/children will use
            assert jip.conf["tpumr.trace.dir"] == master_dir
            t = master.get_job_trace(jid)
            assert {s["trace_id"] for s in t["spans"]} == {jid}
        finally:
            master.stop()


class TestMiniClusterTracing:
    def test_wordcount_e2e_trace(self, traced_cluster):
        """Acceptance: one merged Chrome trace with spans from ≥3 roles,
        consistent trace_id/parent links, schema-validated, and a
        critical path whose durations sum past the measured makespan
        lower bound (the longest single task span)."""
        result = run_wc(traced_cluster, "e2e")
        jid = str(result.job_id)
        t = wait_for_spans(
            traced_cluster, jid,
            lambda spans: {"jobtracker", "tasktracker", "task"} <=
            {s["role"] for s in spans})
        spans = t["spans"]
        roles = {s["role"] for s in spans}
        assert {"jobtracker", "tasktracker", "task"} <= roles
        # one trace id, every parent link resolvable in-trace
        assert {s["trace_id"] for s in spans} == {jid}
        ids = {s["span_id"] for s in spans}
        orphans = [s for s in spans
                   if s["parent_span_id"] and s["parent_span_id"] not in ids]
        assert not orphans, orphans
        names = {s["name"] for s in spans}
        assert {"job", "job:submit", "schedule", "task:launch",
                "task:run", "reduce:shuffle", "shuffle:fetch",
                "job:finalize"} <= names
        # trace-event export is loadable by the schema
        chrome = tracing.to_chrome_trace(spans)
        assert tracing.validate_chrome_trace(chrome) == []
        # the critical path covers at least the longest task span (a
        # hard lower bound on the job makespan)
        cp = tracing.critical_path(spans)
        task_max = max(s["end"] - s["start"] for s in spans
                       if s["role"] == "task")
        assert cp["total_s"] >= task_max
        assert cp["makespan_s"] >= task_max
        assert [p["name"] for p in cp["path"]][0] == "job"
        assert any(p["role"] == "task" for p in cp["path"])
        # CI artifact: the merged trace of this e2e run (uploaded by
        # .github/workflows/tier1.yml)
        out = os.environ.get("TPUMR_E2E_TRACE_OUT",
                             "/tmp/tpumr-e2e-trace.json")
        try:
            with open(out, "w") as f:
                json.dump(chrome, f, indent=1)
        except OSError:
            pass

    def test_http_endpoints_and_cli_export(self, traced_cluster,
                                           tmp_path):
        result = run_wc(traced_cluster, "http")
        jid = str(result.job_id)
        wait_for_spans(traced_cluster, jid,
                       lambda spans: any(s["role"] == "task"
                                         for s in spans))
        base = traced_cluster.master.http_url
        code, body = fetch(base + f"/tracejson?job={jid}")
        assert code == 200
        doc = json.loads(body)
        assert tracing.validate_chrome_trace(doc) == []
        assert any(e.get("ph") == "X" for e in doc["traceEvents"])
        code, body = fetch(base + f"/trace?job={jid}")
        assert code == 200
        assert "<svg" in body and "Critical path" in body
        code, body = fetch(base + f"/json/trace?job={jid}")
        assert code == 200 and json.loads(body)["trace_id"] == jid
        # the job page links the timeline
        code, body = fetch(base + f"/job?id={jid}")
        assert f"/trace?job={jid}" in body

        # CLI offline export: merges the flushed span files directly
        from tpumr.cli import main as cli_main
        out = str(tmp_path / "t.json")
        cwd = os.getcwd()
        os.chdir(str(tmp_path))
        try:
            rc = cli_main(["job", "trace", jid, "-dir",
                           traced_cluster.history_dir, "-out", out])
        finally:
            os.chdir(cwd)
        assert rc == 0
        exported = json.load(open(out))
        assert tracing.validate_chrome_trace(exported) == []
        # unknown job: error, not a traceback
        rc = cli_main(["job", "trace", "job_nope_1", "-dir",
                       traced_cluster.history_dir])
        assert rc == 1

    def test_off_by_default_and_output_bytes_unchanged(
            self, tmp_path_factory, monkeypatch):
        """Tracing is opt-in: an untraced cluster writes no span files,
        stamps no trace context, makes no Span and opens no profiler
        annotation; enabling it changes observability only — job output
        bytes are identical."""
        import jax
        made = {"spans": 0, "annotations": 0}

        class CountedSpan(tracing.Span):
            def __init__(self, *a, **kw):
                # not the heartbeat spans of another module-scoped
                # cluster whose TRACKER conf enables tracing
                if not kw["trace_id"].startswith("daemon-"):
                    made["spans"] += 1
                super().__init__(*a, **kw)

        class CountedAnnotation(jax.profiler.TraceAnnotation):
            def __init__(self, *a, **kw):
                made["annotations"] += 1
                super().__init__(*a, **kw)

        monkeypatch.setattr(tracing, "Span", CountedSpan)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            CountedAnnotation)
        hist = str(tmp_path_factory.mktemp("untraced-hist"))
        conf = JobConf()
        conf.set("tpumr.history.dir", hist)
        with MiniMRCluster(num_trackers=1, cpu_slots=2, tpu_slots=0,
                           conf=conf) as c:
            fs = get_filesystem("mem:///")
            fs.write_bytes("/ob/in.txt", b"x y x\ny z x\n" * 50)

            def run(name, traced):
                jc = c.create_job_conf()
                jc.set_input_paths("mem:///ob/in.txt")
                jc.set_output_path(f"mem:///ob/{name}")
                jc.set_class("mapred.mapper.class", WcMapper)
                jc.set_class("mapred.reducer.class", SumReducer)
                jc.set_num_reduce_tasks(1)
                if traced:
                    jc.set("tpumr.trace.enabled", True)
                result = JobClient(jc).run_job(jc)
                assert result.successful
                return b"".join(
                    fs.read_bytes(st.path)
                    for st in sorted(fs.list_files(f"mem:///ob/{name}"),
                                     key=lambda s: str(s.path))
                    if "part-" in str(st.path)), str(result.job_id)

        # plain job: off by default — no trace id, no span files
            plain_bytes, plain_jid = run("plain", traced=False)
            assert c.master.jobs[plain_jid].trace_id == ""
            t = c.master.get_job_trace(plain_jid)
            assert t["spans"] == [] and "not traced" in t["error"]
            assert not [f for f in os.listdir(hist)
                        if f.startswith("trace-")]
            assert made == {"spans": 0, "annotations": 0}
            # per-JOB opt-in on an untraced cluster still traces
            traced_bytes, traced_jid = run("traced", traced=True)
            assert c.master.jobs[traced_jid].trace_id == traced_jid
            time.sleep(0.3)
            spans = c.master.get_job_trace(traced_jid)["spans"]
            assert {s["role"] for s in spans} >= {"jobtracker", "task"}
            # the in-process tasks' ambient spans went to the profiler too
            assert made["spans"] >= len(spans) and made["annotations"] > 0
            # observability must not perturb the data plane
            assert plain_bytes == traced_bytes and plain_bytes


class TestTracePropagationThroughReexecution:
    def test_trace_survives_fetch_failure_withdrawal(self):
        """PR 1's recovery path, traced: a persistent serve fault burns
        the map's first attempt; the re-executed attempt's spans join
        the SAME trace with consistent parent links, and the master's
        withdrawal decision is on the timeline."""
        fi.reset()
        import tempfile
        hist = tempfile.mkdtemp(prefix="trace-ff-")
        base = JobConf()
        base.set("tpumr.history.dir", hist)
        base.set("tpumr.trace.enabled", True)
        base.set("tpumr.fi.shuffle.serve.a0.probability", 1.0)
        base.set("tpumr.shuffle.fetch.retries.per.source", 1)
        base.set("tpumr.shuffle.copy.backoff.ms", 10)
        base.set("tpumr.shuffle.copy.backoff.max.ms", 100)
        base.set("mapred.max.fetch.failures.per.map", 2)
        try:
            with MiniMRCluster(num_trackers=2, conf=base) as c:
                fs = get_filesystem("mem:///")
                fs.write_bytes("/tff/in.txt", b"w x\n" * 500)
                conf = c.create_job_conf()
                conf.set_input_paths("mem:///tff/in.txt")
                conf.set_output_path("mem:///tff/out")
                conf.set("mapred.mapper.class",
                         "tpumr.mapred.lib.TokenCountMapper")
                conf.set("mapred.reducer.class",
                         "tpumr.examples.basic.LongSumReducer")
                conf.set("mapred.map.tasks", 1)
                conf.set_num_reduce_tasks(2)
                result = JobClient(conf).run_job(conf)
                assert result.successful
                jid = str(result.job_id)
                t = wait_for_spans(
                    c, jid,
                    lambda spans: any(
                        s["name"] == "fetch_failure:withdraw"
                        for s in spans))
                spans = t["spans"]
                # the withdrawal decision is a traced event
                withdraw = [s for s in spans
                            if s["name"] == "fetch_failure:withdraw"]
                assert withdraw
                assert withdraw[0]["attributes"]["reexecuted"] is True
                # BOTH map attempt generations ran under this trace
                map_runs = sorted(
                    (s["attributes"].get("attempt_id", "")
                     for s in spans
                     if s["name"] == "task:run"
                     and "_m_" in s["attributes"].get("attempt_id", "")))
                assert len(map_runs) == 2, map_runs
                assert map_runs[0].endswith("_0")
                assert map_runs[1].endswith("_1")
                # single trace, no dangling parents — the re-run's spans
                # hang off their own schedule span under the same root
                assert {s["trace_id"] for s in spans} == {jid}
                ids = {s["span_id"] for s in spans}
                assert not [s for s in spans if s["parent_span_id"]
                            and s["parent_span_id"] not in ids]
                # shuffle penalty/report spans from the stalled reduces
                assert any(s["name"] == "shuffle:penalty"
                           for s in spans)
                # no reduce attempt was failed by the fault (PR 1's
                # contract, restated under tracing)
                jip = c.master.jobs[jid]
                for tip in jip.reduces:
                    assert not [s for s in tip.attempts.values()
                                if s.state == TaskState.FAILED]
        finally:
            fi.reset()
            FileSystem.clear_cache()
