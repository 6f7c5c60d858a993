"""Spans where the work happens (PR 24): the phases inside the gang
reduce (``dshuffle:*``), the master's ``task:done``, slot sizes on
``task:launch``, monotonic span lengths, the mirror onto the profiler's
host line, and the two HBM gauges."""

import sys
import time

import pytest

from tpumr.core import tracing
from tpumr.core.counters import BackendCounter, TaskCounter
from tpumr.fs import get_filesystem
from tpumr.mapred.job_client import JobClient
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.mini_cluster import MiniMRCluster

PHASES = ["dshuffle:locate", "dshuffle:fetch", "dshuffle:assemble",
          "dshuffle:pack", "dshuffle:device", "dshuffle:gather",
          "dshuffle:write"]
ROWS, MAPS, RANGES = 6000, 3, 4


@pytest.fixture(scope="module")
def sorted_job(tmp_path_factory):
    """One traced device-shuffled terasort through the mini cluster
    (eight CPU devices stand in: the mesh branch) and its merged spans."""
    from tpumr.cli import main as cli_main
    from tpumr.examples.terasort import make_terasort_conf
    assert cli_main(["examples", "teragen", str(ROWS), "mem:///tph/gen",
                     "-m", str(MAPS)]) == 0
    master_conf = JobConf()
    hist = str(tmp_path_factory.mktemp("tph-hist"))
    master_conf.set("tpumr.history.dir", hist)
    with MiniMRCluster(num_trackers=1, cpu_slots=2, tpu_slots=0,
                       conf=master_conf) as c:
        conf = make_terasort_conf("mem:///tph/gen", "mem:///tph/out", RANGES,
                                  device_shuffle=True)
        for k, v in c.create_job_conf():
            conf.set_if_unset(k, v)
        conf.set("tpumr.trace.enabled", True)
        result = JobClient(conf).run_job(conf)
        assert result.successful
        jid = str(result.job_id)
        deadline = time.monotonic() + 5.0
        while True:     # the tracker's flush trails the client by a beat
            spans = c.master.get_job_trace(jid)["spans"]
            if any(s["name"] == "dshuffle" for s in spans) \
                    or time.monotonic() > deadline:
                break
            time.sleep(0.05)
    return {"spans": spans, "counters": result.counters, "job_id": jid,
            "history": hist}


def _children(spans, parent):
    return sorted((s for s in spans
                   if s["parent_span_id"] == parent["span_id"]),
                  key=lambda s: s["start"])


def test_one_dshuffle_span_inside_the_reduces_task_run(sorted_job):
    spans = sorted_job["spans"]
    tops = [s for s in spans if s["name"] == "dshuffle"]
    assert len(tops) == 1
    top = tops[0]
    by_id = {s["span_id"]: s for s in spans}
    run = by_id[top["parent_span_id"]]
    assert run["name"] == "task:run" and "_r_" in run["attributes"][
        "attempt_id"]
    assert run["start"] <= top["start"] and top["end"] <= run["end"] + 1e-6
    assert top["trace_id"] == sorted_job["job_id"]
    assert top["attributes"]["rows"] == ROWS
    assert top["attributes"]["n_dev"] == 8
    assert top["attributes"]["host_fallback"] is False
    assert top["attributes"]["overflow"] == 0


def test_the_phases_are_the_children_in_order_and_do_not_overlap(sorted_job):
    spans = sorted_job["spans"]
    top = next(s for s in spans if s["name"] == "dshuffle")
    kids = _children(spans, top)
    assert sorted({k["name"] for k in kids}) == sorted(PHASES)
    # each phase where the work is done, in the order the work is done
    order = [k["name"] for k in kids]
    # a map's rows land when it arrives (on a mesh its key words are the
    # devices' to make); one more assemble closes the copy phase
    assert order == ["dshuffle:locate", "dshuffle:fetch",
                     "dshuffle:assemble"] * MAPS + [
        "dshuffle:assemble", "dshuffle:pack", "dshuffle:device",
        "dshuffle:gather", "dshuffle:write"]
    for a, b in zip(kids, kids[1:]):
        assert a["end"] <= b["start"] + 1e-6, (a["name"], b["name"])
    for k in kids:
        assert top["start"] - 1e-6 <= k["start"] <= k["end"] \
            <= top["end"] + 1e-6
    covered = sum(k["end"] - k["start"] for k in kids)
    assert covered >= 0.9 * (top["end"] - top["start"])


def test_phase_rows_and_bytes_match_the_jobs_counters(sorted_job):
    spans, counters = sorted_job["spans"], sorted_job["counters"]
    rows_in = counters.value(TaskCounter.FRAMEWORK_GROUP,
                             TaskCounter.REDUCE_INPUT_RECORDS)
    rows_out = counters.value(TaskCounter.FRAMEWORK_GROUP,
                              TaskCounter.REDUCE_OUTPUT_RECORDS)
    moved = counters.value(BackendCounter.GROUP,
                           BackendCounter.TPU_SHUFFLE_BYTES)
    assert rows_in == rows_out == ROWS and moved == ROWS * 100

    def attrs(name):
        return [s["attributes"] for s in spans if s["name"] == name]

    *landed, whole = attrs("dshuffle:assemble")
    assert [a["map_index"] for a in landed] == list(range(MAPS))
    assert sum(a["rows"] for a in landed) == whole["rows"] == rows_in
    assert sum(a["bytes"] for a in landed) == whole["bytes"] == moved
    assert "map_index" not in whole
    fetches = attrs("dshuffle:fetch")
    assert sorted(f["map_index"] for f in fetches) == list(range(MAPS))
    # what was read: the rows and one 12-byte header a map; one tracker,
    # so every map's file is its own and none came over the wire
    assert sum(f["bytes"] for f in fetches) == moved + 12 * MAPS
    assert all(f["local"] is True for f in fetches)
    assert counters.value(BackendCounter.GROUP,
                          BackendCounter.TPU_SHUFFLE_LOCAL_MAPS) == MAPS
    assert sorted(a["map_index"] for a in attrs("dshuffle:locate")) \
        == list(range(MAPS))
    pack, = attrs("dshuffle:pack")
    device, = attrs("dshuffle:device")
    assert pack["n_pad"] >= ROWS and pack["bytes_in"] >= moved
    assert device["devices"] == 8 and device["retries"] == 0
    assert device["bytes_in"] == pack["bytes_in"]
    assert device["bytes_out"] >= moved
    gather, = attrs("dshuffle:gather")
    assert gather["rows"] == rows_in and gather["bytes"] == moved
    write, = attrs("dshuffle:write")
    assert write["ranges"] == RANGES
    assert write["rows"] == rows_out and write["bytes"] == moved
    written = attrs("dshuffle:range")
    assert sorted(w["range"] for w in written) == list(range(RANGES))
    assert sum(w["rows"] for w in written) == rows_out
    assert sum(w["bytes"] for w in written) == moved


def test_the_write_phase_is_one_span_with_a_span_a_range_under_it(
        sorted_job):
    """ONE ``dshuffle:write`` around the phase (so the benchmark's
    ``_phase_s`` reads its wall time), and under it a ``dshuffle:range``
    a range, opened in the worker that wrote it; the counter says how
    many workers the phase had."""
    import os
    spans, counters = sorted_job["spans"], sorted_job["counters"]
    top = next(s for s in spans if s["name"] == "dshuffle")
    write, = (s for s in spans if s["name"] == "dshuffle:write")
    assert write["parent_span_id"] == top["span_id"]
    written = _children(spans, write)
    assert [w["name"] for w in written] == ["dshuffle:range"] * RANGES
    for w in written:
        assert write["start"] - 1e-4 <= w["start"] <= w["end"] \
            <= write["end"] + 1e-4
        assert w["trace_id"] == sorted_job["job_id"]
    # four ranges over eight devices: a range a device
    assert sorted((w["attributes"]["range"], w["attributes"]["device"])
                  for w in written) == [(r, r) for r in range(RANGES)]
    writers = min(RANGES, os.cpu_count() or 1)
    assert write["attributes"]["writers"] == writers
    assert 0 <= write["attributes"]["cut_s"] < 0.1
    assert counters.value(BackendCounter.GROUP,
                          BackendCounter.TPU_SHUFFLE_WRITERS) == writers
    # and the master's rollup of the job has it beside the mesh size
    import json
    with open(os.path.join(sorted_job["history"],
                           f"metrics-{sorted_job['job_id']}.json")) as f:
        rolled = json.load(f)["counters"][BackendCounter.GROUP]
    assert rolled["TPU_SHUFFLE_WRITERS"] == writers
    assert rolled["TPU_SHUFFLE_DEVICES"] == 8


def test_the_mesh_device_call_has_a_child_span_per_step(sorted_job):
    """Under ``dshuffle:device`` on a mesh: copy in, destination, one
    exchange per attempt, sort, copy out; in that order, each closed when
    its result is ready, so that they add up to the device call."""
    spans, counters = sorted_job["spans"], sorted_job["counters"]
    device = next(s for s in spans if s["name"] == "dshuffle:device")
    kids = _children(spans, device)
    assert [k["name"] for k in kids] == [
        "dshuffle:put", "dshuffle:dest", "dshuffle:exchange",
        "dshuffle:sort", "dshuffle:get"]
    for a, b in zip(kids, kids[1:]):
        assert a["end"] <= b["start"] + 1e-4, (a["name"], b["name"])
    # starts are wall-clock, lengths monotonic: equal to a few us only
    assert device["start"] - 1e-4 <= kids[0]["start"]
    assert kids[-1]["end"] <= device["end"] + 1e-4
    covered = sum(k["end"] - k["start"] for k in kids)
    assert covered >= 0.9 * (device["end"] - device["start"])
    put, _dest, exchange, _sort, get = (k["attributes"] for k in kids)
    assert put["bytes"] == device["attributes"]["bytes_in"]
    assert get["bytes"] == device["attributes"]["bytes_out"]
    # every live row came back, from the four devices that hold a range
    assert get["live_rows"] == ROWS and get["pieces"] >= RANGES
    assert exchange["attempt"] == 0 and exchange["overflow"] == 0
    # 6000 rows on eight devices: 768 a device, so 2 x 768 / 4 ranges...
    pad = counters.value(BackendCounter.GROUP,
                         BackendCounter.TPU_SHUFFLE_PAD_ROWS)
    assert pad == 8 * 768 - ROWS
    assert exchange["capacity"] == 2 * 768 // RANGES
    assert put["bytes"] == 8 * 768 * 101
    assert counters.value(BackendCounter.GROUP,
                          BackendCounter.TPU_SHUFFLE_DEVICES) == 8
    assert counters.value(BackendCounter.GROUP,
                          BackendCounter.TPU_SHUFFLE_RETRIES) == 0


def test_the_mesh_sort_copies_back_the_rows_and_not_the_slots(sorted_job):
    """``TPU_SHUFFLE_BYTES_BACK`` is what ``dshuffle:get`` says left the
    devices: the job's rows at least, and at most whole pieces of them
    (96 rows: an eighth of a device's 768) and a count a device; the
    slots the exchange reserved would be three times that."""
    spans, counters = sorted_job["spans"], sorted_job["counters"]
    get, = (s["attributes"] for s in spans if s["name"] == "dshuffle:get")
    back = counters.value(BackendCounter.GROUP,
                          BackendCounter.TPU_SHUFFLE_BYTES_BACK)
    moved = counters.value(BackendCounter.GROUP,
                           BackendCounter.TPU_SHUFFLE_BYTES)
    assert back == get["bytes"]
    assert get["pieces"] <= ROWS // 96 + RANGES
    assert moved <= back <= get["pieces"] * 96 * 100 + 4 * 8
    slots = 8 * 8 * (2 * 768 // RANGES) * 101
    assert back < 0.4 * slots


def test_every_finished_attempt_has_one_task_done_after_its_launch(
        sorted_job):
    spans = sorted_job["spans"]
    by_id = {s["span_id"]: s for s in spans}
    launches = {s["attributes"]["attempt_id"]: s for s in spans
                if s["name"] == "task:launch"}
    assert len(launches) == MAPS + 1
    done = [s for s in spans if s["name"] == "task:done"]
    assert sorted(d["attributes"]["attempt_id"] for d in done) \
        == sorted(launches)
    for d in done:
        launch = launches[d["attributes"]["attempt_id"]]
        assert d["role"] == "jobtracker"
        assert d["start"] >= launch["end"] - 1e-3   # one host, one clock
        assert d["attributes"]["state"] == "SUCCEEDED"
        assert d["attributes"]["is_map"] == launch["attributes"]["is_map"]
        assert d["attributes"]["tracker"] == launch["attributes"]["tracker"]
        assert d["backend"] == launch["backend"]
        # parented to the attempt's schedule span, as the launch is
        assert by_id[d["parent_span_id"]]["name"] == "schedule"
        assert d["parent_span_id"] == launch["parent_span_id"]


def test_task_launch_carries_the_size_of_its_slot_pool(sorted_job):
    launches = [s for s in sorted_job["spans"] if s["name"] == "task:launch"]
    maps = [s for s in launches if s["attributes"]["is_map"]]
    reduces = [s for s in launches if not s["attributes"]["is_map"]]
    assert len(maps) == MAPS and len(reduces) == 1
    assert {s["attributes"]["slots"] for s in maps} == {2}      # cpu_slots
    assert reduces[0]["attributes"]["slots"] >= 1
    assert all("device_id" not in s["attributes"] for s in launches)
    assert all(s["attributes"]["slot_wait_s"] >= 0 for s in launches)


def test_a_tpu_map_launch_names_its_device(tmp_path):
    """A task bound to a device says which: the assign gap is per slot."""
    import io

    import numpy as np

    from tpumr.ops.kmeans import clear_centroid_cache
    clear_centroid_cache()
    fs = get_filesystem("mem:///")
    rng = np.random.default_rng(3)
    for path, shape in (("/tph/points.npy", (400, 4)),
                        ("/tph/cents.npy", (3, 4))):
        buf = io.BytesIO()
        np.save(buf, rng.normal(size=shape).astype(np.float32))
        fs.write_bytes(path, buf.getvalue())
    master_conf = JobConf()
    master_conf.set("tpumr.history.dir", str(tmp_path))
    with MiniMRCluster(num_trackers=1, cpu_slots=1, tpu_slots=1,
                       conf=master_conf) as c:
        conf = c.create_job_conf()
        conf.set_input_paths("mem:///tph/points.npy")
        conf.set_output_path("mem:///tph/km-out")
        conf.set("mapred.input.format.class",
                 "tpumr.mapred.input_formats.DenseInputFormat")
        conf.set("tpumr.dense.split.rows", 25)      # 16 splits
        conf.set("tpumr.kmeans.centroids", "mem:///tph/cents.npy")
        conf.set("tpumr.map.kernel", "kmeans-assign")
        conf.set("mapred.mapper.class", "tpumr.ops.kmeans.KMeansCpuMapper")
        conf.set_num_reduce_tasks(0)
        conf.set("tpumr.trace.enabled", True)
        result = JobClient(conf).run_job(conf)
        assert result.successful
        time.sleep(0.3)
        spans = c.master.get_job_trace(str(result.job_id))["spans"]
    tpu = [s for s in spans if s["name"] == "task:launch"
           and s["backend"] == "tpu"]
    assert tpu, "no map ran on the TPU slot"
    assert all(s["attributes"]["device_id"] == 0
               and s["attributes"]["slots"] == 1 for s in tpu)
    cpu = [s for s in spans if s["name"] == "task:launch"
           and s["backend"] == "cpu" and s["attributes"]["is_map"]]
    assert all("device_id" not in s["attributes"] for s in cpu)


# ------------------------------------------------------------ the clocks


def test_span_length_is_monotonic_when_the_wall_clock_steps_back(
        monkeypatch):
    tr = tracing.Tracer("tasktracker")
    wall = iter([1000.0, 990.0, 980.0])     # the wall clock runs backwards
    monkeypatch.setattr(tracing.time, "time", lambda: next(wall))
    mono = iter([50.0, 50.25])
    monkeypatch.setattr(tracing.time, "monotonic", lambda: next(mono))
    s = tr.start_span("x", "t1")
    tr.finish(s)
    assert s.start == 1000.0
    assert s.end - s.start == pytest.approx(0.25)
    assert s.duration == pytest.approx(0.25)


def test_an_open_spans_duration_reads_the_monotonic_clock(monkeypatch):
    tr = tracing.Tracer("tasktracker")
    mono = iter([10.0, 10.5, 12.0])
    monkeypatch.setattr(tracing.time, "monotonic", lambda: next(mono))
    s = tr.start_span("x", "t1")
    assert s.duration == pytest.approx(0.5)
    assert s.elapsed() == pytest.approx(2.0)


def test_a_backdated_span_keeps_both_clocks_in_step(monkeypatch):
    tr = tracing.Tracer("jobtracker")
    wall = iter([100.0])
    monkeypatch.setattr(tracing.time, "time", lambda: next(wall))
    mono = iter([7.0, 7.125])
    monkeypatch.setattr(tracing.time, "monotonic", lambda: next(mono))
    s = tr.start_span("heartbeat:fold", "t1").backdate(99.5)
    tr.finish(s)
    assert s.start == 99.5 and s.end == pytest.approx(100.125)


# ------------------------------------------- the profiler's own timeline


class _CountingAnnotation:
    entered: "list[tuple]" = []
    open_now = 0

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        type(self).entered.append((self.name, self.kwargs))
        type(self).open_now += 1
        return self

    def __exit__(self, *exc):
        type(self).open_now -= 1
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax
    _CountingAnnotation.entered = []
    _CountingAnnotation.open_now = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _CountingAnnotation)
    return _CountingAnnotation


def test_a_traced_ambient_span_enters_a_trace_annotation(annotations):
    assert "jax" in sys.modules
    tr = tracing.Tracer("tasktracker")
    run = tr.start_span("task:run", "job_t_1", role="task")
    with tracing.activate(tr, run):
        with tracing.span("tpu:execute", backend="tpu") as s:
            assert annotations.open_now == 1
            with tracing.span("inner"):
                assert annotations.open_now == 2
        assert annotations.open_now == 0
    assert [n for n, _ in annotations.entered] == ["tpu:execute", "inner"]
    assert annotations.entered[0][1] == {"span_id": s.span_id,
                                         "trace_id": "job_t_1"}


def test_the_annotation_closes_when_the_span_body_raises(annotations):
    tr = tracing.Tracer("tasktracker")
    run = tr.start_span("task:run", "job_t_2", role="task")
    with tracing.activate(tr, run):
        with pytest.raises(ValueError):
            with tracing.span("dshuffle:device"):
                raise ValueError("boom")
    assert annotations.open_now == 0 and len(annotations.entered) == 1
    failed = [s for s in tr.pending() if s.name == "dshuffle:device"]
    assert "boom" in failed[0].attributes["error"]


def test_an_untraced_span_opens_no_annotation_and_makes_no_span(
        annotations, monkeypatch):
    made = []
    real = tracing.Span

    class Counted(real):
        def __init__(self, *a, **kw):
            made.append(1)
            super().__init__(*a, **kw)

    monkeypatch.setattr(tracing, "Span", Counted)
    with tracing.span("tpu:execute", backend="tpu") as s:
        assert s is None
    tracing.instant("marker")
    assert annotations.entered == [] and made == []


def test_no_annotation_in_a_process_that_has_not_imported_jax(
        annotations, monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    tr = tracing.Tracer("task")
    run = tr.start_span("task:run", "job_t_3")
    with tracing.activate(tr, run):
        with tracing.span("map:spill") as s:
            assert s is not None
    assert annotations.entered == []
    assert "jax" not in sys.modules     # and the span did not import it


def test_tracing_module_does_not_import_jax():
    import subprocess
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys; import tpumr.core.tracing; "
         "print('jax' in sys.modules)"],
        capture_output=True, text=True, timeout=60, check=True)
    assert out.stdout.strip() == "False"


# ------------------------------------------------------- the HBM gauges


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_hbm_gauges_read_the_largest_over_the_known_slot_devices(
        monkeypatch):
    from tpumr.mapred import tpu_runner
    from tpumr.parallel import jaxruntime
    reg = tpu_runner.runner_metrics()
    monkeypatch.setattr(jaxruntime, "_known_devices", [
        _Dev({"bytes_in_use": 10, "peak_bytes_in_use": 70}),
        _Dev({"bytes_in_use": 30, "peak_bytes_in_use": 40}),
        _Dev(None)])                # the CPU stand-in reports none
    snap = reg.snapshot()
    assert snap["tpu_hbm_bytes_in_use"] == 30
    assert snap["tpu_hbm_peak_bytes"] == 70


def test_hbm_gauges_read_zero_before_a_slot_device_was_asked_for(
        monkeypatch):
    from tpumr.mapred import tpu_runner
    from tpumr.parallel import jaxruntime
    monkeypatch.setattr(jaxruntime, "_known_devices", [])
    snap = tpu_runner.runner_metrics().snapshot()
    assert snap["tpu_hbm_bytes_in_use"] == 0
    assert snap["tpu_hbm_peak_bytes"] == 0


def test_accelerator_devices_remembers_what_it_answered():
    from tpumr.parallel import jaxruntime
    devices = jaxruntime.accelerator_devices()
    assert jaxruntime.known_accelerator_devices() == devices
