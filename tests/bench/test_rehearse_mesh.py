"""``bench/run.py --rehearse`` of the four-chip sort cell through real
jobtracker, tasktracker and client processes on FOUR CPU devices (the mesh
branch: destination, all_to_all, per-device sort), the guarantee the
``terasort_mesh`` family adds (``TPU_SHUFFLE_DEVICES``), the family's
faults and control on what the rehearsal wrote, and the new readers:
the steps inside ``dshuffle:device`` and the three programs' rooflines on
hand-built spans and traces, None where there is nothing to read.
"""

import copy
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import reducers_mesh, run, work_mesh  # noqa: E402
from bench.families import terasort_mesh  # noqa: E402
# the sort family's own fault helpers: the same faults, this cell's output
from test_reducers_spans import span as _span  # noqa: E402
from test_rehearse import (SEED, _alter_value, _rehearse,  # noqa: E402
                           _swap_two, _ts_rewrite, _verdict)

CELL = "terasort-10m-mesh4.device-shuffle"
STEPS = ("put", "dest", "exchange", "sort", "get")
ROOFLINES = ("mesh_dest_roofline", "mesh_exchange_roofline",
             "mesh_sort_roofline")
BACKEND = "tpumr.BackendCounter"


def _new_metrics() -> "list[str]":
    return [m["name"] for m in run.load_benchmark()["per_layer"]
            if m["workloads"] == [CELL]]


@pytest.fixture(scope="module")
def mesh4():
    """One traced rehearsal. tests/conftest.py asks for eight CPU devices
    and the mesh spans every local device of the tracker: the run's
    daemons get four."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        return _rehearse(CELL, trace=1)


def test_the_mesh_rehearsal_is_correct_on_four_cpu_devices(mesh4):
    line = mesh4["line"]
    assert line["correct"] is True, mesh4["stderr"][-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert line["checks"]["rows_wrong"] == {"value": 0, "limit": 0}
    for j in mesh4["jobs"]:
        c = j["rollup"]["counters"][BACKEND]
        assert c["TPU_SHUFFLE_DEVICES"] == 4
        assert c["TPU_SHUFFLE_RECORDS"] == mesh4["sizes"]["rows"]
        assert c.get("SHUFFLE_HOST_FALLBACKS", 0) == 0
        assert c.get("TPU_SHUFFLE_RETRIES", 0) == 0
        assert c["TPU_SHUFFLE_PAD_ROWS"] == 40960 - 40000
    # every metric this cell adds that reads spans, rollups or the host's
    # clock has a value; none that needs a device trace has
    m = line["metrics"]
    device = set(ROOFLINES) | {"mesh.idle_share"}
    assert set(m) == set(_new_metrics()) - device
    assert m["mesh.window_compiles"]["value"] == 0
    steps = sum(m[f"mesh.{s}_s"]["value"] for s in STEPS)
    call = m["mesh.device_call_s"]["value"]
    assert 0.9 * call <= steps <= call
    # the eight phases still add up to the gang reduce
    phases = sum(m[f"mesh.{p}_s"]["value"] for p in (
        "locate", "fetch", "assemble", "pack", "device_call", "gather",
        "write", "self"))
    assert phases > 0 and "breakdown" not in line


@pytest.mark.parametrize("devices,why", [
    (1, "ran over 1 device(s), not 4"), (None, "ran over 0 device(s)"),
    (8, "ran over 8 device(s)")])
def test_a_sort_over_another_number_of_devices_is_unsound(mesh4, devices,
                                                          why):
    """One chip of a four-chip host sorting alone, or a program that
    writes no such counter (the parent commit), breaks the guarantee the
    configuration adds, whatever the output."""
    jobs = copy.deepcopy(mesh4["jobs"])
    r = jobs[0]["rollup"]
    assert terasort_mesh.job_failure(r, mesh4["sizes"], False) is None
    if devices is None:
        del r["counters"][BACKEND]["TPU_SHUFFLE_DEVICES"]
    else:
        r["counters"][BACKEND]["TPU_SHUFFLE_DEVICES"] = devices
    jobs[0]["failure"] = terasort_mesh.job_failure(r, mesh4["sizes"], False)
    assert why in jobs[0]["failure"]
    correct, checks = _verdict(mesh4, jobs)
    assert correct is False and checks["jobs_unsound"]["value"] == 1
    assert checks["rows_wrong"]["value"] == 0


def test_the_sort_familys_guarantees_still_hold_in_the_mesh_family(mesh4):
    r = copy.deepcopy(mesh4["jobs"][0]["rollup"])
    assert "did not run on a chip" in terasort_mesh.job_failure(
        r, mesh4["sizes"], on_chip=True)    # a CPU rehearsal is not a chip
    r["counters"][BACKEND]["SHUFFLE_HOST_FALLBACKS"] = 1
    assert "fell back" in terasort_mesh.job_failure(r, mesh4["sizes"],
                                                    False)


@pytest.mark.parametrize("fault", ["two_rows_swapped",
                                   "a_value_byte_altered", "half_left_out",
                                   "output_left_as_the_input"])
def test_a_planted_fault_comes_out_not_correct(mesh4, tmp_path, fault):
    if fault == "output_left_as_the_input":
        jobs = copy.deepcopy(mesh4["jobs"])
        jobs[-1]["out"] = mesh4["inputs"]["gen"]
    else:
        change = {"two_rows_swapped": _swap_two,
                  "a_value_byte_altered": _alter_value,
                  "half_left_out": lambda rows: rows[:len(rows) // 2]}[fault]
        jobs = _ts_rewrite(mesh4, tmp_path, change)
    correct, checks = _verdict(mesh4, jobs)
    assert correct is False
    assert checks["rows_wrong"]["value"] > 0
    assert _verdict(mesh4, mesh4["jobs"])[0] is True


def test_the_control_comes_out_not_correct():
    """The family's control (ordered by the first four key bytes alone)
    at a size a test can hold."""
    cfg = run.load_cell(run.load_benchmark(), CELL)["config"]
    sizes = dict(cfg["sizes"], rows=400_000, maps=4)
    checks = terasort_mesh.control(sizes, SEED, {}, cfg["limits"])
    assert checks["rows_wrong"]["value"] > 100
    assert run.judge(checks) is False


def test_the_configuration_is_the_one_chip_sort_on_the_other_machine():
    bm = run.load_benchmark()
    mesh = run.load_cell(bm, CELL)
    one = run.load_cell(bm, "terasort-10m.device-shuffle")
    assert mesh["chips"] == 4 and one["chips"] == 1
    assert mesh["traffic"] == one["traffic"]        # the same file
    a, b = mesh["config"], one["config"]
    assert a["source"] != b["source"]
    assert a["sizes"] == dict(b["sizes"], mesh=4)
    assert a["reduced"] == ["rows"] and a["limits"] == b["limits"]
    assert a["guarantees"][:-1] == b["guarantees"]
    assert "TPU_SHUFFLE_DEVICES == 4" in a["guarantees"][-1]
    assert sum(w["chips"] == 4 for w in bm["workloads"]) == 1


# ------------------------------------------------------- the new readers


def _empty_obs():
    return {"jobs": [], "window_s": 1.0, "spans": None, "trace": None,
            "peak": None, "window_compiles": None,
            "sizes": {"rows": 10_000_000, "maps": 8, "mesh": 4}}


@pytest.mark.parametrize("metric", _new_metrics())
def test_a_new_metric_resolves_to_a_reader_that_reads_none_from_nothing(
        metric):
    spec = run._load_json("layer_metrics", metric + ".json")
    assert list(spec) == ["reducer"]
    reader = run.find_reducer(spec["reducer"])
    assert reader(_empty_obs()) is None     # None, never 0


def test_the_cell_adds_the_metrics_the_issue_names():
    names = _new_metrics()
    assert len(names) == 21 and len(set(names)) == 21
    assert {f"mesh.{s}_s" for s in STEPS} | set(ROOFLINES) <= set(names)


def _gang_reduce(t0, job, exchanges=(0.4,), steps=True):
    top = _span("dshuffle", t0, t0 + 20, job=job)
    call = _span("dshuffle:device", t0 + 5, t0 + 15, parent=top, job=job)
    out, at = [top, call], t0 + 5

    def step(name, seconds, **attrs):
        nonlocal at
        out.append(_span("dshuffle:" + name, at, at + seconds, parent=call,
                         job=job, **attrs))
        at += seconds

    if steps:
        step("put", 3.0, bytes=1)
        step("dest", 0.1)
        for attempt, seconds in enumerate(exchanges):
            step("exchange", seconds, attempt=attempt)
        step("sort", 0.5)
        step("get", 5.0, bytes=2)
    return out


def test_the_steps_are_summed_per_gang_reduce_and_averaged_over_them():
    spans = _gang_reduce(100, "job_1") + _gang_reduce(
        200, "job_2", exchanges=(0.4, 0.6))      # one overflow retry
    obs = dict(_empty_obs(), spans=spans)
    assert reducers_mesh.mesh_put_s(obs) == pytest.approx(3.0)
    assert reducers_mesh.mesh_dest_s(obs) == pytest.approx(0.1)
    assert reducers_mesh.mesh_exchange_s(obs) == pytest.approx(0.7)
    assert reducers_mesh.mesh_sort_s(obs) == pytest.approx(0.5)
    assert reducers_mesh.mesh_get_s(obs) == pytest.approx(5.0)


def test_a_program_without_the_step_spans_gives_none_not_zero():
    """The parent commit records ``dshuffle:device`` and nothing under
    it; so does the one-device branch."""
    obs = dict(_empty_obs(), spans=_gang_reduce(100, "job_1", steps=False))
    for s in STEPS:
        assert getattr(reducers_mesh, f"mesh_{s}_s")(obs) is None
    assert run.find_reducer("shuffle_device_call_s")(obs) == 10.0


def _traced(events):
    planes = {f"/device:TPU:{d}": {"XLA Modules": list(events)}
              for d in range(4)}
    return dict(_empty_obs(), peak=run.load_peak("TPU v5 lite"),
                trace={"devices": planes, "lo": 0.0, "hi": 1e12})


def test_the_rooflines_hold_a_device_to_its_share_of_the_jobs_rows():
    rows, w = 10_000_000 / 4, 100
    assert work_mesh.dest(10_000_000, 4, 10)["bytes"] == rows * 14
    ex = work_mesh.exchange(10_000_000, 4, w)
    assert ex["bytes"] == 2 * rows * w and ex["ici_bytes"] == rows * w * 0.75
    assert work_mesh.sort(10_000_000, 4, w)["bytes"] == 2 * rows * w
    # two jobs in the window; other programs' runs are not these programs'
    obs = _traced([("jit__dest(1)", 1e9, 2e6), ("jit__dest(1)", 5e9, 2e6),
                   ("jit__shuffle(2)", 2e9, 50e6),
                   ("jit__shuffle(2)", 6e9, 50e6),
                   ("jit__sort(3)", 3e9, 400e6), ("jit__sort(3)", 7e9, 400e6),
                   ("jit__argsort(4)", 4e9, 70e6),
                   ("jit__shuffle_keys(5)", 8e9, 1e6)])
    assert reducers_mesh.mesh_dest_roofline(obs) == pytest.approx(
        100 * (rows * 14 / 819e9) / 2e-3)
    # the exchange is held to the chip's whole ICI peak: the larger bound
    assert rows * w * 0.75 / 200e9 > 2 * rows * w / 819e9
    assert reducers_mesh.mesh_exchange_roofline(obs) == pytest.approx(
        100 * (rows * w * 0.75 / 200e9) / 50e-3)
    assert reducers_mesh.mesh_sort_roofline(obs) == pytest.approx(
        100 * (2 * rows * w / 819e9) / 400e-3)
    for name in ROOFLINES:
        assert 0 < getattr(reducers_mesh, name)(obs) < 100


def test_a_roofline_reads_none_without_a_run_and_fails_on_an_unknown_chip():
    only_argsort = _traced([("jit__argsort(4)", 4e9, 70e6)])
    for name in ROOFLINES:
        assert getattr(reducers_mesh, name)(only_argsort) is None
    one_chip_sizes = _traced([("jit__sort(3)", 3e9, 400e6)])
    one_chip_sizes["sizes"] = {"rows": 10_000_000, "maps": 8}
    assert reducers_mesh.mesh_sort_roofline(one_chip_sizes) is None
    unknown = _traced([("jit__sort(3)", 3e9, 400e6)])
    unknown["peak"] = dict(unknown["peak"], hbm_bytes_per_s=1.0)
    with pytest.raises(KeyError):
        reducers_mesh.mesh_sort_roofline(unknown)
