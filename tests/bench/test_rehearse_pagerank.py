"""``bench/run.py --rehearse`` of the PageRank cell (graph500 at a toy
scale, every width as published) through real jobtracker, tasktracker and
client processes on ONE CPU device; the planted faults and the bfloat16
control on what the rehearsal wrote, each ``correct`` false; the
configuration as the cell states it (its own cells only); the limit
between its readings; and the cell's readers on hand-built spans and a
trace, None where there is nothing to read.
"""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import run, work, work_pagerank  # noqa: E402
from bench.families import pagerank as fam  # noqa: E402
from test_reducers_spans import a_round, span  # noqa: E402
from test_rehearse import SEED, _verdict  # noqa: E402

CELL = "pagerank-graph500-s23.rounds"
CONFIG = "pagerank-graph500-s23"
DEVICE_METRICS = {"pagerank.idle_share", "pagerank_map_roofline"}
NAMED_FOR_THE_CELL = {
    "pagerank.client_outside_job_s", "pagerank.tpu_map_share",
    "pagerank.job_tail_s", "pagerank.tpu_map_mean_s",
    "pagerank.staged_bytes_per_job",
    "pagerank.execute_s_per_map", "pagerank.update_s",
    "pagerank.idle_share", "pagerank.window_compiles",
    "pagerank_map_roofline", "pagerank.launch_wait_s",
    "pagerank.report_lag_s", "pagerank.tpu_task_overhead_s"}
BACKEND = "tpumr.BackendCounter"


def _new_metrics() -> "list[str]":
    return [m["name"] for m in run.load_benchmark()["per_layer"]
            if m.get("workloads") == [CELL]]


def _reader(metric: str):
    spec = run._load_json("layer_metrics", metric + ".json")
    assert list(spec) == ["reducer"]
    return run.find_reducer(spec["reducer"])


@pytest.fixture(scope="module")
def rehearsal():
    """One traced rehearsal on ONE CPU device, as the cell has one chip."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=200)
    assert proc.returncode == 1, proc.stderr[-3000:]    # no chip: never 0
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(os.path.join(REPO, "bench", ".work", CELL, "run",
                           "jobs.json")) as f:
        jobs = json.load(f)
    loaded = run.load_cell(run.load_benchmark(), CELL)
    cfg = loaded["config"]
    sizes = dict(cfg["sizes"], **cfg["rehearse"])
    inputs = run.prepare_input(loaded, sizes, SEED, rehearse=True)
    sizes["arcs"] = inputs["arcs"]
    return {"line": line, "stderr": proc.stderr, "jobs": jobs,
            "cell": loaded, "sizes": sizes, "inputs": inputs}


# ------------------------------------------------------- the rehearsal


def test_the_rehearsal_is_correct(rehearsal):
    line = rehearsal["line"]
    assert line["correct"] is True, rehearsal["stderr"][-3000:]
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    checks = line["checks"]
    assert 0 < checks["rank_gap"]["value"] < 1e-6 < checks["rank_gap"][
        "limit"]
    assert checks["rank_sum_gap"]["value"] < 1e-6
    # a fixed number of compared rounds, whatever the window holds
    assert checks["rounds_compared"]["value"] == min(3, line["attempted"])
    assert checks["rounds_read"]["value"] == line["attempted"]
    assert checks["jobs_unsound"] == {"value": 0, "limit": 0}
    # the metrics that read spans, rollups or the host's clock; none that
    # needs a device trace.
    got = set(line["metrics"])
    assert got == set(_new_metrics()) - DEVICE_METRICS
    assert line["metrics"]["pagerank.window_compiles"]["value"] == 0
    assert line["metrics"]["pagerank.update_s"]["value"] > 0


def test_every_job_counts_each_arc_once(rehearsal):
    arcs = rehearsal["inputs"]["arcs"]
    for j in rehearsal["jobs"]:
        c = j["rollup"]["counters"]
        pr = c[fam.PAGERANK]
        assert pr.get("PAGERANK_ARCS_TPU", 0) \
            + pr.get("PAGERANK_ARCS_CPU", 0) == arcs
        assert c["tpumr.TaskCounter"]["MAP_INPUT_RECORDS"] == arcs
        assert c["tpumr.TaskCounter"]["REDUCE_INPUT_RECORDS"] \
            == rehearsal["sizes"]["splits"]


def test_the_spans_name_the_maps_and_the_update(rehearsal):
    hist = os.path.join(REPO, "bench", ".work", CELL, "run", "history")
    spans = []
    for p in os.listdir(hist):
        if p.startswith("trace-") and p.endswith(".jsonl"):
            with open(os.path.join(hist, p)) as f:
                spans += [json.loads(x) for x in f if x.strip()]
    ex = [s for s in spans if s["name"] == "tpu:execute"
          and s["attributes"].get("kernel") == "pagerank"]
    assert ex and all(s["attributes"]["impl"] == "xla"
                      and s["attributes"]["arcs"] > 0
                      and s["attributes"]["vertices"] > 0 for s in ex)
    up = [s for s in spans if s["name"] == "pagerank:update"]
    assert up and all(s["attributes"]["vertices"]
                      == rehearsal["inputs"]["vertices"]
                      and s["attributes"]["dangling_mass"] == 0.0
                      and s["attributes"]["delta_l1"] > 0 for s in up)


# ----------------------------------------------- faults and the control


def _with_last_ranks(r: dict, tmp_path, ranks: np.ndarray) -> "list[dict]":
    """The window's jobs with the last round's ranks replaced."""
    jobs = copy.deepcopy(r["jobs"])
    state = tmp_path / "state"
    state.mkdir()
    np.save(state / "ranks.npy", np.asarray(ranks, np.float32))
    jobs[-1]["state"] = str(state)
    return jobs


def _planted(r: dict, tmp_path, fault: str) -> np.ndarray:
    given = np.load(os.path.join(r["jobs"][-1]["given"], "ranks.npy"))
    knobs = {"control_bfloat16": {"contrib_of": lambda c: fam.bf16(
                 c.astype(np.float32))},
             "a_splits_range_left_unsummed": {
                 "lost_split": len(r["inputs"]["splits"]) // 2},
             "damping_0.86": {"damping": 0.86},
             "a_hub_left_unsummed": {"lost_vertex": int(np.argmax(np.load(
                 os.path.join(r["inputs"]["graph"], "outdeg.npy"))))}}
    if fault == "a_round_unchanged":
        return given
    if fault == "a_vertex_left_out":
        return np.load(os.path.join(r["jobs"][-1]["state"],
                                    "ranks.npy"))[:-1]
    if fault == "ranks_not_normalised":
        return np.load(os.path.join(r["jobs"][-1]["state"],
                                    "ranks.npy")) * 1.001
    return fam.reference_rounds(r["inputs"], r["sizes"], [given],
                                str(tmp_path), **knobs[fault])[0]


FAULTS = ["control_bfloat16", "a_splits_range_left_unsummed",
          "a_hub_left_unsummed", "damping_0.86", "a_round_unchanged",
          "a_vertex_left_out", "ranks_not_normalised"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_comes_out_not_correct(rehearsal, tmp_path, fault):
    jobs = _with_last_ranks(rehearsal, tmp_path,
                            _planted(rehearsal, tmp_path, fault))
    correct, checks = _verdict(rehearsal, jobs)
    assert correct is False, checks


def test_the_untouched_rounds_read_correct_through_the_same_path(
        rehearsal, tmp_path):
    ranks = np.load(os.path.join(rehearsal["jobs"][-1]["state"],
                                 "ranks.npy"))
    correct, checks = _verdict(rehearsal, _with_last_ranks(
        rehearsal, tmp_path, ranks))
    assert correct is True, checks


@pytest.mark.parametrize("counter_,value,why", [
    (("tpumr.BackendCounter", "TPU_MAP_TASKS"), 0, "no map task ran"),
    (("PageRank", "PAGERANK_ARCS_CPU"), 7, "PAGERANK_ARCS"),
    (("tpumr.TaskCounter", "MAP_INPUT_RECORDS"), 1, "the maps read"),
    (("tpumr.TaskCounter", "REDUCE_INPUT_RECORDS"), 99, "one range"),
    (("tpumr.JobCounter", "TPU_DEMOTIONS"), 1, "demoted")])
def test_a_job_whose_counters_break_a_guarantee_is_unsound(
        rehearsal, counter_, value, why):
    r = copy.deepcopy(rehearsal["jobs"][0]["rollup"])
    group, name = counter_
    r["counters"].setdefault(group, {})
    if name == "PAGERANK_ARCS_CPU":
        value += r["counters"][group].get(name, 0)
    r["counters"][group][name] = value
    assert why in (fam.job_failure(r, rehearsal["sizes"], True) or "")
    assert fam.job_failure(rehearsal["jobs"][0]["rollup"],
                           rehearsal["sizes"], True) is None


def test_the_compared_rounds_never_grow_with_the_window():
    for n in range(1, 40):
        got = fam.compared_rounds(n, SEED)
        assert 1 <= len(got) <= 3 and got[0] == 0 and got[-1] == n - 1
    assert fam.compared_rounds(0, SEED) == []


def test_the_family_imports_nothing_of_the_program():
    with open(fam.__file__) as f:
        text = f.read()
    assert "import tpumr" not in text and "from tpumr" not in text


# ------------------------------------------------------ the configuration


def test_the_configuration_is_what_the_cell_states():
    cfg = run._load_json("configs", CONFIG + ".json")
    assert cfg["family"] == "pagerank" and cfg["architecture"] is None
    assert cfg["reduced"] == []
    assert {k: cfg["sizes"][k] for k in ("scale", "edge_factor", "a", "b",
                                         "c", "damping")} == {
        "scale": 23, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "damping": 0.85}
    for word in ("LDBC Graphalytics", "PVLDB 2016", "d=0.85",
                 "graph500-23", "scale 23", "edge factor 16", "undirected",
                 "dedup", "isolated vertices dropped", "one v5e tracker"):
        assert word in cfg["source"], word
    assert set(cfg["assumed"]) >= {"generator", "layout", "splits",
                                   "iterations", "storage"}
    assert set(cfg["rehearse"]) == {"scale"}      # no width is cut
    assert set(cfg["limits_why"]) == set(cfg["limits"])
    assert cfg["cluster"]["tracker_defs"] == run._load_json(
        "configs", "kmeans-100m-d16-k16.json")["cluster"]["tracker_defs"]
    assert run._load_json("traffic", "rounds.json")["warmup_jobs"] == 1
    bm = run.load_benchmark()
    mine = [w for w in bm["workloads"] if w["config"] == CONFIG]
    assert [(w["name"], w["traffic"], w["chips"]) for w in mine] == [
        (CELL, "rounds", 1)]
    entry = next(c for c in bm["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


@pytest.mark.parametrize("check,planted", [
    ("rank_gap", {"control_bfloat16", "a_splits_range_left_unsummed",
                  "a_hub_left_unsummed", "damping_0.86",
                  "a_round_unchanged"}),
    ("rank_sum_gap", {"a_splits_range_left_unsummed",
                      "a_hub_left_unsummed"})])
def test_the_limit_lies_between_its_readings_with_room_on_both_sides(
        check, planted):
    cfg = run._load_json("configs", CONFIG + ".json")
    readings = cfg["limit_readings"][check]
    limit = cfg["limits"][check]
    assert len(readings["lower"]) >= 20
    assert 5 * max(readings["lower"]) <= limit
    assert 5 * limit <= min(min(v) for v in readings["upper"].values())
    assert set(readings["upper"]) >= planted


# ------------------------------------------------------------ the readers


def _empty_obs():
    cfg = run._load_json("configs", CONFIG + ".json")
    return {"jobs": [], "window_s": 1.0, "spans": None, "trace": None,
            "peak": None, "window_compiles": None, "sizes": cfg["sizes"]}


def test_the_cell_adds_the_metrics_it_names():
    names = _new_metrics()
    assert len(names) == len(set(names)) == len(NAMED_FOR_THE_CELL)
    assert set(names) == NAMED_FOR_THE_CELL
    for m in run.load_benchmark()["per_layer"]:
        if m["name"] in NAMED_FOR_THE_CELL:
            assert m["moves"] == "rows_per_s"
        else:                       # none was appended to
            assert CELL not in m.get("workloads", [CELL])


@pytest.mark.parametrize("metric", sorted(NAMED_FOR_THE_CELL))
def test_a_metric_resolves_to_a_reader_that_reads_none_from_nothing(
        metric):
    assert _reader(metric)(_empty_obs()) is None     # None, never 0


def test_the_update_reader_averages_the_span_over_jobs():
    spans = a_round(0.0, "job_1") + a_round(50.0, "job_2")
    spans += [span("pagerank:update", 8.0, 8.25, job="job_1"),
              span("pagerank:update", 58.0, 58.75, job="job_2")]
    obs = dict(_empty_obs(), spans=spans)
    assert _reader("pagerank.update_s")(obs) == pytest.approx(0.5)


def test_the_launch_wait_reads_submit_to_the_first_maps_launch():
    spans = a_round(0.0, "job_1", tpu_maps=((0.9, 1.3),),
                    cpu_maps=((1.1, 2.0),))
    spans += a_round(50.0, "job_2", tpu_maps=((1.1, 1.5),), cpu_maps=())
    obs = dict(_empty_obs(), spans=spans)
    assert _reader("pagerank.launch_wait_s")(obs) == pytest.approx(1.0)


def test_the_roofline_counts_the_maps_arcs_never_a_padded_shape():
    assert work_pagerank.pagerank_map(1000, 10) == {
        "bytes": 12 * 1000 + 4 * 10, "flops": 0}
    arcs, verts = 258_657_378, 4_609_823
    spans = [span("tpu:execute", 1.0 + j, 1.5 + j, job=f"job_{j}",
                  kernel="pagerank", arcs=arcs, vertices=verts, impl="xla")
             for j in range(3)]
    events = [("jit__pagerank_map(5)", 1e9 * (1 + j), 2.2e9)
              for j in range(3)]
    events.append(("jit__last_arcs(2)", 0.5e9, 1e9))    # not the map's
    obs = dict(_empty_obs(), spans=spans, peak=run.load_peak("TPU v5 lite"),
               trace={"devices": {"/device:TPU:0": {
                   "XLA Modules": events}}, "lo": 0.0, "hi": 1e12})
    got = _reader("pagerank_map_roofline")(obs)
    least = work.least_seconds(work_pagerank.pagerank_map(arcs, verts),
                               obs["peak"])
    assert got == pytest.approx(100 * least / 2.2)
    assert 0 < got < 100
    assert _reader("pagerank_map_roofline")(dict(obs, spans=[])) is None
    assert _reader("pagerank_map_roofline")(dict(obs, trace=None)) is None
