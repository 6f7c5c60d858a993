"""``bench/run.py --rehearse`` of the SIFT K-Means cell (d = 128, k = 1024
as published, rows cut) through real jobtracker, tasktracker and round
driver processes on a CPU device; the ``kmeans_sift`` family's points; the
bfloat16 control and every planted fault on what the rehearsal wrote, each
``correct`` false; the configuration against ISSUE 30's table; and the
cell's readers on hand-built spans, rollups and a trace, None where there
is nothing to read.
"""

import copy
import glob
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import run, work  # noqa: E402
from bench.families import kmeans, kmeans_sift  # noqa: E402
from test_reducers_spans import a_round  # noqa: E402
from test_rehearse import (SEED, _km_tampered, _rehearse,  # noqa: E402
                           _verdict)

CELL = "kmeans-sift-d128-k1024.rounds"
CONFIG = "kmeans-sift-d128-k1024"
DEVICE_METRICS = {"sift_assign_roofline", "sift.idle_share"}
NAMED_BY_THE_ISSUE = {
    "sift.client_outside_job_s", "sift.tpu_map_share", "sift.tpu_map_mean_s",
    "sift.staged_bytes_per_job",
    "sift.stage_s_per_map", "sift.execute_s_per_map",
    "sift.tpu_task_overhead_s", "sift.tpu_slot_busy_share",
    "sift.cpu_slot_busy_share", "sift.tpu_assign_gap_s", "sift.report_lag_s",
    "sift.job_tail_s", "sift.idle_share", "sift.window_compiles",
    "sift.cpu_overhang_s", "sift_assign_roofline"}
# ISSUE 30 also names sift.cpu_map_mean_s and sift.accel_factor_observed:
# on the chip no CPU map of a window's job ever finishes (each is killed
# when its speculative twin on the chip wins), so neither has anything to
# read there, and a metric a traced line lacks may not be listed


def _new_metrics() -> "list[str]":
    return [m["name"] for m in run.load_benchmark()["per_layer"]
            if m["workloads"] == [CELL]]


def _reader(metric: str):
    spec = run._load_json("layer_metrics", metric + ".json")
    assert list(spec) == ["reducer"]
    return run.find_reducer(spec["reducer"])


@pytest.fixture(scope="module")
def sift():
    return _rehearse(CELL, trace=1)


# ------------------------------------------------------- the rehearsal


def test_the_sift_rehearsal_is_correct_at_the_published_widths(sift):
    line = sift["line"]
    assert line["correct"] is True, sift["stderr"][-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert (sift["sizes"]["d"], sift["sizes"]["k"]) == (128, 1024)
    gap = line["checks"]["centroid_gap"]
    assert gap["value"] <= gap["limit"] and gap["value"] < 1e-3
    assert line["checks"]["rounds_compared"]["value"] == line["attempted"]
    assert line["checks"]["jobs_unsound"] == {"value": 0, "limit": 0}
    # every metric the cell adds that reads spans, rollups or the host's
    # clock has a value; none that needs a device trace has
    assert set(line["metrics"]) == set(_new_metrics()) - DEVICE_METRICS
    assert line["metrics"]["sift.window_compiles"]["value"] == 0
    assert line["metrics"]["sift.cpu_overhang_s"]["value"] >= 0
    # each round was given centroids of its own, and they moved
    given = [np.load(j["given"]) for j in sift["jobs"]]
    assert all(g.shape == (1024, 128) for g in given)
    assert all(np.abs(a - b).max() > 1e-2 for a, b in zip(given, given[1:]))
    # through the cluster path: a map a job on the TPU slot, all 8 mapped
    for j in sift["jobs"]:
        r = j["rollup"]
        assert r["finished_tpu_maps"] >= 1
        assert r["finished_tpu_maps"] + r["finished_cpu_maps"] == 8


def test_tpu_execute_spans_say_what_ran_at_which_widths(sift):
    """The attributes the K-Means kernel adds to the runner's span."""
    history = os.path.join(REPO, "bench", ".work", CELL, "run", "history")
    spans = []
    for path in glob.glob(os.path.join(history, "trace-*.jsonl")):
        with open(path) as f:
            spans += [json.loads(ln) for ln in f if ln.strip()]
    execs = [s for s in spans if s["name"] == "tpu:execute"]
    assert execs
    for s in execs:
        a = s["attributes"]
        assert (a["rows"], a["d"], a["k"], a["impl"]) == (8000, 128, 1024,
                                                          "xla")
        assert a["kernel"] == "kmeans-assign" and a["compile"] in ("cold",
                                                                   "warm")


def test_the_points_are_whole_numbers_that_no_half_stands_for(sift):
    pts = np.load(sift["inputs"]["points"])
    assert pts.shape == (64000, 128) and pts.dtype == np.float32
    assert pts.min() >= 0 and pts.max() <= 255
    assert np.array_equal(pts, np.rint(pts))
    assert np.array_equal(kmeans.bf16(pts), pts)     # exact in bfloat16
    assert 400 < np.linalg.norm(pts, axis=1).mean() < 650   # SIFT's 512
    # the drift: halves of the set, and of a split, differ in their means
    # by more than the draw explains
    whole = np.abs(pts[:32000].mean(0) - pts[32000:].mean(0)).max()
    split = np.abs(pts[:4000].mean(0) - pts[4000:8000].mean(0)).max()
    assert whole > 1.0 and split > 1.0
    # clustered: a point lies nearer its own centre than a normal draw of
    # the whole set's spread would
    centres = kmeans_sift.component_centres(sift["sizes"], SEED)
    assert centres.shape == (4096, 128)
    x = pts[:2048]
    d2 = ((x * x).sum(1)[:, None] - 2.0 * x @ centres.T
          + (centres * centres).sum(1)[None, :])
    assert np.sqrt(d2.min(1)).mean() < 0.5 * np.sqrt(
        ((x - pts.mean(0)) ** 2).sum(1)).mean()
    # the initial centroids, the first k rows: one from each of the first
    # k components, so no two of them share one
    assert np.array_equal(d2[:1024].argmin(1), np.arange(1024))


def test_the_points_are_a_function_of_the_seed(tmp_path):
    sizes = {"rows": 6000, "d": 128, "k": 1024, "split_rows": 2000,
             "components": 1100, "centre_scale": 32.0, "spread": 12.0,
             "drift": 6.0}
    a = np.load(kmeans_sift.make_input(sizes, SEED, str(tmp_path))["points"])
    os.makedirs(tmp_path / "again")
    os.makedirs(tmp_path / "other")
    b = np.load(kmeans_sift.make_input(sizes, SEED,
                                       str(tmp_path / "again"))["points"])
    c = np.load(kmeans_sift.make_input(sizes, SEED + 1,
                                       str(tmp_path / "other"))["points"])
    assert np.array_equal(a, b) and not np.array_equal(a, c)


# --------------------------------------------------- control and faults


def _altered(given, got):
    out = got.copy()
    out[3, 5] += 1.0        # one unit of a SIFT component
    return out


def _planted(r, **fault):
    """This family's reference with a fault planted, put in the program's
    place."""
    def new(given, _got):
        return kmeans_sift.reference_rounds(
            r["inputs"]["points"], r["sizes"], [given], **fault)[0]
    return new


KM_FAULTS = ["state_unchanged", "answer_altered"] + sorted(
    kmeans.fault_args(8))


@pytest.mark.parametrize("fault", KM_FAULTS)
def test_a_planted_fault_comes_out_not_correct(sift, tmp_path, fault):
    """A round that returns its centroids unchanged, an answer altered
    where it is produced, half of a split's rows left out four ways, a
    split's partial sums lost: each over the limit derived for THIS
    configuration."""
    new = {"state_unchanged": lambda given, got: given,
           "answer_altered": _altered}.get(fault) or _planted(
               sift, **kmeans_sift.fault_args(8)[fault])
    correct, checks = _verdict(sift, _km_tampered(sift, tmp_path, new))
    assert correct is False
    assert checks["centroid_gap"]["value"] > checks["centroid_gap"]["limit"]
    assert _verdict(sift, sift["jobs"])[0] is True


def test_the_control_in_bfloat16_comes_out_not_correct(sift, tmp_path):
    """Whole numbers up to 255 are exact in bfloat16, so the control
    rounds no point; it still rounds the centroids, the distances and the
    sums, and comes out far over the limit."""
    def new(given, _got):
        return kmeans_sift.reference_rounds(sift["inputs"]["points"],
                                            sift["sizes"], [given], "bf16")[0]
    correct, checks = _verdict(sift, _km_tampered(sift, tmp_path, new))
    assert correct is False
    assert checks["centroid_gap"]["value"] > 3 * checks["centroid_gap"][
        "limit"]


def test_maps_on_a_chip_are_held_to_the_one_pass_reference(sift):
    """Had the TPU-slot maps run on a chip, the reference would round the
    centroids to bfloat16 in the dots: it follows the placement."""
    jobs = copy.deepcopy(sift["jobs"])
    assert all(j["chip_maps"] == [] for j in jobs)
    for j in jobs:
        j["rollup"]["finished_tpu_maps"] = 8
        j["chip_maps"] = list(range(8))
    checks = run.compare(sift["cell"], jobs, sift["sizes"], SEED,
                         sift["inputs"])
    assert checks["maps_on_chip"]["value"] == 8 * len(jobs)
    assert checks["centroid_gap"]["value"] > 0


# ------------------------------------------------------ the configuration


def test_the_configuration_is_what_the_issue_says():
    bm = run.load_benchmark()
    cell = run.load_cell(bm, CELL)
    cfg, d16 = cell["config"], run.load_cell(bm, "kmeans-100m.rounds")
    assert cell["chips"] == 1 and cell["traffic"] == d16["traffic"]
    assert cell["family"] is kmeans_sift
    s = cfg["sizes"]
    assert (s["d"], s["k"], s["split_rows"]) == (128, 1024, 500_000)
    assert 5_000_000 <= s["rows"] <= 10_000_000
    assert s["rows"] % s["split_rows"] == 0
    assert s["components"] > s["k"]
    assert cfg["reduced"] == ["rows"]
    assert cfg["published"] == {"rows": 10 ** 9, "d": 128, "k": 1024}
    assert set(cfg["rehearse"]) <= {"rows", "split_rows"}   # never d or k
    assert cfg["cluster"]["tracker_defs"] == d16["config"]["cluster"][
        "tracker_defs"]
    assert cfg["cluster"]["job_defs"] == d16["config"]["cluster"]["job_defs"]
    assert cfg["cluster"]["daemon_defs"] == []
    assert len(cfg["source"]) <= 200 and "1024" in cfg["source"]
    assert list(cfg["limits"]) == ["centroid_gap"]
    assert 0 < cfg["limits"]["centroid_gap"] < 1.0
    assert len(cfg["limits_why"]) > 200
    for key in ("assumed", "guarantees", "reduced_why"):
        assert cfg[key]
    entry = next(c for c in bm["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == ["rows"]
    assert entry["file"] == f"bench/configs/{CONFIG}.json"
    # four cells, one of them on four chips; this config has one cell
    assert len(bm["workloads"]) == 4
    assert sum(w["chips"] == 4 for w in bm["workloads"]) == 1
    assert [w["name"] for w in bm["workloads"]
            if w["config"] == CONFIG] == [CELL]


def test_the_family_is_the_kmeans_family_with_points_and_pieces_of_its_own(
        sift):
    """The client, the guarantees and the comparison's parts are the
    ``kmeans`` family's; the points are its own, and so is the reference's
    loop over them (in-place pieces of 16,384 rows: the size forces it),
    which gives what the family's gives, bit for bit."""
    for name in ("Session", "job_failure", "rows_per_job", "read_centroids",
                 "centroid_gap", "fault_args", "bf16", "KEEP", "control",
                 "faults"):
        assert getattr(kmeans_sift, name) is getattr(kmeans, name)
    for name in ("make_input", "check", "reference_rounds"):
        assert getattr(kmeans_sift, name) is not getattr(kmeans, name)
    for mod in (kmeans_sift, kmeans):       # the reference is plain numpy
        with open(mod.__file__) as f:
            src = f.read()
        assert "import tpumr" not in src and "from tpumr" not in src
        assert "import jax" not in src
    given = [np.load(j["given"]) for j in sift["jobs"][:2]]
    modes = [["chip" if i % 3 else "f32" for i in range(8)]] * len(given)
    for fault in ({}, {"keep": "every_other_tile"}, {"lost_split": 3}):
        args = (sift["inputs"]["points"], sift["sizes"], given, modes)
        for a, b in zip(kmeans.reference_rounds(*args, **fault),
                        kmeans_sift.reference_rounds(*args, **fault)):
            assert np.array_equal(a, b)
    args = (sift["inputs"]["points"], sift["sizes"], given[:1], "bf16")
    assert np.array_equal(kmeans.reference_rounds(*args)[0],
                          kmeans_sift.reference_rounds(*args)[0])


# ------------------------------------------------------------ the readers


def _empty_obs():
    cfg = run._load_json("configs", CONFIG + ".json")
    return {"jobs": [], "window_s": 1.0, "spans": None, "trace": None,
            "peak": None, "window_compiles": None, "sizes": cfg["sizes"]}


def test_the_cell_adds_the_metrics_the_issue_names():
    names = _new_metrics()
    assert len(names) == len(set(names)) == 16
    assert set(names) == NAMED_BY_THE_ISSUE
    bm = run.load_benchmark()
    for m in bm["per_layer"]:       # no accepted metric was appended to
        if m["name"] not in NAMED_BY_THE_ISSUE:
            assert CELL not in m["workloads"]
        else:
            assert m["moves"] == "rows_per_s"


@pytest.mark.parametrize("metric", sorted(NAMED_BY_THE_ISSUE))
def test_a_sift_metric_resolves_to_a_reader_that_reads_none_from_nothing(
        metric):
    assert _reader(metric)(_empty_obs()) is None     # None, never 0


def test_cpu_overhang_is_the_wait_for_maps_the_chip_had_no_part_in():
    read = _reader("sift.cpu_overhang_s")
    # job_1: the last CPU map ends at 6.0, the last TPU map at 2.5
    # job_2: the chip ends last (7.0 against 6.0): 0, not -1
    # job_3: no CPU map at all: 0; job_4: no TPU map: left out
    spans = (a_round(0.0, "job_1")
             + a_round(50.0, "job_2", tpu_maps=((0.0, 0.4), (6.5, 7.0)))
             + a_round(100.0, "job_3", cpu_maps=())
             + a_round(150.0, "job_4", tpu_maps=()))
    obs = dict(_empty_obs(), spans=spans)
    assert read(obs) == pytest.approx((3.5 + 0.0 + 0.0) / 3)
    assert read(dict(_empty_obs(), spans=a_round(0.0, "job_1"))) \
        == pytest.approx(3.5)
    # a reduce is not a map: its launch ends at 9.5 and counts nowhere
    assert read(dict(_empty_obs(),
                     spans=a_round(0.0, "job_4", tpu_maps=()))) is None


def _traced(events):
    return dict(_empty_obs(), peak=run.load_peak("TPU v5 lite"),
                trace={"devices": {"/device:TPU:0": {
                    "XLA Modules": list(events)}}, "lo": 0.0, "hi": 1e12})


def test_the_assign_roofline_is_4ndk_operations_at_the_mxu_peak():
    read = _reader("sift_assign_roofline")
    w = work.kmeans_assign(500_000, 128, 1024)
    assert w["flops"] == 4 * 500_000 * 128 * 1024
    peak = run.load_peak("TPU v5 lite")
    least = w["flops"] / 197e12
    assert least > w["bytes"] / 819e9           # the MXU's side, not HBM's
    assert work.least_seconds(w, peak) == pytest.approx(least)
    # 34 runs in the window; another program's runs are not this one's
    name = "jit__assign_and_partials_jax(7)"
    events = [(name, 1e9 * (i + 1), 4e6) for i in range(34)]
    events += [("jit__argsort(4)", 50e9, 70e6), ("jit_sum(9)", 60e9, 1e6)]
    got = read(_traced(events))
    assert got == pytest.approx(100 * least / 4e-3)
    assert 0 < got < 100
    # a run outside the window is not counted
    assert read(_traced(events + [(name, 2e12, 1e3)])) == pytest.approx(got)


def test_the_assign_roofline_reads_none_without_a_run_and_no_unknown_chip():
    read = _reader("sift_assign_roofline")
    assert read(_traced([("jit__argsort(4)", 4e9, 70e6)])) is None
    assert read(dict(_traced([]), trace=None)) is None
    with pytest.raises(KeyError):       # a chip the table lacks is an error
        run.load_peak("TPU v9")
