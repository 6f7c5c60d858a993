"""``bench/run.py --rehearse`` for each cell through real jobtracker,
tasktracker and client processes on CPU devices; then the comparison that
decides ``correct`` on what those runs wrote, with the timed path's output
broken underneath (it must come out false), and each cell's control.

One rehearsal a cell (a module fixture, under a minute each, every wait
inside it bounded); the fault and control cases reuse what it wrote.
"""

import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import run  # noqa: E402
from bench.families import kmeans, terasort  # noqa: E402

SEED = 2_400_000_011        # the driver's seeds pass 2**31
CELLS = {"kmeans": "kmeans-100m.rounds",
         "terasort": "terasort-10m.device-shuffle"}
DEVICE_METRICS = ("kmeans_assign_roofline", "argsort_roofline",
                  "device.idle_share")


def _rehearse(cell: str, trace: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"),
         "--workload", cell, "--seed", str(SEED), "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=170)
    assert proc.returncode == 1, proc.stderr[-3000:]    # no chip: never 0
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    run_dir = os.path.join(REPO, "bench", ".work", cell, "run")
    with open(os.path.join(run_dir, "jobs.json")) as f:
        jobs = json.load(f)
    bm = run.load_benchmark()
    loaded = run.load_cell(bm, cell)
    cfg = loaded["config"]
    sizes = dict(cfg["sizes"], **cfg["rehearse"])
    inputs = run.prepare_input(loaded, sizes, SEED, rehearse=True)
    return {"line": line, "stderr": proc.stderr, "jobs": jobs, "bm": bm,
            "cell": loaded, "sizes": sizes, "inputs": inputs}


@pytest.fixture(scope="module")
def km():
    return _rehearse(CELLS["kmeans"], trace=1)


@pytest.fixture(scope="module")
def ts():
    return _rehearse(CELLS["terasort"], trace=0)


def _sound(r: dict, trace: bool) -> None:
    line = r["line"]
    assert list(line)[:5] == list(run.RESULT_KEYS)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, r["stderr"][-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert not set(DEVICE_METRICS) & set(line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"]
               for m in run.cell_metrics(r["bm"], r["cell"]["name"], kind)}
    assert line["metrics"] and set(line["metrics"]) <= set(allowed)
    for name, m in line["metrics"].items():
        assert m["unit"] == allowed[name]
        assert isinstance(m["value"], (int, float))
    if not trace:
        assert set(line["metrics"]) == set(allowed)
        assert all(m["value"] > 0 for m in line["metrics"].values())
    # each number compared beside its limit, last on standard error too
    tail = r["stderr"].strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)


def test_kmeans_rehearsal_traced_is_correct_on_cpu(km):
    _sound(km, trace=True)
    assert km["line"]["checks"]["centroid_gap"]["value"] < 1e-5
    assert km["line"]["metrics"]["runtime.window_compiles"]["value"] == 0
    assert "master.tpu_map_share" in km["line"]["metrics"]
    # every round was given centroids of its own, and they moved
    given = [np.load(j["given"]) for j in km["jobs"]]
    assert len(given) >= 2
    assert all(np.abs(a - b).max() > 1e-4
               for a, b in zip(given, given[1:]))


def test_terasort_rehearsal_is_correct_on_cpu(ts):
    _sound(ts, trace=False)
    assert ts["line"]["checks"]["rows_wrong"] == {"value": 0, "limit": 0}


def _verdict(r: dict, jobs: "list[dict]") -> "tuple[bool, dict]":
    """The rest of a run from the comparison on: the checks, the verdict
    and the result line, as ``run.measure`` builds them."""
    checks = run.compare(r["cell"], jobs, r["sizes"], SEED, r["inputs"])
    line = run.result_line(run.judge(checks), jobs, {}, {},
                           r["line"]["device"], checks)
    return line["correct"], checks


def _km_tampered(r: dict, tmp_path, new_centroids) -> "list[dict]":
    """The window's jobs with the last round's output replaced."""
    jobs = copy.deepcopy(r["jobs"])
    last = jobs[-1]
    out = tmp_path / "iter"
    out.mkdir()
    given = np.load(last["given"])
    want = new_centroids(given, kmeans.read_centroids(last["out"], given))
    with open(out / "part-00000", "w") as f:
        for cid, row in enumerate(want):
            f.write(f"{cid}\t{[float(x) for x in row]}\n")
    last["out"] = str(out)
    return jobs


def _altered(given, got):
    out = got.copy()
    out[3, 5] += 0.05
    return out


def _planted(r, **fault):
    """The reference with a fault planted, put in the program's place."""
    def new(given, _got):
        return kmeans.reference_rounds(r["inputs"]["points"], r["sizes"],
                                       [given], **fault)[0]
    return new


KM_FAULTS = ["state_unchanged", "answer_altered"] + sorted(
    kmeans.fault_args(8))


@pytest.mark.parametrize("fault", KM_FAULTS)
def test_kmeans_fault_comes_out_not_correct(km, tmp_path, fault):
    """A round that returns its centroids unchanged, an answer altered
    where it is produced, half of a split's rows left out four ways (the
    mean taken over the rest), a split's partial sums lost. The readings
    at the cell's own size are in PERF.md (``control.py --faults``)."""
    n_splits = km["sizes"]["rows"] // km["sizes"]["split_rows"]
    new = {"state_unchanged": lambda given, got: given,
           "answer_altered": _altered}.get(fault) or _planted(
               km, **kmeans.fault_args(n_splits)[fault])
    correct, checks = _verdict(km, _km_tampered(km, tmp_path, new))
    assert correct is False
    assert checks["centroid_gap"]["value"] > checks["centroid_gap"]["limit"]
    # and untouched, the same jobs pass
    assert _verdict(km, km["jobs"])[0] is True


def test_kmeans_placement_that_is_not_known_is_not_correct(km):
    """The reference computes each split in the precision of the slot it
    ran on; where the master's log does not say, nothing is compared."""
    jobs = copy.deepcopy(km["jobs"])
    assert all(j["chip_maps"] == [] for j in jobs)   # no chip in a rehearsal
    jobs[0]["chip_maps"] = None
    assert _verdict(km, jobs)[0] is False
    jobs[0]["chip_maps"] = [0, 1, 2]     # the rollup counts another number
    assert jobs[0]["rollup"]["finished_tpu_maps"] != 3
    assert _verdict(km, jobs)[0] is False


def test_kmeans_maps_on_a_chip_are_held_to_the_one_pass_reference(km):
    """Had the rehearsal's TPU-slot maps run on a chip, the float32 the
    CPU device computed would lie off the reference by the one-pass
    rounding: the comparison follows the placement."""
    jobs = copy.deepcopy(km["jobs"])
    from bench.cluster import Cluster
    c = Cluster(os.path.join(REPO, "bench", ".work", CELLS["kmeans"], "run"),
                [], [])
    for j in jobs:
        j["chip_maps"] = c.tpu_maps(j["rollup"]["job_id"])
        assert len(j["chip_maps"]) == j["rollup"]["finished_tpu_maps"] > 0
    checks = run.compare(km["cell"], jobs, km["sizes"], SEED, km["inputs"])
    assert checks["maps_on_chip"]["value"] == sum(
        len(j["chip_maps"]) for j in jobs)
    assert checks["centroid_gap"]["value"] > 1e-5


def test_kmeans_job_that_breaks_a_guarantee_is_not_correct(km):
    jobs = copy.deepcopy(km["jobs"])
    r = jobs[0]["rollup"]
    r["counters"]["tpumr.JobCounter"] = dict(
        r["counters"].get("tpumr.JobCounter") or {}, TPU_DEMOTIONS=1)
    jobs[0]["failure"] = kmeans.job_failure(r, km["sizes"], on_chip=False)
    assert jobs[0]["failure"]
    correct, checks = _verdict(km, jobs)
    assert correct is False and checks["jobs_unsound"]["value"] == 1
    r2 = copy.deepcopy(km["jobs"][0]["rollup"])
    r2["counters"]["tpumr.BackendCounter"]["CPU_MAP_TASKS"] -= 1
    assert "splits" in kmeans.job_failure(r2, km["sizes"], on_chip=False)


@pytest.mark.parametrize("name,by,why", [
    ("MAP_INPUT_RECORDS", -1, "rows"),
    ("REDUCE_INPUT_RECORDS", -16, "did not get every record")])
def test_kmeans_rows_or_partials_lost_on_the_way_are_unsound(km, name, by,
                                                             why):
    """The job writes centroids only: rows a map never read and partial
    sums that never reached the reduce show in the counters alone."""
    r = copy.deepcopy(km["jobs"][0]["rollup"])
    assert kmeans.job_failure(r, km["sizes"], on_chip=False) is None
    r["counters"]["tpumr.TaskCounter"][name] += by
    assert why in kmeans.job_failure(r, km["sizes"], on_chip=False)


def test_kmeans_control_in_bfloat16_comes_out_not_correct(km, tmp_path):
    """The control at a size a test can hold: the reference computed in
    bfloat16 and put in the program's place."""
    def new(given, _got):
        return kmeans.reference_rounds(km["inputs"]["points"], km["sizes"],
                                       [given], "bf16")[0]
    correct, checks = _verdict(km, _km_tampered(km, tmp_path, new))
    assert correct is False
    assert checks["centroid_gap"]["value"] > checks["centroid_gap"]["limit"]


def _ts_rewrite(r: dict, tmp_path, change) -> "list[dict]":
    """The window's jobs with the last job's part files rewritten."""
    from tpumr.io import sequencefile
    jobs = copy.deepcopy(r["jobs"])
    last = jobs[-1]
    out = tmp_path / "out"
    shutil.copytree(last["out"], out)
    parts = sorted(p for p in os.listdir(out) if p.startswith("part-"))
    path = os.path.join(out, parts[1])
    with open(path, "rb") as f:
        batch = sequencefile.Reader(f).read_batch_range(
            0, os.path.getsize(path))
    n = len(batch.key_offsets) - 1
    rows = np.concatenate(
        [np.asarray(batch.key_data).reshape(n, 10),
         np.asarray(batch.value_data).reshape(n, 90)], axis=1)
    with open(path, "wb") as f:
        w = sequencefile.Writer(f)
        w.append_fixed_rows(change(rows), 10)
        w.close()
    last["out"] = str(out)
    return jobs


def _swap_two(rows):
    out = rows.copy()
    out[[10, 11]] = out[[11, 10]]
    return out


def _alter_value(rows):
    out = rows.copy()
    out[7, 50] ^= 1
    return out


@pytest.mark.parametrize("fault,wrong", [
    ("two_rows_swapped", 2), ("a_value_byte_altered", 1),
    ("half_left_out", None)])
def test_terasort_fault_comes_out_not_correct(ts, tmp_path, fault, wrong):
    change = {"two_rows_swapped": _swap_two,
              "a_value_byte_altered": _alter_value,
              "half_left_out": lambda rows: rows[:len(rows) // 2]}[fault]
    correct, checks = _verdict(ts, _ts_rewrite(ts, tmp_path, change))
    assert correct is False
    assert checks["rows_wrong"]["value"] > 0
    if wrong is not None:
        assert checks["rows_wrong"]["value"] == wrong
    assert _verdict(ts, ts["jobs"])[0] is True


def test_terasort_output_left_as_the_input_is_not_correct(ts):
    """A step that returns its state unchanged: the unsorted input."""
    jobs = copy.deepcopy(ts["jobs"])
    jobs[-1]["out"] = ts["inputs"]["gen"]
    correct, checks = _verdict(ts, jobs)
    assert correct is False
    assert checks["rows_wrong"]["value"] > ts["sizes"]["rows"] // 2


def test_terasort_host_fallback_counts_as_unsound(ts):
    r = copy.deepcopy(ts["jobs"][0]["rollup"])
    assert terasort.job_failure(r, ts["sizes"], on_chip=False) is None
    assert "did not run on a chip" in terasort.job_failure(
        r, ts["sizes"], on_chip=True)    # a CPU rehearsal is not a chip
    r["counters"]["tpumr.BackendCounter"]["SHUFFLE_HOST_FALLBACKS"] = 1
    assert "fell back" in terasort.job_failure(r, ts["sizes"], False)


def test_terasort_control_breaks_the_total_order():
    """The control at a size a test can hold: ordered by the first four
    key bytes alone, the output is not the reference's."""
    sizes = {"rows": 400_000, "maps": 4}
    want = terasort.reference_sorted(sizes, SEED)
    control = terasort.reference_sorted(sizes, SEED, mode="prefix4")
    assert terasort.rows_wrong(want, want) == 0
    assert terasort.rows_wrong(control, want) > 100
    assert sorted(control.tolist()) == sorted(want.tolist())   # same rows
