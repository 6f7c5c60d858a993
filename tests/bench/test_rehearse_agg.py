"""``bench/run.py --rehearse`` of the aggregation cell (the nine columns,
the 16-byte key and the float32 value as published; rows and groups cut)
through real jobtracker, tasktracker and client processes on ONE CPU
device; the ``uservisits_agg`` family's table and parser; the bfloat16
control and every planted fault on what the rehearsal wrote, each
``correct`` false; the configuration against ISSUE 33's list; and the
cell's readers on hand-built spans, rollups and a trace, None where there
is nothing to read.
"""

import copy
import io
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

from bench import run, work, work_agg  # noqa: E402
from bench.families import uservisits_agg as uv  # noqa: E402
from test_reducers_spans import a_round, span  # noqa: E402
from test_rehearse import SEED, _verdict  # noqa: E402

CELL = "uservisits-agg-10m.device-reduce"
CONFIG = "uservisits-agg-10m"
DEVICE_METRICS = {"agg_sort_roofline", "agg_segment_sum_roofline",
                  "agg.idle_share"}
NAMED_BY_THE_ISSUE = {
    "agg.client_outside_job_s", "agg.job_tail_s", "agg.report_lag_s",
    "agg.locate_s", "agg.fetch_s", "agg.assemble_s", "agg.pack_s",
    "agg.device_call_s", "agg.write_s", "agg.self_s", "agg.idle_share",
    "agg.window_compiles", "agg.reduce_s", "agg.groups_bytes_back",
    "agg.map_phase_s", "agg.cpu_map_mean_s", "agg_sort_roofline",
    "agg_segment_sum_roofline"}
BACKEND = "tpumr.BackendCounter"


def _new_metrics() -> "list[str]":
    return [m["name"] for m in run.load_benchmark()["per_layer"]
            if m["workloads"] == [CELL]]


def _reader(metric: str):
    spec = run._load_json("layer_metrics", metric + ".json")
    assert list(spec) == ["reducer"]
    return run.find_reducer(spec["reducer"])


@pytest.fixture(scope="module")
def agg():
    """One traced rehearsal. The tracker gets ONE CPU device, as the cell
    has one chip: over the eight the suite forces, the gang reduce would
    build a mesh and reduce on the host."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"),
         "--workload", CELL, "--seed", str(SEED), "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=170)
    assert proc.returncode == 1, proc.stderr[-3000:]    # no chip: never 0
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(os.path.join(REPO, "bench", ".work", CELL, "run",
                           "jobs.json")) as f:
        jobs = json.load(f)
    bm = run.load_benchmark()
    loaded = run.load_cell(bm, CELL)
    cfg = loaded["config"]
    sizes = dict(cfg["sizes"], **cfg["rehearse"])
    inputs = run.prepare_input(loaded, sizes, SEED, rehearse=True)
    return {"line": line, "stderr": proc.stderr, "jobs": jobs, "bm": bm,
            "cell": loaded, "sizes": sizes, "inputs": inputs}


# ------------------------------------------------------- the rehearsal


def test_the_rehearsal_is_correct_at_the_published_widths(agg):
    line = agg["line"]
    assert line["correct"] is True, agg["stderr"][-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["checks"]["groups_wrong"] == {"value": 0, "limit": 0}
    gap = line["checks"]["sum_gap"]
    assert 0 < gap["value"] < 1e-6 < gap["limit"]
    assert line["checks"]["jobs_compared"]["value"] == line["attempted"]
    assert line["checks"]["jobs_unsound"] == {"value": 0, "limit": 0}
    # every metric the cell adds that reads spans, rollups or the host's
    # clock has a value; none that needs a device trace has
    assert set(line["metrics"]) == set(_new_metrics()) - DEVICE_METRICS
    assert line["metrics"]["agg.window_compiles"]["value"] == 0
    assert line["metrics"]["agg.reduce_s"]["value"] > 0
    assert 0 < line["metrics"]["agg.map_phase_s"]["value"] < 60
    tail = agg["stderr"].strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)


def test_the_rollup_says_the_device_reduced_and_only_groups_came_back(agg):
    rows = agg["sizes"]["rows"]
    groups = len(uv.reference(agg["sizes"], SEED)[0])
    for j in agg["jobs"]:
        c = j["rollup"]["counters"]
        assert c[BACKEND]["TPU_REDUCE_RECORDS"] == rows
        assert c[BACKEND]["TPU_REDUCE_GROUPS"] == groups
        assert c[BACKEND]["REDUCE_HOST_TWIN"] == 0
        assert c[BACKEND]["DEVICE_REDUCE_ON_ACCEL"] == 0    # a CPU device
        assert c["tpumr.TaskCounter"]["REDUCE_OUTPUT_RECORDS"] == groups
        assert c["tpumr.TaskCounter"]["REDUCE_INPUT_GROUPS"] == groups
        # near 20 bytes a group, not 20 bytes a row
        assert 20 * groups <= c[BACKEND]["TPU_REDUCE_BYTES_BACK"] \
            < 20 * groups + 20 * 700
    assert agg["line"]["metrics"]["agg.groups_bytes_back"]["value"] \
        < 20 * rows / 2


# ----------------------------------------------- faults and the control


def _written(job: dict) -> "list[np.ndarray]":
    out = []
    for p in sorted(os.listdir(job["out"])):
        if p.startswith("part-"):
            with open(os.path.join(job["out"], p), "rb") as f:
                out.append(uv.parse_container(f.read()))
    return out


def _rewritten(r: dict, tmp_path, change) -> "list[dict]":
    """The window's jobs with the last job's part files rewritten from
    ``change(parts)``, each part ``[n, 20]`` records."""
    from tpumr.io import sequencefile
    jobs = copy.deepcopy(r["jobs"])
    last = jobs[-1]
    parts = change([p.copy() for p in _written(last)])
    out = tmp_path / "out"
    out.mkdir()
    for i, rows in enumerate(parts):
        with open(out / f"part-{i:05d}", "wb") as f:
            w = sequencefile.Writer(f)
            w.append_fixed_rows(rows, uv.KEY_LEN)
            w.close()
    last["out"] = str(out)
    return jobs


def _sums(part: np.ndarray) -> np.ndarray:
    """A view of a part's float32 sums, writable in place."""
    return part[:, uv.KEY_LEN:].view("<f4")[:, 0]


def _least_row(r: dict) -> "tuple[bytes, np.float32]":
    """The row that is the smallest share of its group's sum (never a
    group's only row): its key and its value."""
    g, revenue, _ = uv.table_columns(r["sizes"], SEED)
    i = uv.least_share_row(g, revenue)
    return bytes(uv.group_keys(SEED, r["sizes"]["groups"])[0][g[i]]), \
        revenue[i]


def _add_to_a_group(r: dict, sign: int):
    key, value = _least_row(r)

    def change(parts):
        hit = 0
        for p in parts:
            p = np.ascontiguousarray(p)
            at = np.flatnonzero((p[:, :uv.KEY_LEN] == np.frombuffer(
                key, np.uint8)).all(axis=1))
            for i in at:
                _sums(p)[i] = np.float32(_sums(p)[i] + sign * value)
                hit += 1
        assert hit == 1
        return parts
    return change


def _swap_two_sums(parts):
    s = _sums(parts[1])
    s[[4, 5]] = s[[5, 4]]
    assert s[4] != s[5]
    return parts


def _alter_a_key(parts):
    parts[2][7, uv.KEY_LEN - 1] = 1     # the padding's last byte
    return parts


def _break_the_order(parts):
    parts[0][[10, 11]] = parts[0][[11, 10]]
    return parts


def _swap_two_parts(parts):
    return [parts[1], parts[0]] + parts[2:]


FAULTS = {"a_row_left_out": "gap", "a_row_counted_twice": "gap",
          "two_sums_swapped": "gap", "a_key_altered": "keys",
          "a_parts_order_broken": "keys", "two_parts_swapped": "keys",
          "a_group_left_out": "keys"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_not_correct(agg, tmp_path, fault):
    change = {
        "a_row_left_out": _add_to_a_group(agg, -1),
        "a_row_counted_twice": _add_to_a_group(agg, +1),
        "two_sums_swapped": _swap_two_sums,
        "a_key_altered": _alter_a_key,
        "a_parts_order_broken": _break_the_order,
        "two_parts_swapped": _swap_two_parts,
        "a_group_left_out": lambda parts: [parts[0][1:]] + parts[1:],
    }[fault]
    assert _verdict(agg, agg["jobs"])[0] is True
    correct, checks = _verdict(agg, _rewritten(agg, tmp_path, change))
    assert correct is False
    gap, wrong = checks["sum_gap"], checks["groups_wrong"]
    if FAULTS[fault] == "gap":      # the keys are right, one sum is not
        assert wrong["value"] == 0 and gap["value"] > gap["limit"]
        if fault != "two_sums_swapped":     # planted where it shows least
            assert gap["value"] < 1e-2
    else:                           # the sums are right, the keys are not
        assert wrong["value"] > 0 and gap["value"] <= gap["limit"]
    if fault == "a_key_altered":
        assert wrong["value"] == 1
    if fault == "a_parts_order_broken":
        assert wrong["value"] == 2


def test_the_control_in_bfloat16_comes_out_not_correct(agg, tmp_path):
    """Every group's sum made in bfloat16 and put in the program's
    place, in the program's own container."""
    g, revenue, _ = uv.table_columns(agg["sizes"], SEED)
    low = uv.sums_in_bfloat16(agg["sizes"], g, revenue)
    keys = uv.group_keys(SEED, agg["sizes"]["groups"])[0]
    index = {bytes(k): i for i, k in enumerate(keys)}

    def change(parts):
        for p in parts:
            at = [index[bytes(k)] for k in p[:, :uv.KEY_LEN]]
            _sums(p)[:] = low[at].astype(np.float32)
        return parts
    correct, checks = _verdict(agg, _rewritten(agg, tmp_path, change))
    assert correct is False and checks["groups_wrong"]["value"] == 0
    assert checks["sum_gap"]["value"] > 100 * checks["sum_gap"]["limit"]
    # and the family's own control, as bench/control.py reads it
    c = uv.control(agg["sizes"], SEED, agg["inputs"],
                   agg["cell"]["config"]["limits"])
    assert not run.judge(c)
    assert c["sum_gap"]["value"] == pytest.approx(
        checks["sum_gap"]["value"])


def test_the_familys_faults_all_read_over_the_limit(agg):
    got = uv.faults(agg["sizes"], SEED, agg["inputs"],
                    agg["cell"]["config"]["limits"])
    assert set(got) == {"a_row_left_out", "a_row_counted_twice",
                        "two_sums_swapped", "a_maps_output_lost"}
    for name, c in got.items():
        assert c["least"] > 5 * c["limit"], name
    assert got["a_maps_output_lost"]["groups_wrong"] > 0


@pytest.mark.parametrize("counter_,value,why", [
    ("SHUFFLE_HOST_FALLBACKS", 1, "fell back"),
    ("REDUCE_HOST_TWIN", 1, "on the host"),
    ("TPU_REDUCE_RECORDS", 39_999, "reduced 39999"),
    ("TPU_SHUFFLE_RECORDS", 39_000, "moved 39000"),
    ("TPU_REDUCE_GROUPS", 5, "REDUCE_INPUT_GROUPS")])
def test_a_job_whose_counters_break_a_guarantee_is_unsound(agg, counter_,
                                                           value, why):
    """A host fallback and the reduce on the host twin give the right
    output: only the counters show that the device did not do the work."""
    jobs = copy.deepcopy(agg["jobs"])
    r = jobs[0]["rollup"]
    assert uv.job_failure(r, agg["sizes"], on_chip=False) is None
    assert "did not run on a chip" in uv.job_failure(r, agg["sizes"], True)
    r["counters"][BACKEND][counter_] = value
    jobs[0]["failure"] = uv.job_failure(r, agg["sizes"], on_chip=False)
    assert why in jobs[0]["failure"]
    correct, checks = _verdict(agg, jobs)
    assert correct is False and checks["jobs_unsound"]["value"] == 1


@pytest.mark.parametrize("name,why", [("MAP_INPUT_RECORDS", "MAP_INPUT"),
                                      ("REDUCE_INPUT_RECORDS", "REDUCE")])
def test_rows_lost_on_the_way_are_unsound(agg, name, why):
    r = copy.deepcopy(agg["jobs"][0]["rollup"])
    r["counters"]["tpumr.TaskCounter"][name] -= 1
    assert why in uv.job_failure(r, agg["sizes"], on_chip=False)
    on_chip = copy.deepcopy(agg["jobs"][0]["rollup"])
    on_chip["counters"][BACKEND]["DEVICE_SORT_ON_ACCEL"] = 1
    assert "reduce did not run" in uv.job_failure(on_chip, agg["sizes"],
                                                  True)
    on_chip["counters"][BACKEND]["DEVICE_REDUCE_ON_ACCEL"] = 1
    assert uv.job_failure(on_chip, agg["sizes"], True) is None


# ------------------------------------------------- the family's pieces


def test_the_table_is_a_function_of_the_seed_at_the_declared_widths(
        tmp_path):
    sizes = {"rows": 6_000, "groups": 900, "files": 3}
    a = uv.make_input(sizes, SEED, str(tmp_path / "a"))["table"]
    b = uv.make_input(sizes, SEED, str(tmp_path / "b"))["table"]
    c = uv.make_input(sizes, SEED + 1, str(tmp_path / "c"))["table"]
    names = sorted(os.listdir(a))
    assert len(names) == 3 == len(os.listdir(b))
    read = [open(os.path.join(d, n), "rb").read()
            for d in (a, b, c) for n in names]
    assert read[:3] == read[3:6] and read[:3] != read[6:]
    lines = b"".join(read[:3]).split(b"\n")[:-1]
    assert len(lines) == 6_000
    widths = (16, 100, 10, 6, 64, 3, 6, 32, 5)
    fields = [line.split(b"|") for line in lines]
    assert all(len(f) == 9 for f in fields)
    for col, width in enumerate(widths):
        lens = {len(f[col]) for f in fields}
        assert 1 <= min(lens) and max(lens) <= width, col
    assert len({len(f[1]) for f in fields}) > 50    # rows vary in width
    assert 120 < np.mean([len(x) + 1 for x in lines]) < 160
    g, revenue, _ = uv.table_columns(sizes, SEED)
    keys = uv.group_keys(SEED, 900)[0]
    assert len({bytes(k) for k in keys}) == 900
    for i in (0, 1999, 2000, 5999):     # files in order, rows in order
        assert fields[i][0].ljust(16, b"\0") == bytes(keys[g[i]])
        assert np.float32(float(fields[i][3])) == revenue[i]
    assert revenue.min() >= 1.0 and revenue.max() < 1000.0
    # the reference is numpy.unique on the 16-byte keys, sums in float64
    want_keys, want_sums = uv.reference(sizes, SEED)
    uniq, inverse = np.unique(uv._as_s16(keys[g]), return_inverse=True)
    assert (uv._as_s16(want_keys) == uniq).all()
    assert want_sums == pytest.approx(np.bincount(
        inverse, weights=revenue.astype(np.float64)), rel=1e-15)


def _container(rows, how):
    from tpumr.io import sequencefile
    buf = io.BytesIO()
    w = sequencefile.Writer(buf, codec="zlib" if how == "zlib" else "none")
    if how == "rows":
        w.append_fixed_rows(rows, uv.KEY_LEN)
    else:
        for r in rows:
            w.append(bytes(r[:uv.KEY_LEN]), bytes(r[uv.KEY_LEN:]))
    w.close()
    return buf.getvalue()


@pytest.mark.parametrize("how", ["rows", "scalar", "truncated", "zlib",
                                 "magic", "width"])
def test_the_output_is_parsed_without_the_programs_reader(how):
    """``parse_container`` reads the container from its description alone
    and agrees with what the program's writer wrote, bulk or one by one;
    anything else gives no records, and no records compare as all
    wrong."""
    rows = np.random.default_rng(SEED).integers(
        0, 256, size=(2_500, 20), dtype=np.uint8)
    if how in ("rows", "scalar"):
        got = uv.parse_container(_container(rows, how))
        assert got.shape == rows.shape and (got == rows).all()
        return
    if how == "width":      # 17-byte keys: not this job's records
        buf = _container(np.concatenate([rows[:, :1], rows], 1), "scalar")
    elif how == "truncated":
        buf = _container(rows, "rows")[:-5]
    elif how == "magic":
        buf = b"XSEQ" + _container(rows, "rows")[4:]
    else:
        buf = _container(rows, "zlib")
    assert uv.parse_container(buf) is None
    want = (rows[:, :16], np.ones(2_500))
    assert uv.compare(None, want) == (2_500, float("inf"))


def test_the_family_imports_nothing_of_the_program():
    with open(uv.__file__) as f:
        src = f.read()
    assert "import tpumr" not in src and "from tpumr" not in src
    assert "import jax" not in src


def test_the_control_rounds_to_bfloat16_ties_to_even():
    x = np.array([1.0, 1.00390625, 1.01171875, 999.99, 3.0e38], np.float32)
    got = uv._bf16(x)
    assert got.tolist()[:3] == [1.0, 1.0, 1.015625]    # ties go to even
    assert abs(got[3] - 999.99) / 999.99 < 2 ** -8
    assert (got.view(np.uint32) & 0xFFFF == 0).all()


# ------------------------------------------------------ the configuration


def test_the_configuration_is_what_the_issue_says():
    cfg = run._load_json("configs", CONFIG + ".json")
    assert cfg["family"] == "uservisits_agg"
    assert cfg["architecture"] is None
    assert cfg["sizes"] == {"rows": 10_000_000, "groups": 2_500_000,
                            "files": 8}
    assert cfg["reduced"] == ["rows"]
    assert cfg["published"]["rows"] == 155_000_000
    assert cfg["published"]["groups"] == cfg["sizes"]["groups"]
    for word in ("Pavlo", "SIGMOD 2009", "4.3.3", "Aggregation",
                 "UserVisits", "2.5M groups", "HiBench"):
        assert word in cfg["source"], word
    for col in ("sourceIP VARCHAR(16)", "destURL VARCHAR(100)",
                "visitDate DATE", "adRevenue FLOAT", "userAgent VARCHAR(64)",
                "countryCode VARCHAR(3)", "languageCode VARCHAR(6)",
                "searchWord VARCHAR(32)", "duration INT"):
        assert col in cfg["kept_as_published"]["columns"], col
    assert set(cfg["assumed"]) >= {"sourceIP", "unread_columns",
                                   "adRevenue", "delimiter", "files",
                                   "reduces", "combiner", "storage"}
    assert set(cfg["rehearse"]) == {"rows", "groups"}   # no width is cut
    assert cfg["limits"]["groups_wrong"] == 0
    assert set(cfg["limits_why"]) == set(cfg["limits"])
    assert cfg["cluster"]["tracker_defs"] == run._load_json(
        "configs", "terasort-10m.json")["cluster"]["tracker_defs"]
    traffic = run._load_json("traffic", "reaggregate.json")
    assert traffic["warmup_jobs"] == 0
    assert traffic["job"]["args"] == ["-r", "4", "--device-shuffle"]
    bm = run.load_benchmark()
    entry = next(w for w in bm["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "reaggregate", 1)


def test_the_limit_lies_between_its_readings_with_room_on_both_sides():
    """The readings ``limits_why`` states: the limit is at least 5 times
    over the highest lower one and 5 times under the least upper one."""
    cfg = run._load_json("configs", CONFIG + ".json")
    readings = cfg["limit_readings"]["sum_gap"]
    limit = cfg["limits"]["sum_gap"]
    assert 5 * max(readings["lower"]) <= limit
    assert 5 * limit <= min(min(v) for v in readings["upper"].values())
    assert set(readings["upper"]) >= {
        "control_bfloat16", "a_row_left_out", "a_row_counted_twice",
        "two_sums_swapped", "a_maps_output_lost"}


# ------------------------------------------------------------ the readers


def _empty_obs():
    cfg = run._load_json("configs", CONFIG + ".json")
    return {"jobs": [], "window_s": 1.0, "spans": None, "trace": None,
            "peak": None, "window_compiles": None, "sizes": cfg["sizes"]}


def test_the_cell_adds_the_metrics_the_issue_names():
    names = _new_metrics()
    assert len(names) == len(set(names)) == 18
    assert set(names) == NAMED_BY_THE_ISSUE
    for m in run.load_benchmark()["per_layer"]:
        if m["name"] not in NAMED_BY_THE_ISSUE:     # none was appended to
            assert CELL not in m["workloads"]
        else:
            assert m["moves"] == "rows_per_s"


@pytest.mark.parametrize("metric", sorted(NAMED_BY_THE_ISSUE))
def test_an_agg_metric_resolves_to_a_reader_that_reads_none_from_nothing(
        metric):
    assert _reader(metric)(_empty_obs()) is None     # None, never 0


def _job(counters: dict) -> dict:
    return {"rollup": {"counters": {BACKEND: counters}}}


def test_the_span_and_counter_readers_read_what_they_name():
    spans = a_round(0.0, "job_1") + a_round(50.0, "job_2", lag=0.2)
    # job_1's last SUCCEEDED map is done at 6.0 + 0.6; a killed attempt's
    # report after it does not lengthen the phase
    spans.append(span("task:done", 9.0, 9.0, job="job_1", backend="cpu",
                      attempt_id="x", is_map=True, state="KILLED"))
    obs = dict(_empty_obs(), spans=spans)
    assert _reader("agg.map_phase_s")(obs) == pytest.approx(
        (6.6 + 6.2) / 2)
    assert _reader("agg.cpu_map_mean_s")(obs) == pytest.approx(
        (2.5 + 3.0 + 2.8 + 2.9) / 4)
    # the kernel's span lies under dshuffle:device, a job's are summed
    dev = span("dshuffle:device", 1.0, 3.0, job="job_1")
    obs = dict(_empty_obs(), spans=[
        dev, span("dshuffle:reduce", 2.0, 2.75, parent=dev, job="job_1"),
        span("dshuffle:reduce", 60.0, 60.25, job="job_2"),
        span("dshuffle:sort", 1.0, 2.0, parent=dev, job="job_1")])
    assert _reader("agg.reduce_s")(obs) == pytest.approx((0.75 + 0.25) / 2)
    read = _reader("agg.groups_bytes_back")
    assert read(dict(_empty_obs(), jobs=[
        _job({"TPU_REDUCE_BYTES_BACK": 50}),
        _job({"TPU_REDUCE_BYTES_BACK": 70})])) == 60
    # a program that has no such counter (the parent) gives None, not 0
    assert read(dict(_empty_obs(), jobs=[_job({"X": 1})])) is None


def _traced(events, jobs):
    return dict(_empty_obs(), jobs=jobs,
                peak=run.load_peak("TPU v5 lite"),
                trace={"devices": {"/device:TPU:0": {
                    "XLA Modules": list(events)}}, "lo": 0.0, "hi": 1e12})


def test_the_rooflines_count_the_jobs_rows_and_groups_never_the_bucket():
    rows, groups = 10_000_000, 2_450_000
    sort_w, sum_w = work_agg.sort(rows), work_agg.segment_sum(rows, groups)
    assert sort_w == work.argsort(rows, 4) == {
        "bytes": rows * 20, "flops": 0}
    assert sum_w == {"bytes": rows * 24 + groups * 20, "flops": 0}
    jobs = [_job({"TPU_REDUCE_GROUPS": groups})] * 2
    events = []
    for j in range(2):      # a job: the sort, the kernel, the pieces
        t = 1e9 * (10 * j + 1)
        events += [("jit__sort_words(3)", t, 140e6),
                   ("jit__segment_sum(7)", t + 6e8, 40e6),
                   ("jit__piece(8)", t + 7e8, 1e6)]
    events.append(("jit__argsort(4)", 50e9, 70e6))      # another job's
    obs = _traced(events, jobs)
    sort = _reader("agg_sort_roofline")(obs)
    seg = _reader("agg_segment_sum_roofline")(obs)
    assert sort == pytest.approx(100 * (rows * 20 / 819e9) / 0.140)
    assert seg == pytest.approx(
        100 * ((rows * 24 + groups * 20) / 819e9) / 0.040)
    assert 0 < sort < 100 and 0 < seg < 100
    # without the kernel's run in the window, or its counter: None
    no_kernel = [e for e in events if "segment_sum" not in e[0]]
    assert _reader("agg_sort_roofline")(_traced(no_kernel, jobs)) is None
    assert _reader("agg_segment_sum_roofline")(
        _traced(events, [_job({})])) is None
    assert _reader("agg_sort_roofline")(dict(obs, trace=None)) is None
