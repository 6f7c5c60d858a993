"""The benchmark's loader, names and window arithmetic (no cluster)."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return run.load_benchmark()


def test_every_cell_loads_by_name(bm):
    for w in bm["workloads"]:
        cell = run.load_cell(bm, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        for fn in ("make_input", "rows_per_job", "Session", "job_failure",
                   "check"):
            assert hasattr(cell["family"], fn), (w["name"], fn)
        assert set(cell["config"]["limits"])
        assert cell["traffic"]["warmup_jobs"] >= 0


def test_every_layer_metric_has_a_file_and_a_reader(bm):
    for w in bm["workloads"]:
        readers = run.layer_readers(bm, w["name"])
        assert readers, w["name"]
        for m, reader in readers:
            assert callable(reader)
    for m in bm["per_layer"]:   # the entry is the one source of the rest
        spec = run._load_json("layer_metrics", m["name"] + ".json")
        assert list(spec) == ["reducer"], m["name"]


@pytest.mark.parametrize("what", ["workload", "config", "traffic",
                                  "family", "metric", "reader"])
def test_an_unknown_name_is_rejected(bm, what):
    fake = json.loads(json.dumps(bm))
    if what == "workload":
        with pytest.raises(KeyError):
            run.load_cell(bm, "no-such.cell")
        return
    if what in ("config", "traffic"):
        fake["workloads"][0][what] = "no-such"
        with pytest.raises(KeyError):
            run.load_cell(fake, fake["workloads"][0]["name"])
        return
    if what == "family":
        with pytest.raises(ImportError):
            __import__("importlib").import_module("bench.families.nosuch")
        return
    if what == "metric":
        fake["per_layer"].append(dict(fake["per_layer"][0], name="no.such"))
        with pytest.raises(KeyError):
            run.layer_readers(fake, fake["per_layer"][0]["workloads"][0])
        return
    with pytest.raises(KeyError):
        run.find_reducer("no_such_reader")


def test_names_and_units_use_the_allowed_characters(bm):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bm[kind]:
            assert NAME.match(e["name"]), e["name"]
            assert "/" not in e["name"] and "%" not in e["name"]
            names.append((kind in ("end_to_end", "per_layer"), e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in bm["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in bm["configs"]:
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bm["workloads"]}
    layers = set()
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.add(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
    assert all(0 < len(x) <= 200 and "\n" not in x for x in layers)
    assert 1 <= bm["run_seconds"] <= 51


def _jobs(seconds, failures=()):
    return [{"client_s": s, "failure": ("x" if i in failures else None)}
            for i, s in enumerate(seconds)]


def test_window_arithmetic_counts_all_the_work_over_all_the_time():
    m = run.window_metrics(_jobs([15.0, 15.0, 15.0]), 100, 45.0)
    assert m["rows_per_s"] == pytest.approx(300 / 45.0)
    assert m["job_max_s"] == 15.0
    # a stalled job lengthens the window and lowers rows_per_s
    stalled = run.window_metrics(_jobs([15.0, 40.0, 15.0]), 100, 70.0)
    assert stalled["rows_per_s"] < m["rows_per_s"]
    assert stalled["job_max_s"] == 40.0
    # a job that counts as failed gives no rows, and its time still counts
    failed = run.window_metrics(_jobs([15.0, 15.0, 15.0], {1}), 100, 45.0)
    assert failed["rows_per_s"] == pytest.approx(200 / 45.0)


def test_judge_needs_every_number_within_its_limit():
    ok = {"a": {"value": 0.001, "limit": 0.005},
          "n": {"value": 3, "limit": None},
          "jobs_unsound": {"value": 0, "limit": 0}}
    assert run.judge(ok)
    assert not run.judge(dict(ok, a={"value": 0.006, "limit": 0.005}))
    assert not run.judge(dict(ok, a={"value": float("inf"), "limit": 1.0}))
    assert not run.judge(dict(ok, a={"value": float("nan"), "limit": 1.0}))
    assert not run.judge(dict(ok, jobs_unsound={"value": 1, "limit": 0}))
    assert not run.judge({"n": {"value": 3, "limit": None}})


def test_the_last_line_has_exactly_the_contracts_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    checks = {"a": {"value": 0, "limit": 0}}
    line = run.result_line(True, _jobs([1.0, 2.0], {1}),
                           {"rows_per_s": 1.5}, {"rows_per_s": "rows/s"},
                           device, checks)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["attempted"] == 2 and line["failed"] == 1
    assert line["metrics"] == {"rows_per_s": {"value": 1.5,
                                              "unit": "rows/s"}}
    traced = run.result_line(True, _jobs([1.0]), {}, {}, device, checks,
                             {"device_ops": [], "idle_gaps": []})
    assert list(traced)[-2:] == ["breakdown", "checks"]
    json.dumps(traced)


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    peak = run.load_peak("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        run.load_peak("TPU v9 imaginary")


# ---------------------------------------------- the families' own pieces


def _container(rows, how):
    import io

    from tpumr.io import sequencefile
    buf = io.BytesIO()
    w = sequencefile.Writer(buf, codec="zlib" if how == "zlib" else "none")
    if how == "rows":
        w.append_fixed_rows(rows, 10)
    else:
        for r in rows:
            w.append(bytes(r[:10]), bytes(r[10:]))
    w.close()
    return buf.getvalue()


@pytest.mark.parametrize("how", ["rows", "scalar"])
def test_the_sort_output_is_parsed_without_the_programs_reader(how):
    """``parse_container`` reads the container from its description alone
    and agrees with what the program's writer wrote, bulk or one by one."""
    from bench.families import terasort
    rows = terasort.gen_rows(2_400_000_011, 1, 500, 4321)
    got = terasort.parse_container(_container(rows, how))
    assert got.shape == rows.shape and (got == rows).all()


@pytest.mark.parametrize("how", ["truncated", "zlib", "magic", "width"])
def test_a_container_that_is_anything_else_gives_no_rows(how):
    import numpy as np

    from bench.families import terasort
    rows = terasort.gen_rows(7, 0, 0, 2500)
    if how == "width":      # 11-byte keys: not the sort's rows
        buf = _container(np.concatenate([rows[:, :1], rows], 1), "scalar")
    elif how == "truncated":
        buf = _container(rows, "rows")[:-5]
    elif how == "magic":
        buf = b"XSEQ" + _container(rows, "rows")[4:]
    else:
        buf = _container(rows, "zlib")
    assert terasort.parse_container(buf) is None
    assert terasort.rows_wrong(None, np.zeros(2500, np.uint64)) == 2500


def test_the_points_drift_so_that_no_half_stands_for_the_whole(tmp_path):
    import numpy as np

    from bench.families import kmeans
    sizes = {"rows": 32000, "d": 16, "k": 16, "split_rows": 8000,
             "drift": 0.5}
    periods = kmeans.drift_periods(32000, 8000, 16)
    assert periods[0] == 32000 and list(periods[-6:]) == [
        2048, 512, 128, 32, 8, 2] and periods[-7] == 8000
    assert all(a > b for a, b in zip(periods, periods[1:]))
    full = kmeans.drift_periods(100_000_000, 4_000_000, 16)
    assert full[0] == 100_000_000 and full[4] == 4_000_000
    assert full[5] == 2 ** 21 and full[-1] == 2
    path = kmeans.make_input(sizes, 2_400_000_011, str(tmp_path))["points"]
    x = np.load(path)
    assert x.shape == (32000, 16) and x.dtype == np.float32
    again = kmeans.make_input(sizes, 2_400_000_011, str(tmp_path))["points"]
    assert (np.load(again) == x).all()      # the same seed, the same input
    # halves of the set, of a split and of the rows differ in their means
    # by far more than the draw's noise (about 0.01 at 16,000 rows)
    assert abs(x[:16000, 0].mean() - x[16000:, 0].mean()) > 0.3
    assert abs(x[0::2, 15].mean() - x[1::2, 15].mean()) > 0.3
    first = np.concatenate([x[a:a + 4000] for a in range(0, 32000, 8000)])
    assert np.abs(first.mean(0) - x.mean(0)).max() > 0.05
    assert np.abs(x.mean(0)).max() < 0.15 and 0.95 < x.std() < 1.15


def test_the_reference_follows_the_precision_of_each_split(tmp_path):
    """``chip`` (both dots in one bfloat16 pass) lies off float32 by a
    little, the control in bfloat16 by far more; a list of modes mixes
    them split by split."""
    import numpy as np

    from bench.families import kmeans
    sizes = {"rows": 64000, "d": 16, "k": 16, "split_rows": 8000,
             "drift": 0.5}
    path = kmeans.make_input(sizes, 5, str(tmp_path))["points"]
    given = [np.load(path)[:16]]
    f32, chip, low = (kmeans.reference_rounds(path, sizes, given, m)[0]
                      for m in ("f32", "chip", "bf16"))
    mixed = kmeans.reference_rounds(
        path, sizes, given, [["chip", "f32"] * 4])[0]
    gap = kmeans.centroid_gap
    assert 0 < gap(chip, f32) < gap(low, f32)
    assert 0 < gap(mixed, f32) and 0 < gap(mixed, chip)
    assert gap(kmeans.reference_rounds(path, sizes, given,
                                       [["f32"] * 8])[0], f32) == 0
    # a job's placement becomes the modes; one the rollup denies does not
    job = {"chip_maps": [1, 3], "rollup": {"finished_tpu_maps": 2}}
    assert kmeans._modes(job, 4) == ["f32", "chip", "f32", "chip"]
    assert kmeans._modes(dict(job, chip_maps=[1]), 4) is None
    assert kmeans._modes(dict(job, chip_maps=None), 4) is None
    assert kmeans._modes(dict(job, chip_maps=[]), 2) == ["f32", "f32"]


def test_where_each_map_ran_is_read_from_the_masters_event_log(tmp_path):
    from bench.cluster import Cluster
    c = Cluster(str(tmp_path), [], [])
    os.makedirs(c.history)

    def ev(i, on_tpu, kind="TASK_FINISHED", is_map=True):
        return json.dumps({"event": kind, "is_map": is_map,
                           "attempt_id": f"attempt_1_0001_m_{i:06d}_0",
                           "run_on_tpu": on_tpu}) + "\n"
    with open(os.path.join(c.history, "job_1_0001.jsonl"), "w") as f:
        f.write(ev(0, True, "TASK_STARTED") + ev(0, True) + ev(1, False)
                + ev(2, True) + ev(0, False, is_map=False))
    assert c.tpu_maps("job_1_0001") == [0, 2]
    with open(os.path.join(c.history, "job_1_0002.jsonl"), "w") as f:
        f.write(ev(0, True) + ev(0, False))     # one task finished twice
    assert c.tpu_maps("job_1_0002") is None
    assert c.tpu_maps("job_1_0003") is None     # no log
