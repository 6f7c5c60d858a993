"""The trace reduction on hand-built events, and the work counts against
hand-worked values."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import reducers, work, xplane  # noqa: E402

S = 1e9     # the trace's times are nanoseconds


def _trace():
    """One device, a 10 s window from 1 s to 11 s: two runs of a program
    (0.5 s and 0.3 s) made of overlapping operations, another program,
    and an operation that straddles the window's end."""
    ops = [("fusion.1", 2.0 * S, 0.4 * S), ("copy.2", 2.3 * S, 0.2 * S),
           ("fusion.1", 5.0 * S, 0.3 * S), ("sort.3", 7.0 * S, 1.0 * S),
           ("fusion.1", 10.8 * S, 0.5 * S), ("early", 0.2 * S, 0.1 * S)]
    modules = [("jit__assign_and_partials_jax(1)", 2.0 * S, 0.5 * S),
               ("jit__assign_and_partials_jax(1)", 5.0 * S, 0.3 * S),
               ("jit__argsort(2)", 7.0 * S, 1.0 * S),
               ("jit__assign_and_partials_jax(1)", 10.8 * S, 0.5 * S)]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": modules}},
            "lo": 1.0 * S, "hi": 11.0 * S}


def test_union_merges_overlapping_intervals():
    merged = xplane.union([("a", 0, 10), ("b", 5, 10), ("c", 30, 5),
                           ("d", 31, 1)])
    assert merged == [(0, 15), (30, 35)]
    assert xplane.busy_ns(merged) == 20


def test_busy_seconds_and_idle_share_over_the_window():
    t = _trace()
    # 2.0-2.5, 5.0-5.3, 7.0-8.0 and the 0.2 s of the last op inside
    assert xplane.busy_seconds(t, t["lo"], t["hi"]) == pytest.approx(2.0)
    assert reducers.device_idle_share({"trace": t}) == pytest.approx(80.0)
    assert xplane.busy_seconds({"devices": {}}, 0, 1) is None
    assert reducers.device_idle_share({"trace": None}) is None


def test_program_runs_count_whole_executions_inside_the_window():
    t = _trace()
    runs = xplane.program_runs(t, r"_assign_and_partials_jax", t["lo"],
                               t["hi"])
    assert sorted(runs) == pytest.approx([0.3, 0.5])
    assert xplane.program_runs(t, r"_argsort", t["lo"], t["hi"]) == \
        pytest.approx([1.0])
    assert xplane.program_runs(t, r"nothing", t["lo"], t["hi"]) == []


def test_top_ops_and_longest_gaps():
    t = _trace()
    top = xplane.top_ops(t, t["lo"], t["hi"], n=2)
    assert top[0][0] == "sort.3" and top[0][1] == pytest.approx(1.0)
    assert top[1][0] == "fusion.1" and top[1][1] == pytest.approx(0.9)
    ops = xplane.device_ops(t, t["lo"], t["hi"])["/device:TPU:0"]
    gaps = xplane.longest_gaps(xplane.union(ops), t["lo"], t["hi"], n=2)
    assert [(round(a / S, 3), round(b / S, 3)) for a, b in gaps] == \
        [(8.0, 10.8), (2.5, 5.0)]
    spans = [("heartbeat", 8.1 * S, 8.2 * S), ("task:run", 8.5 * S, 10.9 * S),
             ("tpu:stage", 3.0 * S, 3.1 * S)]
    assert xplane.label_gap(gaps[0], spans) == "task:run"
    assert xplane.label_gap(gaps[1], spans) == "tpu:stage"
    assert xplane.label_gap((20 * S, 21 * S), spans) == "none"


def test_work_counts_against_hand_worked_values():
    # one split: 4M x 16 float32, k = 16
    w = work.kmeans_assign(4_000_000, 16, 16)
    assert w["bytes"] == 256_000_000 + 2 * 1024 + 64
    assert w["flops"] == 4 * 4_000_000 * 16 * 16 == 4_096_000_000
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # bound by memory: 256 MB at 819 GB/s is 0.3126 ms; the operations
    # would take 0.0208 ms
    assert work.least_seconds(w, peak) == pytest.approx(3.1258e-4, rel=1e-3)
    s = work.argsort(10_000_000, 3)
    assert s == {"bytes": 160_000_000, "flops": 0}
    assert work.least_seconds(s, peak) == pytest.approx(1.9536e-4, rel=1e-3)


def test_a_roofline_share_is_the_least_time_over_the_measured_time():
    t = _trace()
    obs = {"trace": t, "peak": {"flops_bf16": 197e12,
                                "hbm_bytes_per_s": 819e9},
           "sizes": {"rows": 8_000_000, "split_rows": 4_000_000, "d": 16,
                     "k": 16}}
    share = reducers.kmeans_assign_roofline(obs)
    assert share == pytest.approx(100 * 2 * 3.1258e-4 / 0.8, rel=1e-3)
    assert reducers.argsort_roofline(obs) is None   # not this family
    obs["sizes"] = {"rows": 10_000_000, "maps": 8}
    assert reducers.argsort_roofline(obs) == pytest.approx(
        100 * 1.9536e-4 / 1.0, rel=1e-3)
    # nothing to read: nothing returned, never a 0
    assert reducers.argsort_roofline(dict(obs, trace=None)) is None
    empty = dict(t, devices={"/device:TPU:0": {"XLA Ops": [],
                                              "XLA Modules": []}})
    assert reducers.argsort_roofline(dict(obs, trace=empty)) is None


def test_rollup_readers_on_a_synthetic_window():
    def rollup(wall, tpu, cpu, staged, red):
        return {"wall_time": wall, "num_maps": tpu + cpu,
                "finished_tpu_maps": tpu,
                "map_latency_tpu": {"count": tpu, "mean": 0.5},
                "map_latency_cpu": {"count": cpu, "mean": 3.0} if cpu else {},
                "reduce_latency": {"max": red, "count": 1},
                "counters": {"tpumr.BackendCounter":
                             {"TPU_DEVICE_BYTES_STAGED": staged}}}
    obs = {"jobs": [{"rollup": rollup(14.0, 13, 12, 100, 2.0)},
                    {"rollup": rollup(15.0, 10, 15, 300, 4.0)}],
           "window_s": 30.0,
           "spans": [{"name": "tpu:stage", "start": 1.0, "end": 1.5},
                     {"name": "tpu:stage", "start": 2.0, "end": 2.1},
                     {"name": "task:run", "start": 0.0, "end": 9.0}],
           "window_compiles": 0}
    assert reducers.outside_job_s(obs) == pytest.approx(0.5)
    assert reducers.tpu_map_share(obs) == pytest.approx(46.0)
    assert reducers.tpu_map_mean_s(obs) == pytest.approx(0.5)
    assert reducers.cpu_map_mean_s(obs) == pytest.approx(3.0)
    assert reducers.staged_bytes_per_job(obs) == pytest.approx(200.0)
    assert reducers.stage_s_per_map(obs) == pytest.approx(0.3)
    assert reducers.gang_reduce_s(obs) == pytest.approx(3.0)
    assert reducers.window_compiles(obs) == 0
    assert reducers.stage_s_per_map(dict(obs, spans=None)) is None
    assert reducers.tpu_map_share({"jobs": []}) is None
