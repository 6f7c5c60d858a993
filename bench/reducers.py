"""One reader per per-layer metric, found by the name its
``layer_metrics/<metric>.json`` gives under ``reducer``. Each takes the
run's observations (``obs``) and returns the metric's value, or None where
it finds nothing to read: the harness then leaves the metric out. A later
PR adds readers in a module of its own, ``bench/reducers_<something>.py``.

``obs``: ``jobs`` (the window's jobs, each with its client seconds and the
master's ``rollup``), ``window_s``, ``spans`` (the program's own, traced
run only), ``trace`` (``xplane.read`` of the profiler's trace, with
``lo``/``hi``, the window inside it in ns), ``peak`` (the device's row of
``peaks.json``), ``sizes``, ``window_compiles``.
"""

from __future__ import annotations

from bench import work, xplane
from bench.cluster import BACKEND, counter


def _rollups(obs: dict) -> "list[dict]":
    return [j["rollup"] for j in obs["jobs"] if j.get("rollup")]


def outside_job_s(obs: dict):
    """Window seconds outside any job's master-side wall time, per job:
    submit, polling, and the client's work between jobs."""
    rs = _rollups(obs)
    if not rs or len(rs) != len(obs["jobs"]):
        return None
    return (obs["window_s"] - sum(r["wall_time"] for r in rs)) / len(rs)


def tpu_map_share(obs: dict):
    rs = _rollups(obs)
    maps = sum(r["num_maps"] for r in rs)
    return 100.0 * sum(r["finished_tpu_maps"] for r in rs) / maps \
        if maps else None


def _weighted_mean(obs: dict, key: str):
    n = sum(r[key].get("count", 0) for r in _rollups(obs))
    if not n:
        return None
    return sum(r[key]["mean"] * r[key]["count"]
               for r in _rollups(obs) if r[key]) / n


def tpu_map_mean_s(obs: dict):
    return _weighted_mean(obs, "map_latency_tpu")


def cpu_map_mean_s(obs: dict):
    return _weighted_mean(obs, "map_latency_cpu")


def staged_bytes_per_job(obs: dict):
    rs = _rollups(obs)
    if not rs:
        return None
    return sum(counter(r, BACKEND, "TPU_DEVICE_BYTES_STAGED")
               for r in rs) / len(rs)


def stage_s_per_map(obs: dict):
    stage = [s for s in obs.get("spans") or [] if s.get("name") == "tpu:stage"
             and s.get("end")]
    if not stage:
        return None
    return sum(s["end"] - s["start"] for s in stage) / len(stage)


def gang_reduce_s(obs: dict):
    """The slowest reduce of each job (host copy in, device sort, write),
    averaged over the window's jobs."""
    mx = [r["reduce_latency"]["max"] for r in _rollups(obs)
          if r.get("reduce_latency")]
    return sum(mx) / len(mx) if mx else None


def _roofline(obs: dict, pattern: str, per_run_work) -> "float | None":
    t = obs.get("trace")
    if not t or not obs.get("peak"):
        return None
    runs = xplane.program_runs(t, pattern, t["lo"], t["hi"])
    if not runs:
        return None
    least = work.least_seconds(per_run_work, obs["peak"])
    return 100.0 * least * len(runs) / sum(runs)


def kmeans_assign_roofline(obs: dict):
    """Every execution of the assign-and-partials program in the window
    works on one split."""
    s = obs["sizes"]
    if "split_rows" not in s:
        return None
    return _roofline(obs, r"_assign_and_partials_jax",
                     work.kmeans_assign(s["split_rows"], s["d"], s["k"]))


def argsort_roofline(obs: dict):
    """Every execution of the argsort program in the window orders the
    job's rows (three uint32 words of a 10-byte key)."""
    s = obs["sizes"]
    if "maps" not in s:
        return None
    return _roofline(obs, r"_argsort", work.argsort(s["rows"], 3))


def device_idle_share(obs: dict):
    t = obs.get("trace")
    if not t:
        return None
    busy = xplane.busy_seconds(t, t["lo"], t["hi"])
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ((t["hi"] - t["lo"]) / 1e9))


def window_compiles(obs: dict):
    return obs.get("window_compiles")
