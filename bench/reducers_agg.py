"""Readers for the aggregation cell (found by ``run.find_reducer``, as
``reducers.py`` says): the reduce kernel's span and counter, the end of
the map phase, and the two device steps' rooflines. A program that has no
such span, counter or device program gives None: the metric is left out.
"""

from __future__ import annotations

from bench import work, work_agg, xplane
from bench.cluster import BACKEND
from bench.reducers_spans import _by_job, _len, _maps, _mean, _named, _spans

#: the device programs of the job's sort (a loop of single-key passes,
#: then the gather of the columns) and of its reduce kernel, as the trace
#: names them
SORT_PROGRAM = r"jit__sort_words"
REDUCE_PROGRAM = r"jit__segment_sum"


def agg_reduce_s(obs: dict):
    """Seconds under ``dshuffle:reduce`` (the kernel on the device until
    the groups are on the host, or its twin on the host) a job, averaged
    over the window's jobs that have the span."""
    per = [sum(_len(s) for s in _named(spans, "dshuffle:reduce"))
           for spans in _by_job(_spans(obs)).values()
           if _named(spans, "dshuffle:reduce")]
    return _mean(per)


def _counted(obs: dict, name: str) -> "list[int]":
    """This backend counter of every job of the window whose rollup has
    it (a program without the counter has none)."""
    got = [(j["rollup"]["counters"].get(BACKEND) or {}).get(name)
           for j in obs["jobs"] if j.get("rollup")]
    return [int(v) for v in got if v is not None]


def agg_groups_bytes_back(obs: dict):
    """``TPU_REDUCE_BYTES_BACK`` a job: what the device copied back of a
    kernel's groups."""
    return _mean(_counted(obs, "TPU_REDUCE_BYTES_BACK"))


def agg_map_phase_s(obs: dict):
    """The start of the ``job`` span to the last SUCCEEDED map's
    ``task:done``: how long the job's maps took as the master saw them."""
    phases = []
    for spans in _by_job(_spans(obs)).values():
        job = _named(spans, "job")
        done = [d["start"] for d in _maps(spans, "task:done")
                if d["attributes"].get("state") == "SUCCEEDED"]
        if job and done:
            phases.append(max(done) - job[0]["start"])
    return _mean(phases)


def agg_cpu_map_mean_s(obs: dict):
    """Mean ``task:launch`` of a map on a CPU slot."""
    return _mean([_len(s) for s in _maps(_spans(obs), "task:launch", "cpu")])


def _roofline(obs: dict, pattern: str, job_work: dict):
    """The least time for a job's work over the device time of the
    programs that did it, over the jobs whose reduce kernel ran in the
    window (one execution of the kernel's program a job)."""
    t = obs.get("trace")
    if not t or not obs.get("peak"):
        return None
    jobs = len(xplane.program_runs(t, REDUCE_PROGRAM, t["lo"], t["hi"]))
    runs = xplane.program_runs(t, pattern, t["lo"], t["hi"])
    if not jobs or not runs:
        return None
    return 100.0 * work.least_seconds(job_work, obs["peak"]) * jobs \
        / sum(runs)


def agg_sort_roofline(obs: dict):
    s = obs["sizes"]
    if "groups" not in s:
        return None
    return _roofline(obs, SORT_PROGRAM, work_agg.sort(s["rows"]))


def agg_segment_sum_roofline(obs: dict):
    """Groups as the window's jobs counted them (``TPU_REDUCE_GROUPS``),
    not the configuration's most."""
    s = obs["sizes"]
    counted = [v for v in _counted(obs, "TPU_REDUCE_GROUPS") if v]
    if "groups" not in s or not counted:
        return None
    return _roofline(obs, REDUCE_PROGRAM, work_agg.segment_sum(
        s["rows"], max(counted)))
