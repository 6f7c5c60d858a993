"""Readers for the PageRank cell (found by ``run.find_reducer``, as
``reducers.py`` says): the wait from a job's submit to its map's launch,
the reduce's update of the ranks, and the map kernel's roofline. A program that has no such span or device program gives
None: the metric is left out.
"""

from __future__ import annotations

from bench import work, work_pagerank, xplane
from bench.reducers_spans import _by_job, _len, _maps, _mean, _named, _spans

#: the map kernel's device program, as the trace names it
MAP_PROGRAM = r"jit__pagerank_map"


def pagerank_launch_wait_s(obs: dict):
    """Seconds from a job's submit (the start of its ``job`` span) to the
    start of its first map's ``task:launch``: the wait for the tracker's
    beat that takes the map, averaged over the window's jobs."""
    waits = []
    for spans in _by_job(_spans(obs)).values():
        job, runs = _named(spans, "job"), _maps(spans, "task:launch")
        if job and runs:
            waits.append(min(s["start"] for s in runs) - job[0]["start"])
    return _mean(waits)


def pagerank_update_s(obs: dict):
    """Seconds under ``pagerank:update`` (the reduce putting the ranges
    together and writing the next round's state) a job, averaged over the
    window's jobs that have the span."""
    per = [sum(_len(s) for s in _named(spans, "pagerank:update"))
           for spans in _by_job(_spans(obs)).values()
           if _named(spans, "pagerank:update")]
    return _mean(per)


def pagerank_map_roofline(obs: dict):
    """The least time of the window's chip maps over the device time of
    the map program: each ``tpu:execute`` of a PageRank map names its
    ``arcs`` and ``vertices``, each counts at ``work_pagerank``'s bytes
    over the HBM peak, and every execution of the program in the window
    at their mean (the splits hold about equal arcs)."""
    t = obs.get("trace")
    if not t or not obs.get("peak"):
        return None
    maps = [s["attributes"] for s in _named(_spans(obs), "tpu:execute")
            if s["attributes"].get("arcs")]
    runs = xplane.program_runs(t, MAP_PROGRAM, t["lo"], t["hi"])
    if not maps or not runs:
        return None
    least = _mean([work.least_seconds(work_pagerank.pagerank_map(
        int(a["arcs"]), int(a["vertices"])), obs["peak"]) for a in maps])
    return 100.0 * least * len(runs) / sum(runs)
