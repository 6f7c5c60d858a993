"""The reader of a map's own read span (bench/reducers_map.py), on
hand-built span lists: the mean over CPU maps, and None where the span is
not there (as on a program that lacks it)."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench import reducers_map as rm  # noqa: E402
from bench import run  # noqa: E402

_ids = iter(range(1, 1 << 30))


def span(name, start, end, parent=None, backend="", **attrs):
    return {"name": name, "span_id": f"m{next(_ids)}",
            "parent_span_id": parent["span_id"] if parent else "",
            "backend": backend, "start": float(start), "end": float(end),
            "attributes": attrs, "job_id": "job_1"}


def a_map(t0, read_s, backend="cpu", thread=True):
    """A map's ``task:launch`` (and, in the tracker's process, its
    ``task:run``) with a ``map:read`` of ``read_s`` seconds under it."""
    launch = span("task:launch", t0, t0 + 3, backend=backend, is_map=True)
    run_ = span("task:run", t0 + 0.1, t0 + 2.9, parent=launch,
                backend=backend) if thread else launch
    read = span("map:read", t0 + 0.2, t0 + 0.2 + read_s, parent=run_,
                records=617000, form="strided")
    return [launch, run_, read] if thread else [launch, read]


def test_the_mean_read_of_the_cpu_maps():
    spans = (a_map(0, 0.4) + a_map(1, 0.8) + a_map(2, 0.3, thread=False)
             + a_map(3, 9.0, backend="tpu"))
    assert abs(rm.map_read_s({"spans": spans}) - 0.5) < 1e-9


def test_nothing_to_read_reads_none():
    assert rm.map_read_s({"spans": None}) is None
    assert rm.map_read_s({"spans": []}) is None
    launch = span("task:launch", 0, 3, backend="cpu", is_map=True)
    assert rm.map_read_s({"spans": [launch]}) is None   # the parent's maps
    orphan = span("map:read", 0, 1)
    assert rm.map_read_s({"spans": [orphan]}) is None
    open_read = dict(a_map(0, 0.5)[2], end=0.0)
    assert rm.map_read_s({"spans": [open_read]}) is None


def test_the_metric_names_this_reader_and_the_sort_cell():
    bm = run.load_benchmark()
    entry = next(m for m in bm["per_layer"] if m["name"] == "map.read_s")
    assert run.find_reducer(run._load_json(
        "layer_metrics", "map.read_s.json")["reducer"]) is rm.map_read_s
    assert entry["workloads"] == ["terasort-10m.device-shuffle"]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "tracker", "rows_per_s", "program_span")
