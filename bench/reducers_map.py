"""Readers of a map's own spans (found by ``run.find_reducer``, as
``reducers.py`` says): the read of its split. A program that has no such
span gives None: the metric is left out.
"""

from __future__ import annotations

from bench.reducers_spans import _len, _mean, _named, _spans


def _on_cpu(span: dict, by_id: "dict[str, dict]") -> bool:
    """Whether the task this span ran under (the nearest ``task:run`` or
    ``task:launch`` above it) ran on a CPU slot."""
    up = by_id.get(span["parent_span_id"])
    while up is not None and up["name"] not in ("task:run", "task:launch"):
        up = by_id.get(up["parent_span_id"])
    return up is not None and up.get("backend") == "cpu"


def map_read_s(obs: dict):
    """Mean over the window's CPU maps of the ``map:read`` span: a map's
    whole-split read, from the file to the RecordBatch its vectorized
    path works on (a SequenceFile split's blocks; a text split's lines)."""
    spans = _spans(obs)
    by_id = {s["span_id"]: s for s in spans}
    return _mean([_len(s) for s in _named(spans, "map:read")
                  if _on_cpu(s, by_id)])
