"""Readers of the program's own spans (``obs["spans"]``, traced run only):
the phases inside the gang reduce (``dshuffle`` and its children), when the
master learnt that a task ended (``task:done``) against when the tracker
ended it (``task:launch``), and how full the tracker's slots were. Found by
``run.find_reducer`` through ``layer_metrics/<metric>.json``, as
``reducers.py`` says.

A span is ``{name, span_id, parent_span_id, backend, start, end,
attributes, job_id}``; starts are wall-clock seconds, ``end - start`` is
the span's monotonic length. Every reader averages over the window's jobs
that have the spans it reads, and returns None where none has.
"""

from __future__ import annotations


def _spans(obs: dict) -> "list[dict]":
    return [s for s in obs.get("spans") or [] if s.get("end")]


def _len(s: dict) -> float:
    return s["end"] - s["start"]


def _mean(values: "list[float]"):
    return sum(values) / len(values) if values else None


def _by_job(spans: "list[dict]") -> "dict[str, list[dict]]":
    out: "dict[str, list[dict]]" = {}
    for s in spans:
        out.setdefault(s.get("job_id", ""), []).append(s)
    return out


def _named(spans: "list[dict]", name: str) -> "list[dict]":
    return [s for s in spans if s["name"] == name]


def _covered(parent: dict, children: "list[dict]") -> float:
    """Seconds of ``parent`` that lie inside at least one child."""
    total, at = 0.0, parent["start"]
    for c in sorted(children, key=lambda c: c["start"]):
        a, b = max(c["start"], at), min(c["end"], parent["end"])
        if b > a:
            total, at = total + (b - a), b
    return total


# ------------------------------------------------ inside the gang reduce


def _reduces(obs: dict) -> "list[tuple[dict, list[dict]]]":
    """Each ``dshuffle`` span of the window with its direct children."""
    spans = _spans(obs)
    return [(d, [s for s in spans if s["parent_span_id"] == d["span_id"]])
            for d in _named(spans, "dshuffle")]


def _phase_s(obs: dict, phase: str):
    """Seconds under ``dshuffle:<phase>`` per gang reduce, averaged over
    the window's gang reduces; None where no reduce has the phase."""
    per = [[_len(c) for c in kids if c["name"] == "dshuffle:" + phase]
           for _d, kids in _reduces(obs)]
    return _mean([sum(p) for p in per]) if any(per) else None


def shuffle_locate_s(obs: dict):
    """The reduce waiting for maps to finish (a poll of the master)."""
    return _phase_s(obs, "locate")


def shuffle_fetch_s(obs: dict):
    return _phase_s(obs, "fetch")


def shuffle_assemble_s(obs: dict):
    return _phase_s(obs, "assemble")


def shuffle_pack_s(obs: dict):
    return _phase_s(obs, "pack")


def shuffle_device_call_s(obs: dict):
    """Copy in, the device's programs, copy out."""
    return _phase_s(obs, "device")


def shuffle_gather_s(obs: dict):
    return _phase_s(obs, "gather")


def shuffle_write_s(obs: dict):
    return _phase_s(obs, "write")


def shuffle_self_s(obs: dict):
    """``dshuffle`` minus what its children cover: what no span explains."""
    return _mean([_len(d) - _covered(d, kids) for d, kids in _reduces(obs)])


# --------------------------------------------------- master and scheduler


def _maps(spans: "list[dict]", name: str, backend: "str | None" = None
          ) -> "list[dict]":
    return [s for s in _named(spans, name)
            if s["attributes"].get("is_map")
            and (backend is None or s.get("backend") == backend)]


def report_lag_s(obs: dict):
    """Mean over maps of the master's ``task:done`` minus the end of the
    attempt's ``task:launch`` on the tracker: how long a finished task
    waits to be known."""
    lags = []
    for spans in _by_job(_spans(obs)).values():
        ended = {s["attributes"].get("attempt_id"): s["end"]
                 for s in _maps(spans, "task:launch")}
        lags += [d["start"] - ended[d["attributes"].get("attempt_id")]
                 for d in _maps(spans, "task:done")
                 if d["attributes"].get("attempt_id") in ended]
    return _mean(lags)


def tpu_assign_gap_s(obs: dict):
    """Mean gap between the end of one TPU-backend map's ``task:launch``
    and the start of the next on the same device, inside one job (never
    from one job's last map to the next job's first)."""
    gaps = []
    for spans in _by_job(_spans(obs)).values():
        slots: "dict[object, list[dict]]" = {}
        for s in _maps(spans, "task:launch", "tpu"):
            slots.setdefault(s["attributes"].get("device_id"), []).append(s)
        for runs in slots.values():
            runs.sort(key=lambda s: s["start"])
            gaps += [max(0.0, b["start"] - a["end"])
                     for a, b in zip(runs, runs[1:])]
    return _mean(gaps)


def job_tail_s(obs: dict):
    """End of the ``job`` span minus the last map's ``task:done``: reduce,
    commit and finalisation after the map phase."""
    tails = []
    for spans in _by_job(_spans(obs)).values():
        job, done = _named(spans, "job"), _maps(spans, "task:done")
        if job and done:
            tails.append(job[0]["end"] - max(d["start"] for d in done))
    return _mean(tails)


# ------------------------------------------------------------- the tracker


def _slot_busy_share(obs: dict, backend: str):
    """``task:launch`` seconds of this backend's maps over ``slots`` times
    the ``job`` span's seconds, summed over the window's jobs."""
    busy = capacity = 0.0
    for spans in _by_job(_spans(obs)).values():
        job, runs = _named(spans, "job"), _maps(spans, "task:launch", backend)
        slots = [s["attributes"]["slots"] for s in runs
                 if s["attributes"].get("slots")]
        if job and slots:
            busy += sum(_len(s) for s in runs)
            capacity += max(slots) * _len(job[0])
    return 100.0 * busy / capacity if capacity else None


def tpu_slot_busy_share(obs: dict):
    return _slot_busy_share(obs, "tpu")


def cpu_slot_busy_share(obs: dict):
    return _slot_busy_share(obs, "cpu")


def execute_s_per_map(obs: dict):
    return _mean([_len(s) for s in _named(_spans(obs), "tpu:execute")])


def tpu_task_overhead_s(obs: dict):
    """Mean over TPU maps of ``task:launch`` minus what ``tpu:stage`` and
    ``tpu:execute`` under it cover: localisation, the thread's start,
    status and ``task:commit``."""
    spans = _spans(obs)
    by_id = {s["span_id"]: s for s in spans}
    inside: "dict[str, list[dict]]" = {}
    for s in spans:
        if s["name"] not in ("tpu:stage", "tpu:execute"):
            continue
        up = by_id.get(s["parent_span_id"])
        while up is not None and up["name"] != "task:launch":
            up = by_id.get(up["parent_span_id"])
        if up is not None:
            inside.setdefault(up["span_id"], []).append(s)
    return _mean([_len(s) - _covered(s, inside[s["span_id"]])
                  for s in _maps(spans, "task:launch", "tpu")
                  if s["span_id"] in inside])
