"""Readers for the mesh device shuffle (found by ``run.find_reducer``, as
``reducers.py`` says): the steps inside ``dshuffle:device`` on a mesh,
from the program's spans, and the share of its roofline each of the three
mesh programs reaches, from the device trace. A program that records no
such span, or a run without a trace, gives None: the metric is left out.
"""

from __future__ import annotations

import json
import os

from bench import work_mesh, xplane
from bench.reducers_spans import _len, _mean, _reduces, _spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROW_BYTES, KEY_BYTES = 100, 10      # the Sort Benchmark's rows


# ------------------------------------------------ inside dshuffle:device


def _step_s(obs: dict, step: str):
    """Seconds under ``dshuffle:<step>``, a child of ``dshuffle:device``,
    per gang reduce, averaged over the window's gang reduces; None where
    no reduce has the step."""
    spans = _spans(obs)
    per = []
    for _d, kids in _reduces(obs):
        calls = {k["span_id"] for k in kids if k["name"] == "dshuffle:device"}
        per.append([_len(s) for s in spans
                    if s["parent_span_id"] in calls
                    and s["name"] == "dshuffle:" + step])
    return _mean([sum(p) for p in per]) if any(per) else None


def mesh_put_s(obs: dict):
    """The padded rows copied to the devices' memory."""
    return _step_s(obs, "put")


def mesh_dest_s(obs: dict):
    return _step_s(obs, "dest")


def mesh_exchange_s(obs: dict):
    """Every attempt of the all_to_all, overflow retries included."""
    return _step_s(obs, "exchange")


def mesh_sort_s(obs: dict):
    return _step_s(obs, "sort")


def mesh_get_s(obs: dict):
    """The sorted slots, padding and all, copied back to the host."""
    return _step_s(obs, "get")


# ------------------------------------------------------- the three programs


def _load(name: str) -> dict:
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def _ici_peak(obs: dict) -> dict:
    """The row of ``peaks_ici.json`` for the device kind whose row of
    ``peaks.json`` the harness chose; a kind it lacks is an error."""
    kinds = [k for k, row in _load("peaks.json").items()
             if row == obs["peak"]]
    ici = _load("peaks_ici.json")
    if len(kinds) != 1 or kinds[0] not in ici:
        raise KeyError(f"device kind {kinds} is not in bench/peaks_ici.json")
    return ici[kinds[0]]


def _roofline(obs: dict, pattern: str, per_device_work):
    """Per-device work over per-device time: ``program_runs`` gives one
    run per device plane and execution, each held to one device's work."""
    t, s = obs.get("trace"), obs["sizes"]
    if not t or not obs.get("peak") or "mesh" not in s:
        return None
    runs = xplane.program_runs(t, pattern, t["lo"], t["hi"])
    if not runs:
        return None
    least = work_mesh.least_seconds(per_device_work(s["rows"], s["mesh"]),
                                    obs["peak"], _ici_peak(obs))
    return 100.0 * least * len(runs) / sum(runs)


def mesh_dest_roofline(obs: dict):
    return _roofline(obs, r"jit__dest\b",
                     lambda n, d: work_mesh.dest(n, d, KEY_BYTES))


def mesh_exchange_roofline(obs: dict):
    return _roofline(obs, r"jit__shuffle\b",
                     lambda n, d: work_mesh.exchange(n, d, ROW_BYTES))


def mesh_sort_roofline(obs: dict):
    return _roofline(obs, r"jit__sort\b",
                     lambda n, d: work_mesh.sort(n, d, ROW_BYTES))
