"""Scenario lab: seed-deterministic traffic replay with chaos.

A scenario is a named, parameterized traffic TRACE — interactive
bursts, wide batch jobs, iterative/pipeline rounds — replayed against a
REAL ``JobMaster`` by the scale harness (``SimFleet`` heartbeats the
real wire protocol, ``ScaleDriver`` submits over the real client RPC
surface), interleaved with chaos: tracker churn (hard-kill mid-beat +
cold rejoin), a mid-mix master kill/restart, straggler phases
(fi ``task.slow``), a master-side heartbeat stall (fi
``jt.heartbeat.slow``), and fetch-failure reports. Every job carries a
traffic class (``tpumr.scenario.class``), so the master's flight
recorder windows per-class submit→first-assignment and submit→complete
latency against per-class SLOs and the run emits a machine-readable
pass/fail PER CLASS — with incident bundles as the failure artifact.

A spec with a ``dfs`` table extends the lab to the STORAGE layer: a
real ``MiniDFSCluster`` (NameNode + DataNodes over localhost RPC)
carries a ``SimDFSFleet`` of verifying ``DFSClient``s alongside the
MapReduce classes, and four storage chaos kinds drive its recovery
machinery — ``dn_crash`` (hard-kill mid-read, optional cold rejoin:
client replica failover + NN expiry + re-replication), ``dn_partition``
(heartbeat silence WITHOUT process death via the fi ``dn.partition``
seam: expiry, then rejoin through re-register + block report),
``nn_restart`` (SIGKILL-equivalent + rebind on the same port: editlog
replay, safemode entry/exit timed into the chaos log, clients riding
RPC retries with safemode refusals budgeted separately from errors),
and ``block_corrupt`` (flip bytes in one replica on disk via the fi
``dn.read.corrupt.b<id>`` seam: checksum detection, bad-block report,
drop + re-replicate — the fleet's verified reads prove readers NEVER
see the rot). The report gains a ``dfs`` section with its own SLO
verdicts (error fraction, corrupt reads == 0, read/meta p99, end-of-run
fsck heal) that feeds the overall pass.

Determinism: :func:`plan` expands a spec into a timestamped event list
using only ``(spec, seed)`` — submissions (with per-class jitter) and
chaos targets are all drawn from one seeded stream, the master's fault
seams replay from ``tpumr.fi.seed``, and every SimTracker RNG derives
from the fleet seed. Two runs under one seed produce identical job
schedules and chaos event sequences (the ``plan`` list in the report is
the comparable surface).

Specs are plain dicts — committed here as the built-in mixes, or
authored by operators as TOML files (``tpumr scenario -list`` /
``tpumr simulate -scenario NAME``); TOML loading needs Python 3.11+
(``tomllib``) or an installed ``tomli``.
"""

from __future__ import annotations

import os
import random
import shutil
import tempfile
import threading
import time
from typing import Any

from tpumr.scale.driver import ScaleDriver
from tpumr.scale.simtracker import SimFleet
from tpumr.utils import fi


class ScenarioError(ValueError):
    """A spec that cannot be replayed (unknown key, bad shape…)."""


_PRIORITIES = ("VERY_HIGH", "HIGH", "NORMAL", "LOW", "VERY_LOW")
_CHAOS_KINDS = ("tracker_crash", "tracker_partition",
                "master_restart", "fi",
                "dn_crash", "dn_partition", "nn_restart",
                "block_corrupt")
#: the storage chaos kinds — only valid when the spec has a [dfs] table
_DFS_CHAOS_KINDS = ("dn_crash", "dn_partition", "nn_restart",
                    "block_corrupt")

_SPEC_KEYS = {"name", "seed", "fleet", "master", "classes", "chaos",
              "dfs", "timeout_s", "max_breach_fraction"}
_FLEET_DEFAULTS = {"trackers": 8, "interval_ms": 100, "cpu_slots": 2,
                   "reduce_slots": 1, "task_mean_ms": 250,
                   "fetch_failure_rate": 0.0, "batch": 0}
_MASTER_DEFAULTS = {"expiry_ms": 60_000, "beats_per_second": 0,
                    "interval_max_ms": 0, "brownout": False,
                    "conf": {}}
_CLASS_DEFAULTS = {"jobs": 1, "maps": 2, "reduces": 0, "start_ms": 0,
                   "period_ms": 500, "jitter_ms": 0, "rounds": 1,
                   "priority": "NORMAL", "slo_assign_ms": None,
                   "slo_complete_ms": None}
#: the storage twin of the fleet table: datanode count, verifying
#: client fleet shape, seeded working set, recovery-speed knobs, and
#: the DFS-side SLO budgets the report's ``dfs`` verdict judges
_DFS_DEFAULTS = {"datanodes": 3, "clients": 4, "interval_ms": 50,
                 "files": 4, "file_kb": 64, "hot_read_p": 0.5,
                 "read_kb": 48, "replication_interval_ms": 200,
                 "expiry_ms": 1500, "slo_read_p99_ms": None,
                 "slo_meta_p99_ms": None, "max_error_fraction": 0.02,
                 "conf": {}}
_CHAOS_DEFAULTS = {
    "tracker_crash": {"count": 1, "targets": None, "rejoin_ms": None},
    "tracker_partition": {"count": 1, "targets": None,
                          "duration_ms": 2500},
    "master_restart": {},
    "fi": {"point": None, "probability": 0.0, "max_failures": 0,
           "ms": None},
    # hard-kill datanode(s) mid-whatever; rejoin_ms=None means they
    # never come back (re-replication alone must restore the targets)
    "dn_crash": {"count": 1, "targets": None, "rejoin_ms": None},
    # heartbeat silence without process death: the NN expires the
    # node(s) while reads keep serving, then block reports rejoin them.
    # Which datanodes fall silent is whoever draws the seam first —
    # deterministic in COUNT, not in identity (the seam fires in the
    # datanodes' own heartbeat threads)
    "dn_partition": {"count": 1, "duration_ms": 2500},
    # SIGKILL-equivalent on the NameNode, rebind on the same port after
    # the outage: editlog replay + safemode, clients riding retries
    "nn_restart": {"outage_ms": 300},
    # flip bytes in ONE replica of the file's first block just before
    # a read serves it; file_index=None draws from the seeded stream
    "block_corrupt": {"file_index": None, "count": 1},
}


def _ident(value: Any, what: str) -> str:
    s = str(value or "")
    if not s or not all(c.isalnum() or c in "_-" for c in s) \
            or not s[0].isalpha():
        raise ScenarioError(f"{what} must be a simple identifier "
                            f"([a-z0-9_-], letter first): {value!r}")
    return s


def _merged(defaults: dict, given: Any, what: str) -> dict:
    if given is None:
        given = {}
    if not isinstance(given, dict):
        raise ScenarioError(f"{what} must be a table, got "
                            f"{type(given).__name__}")
    unknown = set(given) - set(defaults)
    if unknown:
        raise ScenarioError(
            f"{what} has unknown keys {sorted(unknown)} "
            f"(valid: {sorted(defaults)})")
    out = dict(defaults)
    out.update(given)
    return out


def _non_negative(row: dict, keys: "tuple[str, ...]",
                  what: str) -> None:
    for k in keys:
        v = row.get(k)
        if v is not None and (not isinstance(v, (int, float))
                              or v < 0):
            raise ScenarioError(f"{what}.{k} must be a non-negative "
                                f"number, got {v!r}")


def validate_spec(spec: Any) -> dict:
    """Normalize + validate one scenario spec (idempotent). Raises
    :class:`ScenarioError` with an author-actionable message."""
    if not isinstance(spec, dict):
        raise ScenarioError("spec must be a table/dict")
    unknown = set(spec) - _SPEC_KEYS
    if unknown:
        raise ScenarioError(f"unknown top-level keys {sorted(unknown)} "
                            f"(valid: {sorted(_SPEC_KEYS)})")
    out: "dict[str, Any]" = {
        "name": _ident(spec.get("name"), "scenario name"),
        "seed": int(spec.get("seed", 0)),
        "timeout_s": float(spec.get("timeout_s", 60.0)),
        "max_breach_fraction": float(
            spec.get("max_breach_fraction", 0.5)),
    }
    out["fleet"] = _merged(_FLEET_DEFAULTS, spec.get("fleet"), "fleet")
    out["master"] = _merged(_MASTER_DEFAULTS, spec.get("master"),
                            "master")
    _non_negative(out["fleet"], ("interval_ms", "task_mean_ms",
                                 "fetch_failure_rate", "batch"),
                  "fleet")
    if int(out["fleet"]["trackers"]) < 1:
        raise ScenarioError("fleet.trackers must be >= 1")
    classes = spec.get("classes")
    if not isinstance(classes, list) or not classes:
        raise ScenarioError("classes must be a non-empty list "
                            "(every job needs a traffic class)")
    out["classes"] = []
    for i, c in enumerate(classes):
        row = _merged(dict(_CLASS_DEFAULTS, name=None), c,
                      f"classes[{i}]")
        row["name"] = _ident(row["name"], f"classes[{i}].name")
        _non_negative(row, ("jobs", "maps", "reduces", "start_ms",
                            "period_ms", "jitter_ms", "rounds",
                            "slo_assign_ms", "slo_complete_ms"),
                      f"classes[{i}]")
        if int(row["jobs"]) < 1 or int(row["maps"]) < 1 \
                or int(row["rounds"]) < 1:
            raise ScenarioError(f"classes[{i}] jobs/maps/rounds "
                                "must be >= 1")
        if row["priority"] not in _PRIORITIES:
            raise ScenarioError(
                f"classes[{i}].priority {row['priority']!r} not in "
                f"{_PRIORITIES}")
        out["classes"].append(row)
    out["dfs"] = None
    if spec.get("dfs") is not None:
        d = _merged(_DFS_DEFAULTS, spec.get("dfs"), "dfs")
        _non_negative(d, ("interval_ms", "file_kb", "hot_read_p",
                          "read_kb", "replication_interval_ms",
                          "expiry_ms", "slo_read_p99_ms",
                          "slo_meta_p99_ms", "max_error_fraction"),
                      "dfs")
        # the seeded working set is written at replication=2, so a
        # single datanode loss must leave a surviving replica
        if int(d["datanodes"]) < 2:
            raise ScenarioError("dfs.datanodes must be >= 2")
        if int(d["clients"]) < 1 or int(d["files"]) < 1:
            raise ScenarioError("dfs.clients/files must be >= 1")
        out["dfs"] = d
    out["chaos"] = []
    for i, ev in enumerate(spec.get("chaos") or []):
        if not isinstance(ev, dict) or ev.get("kind") \
                not in _CHAOS_KINDS:
            raise ScenarioError(
                f"chaos[{i}].kind must be one of {_CHAOS_KINDS}")
        kind = ev["kind"]
        row = _merged(dict(_CHAOS_DEFAULTS[kind], kind=kind,
                           at_ms=None), ev, f"chaos[{i}]")
        if not isinstance(row.get("at_ms"), (int, float)) \
                or row["at_ms"] < 0:
            raise ScenarioError(f"chaos[{i}].at_ms must be a "
                                "non-negative number")
        if kind in _DFS_CHAOS_KINDS and out["dfs"] is None:
            raise ScenarioError(
                f"chaos[{i}].{kind} needs a [dfs] table (the storage "
                "chaos kinds drive the mini-DFS cluster)")
        if kind == "dn_crash" and row["targets"] is not None:
            n_dn = int(out["dfs"]["datanodes"])
            if any(not isinstance(t, int) or not 0 <= t < n_dn
                   for t in row["targets"]):
                raise ScenarioError(
                    f"chaos[{i}].targets must be datanode indexes "
                    f"in [0, {n_dn})")
        if kind == "block_corrupt" and row["file_index"] is not None:
            n_files = int(out["dfs"]["files"])
            if not isinstance(row["file_index"], int) \
                    or not 0 <= row["file_index"] < n_files:
                raise ScenarioError(
                    f"chaos[{i}].file_index must be in "
                    f"[0, {n_files})")
        if kind == "fi":
            if not row["point"] or "tpumr" in str(row["point"]):
                raise ScenarioError(
                    f"chaos[{i}].point must be a bare seam name "
                    f"(e.g. 'jt.heartbeat.slow'), got "
                    f"{row['point']!r}")
            p = row["probability"]
            if not isinstance(p, (int, float)) or not 0 <= p <= 1:
                raise ScenarioError(
                    f"chaos[{i}].probability must be in [0, 1]")
        out["chaos"].append(row)
    return out


def plan(spec: dict) -> "list[dict]":
    """Expand a spec into the deterministic, timestamped event list a
    run replays: pure function of (spec, seed) — class jitter and
    default chaos targets come from one seeded stream, drawn in spec
    order before the final sort."""
    spec = validate_spec(spec)
    rng = random.Random(f"{spec['seed']}:{spec['name']}")
    events: "list[dict]" = []
    for ci, c in enumerate(spec["classes"]):
        for j in range(int(c["jobs"])):
            jitter = rng.randrange(int(c["jitter_ms"]) + 1) \
                if c["jitter_ms"] else 0
            events.append({
                "t_s": round((c["start_ms"] + j * c["period_ms"]
                              + jitter) / 1000.0, 4),
                "kind": "submit", "class": c["name"],
                "name": f"{c['name']}{ci}-{j}",
                "maps": int(c["maps"]), "reduces": int(c["reduces"]),
                "rounds": int(c["rounds"]),
                "priority": c["priority"]})
    n_trackers = int(spec["fleet"]["trackers"])
    for ev in spec["chaos"]:
        row: "dict[str, Any]" = {"t_s": round(ev["at_ms"] / 1000.0, 4),
                                 "kind": ev["kind"]}
        if ev["kind"] in ("tracker_crash", "tracker_partition"):
            targets = ev["targets"]
            if targets is None:
                targets = sorted(rng.sample(
                    range(n_trackers),
                    min(int(ev["count"]), n_trackers)))
            row["targets"] = [int(t) for t in targets]
            if ev["kind"] == "tracker_crash":
                row["rejoin_s"] = (
                    ev["rejoin_ms"] / 1000.0
                    if ev["rejoin_ms"] is not None else None)
            else:
                row["duration_s"] = ev["duration_ms"] / 1000.0
        elif ev["kind"] == "fi":
            row.update(point=str(ev["point"]),
                       probability=float(ev["probability"]),
                       max_failures=int(ev["max_failures"]),
                       ms=ev["ms"])
        elif ev["kind"] == "dn_crash":
            targets = ev["targets"]
            if targets is None:
                n_dn = int(spec["dfs"]["datanodes"])
                targets = sorted(rng.sample(
                    range(n_dn), min(int(ev["count"]), n_dn)))
            row["targets"] = [int(t) for t in targets]
            row["rejoin_s"] = (ev["rejoin_ms"] / 1000.0
                               if ev["rejoin_ms"] is not None else None)
        elif ev["kind"] == "dn_partition":
            row["count"] = int(ev["count"])
            row["duration_s"] = ev["duration_ms"] / 1000.0
        elif ev["kind"] == "nn_restart":
            row["outage_s"] = ev["outage_ms"] / 1000.0
        elif ev["kind"] == "block_corrupt":
            idx = ev["file_index"]
            if idx is None:
                idx = rng.randrange(int(spec["dfs"]["files"]))
            row["file_index"] = int(idx)
            row["count"] = int(ev["count"])
        events.append(row)
    events.sort(key=lambda e: (e["t_s"], e["kind"],
                               e.get("name", "")))
    return events


# ------------------------------------------------------------ built-ins

BUILTIN_SCENARIOS: "dict[str, dict]" = {
    # the north-star mix: interactive bursts + wide batch + an
    # iterative pipeline sharing one master, no chaos — the baseline
    # every chaos mix is judged against
    "steady_mix": {
        "name": "steady_mix",
        "fleet": {"trackers": 8, "task_mean_ms": 250},
        "classes": [
            {"name": "interactive", "jobs": 8, "maps": 2, "reduces": 0,
             "period_ms": 1200, "jitter_ms": 400, "priority": "HIGH",
             "slo_assign_ms": 1500, "slo_complete_ms": 8000},
            {"name": "batch", "jobs": 3, "maps": 16, "reduces": 2,
             "start_ms": 500, "period_ms": 3000,
             "slo_complete_ms": 45_000},
            {"name": "pipeline", "jobs": 2, "maps": 4, "reduces": 1,
             "rounds": 3, "start_ms": 1000, "period_ms": 4000},
        ],
        "timeout_s": 60,
    },
    # two tight interactive bursts landing on a master already busy
    # with wide batch work: does HIGH priority actually buy the bursts
    # their first assignments?
    "interactive_burst": {
        "name": "interactive_burst",
        "fleet": {"trackers": 8, "task_mean_ms": 300},
        "classes": [
            {"name": "batch", "jobs": 2, "maps": 24, "reduces": 2,
             "period_ms": 1000, "slo_complete_ms": 60_000},
            {"name": "interactive", "jobs": 10, "maps": 2,
             "start_ms": 2000, "period_ms": 200, "priority": "HIGH",
             "slo_assign_ms": 2000, "slo_complete_ms": 10_000},
            {"name": "interactive", "jobs": 10, "maps": 2,
             "start_ms": 8000, "period_ms": 200, "priority": "HIGH",
             "slo_assign_ms": 2000, "slo_complete_ms": 10_000},
        ],
        "timeout_s": 60,
    },
    # tracker churn under a short expiry: hard kills mid-task with cold
    # rejoins (re-registration), a partition that outlives the expiry
    # sweep so the rejoin takes the ADOPTION path, a straggler phase,
    # fetch-failure chaos — every job must still complete
    "churn_storm": {
        "name": "churn_storm",
        "fleet": {"trackers": 8, "task_mean_ms": 300,
                  "fetch_failure_rate": 0.02},
        "master": {"expiry_ms": 1200},
        "classes": [
            {"name": "interactive", "jobs": 6, "maps": 2, "reduces": 0,
             "period_ms": 1500, "jitter_ms": 300, "priority": "HIGH",
             "slo_assign_ms": 2500, "slo_complete_ms": 15_000},
            {"name": "batch", "jobs": 2, "maps": 20, "reduces": 2,
             "period_ms": 2000, "slo_complete_ms": 60_000},
        ],
        "chaos": [
            {"kind": "fi", "at_ms": 1000, "point": "task.slow",
             "probability": 0.08, "max_failures": 12, "ms": 1500},
            # targets pinned disjoint so the three churn flavors can't
            # collide on a slot: evict-then-fresh-register (rejoin
            # outlives the expiry), partition-then-ADOPT (silence
            # outlives the expiry, process survives), and crash with a
            # fast rejoin (inside the expiry: cold re-registration)
            {"kind": "tracker_crash", "at_ms": 2500,
             "targets": [2, 3], "rejoin_ms": 2500},
            {"kind": "tracker_partition", "at_ms": 3000,
             "targets": [0, 1], "duration_ms": 3000},
            {"kind": "tracker_crash", "at_ms": 6000,
             "targets": [4, 5], "rejoin_ms": 500},
            # the probabilistic seam variant: exactly one self-crash
            # drawn from the seeded fi stream, no respawn — the fleet
            # must absorb a tracker that just never comes back
            {"kind": "fi", "at_ms": 500, "point": "tracker.crash",
             "probability": 0.02, "max_failures": 1},
        ],
        "timeout_s": 90,
    },
    # sustained master-side heartbeat stall → brownout engages, sheds
    # in ranked steps, interactive recovers while batch slows, then
    # full step-down once the pressure clears
    "overload_brownout": {
        "name": "overload_brownout",
        "fleet": {"trackers": 10, "task_mean_ms": 250},
        "master": {"brownout": True, "beats_per_second": 400,
                   "interval_max_ms": 1000,
                   "conf": {"tpumr.brownout.dwell.ms": 1500}},
        "classes": [
            {"name": "interactive", "jobs": 20, "maps": 2,
             "reduces": 0, "period_ms": 700, "priority": "HIGH",
             "slo_assign_ms": 1500, "slo_complete_ms": 10_000},
            {"name": "batch", "jobs": 3, "maps": 16, "reduces": 1,
             "period_ms": 2500, "slo_complete_ms": 60_000},
        ],
        "chaos": [
            {"kind": "fi", "at_ms": 3000, "point": "jt.heartbeat.slow",
             "probability": 0.35, "max_failures": 60, "ms": 250},
        ],
        "timeout_s": 90,
    },
    # the storage churn storm: a replica corrupted under a live
    # verified-read mix (readers must NEVER see the rot), a datanode
    # hard-kill with a cold rejoin (client failover + re-replication),
    # and a heartbeat partition that outlives the expiry sweep (expire,
    # then rejoin via block report) — while MapReduce classes keep
    # completing on the same box
    "dfs_churn_storm": {
        "name": "dfs_churn_storm",
        "fleet": {"trackers": 4, "task_mean_ms": 250},
        "classes": [
            {"name": "interactive", "jobs": 4, "maps": 2, "reduces": 0,
             "period_ms": 1500, "jitter_ms": 300, "priority": "HIGH",
             "slo_assign_ms": 2500, "slo_complete_ms": 15_000},
            {"name": "batch", "jobs": 2, "maps": 8, "reduces": 1,
             "period_ms": 2000, "slo_complete_ms": 60_000},
        ],
        "dfs": {"datanodes": 3, "clients": 4, "files": 4,
                "file_kb": 64, "interval_ms": 50,
                "slo_read_p99_ms": 2500, "max_error_fraction": 0.05,
                # arm the NN flight recorder: a chaos-driven op-p99
                # breach writes nn-* bundles into the artifacts dir
                "conf": {"tpumr.nn.incident.slo.ms": 250}},
        "chaos": [
            {"kind": "block_corrupt", "at_ms": 1500},
            {"kind": "dn_crash", "at_ms": 2500, "targets": [1],
             "rejoin_ms": 3000},
            {"kind": "dn_partition", "at_ms": 5500,
             "duration_ms": 2500},
        ],
        "timeout_s": 90,
    },
    # the storage twin of master_failover: a NameNode SIGKILL mid-mix
    # (no editlog close), rebind on the same port — editlog replay +
    # safemode timed into the chaos log, DFS clients riding their RPC
    # retry policy (safemode refusals budgeted separately from
    # errors), MapReduce classes unaffected
    "dfs_nn_failover": {
        "name": "dfs_nn_failover",
        "fleet": {"trackers": 4, "task_mean_ms": 250},
        "classes": [
            {"name": "interactive", "jobs": 4, "maps": 2, "reduces": 0,
             "period_ms": 1200, "jitter_ms": 300, "priority": "HIGH",
             "slo_assign_ms": 4000, "slo_complete_ms": 20_000},
            {"name": "batch", "jobs": 2, "maps": 8, "reduces": 1,
             "period_ms": 2000, "slo_complete_ms": 60_000},
        ],
        "dfs": {"datanodes": 3, "clients": 4, "files": 4,
                "file_kb": 64, "interval_ms": 50,
                "max_error_fraction": 0.05},
        "chaos": [
            {"kind": "nn_restart", "at_ms": 3000, "outage_ms": 300},
        ],
        "timeout_s": 90,
    },
    # a mid-mix master kill/restart with journal recovery: the fleet
    # keeps beating, the driver keeps polling old job ids, every job
    # still completes
    "master_failover": {
        "name": "master_failover",
        "fleet": {"trackers": 8, "task_mean_ms": 300},
        "classes": [
            {"name": "interactive", "jobs": 6, "maps": 2, "reduces": 0,
             "period_ms": 1200, "jitter_ms": 300, "priority": "HIGH",
             "slo_assign_ms": 4000, "slo_complete_ms": 20_000},
            {"name": "batch", "jobs": 2, "maps": 16, "reduces": 2,
             "period_ms": 2000, "slo_complete_ms": 60_000},
            {"name": "pipeline", "jobs": 2, "maps": 4, "reduces": 1,
             "rounds": 2, "start_ms": 500, "period_ms": 3000},
        ],
        "chaos": [
            {"kind": "master_restart", "at_ms": 4000},
        ],
        "timeout_s": 90,
    },
}


def _read_toml(path: str) -> dict:
    try:
        import tomllib
    except ImportError:
        try:
            import tomli as tomllib  # type: ignore[no-redef]
        except ImportError as e:
            raise ScenarioError(
                "TOML scenario specs need Python 3.11+ (tomllib) or "
                "an installed tomli") from e
    try:
        with open(path, "rb") as f:
            return tomllib.load(f)
    except OSError as e:
        raise ScenarioError(f"cannot read scenario {path}: {e}") from e
    except Exception as e:  # tomllib.TOMLDecodeError
        raise ScenarioError(f"bad TOML in {path}: {e}") from e


def load_spec(source: Any,
              scenario_dir: "str | None" = None) -> dict:
    """Resolve ``source`` — a spec dict, a built-in name, or a TOML
    path / ``<scenario_dir>/<name>.toml`` — to a validated spec."""
    if isinstance(source, dict):
        return validate_spec(source)
    name = str(source)
    if name in BUILTIN_SCENARIOS:
        return validate_spec(dict(BUILTIN_SCENARIOS[name]))
    candidates = [name] if name.endswith(".toml") else []
    if scenario_dir:
        candidates.append(os.path.join(scenario_dir,
                                       f"{name}.toml"))
    for path in candidates:
        if os.path.exists(path):
            doc = _read_toml(path)
            doc.setdefault("name",
                           os.path.splitext(os.path.basename(path))[0])
            return validate_spec(doc)
    raise ScenarioError(
        f"unknown scenario {name!r} (built-ins: "
        f"{', '.join(sorted(BUILTIN_SCENARIOS))}; TOML specs load "
        f"from tpumr.scenario.dir)")


def list_scenarios(scenario_dir: "str | None" = None) -> "list[dict]":
    """Catalog rows for ``tpumr scenario -list``: built-ins plus any
    ``*.toml`` in ``scenario_dir`` (unreadable files listed with their
    error, not skipped silently)."""
    rows = []
    sources = [(name, "builtin") for name in sorted(BUILTIN_SCENARIOS)]
    if scenario_dir and os.path.isdir(scenario_dir):
        sources += [(os.path.join(scenario_dir, n), "toml")
                    for n in sorted(os.listdir(scenario_dir))
                    if n.endswith(".toml")]
    for source, origin in sources:
        try:
            spec = load_spec(source, scenario_dir)
            events = plan(spec)
            rows.append({
                "name": spec["name"], "origin": origin,
                "classes": sorted({c["name"]
                                   for c in spec["classes"]}),
                "jobs": sum(int(c["jobs"]) for c in spec["classes"]),
                "chaos": sorted({c["kind"] for c in spec["chaos"]}),
                "dfs": spec.get("dfs") is not None,
                "trace_s": events[-1]["t_s"] if events else 0.0,
            })
        except ScenarioError as e:
            rows.append({"name": str(source), "origin": origin,
                         "error": str(e)})
    return rows


# ------------------------------------------------------------ runner

class ScenarioRunner:
    """Replay one spec against a self-hosted master + sim fleet and
    emit the machine-readable report (per-class latencies + verdicts,
    chaos counters, incident artifacts)."""

    def __init__(self, spec: Any, *,
                 artifacts_dir: "str | None" = None,
                 scenario_dir: "str | None" = None) -> None:
        self.spec = load_spec(spec, scenario_dir)
        self.artifacts_dir = artifacts_dir

    # -------------------------------------------------------- conf

    def _master_conf(self, workdir: str) -> Any:
        from tpumr.mapred.jobconf import JobConf
        spec = self.spec
        fleet, mast = spec["fleet"], spec["master"]
        conf = JobConf()
        conf.set("tpumr.history.dir", os.path.join(workdir, "history"))
        # the recorder nests bundles under <dir>/incidents
        conf.set("tpumr.prof.incident.dir", workdir)
        conf.set("tpumr.prof.enabled", True)
        conf.set("tpumr.heartbeat.interval.ms",
                 int(fleet["interval_ms"]))
        conf.set("tpumr.tracker.expiry.ms", int(mast["expiry_ms"]))
        # recovery armed from the start: the first boot finds an empty
        # journal (no-op); a mid-mix restart reuses the SAME conf
        # object, so fi seam state and scenario keys survive the swap
        conf.set("mapred.jobtracker.restart.recover", True)
        conf.set("mapred.jobtracker.restart.recovery.grace.ms",
                 int(4 * fleet["interval_ms"]))
        conf.set("tpumr.fi.seed", spec["seed"])
        conf.set("tpumr.scenario.name", spec["name"])
        if mast["beats_per_second"]:
            conf.set("tpumr.heartbeat.beats.per.second",
                     int(mast["beats_per_second"]))
        if mast["interval_max_ms"]:
            conf.set("tpumr.heartbeat.interval.max.ms",
                     int(mast["interval_max_ms"]))
        if mast["brownout"]:
            conf.set("tpumr.brownout.enabled", True)
        if fleet["batch"]:
            # the fleet's coalescing twin of the master's batch RPC —
            # one knob in the conf so the run() fleet reads it back
            conf.set("tpumr.heartbeat.batch", int(fleet["batch"]))
        for c in spec["classes"]:
            for kind, key in (("slo_assign_ms", "assign"),
                              ("slo_complete_ms", "complete")):
                if c[kind] is not None:
                    conf.set(f"tpumr.scenario.slo.{c['name']}."
                             f"{key}.ms", int(c[kind]))
        dfs = spec.get("dfs")
        if dfs:
            # the storage lab shares THIS conf object with the master,
            # the mini-DFS cluster, and every DFSClient — one
            # tpumr.fi.seed, and chaos armed by conf.set is visible to
            # all of them immediately
            conf.set("tdfs.http.port", -1)
            conf.set("dfs.replication", 2)
            conf.set("tdfs.replication.interval.s",
                     dfs["replication_interval_ms"] / 1000.0)
            conf.set("tdfs.datanode.expiry.s",
                     dfs["expiry_ms"] / 1000.0)
            # clients must ride an nn_restart outage on transport-level
            # retries (safemode refusals are application-level and
            # counted separately by the fleet)
            conf.set("tdfs.client.nn.retries", 60)
            conf.set("tdfs.client.nn.backoff.ms", 100.0)
            for k, v in (dfs["conf"] or {}).items():
                conf.set(str(k), v)
        for k, v in (mast["conf"] or {}).items():
            conf.set(str(k), v)
        return conf

    # -------------------------------------------------------- helpers

    @staticmethod
    def _apply_fi(conf: Any, ev: dict) -> None:
        conf.set(f"tpumr.fi.{ev['point']}.probability",
                 ev["probability"])
        if ev["max_failures"]:
            conf.set(f"tpumr.fi.{ev['point']}.max.failures",
                     ev["max_failures"])
        if ev.get("ms") is not None:
            conf.set(f"tpumr.fi.{ev['point']}.ms", int(ev["ms"]))

    def _submit(self, driver: ScaleDriver, ev: dict,
                round_no: int = 1) -> str:
        name = ev["name"] if round_no <= 1 \
            else f"{ev['name']}.r{round_no}"
        ids = driver.submit(
            1, ev["maps"], ev["reduces"], name=name,
            **{"tpumr.scenario.class": ev["class"],
               "mapred.job.priority": ev["priority"]})
        return ids[0]

    def _poll_jobs(self, driver: ScaleDriver, states: dict,
                   pending: set, chains: dict,
                   job_ids: list) -> None:
        """One status sweep; completed chain rounds submit the next
        round (the iterative/pipeline stage shape — reactive, like a
        real driver resubmitting on stage completion)."""
        for jid in sorted(pending):
            try:
                st = driver.client.call("get_job_status", jid)
            except Exception:  # noqa: BLE001 — master restart window
                continue
            state = st.get("state", "RUNNING")
            states[jid] = state
            if state not in ("SUCCEEDED", "FAILED", "KILLED"):
                continue
            pending.discard(jid)
            link = chains.pop(jid, None)
            if link and state == "SUCCEEDED" \
                    and link["rounds_left"] > 0:
                nxt_round = link["round"] + 1
                njid = self._submit(driver, link, nxt_round)
                job_ids.append(njid)
                states[njid] = "RUNNING"
                pending.add(njid)
                chains[njid] = dict(link,
                                    rounds_left=link["rounds_left"] - 1,
                                    round=nxt_round)

    @staticmethod
    def _dfs_heal_wait(cluster: Any, timeout_s: float = 20.0) -> dict:
        """Bounded wait for the mini-DFS to converge after the chaos:
        safemode exited, no missing/corrupt blocks, every block back at
        its replication target (fsck clean, open files excepted — the
        fleet's in-flight writes at stop time hold leases, which is not
        damage). Returns the heal receipt for the report."""
        t0 = time.monotonic()
        deadline = t0 + timeout_s
        last: dict = {}
        while time.monotonic() < deadline:
            try:
                last = cluster.namenode.ns.fsck("/")
            except Exception:  # noqa: BLE001 — safemode window
                last = {}
            else:
                if not cluster.namenode.ns.safemode \
                        and not last["missing"] \
                        and not last["corrupt"] \
                        and not last["under_replicated"]:
                    return {"healed": True,
                            "heal_s": round(time.monotonic() - t0, 3),
                            "blocks": int(last["blocks"])}
            time.sleep(0.1)
        return {"healed": False, "heal_s": None,
                "blocks": int(last.get("blocks", 0)),
                "missing": len(last.get("missing", ())),
                "corrupt": len(last.get("corrupt", ())),
                "under_replicated": len(
                    last.get("under_replicated", ()))}

    @staticmethod
    def _class_typed(master: Any) -> "dict[tuple[str, str], dict]":
        return {key: h.typed()
                for key, h in master._class_hists.items()}

    @staticmethod
    def _merged_class_ms(states: "list[dict]") -> dict:
        """Cumulative per-class latency percentiles ACROSS master
        incarnations: fold each incarnation's typed histogram state
        into one scratch histogram per (class, kind)."""
        from tpumr.metrics.flightrec import typed_p99
        from tpumr.metrics.histogram import Histogram
        scratch: "dict[tuple[str, str], Histogram]" = {}
        for st in states:
            for (kind, cls_name), typed in st.items():
                h = scratch.setdefault(
                    (kind, cls_name), Histogram(f"{kind}_{cls_name}"))
                h.merge_typed(typed)
        out: "dict[str, dict]" = {}
        for (kind, cls_name), h in sorted(scratch.items()):
            t = h.typed()
            row = out.setdefault(cls_name, {})
            row[f"{kind}_p50_ms"] = round(
                typed_p99(t, 0.5) * 1000, 2)
            row[f"{kind}_p99_ms"] = round(
                typed_p99(t, 0.99) * 1000, 2)
            row[f"{kind}_count"] = int(t.get("count", 0))
        return out

    # -------------------------------------------------------- run

    def run(self) -> dict:
        from tpumr.mapred.jobtracker import JobMaster
        spec = self.spec
        events = plan(spec)
        fi.reset()   # counters + RNG streams replay from this run's seed
        workdir = self.artifacts_dir or tempfile.mkdtemp(
            prefix=f"tpumr-scenario-{spec['name']}-")
        own_workdir = self.artifacts_dir is None
        conf = self._master_conf(workdir)
        fleet_spec = spec["fleet"]
        interval_s = fleet_spec["interval_ms"] / 1000.0
        from tpumr.core import confkeys
        master = JobMaster(conf).start()
        host, port = master.address
        masters = [master]
        fleet = SimFleet(
            host, port, int(fleet_spec["trackers"]),
            interval_s=interval_s, seed=spec["seed"],
            cpu_slots=int(fleet_spec["cpu_slots"]),
            reduce_slots=int(fleet_spec["reduce_slots"]),
            task_time_mean_s=fleet_spec["task_mean_ms"] / 1000.0,
            fetch_failure_rate=fleet_spec["fetch_failure_rate"],
            batch=confkeys.get_int(conf, "tpumr.heartbeat.batch"),
            fi_conf=conf).start()
        driver = ScaleDriver(host, port)
        cluster = dfs_fleet = None
        dfs_files: "list[str]" = []
        dfs_timers: "list[threading.Timer]" = []
        dfs_fi_points: "list[str]" = []
        dfs_spec = spec.get("dfs")
        if dfs_spec:
            from tpumr.dfs.mini_cluster import MiniDFSCluster
            from tpumr.scale.simdfs import SimDFSFleet, seed_files
            cluster = MiniDFSCluster(int(dfs_spec["datanodes"]),
                                     conf=conf)
            dfs_files = seed_files(
                cluster.nn_host, cluster.nn_port, conf,
                n_files=int(dfs_spec["files"]),
                file_bytes=int(dfs_spec["file_kb"]) * 1024,
                root="/scenario/data")
            dfs_fleet = SimDFSFleet(
                cluster.nn_host, cluster.nn_port,
                int(dfs_spec["clients"]), conf,
                interval_s=dfs_spec["interval_ms"] / 1000.0,
                seed=spec["seed"], files=dfs_files,
                hot_read_p=dfs_spec["hot_read_p"],
                read_bytes=int(dfs_spec["read_kb"]) * 1024,
                verify=True).start()
        job_ids: "list[str]" = []
        states: "dict[str, str]" = {}
        pending: "set[str]" = set()
        chains: "dict[str, dict]" = {}
        chaos_log: "list[dict]" = []
        dead_class_states: "list[dict]" = []
        t0 = time.monotonic()
        ok = False
        dfs_heal: "dict | None" = None
        try:
            for ev in events:
                while time.monotonic() - t0 < ev["t_s"]:
                    time.sleep(min(
                        0.1, max(0.0, ev["t_s"]
                                 - (time.monotonic() - t0))))
                    self._poll_jobs(driver, states, pending, chains,
                                    job_ids)
                if ev["kind"] == "submit":
                    jid = self._submit(driver, ev)
                    job_ids.append(jid)
                    states[jid] = "RUNNING"
                    pending.add(jid)
                    if ev["rounds"] > 1:
                        chains[jid] = dict(
                            ev, rounds_left=ev["rounds"] - 1, round=1)
                elif ev["kind"] == "tracker_crash":
                    names = fleet.churn(idxs=ev["targets"],
                                        rejoin_after_s=ev["rejoin_s"])
                    chaos_log.append({
                        "t_s": round(time.monotonic() - t0, 3),
                        "kind": "tracker_crash", "crashed": names,
                        "rejoin_s": ev["rejoin_s"]})
                elif ev["kind"] == "tracker_partition":
                    names = fleet.partition(idxs=ev["targets"],
                                            duration_s=ev["duration_s"])
                    chaos_log.append({
                        "t_s": round(time.monotonic() - t0, 3),
                        "kind": "tracker_partition",
                        "partitioned": names,
                        "duration_s": ev["duration_s"]})
                elif ev["kind"] == "master_restart":
                    dead_class_states.append(
                        self._class_typed(masters[-1]))
                    masters[-1].stop()
                    m2 = None
                    for _ in range(250):
                        try:
                            m2 = JobMaster(conf, host=host,
                                           port=port).start()
                            break
                        except OSError:
                            time.sleep(0.02)
                    if m2 is None:
                        raise RuntimeError(
                            "could not rebind the master port")
                    masters.append(m2)
                    chaos_log.append({
                        "t_s": round(time.monotonic() - t0, 3),
                        "kind": "master_restart"})
                elif ev["kind"] == "fi":
                    self._apply_fi(conf, ev)
                    chaos_log.append({
                        "t_s": round(time.monotonic() - t0, 3),
                        "kind": "fi", "point": ev["point"],
                        "probability": ev["probability"]})
                elif ev["kind"] == "dn_crash":
                    for t in ev["targets"]:
                        cluster.kill_datanode(t)
                        if ev["rejoin_s"] is not None:
                            timer = threading.Timer(
                                ev["rejoin_s"],
                                cluster.restart_datanode, args=(t,))
                            timer.daemon = True
                            timer.start()
                            dfs_timers.append(timer)
                    chaos_log.append({
                        "t_s": round(time.monotonic() - t0, 3),
                        "kind": "dn_crash",
                        "targets": list(ev["targets"]),
                        "rejoin_s": ev["rejoin_s"]})
                elif ev["kind"] == "dn_partition":
                    # armed via conf, drawn by the datanodes' own
                    # heartbeat threads: max.failures is cumulative
                    # against the process-global fired counter so a
                    # second partition event silences `count` MORE
                    conf.set("tpumr.fi.dn.partition.ms",
                             int(ev["duration_s"] * 1000))
                    conf.set("tpumr.fi.dn.partition.probability", 1.0)
                    conf.set("tpumr.fi.dn.partition.max.failures",
                             fi.fired("dn.partition")
                             + int(ev["count"]))
                    dfs_fi_points.append("dn.partition")
                    chaos_log.append({
                        "t_s": round(time.monotonic() - t0, 3),
                        "kind": "dn_partition",
                        "count": int(ev["count"]),
                        "duration_s": ev["duration_s"]})
                elif ev["kind"] == "nn_restart":
                    t_kill = time.monotonic()
                    cluster.kill_namenode()
                    until = t_kill + ev["outage_s"]
                    while time.monotonic() < until:
                        self._poll_jobs(driver, states, pending,
                                        chains, job_ids)
                        time.sleep(min(0.05, max(
                            0.0, until - time.monotonic())))
                    cluster.restart_killed_namenode()
                    # time safemode exit (the recovery headline); the
                    # fleet is retrying meanwhile, refusals counted
                    # separately from errors
                    sm_deadline = time.monotonic() + 30.0
                    while cluster.namenode.ns.safemode \
                            and time.monotonic() < sm_deadline:
                        self._poll_jobs(driver, states, pending,
                                        chains, job_ids)
                        time.sleep(0.05)
                    chaos_log.append({
                        "t_s": round(time.monotonic() - t0, 3),
                        "kind": "nn_restart",
                        "outage_s": ev["outage_s"],
                        "safemode_exit_s": round(
                            time.monotonic() - t_kill, 3),
                        "safemode_exited":
                            not cluster.namenode.ns.safemode})
                elif ev["kind"] == "block_corrupt":
                    path = dfs_files[ev["file_index"]
                                     % len(dfs_files)]
                    inode = cluster.namenode.ns.namespace.get(
                        path) or {}
                    blocks = inode.get("blocks") or []
                    if blocks:
                        bid = int(blocks[0][0])
                        point = f"dn.read.corrupt.b{bid}"
                        conf.set(f"tpumr.fi.{point}.probability", 1.0)
                        conf.set(f"tpumr.fi.{point}.max.failures",
                                 int(ev["count"]))
                        dfs_fi_points.append(point)
                        chaos_log.append({
                            "t_s": round(time.monotonic() - t0, 3),
                            "kind": "block_corrupt", "path": path,
                            "block_id": bid,
                            "count": int(ev["count"])})
                    else:
                        chaos_log.append({
                            "t_s": round(time.monotonic() - t0, 3),
                            "kind": "block_corrupt", "path": path,
                            "block_id": None, "skipped": True})
            trace_end = events[-1]["t_s"] if events else 0.0
            deadline = t0 + trace_end + spec["timeout_s"]
            while pending and time.monotonic() < deadline:
                self._poll_jobs(driver, states, pending, chains,
                                job_ids)
                if pending:
                    time.sleep(0.1)
            # drain ticks: the flight recorder windows at 1 Hz — give
            # it a beat to fold the last completions, and let an active
            # brownout finish stepping down after the pressure cleared
            brown = masters[-1].brownout
            settle_until = time.monotonic() + 2.5
            time.sleep(max(0.0, settle_until - time.monotonic()))
            if brown is not None:
                step_down_cap = time.monotonic() + 30.0
                while brown.level > 0 \
                        and time.monotonic() < step_down_cap:
                    time.sleep(0.25)
            if cluster is not None:
                # freeze DFS traffic, let pending rejoin timers land,
                # then demand the cluster self-heal to a clean fsck —
                # the chaos kinds all promise convergence, this is
                # where the promise is checked
                dfs_fleet.stop()
                for timer in dfs_timers:
                    timer.join(timeout=15.0)
                dfs_heal = self._dfs_heal_wait(cluster)
            ok = True
        finally:
            fleet.stop()
            if dfs_fleet is not None:
                dfs_fleet.stop()
            for timer in dfs_timers:
                timer.cancel()
            driver.close()
            try:
                masters[-1].stop()
            except Exception:  # noqa: BLE001
                pass
            if cluster is not None:
                try:
                    cluster.shutdown()
                except Exception:  # noqa: BLE001
                    pass
        report = self._report(spec, events, masters, fleet, states,
                              pending, chaos_log, dead_class_states,
                              workdir, time.monotonic() - t0,
                              dfs_fleet=dfs_fleet, dfs_heal=dfs_heal,
                              dfs_fi_points=dfs_fi_points)
        if own_workdir and ok and report["pass"]:
            shutil.rmtree(workdir, ignore_errors=True)
            report["artifacts_dir"] = None
        return report

    @staticmethod
    def _dfs_section(spec: dict, dfs_fleet: Any,
                     dfs_heal: "dict | None") -> "dict | None":
        """The storage layer's own verdict block: error budget,
        corrupt-read invariant (== 0, always), optional client-side
        p99 SLOs, and the end-of-run heal receipt."""
        if dfs_fleet is None:
            return None
        d = spec["dfs"]
        st = dfs_fleet.stats()
        ops = sum(st["op_counts"].values()) or 1
        err_frac = st["errors"] / ops
        read_p99_ms = round(float(
            (st["read_rtt"] or {}).get("p99", 0.0)) * 1000, 2)
        meta_p99_ms = round(float(
            (st["meta_rtt"] or {}).get("p99", 0.0)) * 1000, 2)
        verdicts = {
            "errors_ok": err_frac <= float(d["max_error_fraction"]),
            "corrupt_reads_ok": int(st["corrupt_reads"]) == 0,
            "read_p99_ok": (d["slo_read_p99_ms"] is None
                            or read_p99_ms <= d["slo_read_p99_ms"]),
            "meta_p99_ok": (d["slo_meta_p99_ms"] is None
                            or meta_p99_ms <= d["slo_meta_p99_ms"]),
            "healed": bool(dfs_heal and dfs_heal.get("healed")),
        }
        return {
            "clients": int(d["clients"]),
            "datanodes": int(d["datanodes"]),
            "ops": int(st["ops"]),
            "op_counts": st["op_counts"],
            "bytes_read": int(st["bytes_read"]),
            "errors": int(st["errors"]),
            "error_fraction": round(err_frac, 4),
            "corrupt_reads": int(st["corrupt_reads"]),
            "safemode_refusals": int(st["safemode_refusals"]),
            "read_p99_ms": read_p99_ms,
            "meta_p99_ms": meta_p99_ms,
            "heal": dfs_heal,
            "verdicts": verdicts,
            "pass": all(verdicts.values()),
        }

    def _report(self, spec: dict, events: list, masters: list,
                fleet: SimFleet, states: dict, pending: set,
                chaos_log: list, dead_class_states: list,
                workdir: str, wall_s: float, *,
                dfs_fleet: Any = None,
                dfs_heal: "dict | None" = None,
                dfs_fi_points: "list[str] | None" = None) -> dict:
        final = masters[-1]
        jt = final.metrics.snapshot().get("jobtracker", {})
        fr = final.flightrec
        verdicts = fr.class_report() if fr is not None else {}
        history = fr.window_history() if fr is not None else []
        # re-judge with the SPEC's breach-fraction budget (the
        # recorder's class_report uses its default majority rule)
        mbf = spec["max_breach_fraction"]
        for row in verdicts.values():
            ok = True
            for kind in ("assign", "complete"):
                entry = row.get(kind) or {}
                if entry.get("slo_ms") is None:
                    continue
                if entry.get("ok") is False \
                        or entry.get("breach_fraction", 0.0) > mbf:
                    ok = False
            row["pass"] = ok
        class_ms = self._merged_class_ms(
            dead_class_states + [self._class_typed(final)])
        succeeded = sorted(j for j, s in states.items()
                           if s == "SUCCEEDED")
        failed = sorted(j for j, s in states.items()
                        if s in ("FAILED", "KILLED"))
        chaos_points = sorted({ev["point"] for ev in spec["chaos"]
                               if ev["kind"] == "fi"}
                              | {"tracker.crash"}
                              | set(dfs_fi_points or ()))
        dfs_section = self._dfs_section(spec, dfs_fleet, dfs_heal)
        all_pass = (not failed and not pending
                    and all(v.get("pass") for v in verdicts.values())
                    and (dfs_section is None or dfs_section["pass"]))
        return {
            "scenario": spec["name"],
            "seed": spec["seed"],
            "wall_s": round(wall_s, 2),
            "plan": events,
            "jobs": {"submitted": len(states),
                     "succeeded": len(succeeded),
                     "failed": len(failed),
                     "unfinished": len(pending)},
            "classes": class_ms,
            "verdicts": verdicts,
            "chaos": {
                "trackers_crashed": fleet.trackers_crashed,
                "trackers_respawned": fleet.trackers_respawned,
                "trackers_partitioned": fleet.trackers_partitioned,
                "trackers_adopted": int(
                    jt.get("trackers_adopted", 0)),
                "trackers_restarted": int(
                    jt.get("trackers_restarted", 0)),
                "attempts_adopted": int(
                    jt.get("attempts_adopted", 0)),
                "master_restarts": len(masters) - 1,
                "datanodes_killed": sum(
                    len(r.get("targets", ())) for r in chaos_log
                    if r["kind"] == "dn_crash"),
                "nn_restarts": sum(1 for r in chaos_log
                                   if r["kind"] == "nn_restart"),
                "fi_fired": {p: fi.fired(p) for p in chaos_points},
            },
            "dfs": dfs_section,
            "chaos_log": chaos_log,
            "brownout": (final.brownout.snapshot()
                         if final.brownout is not None
                         else {"level": 0}),
            "brownout_max_level": max(
                [r["brownout_level"] for r in history] or [0]),
            "window_history": history,
            "incidents": [r["name"]
                          for r in (fr.list_incidents()
                                    if fr is not None else [])],
            "artifacts_dir": workdir,
            "pass": all_pass,
        }


def run_named(name: Any, seed: "int | None" = None,
              scenario_dir: "str | None" = None,
              artifacts_dir: "str | None" = None) -> dict:
    """Load + replay one scenario (the CLI/bench entry). ``seed``
    overrides the spec's."""
    spec = load_spec(name, scenario_dir)
    if seed is not None:
        spec = dict(spec, seed=int(seed))
    return ScenarioRunner(spec,
                          artifacts_dir=artifacts_dir).run()
