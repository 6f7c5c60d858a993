"""Post-execution job diagnosis — the "vaidya" tier.

≈ ``src/contrib/vaidya`` (reference: vaidya/postexdiagnosis/tests/
{BalancedReducePartitioning,MapSideDiskSpill,MapsReExecutionImpact,
ReducesReExecutionImpact}.java driven by PostExPerformanceDiagnoser and
the postex_diagnosis_tests.xml rule list): each diagnostic rule reads a
finished job's statistics and returns an *impact* in [0, 1]; impact at or
above the rule's threshold flags the problem and attaches a prescription.
The reference parses the field-encoded history format; here the rules read
the JSON-lines job history (tpumr.mapred.history) directly, and two
TPU-era rules replace the HDFS-side-effect rule: backend placement
(is the hybrid scheduler using the measured acceleration?) and map
granularity (the reference's NLineInputFormat 1-line-per-map config made
tiny maps easy to create by accident).

Usage::

    tpumr job -diagnose <history.jsonl>      # CLI
    report = diagnose(events)                # library
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any

from tpumr.core.counters import TaskCounter

_FW = TaskCounter.FRAMEWORK_GROUP


@dataclass
class JobStatistics:
    """A finished job's history, shaped for the rules."""

    job_id: str = ""
    job_name: str = ""
    num_maps: int = 0
    num_reduces: int = 0
    state: str = ""
    wall_time: float = 0.0
    acceleration_factor: float = 0.0
    conf: dict = field(default_factory=dict)
    #: one dict per TERMINAL attempt: event, is_map, run_on_tpu, runtime,
    #: counters {group: {name: value}}
    attempts: list = field(default_factory=list)

    @classmethod
    def from_events(cls, events: "list[dict]") -> "JobStatistics":
        st = cls()
        for ev in events:
            kind = ev.get("event")
            if kind == "JOB_SUBMITTED":
                st.job_id = ev.get("job_id", "")
                st.job_name = ev.get("job_name", "")
                st.num_maps = int(ev.get("num_maps", 0))
                st.num_reduces = int(ev.get("num_reduces", 0))
                st.conf = ev.get("conf", {}) or {}
            elif kind in ("TASK_FINISHED", "TASK_FAILED", "TASK_KILLED"):
                st.attempts.append(ev)
            elif kind == "JOB_FINISHED":
                st.state = ev.get("state", "")
                st.wall_time = float(ev.get("wall_time", 0.0))
                st.acceleration_factor = float(
                    ev.get("acceleration_factor", 0.0) or 0.0)
        return st

    # ------------------------------------------------------------ helpers

    def counter(self, attempt: dict, name: str, group: str = _FW) -> int:
        return int((attempt.get("counters") or {})
                   .get(group, {}).get(name, 0))

    def finished(self, is_map: bool) -> "list[dict]":
        return [a for a in self.attempts
                if a.get("event") == "TASK_FINISHED"
                and a.get("is_map") == is_map]

    def failed(self, is_map: bool) -> "list[dict]":
        return [a for a in self.attempts
                if a.get("event") == "TASK_FAILED"
                and a.get("is_map") == is_map]


class DiagnosticTest:
    """One rule. ``evaluate`` returns impact in [0, 1]; impact >=
    ``threshold`` is a positive finding (the reference's SuccessThreshold
    contract)."""

    name: str = ""
    title: str = ""
    importance: str = "Medium"          # High | Medium | Low
    threshold: float = 0.5

    def evaluate(self, stats: JobStatistics) -> float:
        raise NotImplementedError

    def prescription(self, stats: JobStatistics) -> str:
        return ""


class BalancedReducePartitioning(DiagnosticTest):
    """≈ BalancedReducePartitioning.java: what fraction of reduces carry
    ``percent`` of the reduce input records? Impact = 1 - busy/total."""

    name = "balanced-reduce-partitioning"
    title = "Reduce input is concentrated on few reducers"
    importance = "High"
    threshold = 0.4
    percent = 0.90

    def evaluate(self, stats: JobStatistics) -> float:
        reduces = stats.finished(is_map=False)
        if len(reduces) < 2:
            return 0.0
        recs = sorted(stats.counter(a, TaskCounter.REDUCE_INPUT_RECORDS)
                      for a in reduces)
        total = sum(recs)
        if total == 0:
            return 0.0
        target = self.percent * total
        busy, acc = 0, 0
        for r in reversed(recs):
            acc += r
            busy += 1
            if acc >= target:
                break
        return 1.0 - busy / len(recs)

    def prescription(self, stats: JobStatistics) -> str:
        return ("Partitioning is skewed: use a better partitioner "
                "(TotalOrderPartitioner with sampled splitters, or a "
                "custom get_partition) so reduce input spreads evenly.")


class MapSideDiskSpill(DiagnosticTest):
    """≈ MapSideDiskSpill.java: spilled records beyond the final spill
    mean the sort buffer re-wrote map output to disk multiple times."""

    name = "map-side-disk-spill"
    title = "Map output spills to disk more than once"
    importance = "Medium"
    threshold = 0.3

    def evaluate(self, stats: JobStatistics) -> float:
        maps = stats.finished(is_map=True)
        out = sum(stats.counter(a, TaskCounter.MAP_OUTPUT_RECORDS)
                  for a in maps)
        spilled = sum(stats.counter(a, TaskCounter.SPILLED_RECORDS)
                      for a in maps)
        if out == 0 or spilled <= out:
            return 0.0
        # spilled == out is the single final spill; every extra multiple
        # is a full re-write of the map output
        return min(1.0, (spilled - out) / out)

    def prescription(self, stats: JobStatistics) -> str:
        return ("Raise io.sort.mb (or lower io.sort.spill.percent "
                "pressure) so map output fits the sort buffer in one "
                "spill; add a combiner to shrink records before the "
                "spill.")


class MapsReExecutionImpact(DiagnosticTest):
    """≈ MapsReExecutionImpact.java: failed map attempts re-ran work."""

    name = "maps-reexecution-impact"
    title = "Failed map attempts re-executed work"
    importance = "Medium"
    threshold = 0.3

    def evaluate(self, stats: JobStatistics) -> float:
        done = len(stats.finished(is_map=True))
        failed = len(stats.failed(is_map=True))
        if done + failed == 0:
            return 0.0
        return failed / (done + failed)

    def prescription(self, stats: JobStatistics) -> str:
        return ("Map attempts failed and re-ran: check task logs "
                "(tpumr job -logs), memory limits "
                "(mapred.task.maxvmem.mb), and input corruption.")


class ReducesReExecutionImpact(MapsReExecutionImpact):
    """≈ ReducesReExecutionImpact.java."""

    name = "reduces-reexecution-impact"
    title = "Failed reduce attempts re-executed work"

    def evaluate(self, stats: JobStatistics) -> float:
        done = len(stats.finished(is_map=False))
        failed = len(stats.failed(is_map=False))
        if done + failed == 0:
            return 0.0
        return failed / (done + failed)

    def prescription(self, stats: JobStatistics) -> str:
        return ("Reduce attempts failed and re-ran: check shuffle "
                "fetch failures and reducer memory use.")


class BackendPlacement(DiagnosticTest):
    """TPU-era rule (no reference analog — the GPU work's observability
    was log-only, SURVEY.md §5): when the measured acceleration factor
    says one backend is much faster, most map work should land there.
    Impact = share of map runtime spent on the slower backend, scaled by
    how lopsided the acceleration factor is."""

    name = "backend-placement"
    title = "Map work ran mostly on the slower backend"
    importance = "High"
    threshold = 0.4

    def evaluate(self, stats: JobStatistics) -> float:
        maps = stats.finished(is_map=True)
        accel = stats.acceleration_factor
        if not maps or not accel or accel <= 0:
            return 0.0
        tpu_t = sum(float(a.get("runtime", 0.0)) for a in maps
                    if a.get("run_on_tpu"))
        cpu_t = sum(float(a.get("runtime", 0.0)) for a in maps
                    if not a.get("run_on_tpu"))
        total = tpu_t + cpu_t
        if total == 0:
            return 0.0
        # accel > 1: TPU faster — impact is the CPU share; accel < 1:
        # CPU faster — impact is the TPU share. Near-1 factors mean the
        # backends are comparable and placement doesn't matter.
        lopsided = min(1.0, abs(accel - 1.0))
        slow_share = (cpu_t / total) if accel > 1.0 else (tpu_t / total)
        return lopsided * slow_share

    def prescription(self, stats: JobStatistics) -> str:
        fast = "TPU" if stats.acceleration_factor > 1.0 else "CPU"
        return (f"The measured acceleration factor "
                f"({stats.acceleration_factor:.2f}) says {fast} map "
                f"slots are faster for this job: raise that pool's slot "
                f"count (mapred.tasktracker.map."
                f"{fast.lower()}.tasks.maximum). The scheduler already "
                f"gives the slower pool a map only where that shortens "
                f"the job; the rollup's CPU_MAPS_WITHHELD and "
                f"estimate_from say whether it knew the factor in time "
                f"(a first job of its kind learns it as it runs).")


class MapGranularity(DiagnosticTest):
    """TPU-era rule: per-map runtime far below scheduling overhead means
    the job is paying heartbeat/launch latency per sliver of work (easy
    to hit with NLineInputFormat 1-line-per-map — the reference's GPU
    default config, conf/mapred-site.xml:14-21)."""

    name = "map-granularity"
    title = "Map tasks are too small to amortize scheduling"
    importance = "Low"
    threshold = 0.5
    min_useful_runtime = 1.0  # seconds

    def evaluate(self, stats: JobStatistics) -> float:
        maps = stats.finished(is_map=True)
        if len(maps) < 8:
            return 0.0
        mean = sum(float(a.get("runtime", 0.0)) for a in maps) / len(maps)
        if mean >= self.min_useful_runtime:
            return 0.0
        return 1.0 - mean / self.min_useful_runtime

    def prescription(self, stats: JobStatistics) -> str:
        return ("Increase split size (mapred.min.split.size, "
                "tpumr.dense.split.rows, or linespermap) so each map "
                "carries enough work to amortize launch and heartbeat "
                "latency.")


DEFAULT_TESTS: "list[DiagnosticTest]" = [
    BalancedReducePartitioning(),
    MapSideDiskSpill(),
    MapsReExecutionImpact(),
    ReducesReExecutionImpact(),
    BackendPlacement(),
    MapGranularity(),
]


def diagnose(events: "list[dict]",
             tests: "list[DiagnosticTest] | None" = None) -> dict:
    """Run every rule over one job's history events. Returns the report:
    ``{job_id, job_name, state, wall_time, findings: [...], passed: [...]}``
    with findings ordered High→Low importance then impact."""
    stats = JobStatistics.from_events(events)
    findings, passed = [], []
    for test in tests or DEFAULT_TESTS:
        impact = float(test.evaluate(stats))
        row = {"test": test.name, "title": test.title,
               "importance": test.importance, "impact": round(impact, 3),
               "threshold": test.threshold}
        if impact >= test.threshold:
            row["prescription"] = test.prescription(stats)
            findings.append(row)
        else:
            passed.append(row)
    rank = {"High": 0, "Medium": 1, "Low": 2}
    findings.sort(key=lambda r: (rank.get(r["importance"], 3),
                                 -r["impact"]))
    return {"job_id": stats.job_id, "job_name": stats.job_name,
            "state": stats.state, "wall_time": round(stats.wall_time, 3),
            "findings": findings, "passed": passed}


def diagnose_file(path: str) -> dict:
    """Diagnose a history .jsonl file (local path or any FS URL)."""
    from tpumr.fs import get_filesystem
    if "://" in path:
        data = get_filesystem(path).read_bytes(path).decode()
    else:
        with open(path) as f:
            data = f.read()
    events = [json.loads(line) for line in data.splitlines() if line.strip()]
    return diagnose(events)


def format_report(report: dict) -> str:
    lines = [f"Job {report['job_id']} ({report['job_name'] or 'unnamed'}) "
             f"state={report['state']} wall={report['wall_time']}s",
             f"{len(report['findings'])} finding(s), "
             f"{len(report['passed'])} rule(s) passed", ""]
    for f in report["findings"]:
        lines.append(f"[{f['importance'].upper()}] {f['title']} "
                     f"(impact {f['impact']:.2f} >= {f['threshold']})")
        lines.append(f"  rule: {f['test']}")
        for ln in f["prescription"].splitlines():
            lines.append(f"  {ln}")
        lines.append("")
    if not report["findings"]:
        lines.append("No problems detected.")
    return "\n".join(lines)
