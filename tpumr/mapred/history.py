"""Job history — structured event log.

≈ ``org.apache.hadoop.mapred.JobHistory`` (reference: src/mapred/org/apache/
hadoop/mapred/JobHistory.java, 2703 LoC — field-encoded line format parsed
by HistoryViewer/rumen). Re-designed as JSON-lines per job under
``tpumr.history.dir`` (one self-describing event per line), which serves the
same consumers: post-hoc job analysis, the web status JSON, and recovery
replay. Backend placement is a first-class field on every task event —
the reference's GPU observability was log-grep only (SURVEY.md §5).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any


def _json_safe(v: Any) -> bool:
    try:
        json.dumps(v)
        return True
    except (TypeError, ValueError):
        return False


class JobHistory:
    """Event-log writer. By default (``tpumr.history.async``) events are
    stamped at enqueue time and appended by one daemon writer thread off
    a bounded queue — the heartbeat's deferred phase pays a list append,
    never an fsync-adjacent ``open``/``write``. The queue is bounded
    (``tpumr.history.queue.max``); past the bound events are DROPPED and
    counted (``history_writes_dropped`` — a bench run must keep it 0).
    Recovery readers call :meth:`flush` first, so replay always sees
    every event the master logged before the read."""

    def __init__(self, conf: Any) -> None:
        self.dir = conf.get("tpumr.history.dir") if conf else None
        self._lock = threading.Lock()
        self._async = bool(conf.get_boolean("tpumr.history.async", True)
                           if conf else True)
        self._queue_max = int(conf.get_int("tpumr.history.queue.max",
                                           10_000) if conf else 10_000)
        self._cv = threading.Condition()
        self._queue: "list[tuple[str, dict]]" = []
        self._writing = False     # drain batch in flight (flush waits)
        self._stopped = False
        self._writer: "threading.Thread | None" = None
        self.writes_dropped = 0   # bound into metrics by the master

    # ------------------------------------------------------ write path

    def _write(self, job_id: str, event: dict) -> None:
        if not self.dir:
            return
        event["ts"] = time.time()   # stamped at ENQUEUE: event time,
        #                             not whenever the writer drains
        if not self._async:
            self._write_now([(job_id, event)])
            return
        with self._cv:
            if not self._stopped:
                if len(self._queue) >= self._queue_max:
                    self.writes_dropped += 1
                    return
                self._queue.append((job_id, event))
                if self._writer is None:
                    self._writer = threading.Thread(
                        target=self._drain, name="history-writer",
                        daemon=True)
                    self._writer.start()
                self._cv.notify_all()
                return
        # post-stop stragglers (late finalization racing shutdown)
        # write synchronously so nothing is silently lost
        self._write_now([(job_id, event)])

    def _write_now(self, batch: "list[tuple[str, dict]]") -> None:
        """Append a batch, one ``open`` per job file (per-file order is
        the enqueue order; cross-file order carries no meaning)."""
        by_job: "dict[str, list[str]]" = {}
        for job_id, event in batch:
            by_job.setdefault(job_id, []).append(
                json.dumps(event) + "\n")
        os.makedirs(self.dir, exist_ok=True)
        with self._lock:
            for job_id, lines in by_job.items():
                with open(os.path.join(self.dir,
                                       f"{job_id}.jsonl"), "a") as f:
                    f.write("".join(lines))

    def _drain(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._stopped:
                    self._cv.wait(0.5)
                batch, self._queue = self._queue, []
                stopped = self._stopped
                self._writing = bool(batch)
            if batch:
                try:
                    self._write_now(batch)
                except OSError:
                    self.writes_dropped += len(batch)
                with self._cv:
                    self._writing = False
                    self._cv.notify_all()
            if stopped and not batch:
                return

    def queue_depth(self) -> int:
        return len(self._queue) + (1 if self._writing else 0)

    def flush(self, timeout_s: float = 10.0) -> bool:
        """Block until every enqueued event is on disk (readers that
        replay the log — recovery, retired-status serving — call this
        first). True when the queue fully drained."""
        if not self._async or not self.dir:
            return True
        deadline = time.monotonic() + timeout_s
        with self._cv:
            self._cv.notify_all()
            while self._queue or self._writing:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(0.05, left))
        return True

    def stop(self, timeout_s: float = 10.0) -> None:
        """Flush and retire the writer thread (master shutdown). The
        log must be complete on disk before ``stop()`` returns — a
        restart immediately replays it."""
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
            writer = self._writer
        if writer is not None:
            writer.join(timeout=timeout_s)

    def job_submitted(self, jip: Any) -> None:
        self._write(str(jip.job_id), {
            "event": "JOB_SUBMITTED",
            "job_id": str(jip.job_id),
            "job_name": jip.conf.get("mapred.job.name", ""),
            "num_maps": jip.num_maps,
            "num_reduces": jip.num_reduces,
            "kernel": jip.conf.get("tpumr.map.kernel"),
            "priority": jip.priority,
            # full submission payload so a restarted master can replay the
            # job (≈ RecoveryManager reading the job-info staging file)
            "conf": {k: v for k, v in jip.conf.items()
                     if _json_safe(v)},
            # keys whose values can't ride the wire (in-process class
            # objects): recovery refuses to replay such jobs rather than
            # resubmitting them broken
            "conf_dropped": sorted(k for k, v in jip.conf.items()
                                   if not _json_safe(v)),
            "splits": [t.split for t in jip.maps],
        })

    def job_recovered(self, old_job_id: str, new_job_id: str) -> None:
        """Marks the interrupted job as resubmitted (so a second restart
        doesn't replay it again)."""
        self._write(old_job_id, {"event": "JOB_RECOVERED",
                                 "job_id": old_job_id,
                                 "new_job_id": new_job_id})

    def incomplete_jobs(self) -> list[dict]:
        """JOB_SUBMITTED events of jobs with no terminal/recovered marker —
        the restart-recovery work list (≈ RecoveryManager.recover,
        JobTracker.java:1203)."""
        import glob
        if not self.dir:
            return []
        self.flush()
        out = []
        for path in sorted(glob.glob(os.path.join(self.dir, "*.jsonl"))):
            submitted = None
            finished = False
            priority = None
            for ev in self.read(path):
                kind = ev.get("event")
                if kind == "JOB_SUBMITTED":
                    submitted = ev
                elif kind in ("JOB_FINISHED", "JOB_RECOVERED",
                              "JOB_RECOVERY_FAILED"):
                    finished = True
                elif kind == "JOB_PRIORITY_CHANGED":
                    priority = ev.get("priority")
            if submitted is not None and not finished \
                    and submitted.get("conf") is not None:
                if priority:
                    # replay runtime priority changes into the conf the
                    # recovery resubmits — a restart must not silently
                    # revert `job -set-priority`
                    submitted["conf"]["mapred.job.priority"] = priority
                out.append(submitted)
        return out

    def incomplete_pipelines(self) -> "list[dict]":
        """PIPELINE_SUBMITTED records (full graph payload) of pipelines
        with no terminal marker, plus their replayed stage submissions
        — the pipeline half of restart recovery. A PIPELINE_RECOVERED
        marker does NOT finish the file: the pipeline keeps its id
        across restarts and a second crash replays it again (stage-job
        aliasing is the jobs' problem, handled by the caller)."""
        import glob
        if not self.dir:
            return []
        self.flush()
        out = []
        for path in sorted(glob.glob(os.path.join(self.dir,
                                                  "pipe_*.jsonl"))):
            submitted = None
            finished = False
            stages: "list[dict]" = []
            for ev in self.read(path):
                kind = ev.get("event")
                if kind == "PIPELINE_SUBMITTED":
                    submitted = ev
                elif kind in ("PIPELINE_FINISHED",
                              "PIPELINE_RECOVERY_FAILED"):
                    finished = True
                elif kind == "PIPELINE_STAGE_SUBMITTED":
                    stages.append(ev)
            if submitted is not None and not finished \
                    and submitted.get("graph"):
                out.append({"pipeline_id": submitted["pipeline_id"],
                            "graph": submitted["graph"],
                            "user": submitted.get("user", ""),
                            "stages": stages})
        return out

    def recovered_attempt_state(self, job_id: str) -> dict:
        """Replay one interrupted job's attempt-level outcome from its
        event log (≈ RecoveryManager.JobRecoveryListener walking the
        history file): the LAST successful attempt per task, with the
        detail a restarted master needs to adopt the work instead of
        re-running it — attempt id, serving tracker + shuffle address
        (map outputs), backend, runtime, and counters. ``MAP_OUTPUT_LOST``
        events (fetch-failure withdrawals, lost trackers) erase the
        outputs the old master already declared gone. Returns
        ``{"maps": {partition: record}, "reduces": {partition: record}}``.
        """
        from tpumr.mapred.ids import TaskAttemptID
        maps: dict[int, dict] = {}
        reduces: dict[int, dict] = {}
        if not self.dir:
            return {"maps": maps, "reduces": reduces}
        self.flush()
        path = os.path.join(self.dir, f"{job_id}.jsonl")
        if not os.path.exists(path):
            return {"maps": maps, "reduces": reduces}
        for ev in self.read(path):
            kind = ev.get("event")
            aid = str(ev.get("attempt_id", "") or "")
            if not aid:
                continue
            try:
                attempt = TaskAttemptID.parse(aid)
            except (ValueError, IndexError):
                continue
            idx = attempt.task.id
            if kind == "TASK_FINISHED":
                rec = {
                    "attempt_id": aid,
                    "attempt": attempt.attempt,
                    "is_map": bool(attempt.task.is_map),
                    "runtime": float(ev.get("runtime", 0.0) or 0.0),
                    "tracker": ev.get("tracker", ""),
                    "shuffle_addr": ev.get("shuffle_addr", "") or "",
                    "run_on_tpu": bool(ev.get("run_on_tpu", False)),
                    "tpu_device_id": int(ev.get("tpu_device_id", -1)),
                    "counters": ev.get("counters") or {},
                    "ts": float(ev.get("ts", 0.0) or 0.0),
                }
                (maps if attempt.task.is_map else reduces)[idx] = rec
            elif kind == "MAP_OUTPUT_LOST":
                # the old master withdrew this output (too many fetch
                # failures, or its tracker was lost) — whatever replaced
                # it appears as a LATER TASK_FINISHED, or not at all
                cur = maps.get(idx)
                if cur is not None and cur["attempt_id"] == aid:
                    del maps[idx]
        return {"maps": maps, "reduces": reduces}

    def retired_job_status(self, job_id: str) -> "dict | None":
        """Terminal status of a job known only to HISTORY — a restarted
        master serving polls for jobs that finished (or were already
        recovered) before the crash, ≈ the reference JobTracker's
        retired-jobs cache backed by completed-job history. Returns a
        client-shaped status dict; for a job an EARLIER master already
        resubmitted, ``recovered_as`` names the successor id to chase.
        None when this job's history holds no outcome."""
        if not self.dir:
            return None
        self.flush()
        path = os.path.join(self.dir, f"{job_id}.jsonl")
        if not os.path.exists(path):
            return None
        submitted: "dict | None" = None
        outcome: "dict | None" = None
        for ev in self.read(path):
            kind = ev.get("event")
            if kind == "JOB_SUBMITTED":
                submitted = ev
            elif kind in ("JOB_FINISHED", "JOB_RECOVERED",
                          "JOB_RECOVERY_FAILED"):
                outcome = ev
        if outcome is None:
            return None
        if outcome["event"] == "JOB_RECOVERED":
            return {"job_id": job_id,
                    "recovered_as": outcome.get("new_job_id"), }
        #: the submit-time conf, for the caller's job-view ACL check
        #: (popped before the status goes on the wire)
        acl_conf = (submitted or {}).get("conf") or {}
        n_maps = int((submitted or {}).get("num_maps", 0) or 0)
        n_reduces = int((submitted or {}).get("num_reduces", 0) or 0)
        if outcome["event"] == "JOB_FINISHED":
            state = str(outcome.get("state", "SUCCEEDED"))
            error = str(outcome.get("error", "") or "")
        else:   # JOB_RECOVERY_FAILED
            state = "FAILED"
            error = (f"recovery failed after a master restart: "
                     f"{outcome.get('error', '')}")
        done = state == "SUCCEEDED"
        return {
            "job_id": job_id, "state": state, "priority": "NORMAL",
            "map_progress": 1.0 if done else 0.0,
            "reduce_progress": 1.0 if done else 0.0,
            "finished_maps": n_maps if done else 0,
            "finished_tpu_maps": int(
                outcome.get("finished_tpu_maps", 0) or 0),
            "finished_cpu_maps": int(
                outcome.get("finished_cpu_maps", 0) or 0),
            "num_maps": n_maps, "num_reduces": n_reduces,
            "cpu_map_mean_time": float(
                outcome.get("cpu_map_mean_time", 0.0) or 0.0),
            "tpu_map_mean_time": float(
                outcome.get("tpu_map_mean_time", 0.0) or 0.0),
            "acceleration_factor": float(
                outcome.get("acceleration_factor", 0.0) or 0.0),
            "placement_seq": "", "tpu_disabled": False,
            "tpu_demoted_tips": 0,
            "error": error,
            "retired": True,   # served from history, not a live JIP
            "_acl_conf": acl_conf,
        }

    def job_finished(self, jip: Any) -> None:
        self._write(str(jip.job_id), {
            "event": "JOB_FINISHED",
            "job_id": str(jip.job_id),
            "state": jip.state,
            "wall_time": (jip.finish_time or time.time()) - jip.start_time,
            "finished_cpu_maps": jip.finished_cpu_maps,
            "finished_tpu_maps": jip.finished_tpu_maps,
            "cpu_map_mean_time": jip.cpu_map_mean_time(),
            "tpu_map_mean_time": jip.tpu_map_mean_time(),
            "acceleration_factor": jip.acceleration_factor(),
            # the assignment-order backend series + stamps: the hybrid
            # convergence curve, plottable from the history file alone
            "placement": jip.placement_timeline(),
            "error": jip.error,
        })

    def task_event(self, job_id: str, event: str, **fields: Any) -> None:
        self._write(job_id, {"event": event, **fields})

    # ------------------------------------------------------ stats rollup

    def metrics_path(self, job_id: str) -> "str | None":
        """Where the job's stats rollup lives, next to its event log."""
        if not self.dir:
            return None
        return os.path.join(self.dir, f"metrics-{job_id}.json")

    def write_job_metrics(self, jip: Any) -> "str | None":
        """One-shot per-job stats rollup written at finalization:
        counters plus exact latency percentiles and the TPU-vs-CPU
        task-time split. The machine-readable substrate for ``tpumr job
        stats`` today and for affinity/critical-path scheduling to mine
        later — the history event log answers "what happened", this
        answers "how fast"."""
        path = self.metrics_path(str(jip.job_id))
        if path is None:
            return None
        os.makedirs(self.dir, exist_ok=True)
        rollup = job_metrics_rollup(jip)
        tmp = path + ".tmp"
        with self._lock:
            with open(tmp, "w") as f:
                json.dump(rollup, f, indent=2, default=str)
            os.replace(tmp, path)   # readers never see a torn rollup
        return path

    def read_job_metrics(self, job_id: str) -> "dict | None":
        path = self.metrics_path(job_id)
        if path is None or not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)

    @staticmethod
    def read(path: str) -> list[dict]:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]


def job_metrics_rollup(jip: Any) -> dict:
    """Build the stats rollup from a (terminal) JobInProgress. Exact
    percentiles — the job kept every successful attempt's runtime — and
    the task-time split from those same raw samples (NOT the scheduler's
    profile sums, which deliberately unwind on TPU quarantine)."""
    from tpumr.metrics.histogram import exact_percentiles
    cpu_cost, t_tpu = jip.map_costs()
    with jip.lock:
        map_rts = list(jip.map_runtimes)
        reduce_rts = list(jip.reduce_runtimes)
        dropped = jip.runtimes_dropped
        counters = jip.counters.to_dict()
        state = jip.state
        finish = jip.finish_time
    tpu = [r for r, on_tpu in map_rts if on_tpu]
    cpu = [r for r, on_tpu in map_rts if not on_tpu]
    tpu_s, cpu_s = sum(tpu), sum(cpu)
    map_task_s = tpu_s + cpu_s
    observed_accel = ((cpu_s / len(cpu)) / (tpu_s / len(tpu))
                      if tpu and cpu and tpu_s > 0 else 0.0)
    return {
        "job_id": str(jip.job_id),
        "job_name": str(jip.conf.get("mapred.job.name", "") or ""),
        "state": state,
        "wall_time": (finish or time.time()) - jip.start_time,
        "num_maps": len(jip.maps),
        "num_reduces": len(jip.reduces),
        "map_latency": exact_percentiles([r for r, _ in map_rts]),
        "map_latency_tpu": exact_percentiles(tpu),
        "map_latency_cpu": exact_percentiles(cpu),
        "reduce_latency": exact_percentiles(reduce_rts),
        "task_time_split": {
            "tpu_map_s": tpu_s,
            "cpu_map_s": cpu_s,
            "reduce_s": sum(reduce_rts),
            "tpu_fraction_of_map_time":
                tpu_s / map_task_s if map_task_s > 0 else 0.0,
        },
        # the scheduler's estimate as the job ended (map_cost.py): what
        # it took a CPU map and a TPU slot's turn to cost (the turn
        # alone, and beside a running CPU map of the job), where the CPU
        # number came from (job | running | carried | none), and the
        # ratio of the first two; "observed" is finished maps' means
        # alone
        "t_cpu_estimate_s": cpu_cost.seconds,
        "t_tpu_turn_s": t_tpu.alone,
        "t_tpu_turn_beside_cpu_s": t_tpu.beside,
        "estimate_from": cpu_cost.source,
        "acceleration_factor_profiled": jip.acceleration_factor(),
        "acceleration_factor_observed": observed_accel,
        "finished_tpu_maps": len(tpu),
        "finished_cpu_maps": len(cpu),
        "runtime_samples_dropped": dropped,
        "counters": counters,
    }
