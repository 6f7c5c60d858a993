"""Task child — the isolated per-attempt process main.

≈ ``org.apache.hadoop.mapred.Child`` (reference: src/mapred/org/apache/
hadoop/mapred/Child.java:69 main, :172 task fetch, :255 run): a separate
OS process per task attempt that talks to its tracker over an umbilical
RPC (≈ TaskUmbilicalProtocol, mapred/TaskUmbilicalProtocol.java:65) —
status/progress updates, kill polling, commit approval, and final
completion all flow through the tracker, never directly to the master.

Divergences from the reference, by design:

- the child is launched only for CPU map/reduce attempts when process
  isolation is enabled (``tpumr.task.isolation=process``): TPU tasks stay
  in the tracker process so kernels share one JAX runtime and the HBM
  split cache (tasktracker.py module docstring);
- task state is shipped in one self-contained task file (conf + task +
  umbilical address + job token) written into the attempt's sandbox dir,
  instead of being fetched over the umbilical after launch — one fewer
  startup round-trip, and it gives the setuid task-controller a single
  file whose ownership it can validate;
- there is no JVM-reuse pool (JvmManager.java:322-413): Python process
  startup is milliseconds, and idle-child reuse would keep dead task
  state alive across attempts.

The umbilical methods live on the tracker's existing RPC surface
(NodeRunner.umbilical_*). The child authenticates with its PER-JOB token
(≈ the reference's jobToken file + JobTokenSecretManager), never the
cluster secret: the RPC layer restricts token-scoped callers to the
umbilical/shuffle methods and each method pins the scope to its job.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from typing import Any

_PING_INTERVAL_S = 0.5
_STATUS_INTERVAL_S = 1.0


class _Umbilical:
    """Child side of the tracker umbilical: rate-limited kill polling and
    periodic status push (≈ Child.java's TaskReporter thread)."""

    def __init__(self, client: Any, aid: str) -> None:
        self.client = client
        self.aid = aid
        self._last_ping = 0.0
        self._killed = False

    def kill_requested(self) -> bool:
        # monotonic: the ping rate limit is interval arithmetic — a
        # clock step must not freeze (or flood) the kill poll
        now = time.monotonic()
        if self._killed:
            return True
        if now - self._last_ping >= _PING_INTERVAL_S:
            self._last_ping = now
            try:
                self._killed = bool(
                    self.client.call("umbilical_ping", self.aid))
            except Exception:  # noqa: BLE001 — tracker gone: die quietly
                self._killed = True
        return self._killed

    def push_status(self, reporter: Any, phase: str,
                    progress: float) -> None:
        try:
            self.client.call("umbilical_status", self.aid, {
                "phase": phase,
                "progress": progress,
                "counters": reporter.counters.to_dict(),
                "status": reporter.status,
                # liveness ticks for the tracker's reaper: the push
                # itself is a timer and must NOT count as progress — a
                # hung task keeps pushing identical payloads; only a
                # CHANGING tick count proves the task thread moves
                "ticks": reporter.ticks,
            })
        except Exception:  # noqa: BLE001
            pass


def run_child(task_file: str) -> int:
    """Execute the attempt described by ``task_file``; returns exit code."""
    from tpumr.io.writable import deserialize
    from tpumr.ipc.rpc import RpcClient
    from tpumr.mapred.api import Reporter, TaskKilledError
    from tpumr.mapred.jobconf import JobConf
    from tpumr.mapred.task import Task

    with open(task_file, "rb") as f:
        spec = deserialize(f.read())

    conf = JobConf()
    for k, v in spec["conf"].items():
        conf.set(k, v)
    task = Task.from_dict(spec["task"])
    job_id = spec["job_id"]
    aid = str(task.attempt_id)
    secret = spec.get("secret") or None
    scope = spec.get("scope") or None  # job-token identity (never the
    #                                    cluster secret — see process_runner)

    tracker = RpcClient(spec["tracker_host"], spec["tracker_port"],
                        secret=secret, scope=scope)
    umb = _Umbilical(tracker, aid)
    phase = ["MAP" if task.is_map else "SHUFFLE"]
    progress = [0.0]
    reporter = Reporter(abort_check=umb.kill_requested,
                        on_progress=lambda f: progress.__setitem__(0, f))

    stop = threading.Event()

    def status_loop() -> None:
        while not stop.wait(_STATUS_INTERVAL_S):
            umb.push_status(reporter, phase[0], progress[0])

    threading.Thread(target=status_loop, daemon=True,
                     name="umbilical-status").start()

    def can_commit() -> bool:
        return bool(tracker.call("umbilical_can_commit",
                                 str(task.task_id), aid))

    # distributed tracing: the task file's conf carries the trace flag +
    # dir and the Task carries the tracker's launch-span context — the
    # child's run span (and everything nested: spills, shuffle fetches)
    # joins the job trace across the process boundary
    from tpumr.core import tracing
    tracer = tracing.Tracer.from_conf(conf, "task") \
        if task.trace is not None else None
    run_span = None
    if tracer is not None:
        run_span = tracer.start_span(
            "task:run", task.trace["trace_id"], parent=task.trace,
            backend="cpu", attempt_id=aid, isolation="process",
            pid=os.getpid())

    trace_done_once = [False]

    def _trace_done(state: str) -> None:
        # idempotent: the success path finishes the span BEFORE the
        # umbilical_done RPC (so it can't be lost to a crash mid-call);
        # if that RPC then raises, the exception handler's call must not
        # write a second record with the same span_id
        if tracer is None or run_span is None or trace_done_once[0]:
            return
        trace_done_once[0] = True
        tracer.finish(run_span.set(state=state))
        tracer.flush()

    try:
        out_path, index = "", {}
        committed = True
        from tpumr.mapred.profiler import maybe_profile, profile_dir
        local_dir = os.path.dirname(os.path.abspath(task_file))
        prof_dir = profile_dir(conf, aid, local_dir)
        with tracing.activate(tracer, run_span):
            if task.is_map:
                from tpumr.mapred.map_task import run_map_task
                out_path, index = maybe_profile(
                    conf, task, prof_dir,
                    lambda: run_map_task(conf, task, local_dir, reporter))
                # direct-output maps AND map-side named outputs in jobs
                # with reducers; _commit no-ops with no files
                committed = _commit(conf, task, can_commit)
            else:
                from tpumr.mapred.reduce_task import run_reduce_task
                from tpumr.mapred.tasktracker import make_map_locator

                locate = make_map_locator(
                    lambda cursor: tracker.call("umbilical_events", job_id,
                                                cursor),
                    secret,
                    poll_s=conf.get_int("tpumr.shuffle.poll.ms",
                                        200) / 1000.0,
                    timeout_s=conf.get_int("tpumr.shuffle.timeout.ms",
                                           600_000) / 1000.0,
                    scope=scope)

                from tpumr.mapred.shuffle_copier import RemoteChunkSource
                conf.set("tpumr.task.local.dir",
                         os.path.join(local_dir, "shuffle"))
                fetch = RemoteChunkSource(conf, job_id, locate)

                def report_fetch_failure(map_index: int,
                                         map_attempt: str) -> None:
                    # best-effort: the copier's penalty/retry loop keeps
                    # the reduce alive even when the report can't be
                    # delivered
                    try:
                        tracker.call("umbilical_report_fetch_failure",
                                     aid, map_attempt)
                    except Exception:  # noqa: BLE001
                        pass

                fetch.on_fetch_failure = report_fetch_failure

                maybe_profile(conf, task, prof_dir,
                              lambda: run_reduce_task(conf, task, fetch,
                                                      reporter))
                phase[0] = "REDUCE"
                committed = _commit(conf, task, can_commit)
        stop.set()
        final = {
            "counters": reporter.counters.to_dict(),
            "progress": 1.0,
            "phase": phase[0],
            "state": "SUCCEEDED" if committed else "KILLED",
            "diagnostics": ("" if committed
                            else "commit denied: another attempt won"),
        }
        _trace_done(final["state"])
        tracker.call("umbilical_done", aid, final, job_id,
                     task.partition, out_path, index)
        return 0
    except TaskKilledError:
        stop.set()
        _trace_done("KILLED")
        _report_fail(tracker, aid, "KILLED",
                     "attempt killed while running (preempted or "
                     "superseded)")
        return 0
    except BaseException as e:  # noqa: BLE001 — task failure is data
        stop.set()
        diag = f"{type(e).__name__}: {e}\n" + traceback.format_exc(limit=8)
        if run_span is not None:
            run_span.set(error=diag.splitlines()[0])
        _trace_done("FAILED")
        # classification rides the umbilical so the master's demotion/
        # quarantine plane sees isolated attempts like in-process ones
        from tpumr.mapred.task import classify_exception
        _report_fail(tracker, aid, "FAILED", diag, classify_exception(e))
        return 1


def _commit(conf: Any, task: Any, can_commit: Any) -> bool:
    """Commit gate, child side (same contract as NodeRunner._commit): the
    tracker proxies the grant to the master; a losing attempt aborts its
    work dir and reports KILLED."""
    from tpumr.core import tracing
    from tpumr.mapred.output_formats import FileOutputCommitter
    committer = FileOutputCommitter(conf)
    aid = str(task.attempt_id)
    if not committer.needs_commit(aid):
        return True
    with tracing.span("task:commit", attempt_id=aid) as s:
        if can_commit():
            committer.commit_task(aid)
            return True
        if s is not None:
            s.set(denied=True)
        committer.abort_task(aid)
        return False


def _report_fail(tracker: Any, aid: str, state: str, diag: str,
                 failure_class: str = "") -> None:
    try:
        tracker.call("umbilical_fail", aid, state, diag, failure_class)
    except Exception:  # noqa: BLE001 — tracker reaps us by exit code
        pass


def main(argv: "list[str] | None" = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m tpumr.mapred.child <task-file>",
              file=sys.stderr)
        return 2
    # the tracker process owns the accelerator (one process per chip) and
    # only CPU attempts are isolated: host-side JAX work in this child
    # must never try to open the device, so it gets the CPU backend
    # before anything imports jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    return run_child(argv[0])


if __name__ == "__main__":
    sys.exit(main())
