"""LocalJobRunner — in-process job execution, no daemons.

≈ ``org.apache.hadoop.mapred.LocalJobRunner`` (reference: src/mapred/org/
apache/hadoop/mapred/LocalJobRunner.java:51): the same submission surface as
the distributed runtime (splits → map attempts → shuffle → reduce attempts →
commit) executed in one process; the debugging/API-testing tier of the
reference's test strategy (SURVEY.md §4.3). Map tasks run on a thread pool
(``mapred.local.map.tasks.maximum``); with a registered device kernel
(JobConf.set_map_kernel) maps run through the TPU runner when
``tpumr.local.run.on.tpu`` is set — the single-process analog of hybrid
placement.
"""

from __future__ import annotations

import shutil
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

from tpumr.core.counters import Counters, JobCounter, TaskCounter
from tpumr.mapred.api import Reporter
from tpumr.mapred.ids import JobID, TaskAttemptID, TaskID
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.map_task import run_map_task
from tpumr.mapred.output_formats import FileOutputCommitter
from tpumr.mapred.reduce_task import local_fetch_factory, run_reduce_task
from tpumr.mapred.task import Task
from tpumr.utils.reflection import new_instance


@dataclass
class JobResult:
    job_id: JobID
    successful: bool
    counters: Counters = field(default_factory=Counters)
    num_maps: int = 0
    num_reduces: int = 0
    wall_time: float = 0.0
    error: str = ""


class LocalJobRunner:
    _seq = 0
    _seq_lock = threading.Lock()

    def __init__(self, conf: JobConf | None = None) -> None:
        self.conf = conf or JobConf()

    def submit_job(self, job_conf: JobConf) -> JobResult:
        with LocalJobRunner._seq_lock:
            LocalJobRunner._seq += 1
            job_id = JobID("local", LocalJobRunner._seq)
        t0 = time.monotonic()
        work_root = tempfile.mkdtemp(prefix=f"tpumr-{job_id}-")
        counters = Counters()
        try:
            result = self._run(job_id, job_conf, work_root, counters)
            result.wall_time = time.monotonic() - t0
            return result
        finally:
            shutil.rmtree(work_root, ignore_errors=True)

    def _run(self, job_id: JobID, conf: JobConf, work_root: str,
             counters: Counters) -> JobResult:
        from tpumr.mapred.device_shuffle import (is_device_shuffle,
                                                prepare_device_shuffle_job)
        prepare_device_shuffle_job(conf)  # collapses reduces to 1 gang task
        in_fmt = new_instance(conf.get_input_format(), conf)
        out_fmt = new_instance(conf.get_output_format(), conf)
        out_fmt.check_output_specs(conf)
        splits = in_fmt.get_splits(conf, conf.num_map_tasks_hint)
        num_reduces = conf.num_reduce_tasks
        committer = FileOutputCommitter(conf)
        committer.setup_job()

        run_on_tpu = (conf.get_boolean("tpumr.local.run.on.tpu", False)
                      and (conf.get_map_kernel() is not None
                           or bool(conf.get("tpumr.pipes.tpu.executable"))))

        # ---- map phase
        map_outputs: list[tuple[str, dict] | None] = [None] * len(splits)
        tasks = [
            Task(TaskAttemptID(TaskID(job_id, True, i), 0), partition=i,
                 num_reduces=num_reduces, split=splits[i].to_dict(),
                 run_on_tpu=run_on_tpu,
                 tpu_device_id=0 if run_on_tpu else -1)
            for i in range(len(splits))
        ]

        def one_map(i: int) -> None:
            task = tasks[i]
            reporter = Reporter()
            local_dir = f"{work_root}/map_{i:06d}"
            out = run_map_task(conf, task, local_dir, reporter)
            task.__dict__.pop("_device_prefetch", None)  # free window memory
            if num_reduces == 0 or \
                    committer.needs_commit(str(task.attempt_id)):
                # the OR arm: map-side named outputs (lib.MultipleOutputs)
                # in jobs with reducers
                committer.commit_task(str(task.attempt_id))
            map_outputs[i] = out
            counters.merge(reporter.counters)
            counters.incr(JobCounter.GROUP, JobCounter.LAUNCHED_MAP_TASKS)

        pool_size = conf.get_int("mapred.local.map.tasks.maximum", 1)
        if pool_size > 1:
            with ThreadPoolExecutor(max_workers=pool_size) as pool:
                list(pool.map(one_map, range(len(splits))))
        else:
            # TPU kernel jobs run map windows through the two-phase device
            # pipeline: dispatch a whole window of kernels, fetch every
            # task's output in ONE device_get (tpu_runner.prelaunch_device_
            # maps), then drain each task through the normal collect/spill
            # path — device_get calls per job drop from O(tasks) to
            # O(tasks / window)
            window = (conf.get_int("tpumr.tpu.pipeline.window", 32)
                      if run_on_tpu else 0)
            lo = 0
            while lo < len(splits):
                hi = min(lo + window, len(splits)) if window > 0 else len(splits)
                if window > 0:
                    from tpumr.mapred.tpu_runner import prelaunch_device_maps
                    pre = prelaunch_device_maps(conf, tasks[lo:hi])
                    if pre is None:
                        window, hi = 0, len(splits)  # ineligible: plain path
                    else:
                        hi = lo + len(pre)  # byte budget may shorten a window
                        for t, p in zip(tasks[lo:hi], pre):
                            t._device_prefetch = p
                for i in range(lo, hi):
                    one_map(i)
                lo = hi

        # ---- reduce phase
        if num_reduces > 0 and is_device_shuffle(conf):
            # ONE gang task owns the local mesh: exchange + sort on device
            from tpumr.mapred.device_shuffle import (local_dense_fetch,
                                                    run_device_reduce)
            attempt = TaskAttemptID(TaskID(job_id, False, 0), 0)
            task = Task(attempt, partition=0, num_reduces=1,
                        num_maps=len(splits))
            reporter = Reporter()
            run_device_reduce(conf, task, local_dense_fetch(map_outputs),
                              reporter)
            committer.commit_task(str(attempt))
            counters.merge(reporter.counters)
            counters.incr(JobCounter.GROUP, JobCounter.LAUNCHED_REDUCE_TASKS)
        elif num_reduces > 0:
            fetch = local_fetch_factory([mo for mo in map_outputs])  # type: ignore[misc]
            for r in range(num_reduces):
                attempt = TaskAttemptID(TaskID(job_id, False, r), 0)
                task = Task(attempt, partition=r, num_reduces=num_reduces,
                            num_maps=len(splits))
                reporter = Reporter()
                run_reduce_task(conf, task, fetch, reporter)
                committer.commit_task(str(attempt))
                counters.merge(reporter.counters)
                counters.incr(JobCounter.GROUP, JobCounter.LAUNCHED_REDUCE_TASKS)

        committer.commit_job()
        return JobResult(job_id, True, counters, len(splits), num_reduces)


def run_job(conf: JobConf) -> JobResult:
    """≈ JobClient.runJob: submit and wait (local by default; the distributed
    client takes over when mapred.job.tracker is set — stage 5)."""
    return LocalJobRunner().submit_job(conf)
