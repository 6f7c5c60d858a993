"""Map-side execution: the collect → sort → spill → merge pipeline.

≈ ``org.apache.hadoop.mapred.MapTask`` (reference: src/mapred/org/apache/
hadoop/mapred/MapTask.java, 1758 LoC): ``MapOutputBuffer`` (:869 — the
kvbuffer/kvindices in-memory ring), ``sortAndSpill`` (:1396 — partitioned
sort + combiner at spill time), ``mergeParts`` (:1621 — final merge of spills
into one IFile + index). The ring buffer's byte-level accounting is replaced
by a Python list with byte tallies; spill thresholds (io.sort.mb ×
io.sort.spill.percent) and the combiner-at-spill semantics are kept.

Runner selection ≈ MapTask.java:433-438: ``run_on_tpu`` picks the job's TPU
map runner (JobConf.get_tpu_map_runner_class) over the CPU MapRunner —
exactly where the reference chooses PipesGPUMapRunner.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Iterable, Iterator

from tpumr.core.counters import BackendCounter, Counters, TaskCounter
from tpumr.io import ifile
from tpumr.io.writable import serialize
from tpumr.mapred.api import OutputCollector, Reporter
from tpumr.mapred.split import InputSplit
from tpumr.mapred.task import Task, TaskPhase
from tpumr.utils.reflection import new_instance


class MapOutputBuffer:
    """In-memory partitioned k/v buffer with threshold spills."""

    def __init__(self, conf: Any, num_partitions: int, local_dir: str,
                 reporter: Reporter) -> None:
        self.conf = conf
        self.n_parts = max(1, num_partitions)
        self.local_dir = local_dir
        self.reporter = reporter
        self.partitioner = new_instance(conf.get_partitioner_class(), conf)
        self.comparator = conf.get_output_key_comparator()
        # combiner is instantiated per spill and closed after each combine
        # round (Hadoop semantics: CombinerRunner creates it per use) — this
        # also lets subprocess-backed combiners (StreamCombiner) finish their
        # child deterministically
        self.combiner_cls = conf.get_combiner_class()
        self.combiner = self.combiner_cls  # truthiness gate for callers
        self.codec = conf.compress_map_output
        self._buf: list[tuple[int, bytes, bytes]] = []
        self._bytes = 0
        self._threshold = int(conf.sort_mb * 1024 * 1024 * conf.spill_percent)
        self._spills: list[tuple[str, dict]] = []
        self._c_out_records = reporter.counters.counter(
            TaskCounter.FRAMEWORK_GROUP, TaskCounter.MAP_OUTPUT_RECORDS)
        self._c_out_bytes = reporter.counters.counter(
            TaskCounter.FRAMEWORK_GROUP, TaskCounter.MAP_OUTPUT_BYTES)
        os.makedirs(local_dir, exist_ok=True)

    # ------------------------------------------------------------ collect

    def collect(self, key: Any, value: Any) -> None:
        part = self.partitioner.get_partition(key, value, self.n_parts)
        if not 0 <= part < self.n_parts:
            raise ValueError(f"partition {part} out of range [0,{self.n_parts})")
        kb, vb = serialize(key), serialize(value)
        self._buf.append((part, kb, vb))
        self._bytes += len(kb) + len(vb) + 16
        # hoisted Counter objects: this runs once per map OUTPUT record
        self._c_out_records.increment()
        self._c_out_bytes.increment(len(kb) + len(vb))
        if self._bytes >= self._threshold:
            self.sort_and_spill()

    def collect_raw_batch(self, parts: "list[int]", kbs: "list[bytes]",
                          vbs: "list[bytes]") -> None:
        """Batched ingest for the TPU runner (whole kernel output at once).
        Same accounting and validation as the scalar :meth:`collect` path —
        including the spill threshold, checked at every crossing MID-batch:
        a kernel batch larger than ``io.sort.mb`` must spill as it lands,
        not overshoot the buffer by the whole batch."""
        nbytes = 0
        for p, kb, vb in zip(parts, kbs, vbs):
            if not 0 <= p < self.n_parts:
                raise ValueError(f"partition {p} out of range [0,{self.n_parts})")
            self._buf.append((p, kb, vb))
            nbytes += len(kb) + len(vb)
            self._bytes += len(kb) + len(vb) + 16
            if self._bytes >= self._threshold:
                self.sort_and_spill()
        self.reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                   TaskCounter.MAP_OUTPUT_RECORDS, len(kbs))
        self.reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                   TaskCounter.MAP_OUTPUT_BYTES, nbytes)

    # ------------------------------------------------------------ spill

    def sort_and_spill(self) -> None:
        """≈ MapTask.sortAndSpill (MapTask.java:1396)."""
        if not self._buf:
            return
        from tpumr.core import tracing
        with tracing.span("map:spill", records=len(self._buf),
                          bytes=self._bytes, spill=len(self._spills)):
            self._sort_and_spill_inner()

    def _sort_and_spill_inner(self) -> None:
        sk = self.comparator.sort_key
        self._buf.sort(key=lambda rec: (rec[0], sk(rec[1])))
        spill_path = os.path.join(self.local_dir,
                                  f"spill{len(self._spills)}.out")
        with open(spill_path, "wb") as f:
            w = ifile.Writer(f, codec=self.codec)
            idx = 0
            for part in range(self.n_parts):
                w.start_partition()
                lo = idx
                while idx < len(self._buf) and self._buf[idx][0] == part:
                    idx += 1
                records: "Iterator[tuple[bytes, bytes]]" = \
                    (rec[1:] for rec in self._buf[lo:idx])
                if self.combiner is not None:
                    records = self._combine(records)
                for kb, vb in records:
                    w.append_raw(kb, vb)
                w.end_partition()
            index = w.close()
        self.reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                   TaskCounter.SPILLED_RECORDS, len(self._buf))
        self._spills.append((spill_path, index))
        self._buf.clear()
        self._bytes = 0

    def _combine(self, records: "Iterable[tuple[bytes, bytes]]"
                 ) -> "Iterator[tuple[bytes, bytes]]":
        """Run the combiner over one partition's sorted record stream
        (≈ combiner invocation inside sortAndSpill) — STREAMING, one key
        group resident at a time (combine.combined_stream), never the
        whole partition."""
        from tpumr.mapred.combine import combined_stream
        return combined_stream(self.conf, self.combiner_cls,
                               self.comparator.sort_key, records,
                               self.reporter)

    # ------------------------------------------------------------ finish

    def flush(self) -> tuple[str, dict]:
        """Final spill + merge ≈ MapTask.mergeParts (MapTask.java:1621).
        Returns (output_path, index) of the single merged IFile."""
        self.sort_and_spill()
        final_path = os.path.join(self.local_dir, "file.out")
        if not self._spills:
            # empty output: one empty segment per partition
            with open(final_path, "wb") as f:
                w = ifile.Writer(f, codec=self.codec)
                for _ in range(self.n_parts):
                    w.start_partition()
                    w.end_partition()
                index = w.close()
            return final_path, index
        if len(self._spills) == 1:
            path, index = self._spills[0]
            os.replace(path, final_path)
            return final_path, index
        from tpumr.core import tracing
        with tracing.span("map:merge", spills=len(self._spills)):
            return self._merge_spills(final_path)

    def _merge_spills(self, final_path: str) -> tuple[str, dict]:
        """Final merge of the spill files (≈ mergeParts) with BOUNDED
        fan-in: ``io.sort.factor`` caps open streams / heap entries per
        pass (intermediate passes land in ``merge-tmp`` as IFile runs —
        io.merger.BoundedMerge), spill partitions stream through
        per-chunk file reads instead of one held-open fd per spill, and
        the combiner runs group-at-a-time over the merged stream instead
        of materializing the partition."""
        from tpumr.io import merger as merge_engine
        from tpumr.mapred.shuffle_copier import spill_region_segment
        sk = self.comparator.sort_key
        factor = self.conf.sort_factor
        run_dir = os.path.join(self.local_dir, "merge-tmp")
        with open(final_path, "wb") as f:
            w = ifile.Writer(f, codec=self.codec)
            for part in range(self.n_parts):
                w.start_partition()
                segs = [spill_region_segment(p, idx, part)
                        for p, idx in self._spills]
                bm = merge_engine.BoundedMerge(
                    segs, sk, factor, run_dir=run_dir,
                    reporter=self.reporter, prefix=f"spill-p{part}")
                try:
                    merged: "Iterator[tuple[bytes, bytes]]" = iter(bm)
                    if self.combiner is not None:
                        merged = self._combine(merged)
                    for kb, vb in merged:
                        w.append_raw(kb, vb)
                finally:
                    bm.close()
                w.end_partition()
            index = w.close()
        import shutil
        shutil.rmtree(run_dir, ignore_errors=True)
        for p, _ in self._spills:
            os.remove(p)
        return final_path, index


def localize_task_conf(conf: Any, task: Task) -> Any:
    """Per-attempt conf copy with the task's identity keys set ≈
    Task.localizeConfiguration (mapred.task.id / mapred.task.partition /
    mapred.task.is.map). A copy, not a mutation — tasks share the job conf
    and may run concurrently in one process."""
    from tpumr.mapred.jobconf import JobConf
    local = JobConf(conf)
    local.set("tpumr.task.attempt.id", str(task.attempt_id))
    local.set("tpumr.task.partition", task.partition)
    local.set("tpumr.task.is.map", task.is_map)
    return local


def run_map_task(conf: Any, task: Task, local_dir: str,
                 reporter: Reporter | None = None,
                 status: Any = None) -> tuple[str, dict]:
    """Execute one map attempt ≈ MapTask.run → runOldMapper
    (MapTask.java:340,402): read split, select CPU/TPU runner, collect into
    the buffer, flush to the merged IFile. Returns (output_path, index).

    Map-only jobs (num_reduces == 0) write through the OutputFormat into the
    committer work dir instead (reference behavior: NewDirectOutputCollector).
    """
    reporter = reporter or Reporter()
    conf = localize_task_conf(conf, task)
    from tpumr.utils.fi import fires, maybe_fail
    maybe_fail("map.task", conf)
    if fires("task.hang", conf) or fires(f"task.hang.m{task.partition}",
                                         conf):
        _hang_silently(reporter)
    if fires("task.slow", conf) or fires(f"task.slow.m{task.partition}",
                                         conf):
        _run_slowly(conf, reporter)
    split = InputSplit.from_dict(task.split) if task.split else None
    if split is not None and getattr(split, "path", None):
        # the split's source path, for mappers that dispatch per input
        # source (contrib.datajoin) ≈ map.input.file in the reference
        conf.set("tpumr.task.input.path", str(split.path))
    in_fmt = new_instance(conf.get_input_format(), conf)
    t0 = time.monotonic()

    if task.run_on_tpu:
        runner_cls = conf.get_tpu_map_runner_class()
        backend_tasks, backend_ms = (BackendCounter.TPU_MAP_TASKS,
                                     BackendCounter.TPU_MAP_MILLIS)
    else:
        runner_cls = _cpu_runner_class(conf)
        backend_tasks, backend_ms = (BackendCounter.CPU_MAP_TASKS,
                                     BackendCounter.CPU_MAP_MILLIS)

    def run_mapper(collector: Any) -> None:
        """Batch fast path when eligible, else the per-record runner —
        built HERE so a vectorized split never constructs (and
        configures) a throwaway runner+mapper pair."""
        if task.run_on_tpu or not _host_batch_fast_path(
                conf, in_fmt, split, collector, reporter):
            runner = new_instance(runner_cls, conf)
            reader = _counted_reader(in_fmt, split, conf, reporter)
            runner.run(reader, collector, reporter, task_ctx=task)

    if task.num_reduces == 0:
        from tpumr.mapred.output_formats import FileOutputCommitter
        committer = FileOutputCommitter(conf)
        wd = committer.setup_task(str(task.attempt_id))
        conf.set("tpumr.task.work.dir", wd)  # lib.MultipleOutputs seam
        out_fmt = new_instance(conf.get_output_format(), conf)
        writer = out_fmt.get_record_writer(conf, wd, task.partition)
        collector = OutputCollector(
            writer.write, getattr(writer, "write_fixed_rows", None))
        ok = False
        try:
            run_mapper(collector)
            ok = True
        finally:
            # same success gate as the reduce side: direct-write formats
            # (DBOutputFormat) must not flush a failed task's buffer
            abort = None if ok else getattr(writer, "abort", None)
            (abort or writer.close)()
        reporter.incr_counter(BackendCounter.GROUP, backend_tasks)
        reporter.incr_counter(BackendCounter.GROUP, backend_ms,
                              int((time.monotonic() - t0) * 1000))
        return "", {}

    # map-side named outputs (lib.MultipleOutputs) in jobs WITH reducers
    # write into the attempt's committer work dir; the dir is created
    # lazily by MultipleOutputs, and commit happens through the normal
    # gate only when files exist (FileOutputCommitter.needs_commit)
    from tpumr.mapred.output_formats import FileOutputCommitter
    _side_committer = FileOutputCommitter(conf)
    if _side_committer.fs is not None:
        conf.set("tpumr.task.work.dir",
                 _side_committer.work_dir(str(task.attempt_id)))

    from tpumr.mapred.device_shuffle import is_device_shuffle
    if is_device_shuffle(conf):
        # device-shuffled jobs skip sort/spill/partition entirely — the
        # reduce gang task does all three on the mesh (device_shuffle.py)
        from tpumr.mapred.device_shuffle import DenseMapOutputBuffer
        buffer: Any = DenseMapOutputBuffer(conf, local_dir, reporter)
        if _identity_dense_fast_path(conf, in_fmt, split, buffer, reporter):
            out = buffer.flush()
            reporter.incr_counter(BackendCounter.GROUP, backend_tasks)
            reporter.incr_counter(BackendCounter.GROUP, backend_ms,
                                  int((time.monotonic() - t0) * 1000))
            return out
    else:
        buffer = MapOutputBuffer(conf, task.num_reduces, local_dir, reporter)
    run_mapper(OutputCollector(
        buffer.collect, getattr(buffer, "collect_fixed_rows", None)))
    out = buffer.flush()
    reporter.incr_counter(BackendCounter.GROUP, backend_tasks)
    reporter.incr_counter(BackendCounter.GROUP, backend_ms,
                          int((time.monotonic() - t0) * 1000))
    return out


def _run_slowly(conf: Any, reporter: Reporter) -> None:
    """The ``task.slow`` chaos behavior: a straggler, not a hang — the
    attempt stays ALIVE and keeps reporting slowly-advancing progress
    for ``tpumr.fi.task.slow.ms`` before the real work runs. This is
    the seam the targeted-speculation tests and the straggler bench
    phase inject: progress ticks feed the master's per-TIP rate model
    (so the estimated finish lags honestly), while the kill-flag poll
    lets a speculative twin's win cancel the slow original promptly."""
    from tpumr.core import confkeys as _ck
    total_s = max(0.0, _ck.get_int(conf, "tpumr.fi.task.slow.ms") / 1000.0)
    t0 = time.monotonic()
    while True:
        elapsed = time.monotonic() - t0
        if elapsed >= total_s:
            return
        # crawl toward (but never reach) half done: honest "running but
        # way behind" telemetry for the remaining-work estimator
        reporter.progress(min(0.45, 0.45 * elapsed / total_s))
        reporter.raise_if_aborted()
        time.sleep(min(0.05, total_s - elapsed))


def _hang_silently(reporter: Reporter) -> None:
    """The ``task.hang`` chaos behavior: stop reporting progress forever
    — no counter ticks, no status, no progress — exactly the silent-
    but-alive attempt ``mapred.task.timeout`` exists for. Polls ONLY the
    kill flag: cooperative cancel is how an in-process reap frees the
    thread (isolated children are SIGKILLed regardless, and the poll is
    what keeps their umbilical kill-ping alive without counting as
    progress)."""
    while True:
        reporter.raise_if_aborted()
        time.sleep(0.05)


def _declared_mapper_class(conf: Any, attr: str):
    """The job's mapper class iff the class ITSELF declares ``attr``
    truthy (inherited flags don't count: a subclass overriding map()
    without re-declaring must not have its map() silently bypassed)."""
    mapper_cls = conf.get_class("mapred.mapper.class")
    if mapper_cls is not None and mapper_cls.__dict__.get(attr):
        return mapper_cls
    return None


def _read_batch_for_fast_path(conf: Any, in_fmt: Any, split: Any):
    """One RecordBatch for a vectorized map fast path, or None when the
    input shape is ineligible (no batch reader; dense splits have no
    byte keys to pass through). Shared gate for the identity-dense and
    host-batch-mapper paths so their eligibility can't drift apart."""
    if split is None or getattr(in_fmt, "read_batch", None) is None:
        return None
    from tpumr.mapred.split import DenseSplit
    if isinstance(split, DenseSplit):
        return None
    batch = in_fmt.read_batch(split, conf)
    if not hasattr(batch, "padded_keys"):
        return None  # DenseBatch-shaped input: no byte keys
    return batch


def _identity_dense_fast_path(conf: Any, in_fmt: Any, split: Any,
                              buffer: Any, reporter: Reporter) -> bool:
    """Device-shuffled identity maps (terasort: the mapper passes (k, v)
    through untouched, ``identity_map = True``) skip the per-record
    reader→map→collect loop entirely: the split arrives as one
    RecordBatch (vectorized SequenceFile/text parse) and lands in the
    dense buffer as two array appends. Falls back (False) whenever the
    shape doesn't fit — non-identity mapper, no batch input, or record
    widths that don't match the declared fixed layout (the width check
    needs the read, so THAT fallback re-reads the split — acceptable:
    it only happens on misconfigured fixed-width declarations)."""
    if _declared_mapper_class(conf, "identity_map") is None:
        return False
    batch = _read_batch_for_fast_path(conf, in_fmt, split)
    if batch is None:
        return False
    n = batch.num_records
    if n == 0:
        return True
    klens = batch.key_offsets[1:] - batch.key_offsets[:-1]
    vlens = batch.value_offsets[1:] - batch.value_offsets[:-1]
    if not ((klens == buffer.klen).all() and (vlens == buffer.vlen).all()):
        return False
    keys, _ = batch.padded_keys(buffer.klen)
    values, _ = batch.padded_values(buffer.vlen)
    buffer.collect_fixed_batch(keys, values)
    reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                          TaskCounter.MAP_INPUT_RECORDS, n)
    return True


def _host_batch_fast_path(conf: Any, in_fmt: Any, split: Any,
                          collector: Any, reporter: Reporter) -> bool:
    """Host-vectorized mapper seam: a mapper class that declares
    ``map_record_batch(batch, output, reporter)`` processes the whole
    split as ONE RecordBatch instead of the per-record reader→map loop
    (the host twin of a kernel's ``map_batch_cpu`` — example:
    TeraValidateMapper's consecutive-key order check)."""
    mapper_cls = _declared_mapper_class(conf, "map_record_batch")
    if mapper_cls is None:
        return False
    batch = _read_batch_for_fast_path(conf, in_fmt, split)
    if batch is None:
        return False
    # new_instance already ran configure(conf) — JobConfigurable seam
    mapper = new_instance(mapper_cls, conf)
    try:
        mapper.map_record_batch(batch, collector, reporter)
    finally:
        mapper.close()
    reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                          TaskCounter.MAP_INPUT_RECORDS, batch.num_records)
    return True


def _cpu_runner_class(conf: Any) -> type:
    """CPU runner selection: a kernel job whose kernel ships a vectorized
    host implementation (``map_batch_cpu``) processes batches on CPU slots
    too — the reference's hybrid premise (CPU slots carry real work,
    JobQueueTaskScheduler.java:127-178) demands a batch CPU path, not
    per-record Python. ``tpumr.cpu.batch.map=false`` opts out (e.g. to
    measure the per-record baseline)."""
    name = conf.get_map_kernel()
    if name and conf.get_boolean("tpumr.cpu.batch.map", True):
        from tpumr.mapred.tpu_runner import CpuBatchMapRunner
        from tpumr.ops import get_kernel
        if get_kernel(name).map_batch_cpu is not None:
            return CpuBatchMapRunner
    return conf.get_map_runner_class()


def _counted_reader(in_fmt: Any, split: InputSplit | None, conf: Any,
                    reporter: Reporter) -> Iterator[tuple[Any, Any]]:
    reader = in_fmt.get_record_reader(split, conf, reporter)
    c_in = reporter.counters.counter(TaskCounter.FRAMEWORK_GROUP,
                                     TaskCounter.MAP_INPUT_RECORDS)
    for i, (k, v) in enumerate(reader):
        if (i & 0x1FF) == 0:  # cooperative kill poll every 512 records —
            reporter.raise_if_aborted()  # preemption frees the slot NOW
        c_in.increment()
        yield k, v
