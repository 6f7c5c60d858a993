"""TPU map runner — stages the whole split into device memory and executes
the mapper as a JAX/XLA/Pallas program.

Replaces the reference's GPU pipes data path end to end:

- ``PipesGPUMapRunner`` (mapred/pipes/PipesGPUMapRunner.java:40-118) forked
  the *GPU* executable and streamed the split record-by-record over a socket
  (the MAP_ITEM hot loop :97-107) → here the split becomes ONE staged batch
  (DenseBatch via the input format's ``read_batch``, or a RecordBatch built
  from the record reader) and the kernel mapper consumes it whole.
- ``Application`` appended GPUDeviceId to argv so the CUDA child could
  ``cudaSetDevice`` (mapred/pipes/Application.java:162-181) → here
  ``task.tpu_device_id`` selects the ``jax.Device`` the batch is put on.
- Output returns pre-aggregated (kernels combine on device), entering the
  normal MapOutputBuffer → sort/spill → shuffle pipeline.

Selected by ``run_map_task`` when ``task.run_on_tpu`` is set — the same seam
where the reference picks the GPU runner (mapred/MapTask.java:433-438).
"""

from __future__ import annotations

import time
from typing import Any

import threading
from collections import OrderedDict

import numpy as np

from tpumr.core.counters import BackendCounter, TaskCounter
from tpumr.io.recordbatch import DenseBatch, RecordBatch
from tpumr.io.writable import serialize
from tpumr.mapred.api import MapRunnable
from tpumr.mapred.split import DenseSplit, InputSplit
from tpumr.utils.reflection import new_instance


class HbmSplitCache:
    """LRU cache of device-resident staged splits.

    New capability beyond the reference: iterative jobs (K-Means rounds,
    repeated scans) re-read their InputSplits from storage every round in
    MapReduce; here a split staged into HBM stays resident across tasks of
    the same process, so later rounds skip both storage I/O and the
    host→device transfer — the dominant cost off-host. Keyed by the split's
    identity (path, row range, dtype); bounded by bytes with LRU eviction.
    """

    def __init__(self, capacity_bytes: int) -> None:
        self.capacity = capacity_bytes
        self._entries: "OrderedDict[tuple, Any]" = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple) -> Any | None:
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return entry[0]
            self.misses += 1
            return None

    def put(self, key: tuple, value: Any, nbytes: int) -> None:
        with self._lock:
            if key in self._entries or nbytes > self.capacity:
                return  # oversized items never evict resident ones
            while self._bytes + nbytes > self.capacity and self._entries:
                # entries carry their CHARGED size: eviction accounting
                # must not depend on any particular value shape (split
                # tuples and device-output dicts share this cache)
                _, (_old, old_bytes) = self._entries.popitem(last=False)
                self._bytes -= old_bytes
            self._entries[key] = (value, nbytes)
            self._bytes += nbytes

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    def drop_where(self, pred) -> None:
        """Evict every entry whose KEY satisfies ``pred`` (targeted
        invalidation — e.g. one side-input family of the ops devcache)."""
        with self._lock:
            for k in [k for k in self._entries if pred(k)]:
                _v, b = self._entries.pop(k)
                self._bytes -= b

    def snapshot(self) -> "list[tuple[tuple, int]]":
        """Locked point-in-time (key, charged_bytes) listing, LRU→MRU —
        the devcache inventory the tracker piggybacks on heartbeats.
        Values are deliberately NOT exposed (device arrays stay put)."""
        with self._lock:
            return [(k, b) for k, (_v, b) in self._entries.items()]

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes


_split_caches: dict[str, HbmSplitCache] = {}
_cache_lock = threading.Lock()


def runner_metrics():
    """The process-wide ``tpu`` metrics source: stage (host→device) and
    execute wall-time distributions for the device path, the CPU batch
    runner's twin, and a ``tpu_observed_acceleration`` gauge — measured
    mean CPU-batch time over mean TPU-execute time, sitting next to the
    per-job PROFILED factor the scheduler derives from whole-task
    runtimes (job status ``acceleration_factor``). The two disagreeing
    is signal: profiled includes staging + per-task overhead, observed
    is pure kernel wall time. ``tpu_hbm_bytes_in_use`` and
    ``tpu_hbm_peak_bytes`` are the device memory as the program sees it:
    ``memory_stats()`` of this process's TPU slot devices at scrape, the
    largest over the devices (0 where the backend reports none, as the
    CPU stand-in does, or before a slot device was ever asked for)."""
    from tpumr.metrics.core import process_registry
    reg = process_registry("tpu")
    reg.histogram("tpu_stage_seconds")
    execute = reg.histogram("tpu_execute_seconds")
    cpu = reg.histogram("tpu_cpu_batch_seconds")

    def _observed() -> float:
        if not execute.count or not cpu.count:
            return 0.0
        tpu_mean = execute.sum / execute.count
        cpu_mean = cpu.sum / cpu.count
        return cpu_mean / tpu_mean if tpu_mean > 0 else 0.0

    reg.set_gauge("tpu_observed_acceleration", _observed)
    reg.set_gauge("tpu_hbm_bytes_in_use", lambda: _hbm_stat("bytes_in_use"))
    reg.set_gauge("tpu_hbm_peak_bytes",
                  lambda: _hbm_stat("peak_bytes_in_use"))
    return reg


def _hbm_stat(stat: str) -> int:
    from tpumr.parallel.jaxruntime import known_accelerator_devices
    return max((int((d.memory_stats() or {}).get(stat, 0))
                for d in known_accelerator_devices()), default=0)

#: (kernel, input signature) pairs this process has dispatched before —
#: the trace's compile-cache attribute: a first dispatch ("cold") pays
#: XLA compilation or a persistent-cache load (parallel/jaxruntime.py);
#: later dispatches of the same signature hit the in-process jit cache
_dispatched: set = set()
_dispatched_lock = threading.Lock()


def _dispatch_signature(kernel_name: str, batch: Any) -> tuple:
    values = getattr(batch, "values", None)
    shape = tuple(getattr(values, "shape", ()) or ())
    dtype = str(getattr(values, "dtype", ""))
    return (kernel_name, shape, dtype)


def _compile_temperature(kernel_name: str, batch: Any) -> str:
    """'cold' before this process's first SUCCESSFUL dispatch of
    (kernel, signature) — XLA compiles or loads the persistent cache —
    else 'warm'. Mark with :func:`_mark_dispatched` only after the
    execution completes: a failed cold attempt's retry pays the compile
    again and must not report warm."""
    with _dispatched_lock:
        return ("warm" if _dispatch_signature(kernel_name, batch)
                in _dispatched else "cold")


def _mark_dispatched(kernel_name: str, batch: Any) -> None:
    with _dispatched_lock:
        _dispatched.add(_dispatch_signature(kernel_name, batch))


def split_cache(device: Any, capacity_bytes: int) -> HbmSplitCache:
    key = str(device)
    with _cache_lock:
        c = _split_caches.get(key)
        if c is None:
            c = _split_caches[key] = HbmSplitCache(capacity_bytes)
        c.capacity = capacity_bytes
        return c


def clear_split_caches() -> None:
    with _cache_lock:
        for c in _split_caches.values():
            c.clear()
        _split_caches.clear()


def _maybe_fail_accelerator(conf, dev_id: int) -> None:
    """Chaos seams for the accelerator fault-tolerance layer, classed so
    the demotion/quarantine pipeline sees exactly what a real fault
    would report: ``tpu.compile`` (failure_class=compile), ``tpu.execute``
    and the device-qualified ``tpu.execute.d<id>`` (failure_class=device
    — the qualified point lets a test sicken ONE physical device while
    its siblings keep serving)."""
    from tpumr.mapred.task import FailureClass
    from tpumr.utils.fi import maybe_fail
    maybe_fail("tpu.compile", conf, failure_class=FailureClass.COMPILE)
    maybe_fail("tpu.execute", conf, failure_class=FailureClass.DEVICE)
    if dev_id >= 0:
        maybe_fail(f"tpu.execute.d{dev_id}", conf,
                   failure_class=FailureClass.DEVICE)


class TpuMapRunner(MapRunnable):
    def configure(self, conf) -> None:
        self.conf = conf

    def run(self, reader, output, reporter, task_ctx=None) -> None:
        import jax
        from tpumr.ops import get_kernel
        from tpumr.parallel.jaxruntime import configure_persistent_cache

        conf = self.conf
        configure_persistent_cache(conf)
        _maybe_fail_accelerator(
            conf, getattr(task_ctx, "tpu_device_id", -1) if task_ctx else -1)
        name = conf.get_map_kernel()
        if not name:
            raise ValueError(
                "task placed on TPU but no kernel mapper configured "
                "(JobConf.set_map_kernel) — the scheduler should not place "
                "kernel-less jobs on TPU (JobQueueTaskScheduler.java:342-347 "
                "semantics)")
        kernel = get_kernel(name)

        # a windowed prelaunch (prelaunch_device_maps) already staged,
        # dispatched, and fetched this task's kernel output as part of a
        # many-task batched transfer — only the drain remains
        from tpumr.core import tracing

        mreg = runner_metrics()
        pre = getattr(task_ctx, "_device_prefetch", None) if task_ctx else None
        if pre is not None:
            if pre.device_rows is not None:
                from tpumr.mapred import device_output
                device_output.offer(
                    str(conf.get("tpumr.task.attempt.id", "")),
                    pre.device_rows)
            reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                  TaskCounter.MAP_INPUT_RECORDS,
                                  pre.num_records)
            reporter.incr_counter(BackendCounter.GROUP,
                                  BackendCounter.TPU_DEVICE_BYTES_STAGED,
                                  pre.staged_bytes)
            _count(kernel, reporter, pre.num_records, True)
            t0 = time.monotonic()
            with tracing.span("tpu:window_drain", backend="tpu",
                              records=pre.num_records,
                              staged_bytes=pre.staged_bytes):
                with mreg.histogram("tpu_window_drain_seconds").time():
                    for key, value in kernel.map_batch_drain(pre.fetched,
                                                             conf,
                                                             task_ctx):
                        output.collect(key, value)
            reporter.set_status(
                f"kernel {name} (pipelined window): {pre.num_records} "
                f"records, drained in {time.monotonic() - t0:.3f}s")
            return

        # device binding ≈ GPUDeviceId → cudaSetDevice
        dev_id = getattr(task_ctx, "tpu_device_id", -1) if task_ctx else -1
        device = _select_device(dev_id)

        with tracing.span("tpu:stage", backend="tpu",
                          device=str(device)) as st:
            try:
                with mreg.histogram("tpu_stage_seconds").time():
                    batch, counted_by_reader, staged_bytes = stage_batch(
                        self.conf, reader, task_ctx, device)
            except Exception as e:  # noqa: BLE001 — classify at the site
                from tpumr.mapred.task import (classify_accelerator_exception,
                                               tag_failure)
                raise tag_failure(e, classify_accelerator_exception(e))
            if st is not None:
                # staged_bytes == 0 means the split was already device-
                # resident (HBM split cache / output chain) — the stage
                # cost this span exists to surface was skipped entirely
                st.set(staged_bytes=staged_bytes,
                       hbm_cache="hit" if staged_bytes == 0 else "miss",
                       records=getattr(batch, "num_records", 0))
        if not counted_by_reader:
            # the record-reader path already counts MAP_INPUT_RECORDS
            reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                  TaskCounter.MAP_INPUT_RECORDS,
                                  getattr(batch, "num_records", 0))
        reporter.incr_counter(BackendCounter.GROUP,
                              BackendCounter.TPU_DEVICE_BYTES_STAGED,
                              staged_bytes)

        t0 = time.monotonic()
        temperature = _compile_temperature(name, batch)
        try:
            with mreg.histogram("tpu_execute_seconds").time(), \
                    jax.default_device(device):
                with tracing.span("tpu:execute", backend="tpu",
                                  kernel=name, device=str(device)) as ex:
                    if ex is not None:
                        ex.set(compile=temperature)
                    state = (kernel.map_batch_launch(batch, conf, task_ctx)
                             if type(kernel).supports_launch() else None)
                    if state is not None:
                        _offer_device_rows(kernel, state, conf)
                        # coalesce this task's device→host transfer with
                        # any concurrently-fetching TPU-slot threads: one
                        # device_get can carry many tasks' outputs
                        from tpumr.mapred.fetch_batcher import shared_batcher
                        fetched = shared_batcher().fetch(state)
                        records = kernel.map_batch_drain(fetched, conf,
                                                         task_ctx)
                    else:
                        records = kernel.map_batch(batch, conf, task_ctx)
                    for key, value in records:
                        output.collect(key, value)
                    _mark_dispatched(name, batch)
            _count(kernel, reporter, getattr(batch, "num_records", 0), True)
        except Exception as e:  # noqa: BLE001 — classify at the site
            from tpumr.mapred.task import (classify_accelerator_exception,
                                           tag_failure)
            raise tag_failure(e, classify_accelerator_exception(
                e, compile_cold=temperature == "cold"))
        reporter.set_status(
            f"kernel {name} on {device}: "
            f"{getattr(batch, 'num_records', 0)} records in "
            f"{time.monotonic() - t0:.3f}s")


def stage_batch(conf, reader, task_ctx, device=None) -> tuple[Any, bool, int]:
    """Batch-native input formats hand over the split whole; otherwise
    drain the record reader into a RecordBatch (keys discarded — kernel
    inputs are values, matching the pipes data path where keys were
    offsets). With a ``device``, dense splits go through the HBM split
    cache: a cache hit skips storage I/O and the host→device transfer
    entirely; ``device=None`` stages on host (the CPU batch runner). A
    split read from storage is uploaded in the job kernel's
    ``staged_form`` (a padded shape keeps the split's own count in
    ``DenseBatch.rows``). Returns (batch, counted_by_reader,
    bytes_actually_staged)."""
    if device is not None:
        from tpumr.parallel.jaxruntime import configure_persistent_cache
        configure_persistent_cache(conf)
    in_fmt = new_instance(conf.get_input_format(), conf)
    split = None
    if task_ctx is not None and getattr(task_ctx, "split", None):
        split = InputSplit.from_dict(task_ctx.split)
    if split is not None and getattr(in_fmt, "read_batch", None) is not None:
        use_cache = conf.get_boolean("tpumr.tpu.split.cache", True)
        cache_mb = conf.get_int("tpumr.tpu.split.cache.mb", 2048)
        if device is not None and use_cache and isinstance(split, DenseSplit):
            import jax

            from tpumr.fs.filesystem import FileSystem
            cache = split_cache(device, cache_mb * 1024 * 1024)
            # file freshness (length, mtime) is part of the key so a
            # rewritten input never serves stale resident data
            st = FileSystem.get(split.path, conf).get_status(split.path)
            key = (split.path, split.row_start, split.num_rows,
                   split.dtype, split.data_offset, st.length, st.mtime)
            entry = cache.get(key)
            if entry is not None:
                staged, ids, meta, rows = entry
                return DenseBatch(staged, ids, dict(meta), rows), False, 0
            # output chain: a predecessor job may have left this FILE's
            # image resident (device_output.publish) — slice the split's
            # rows on device, skipping the read AND the upload
            from tpumr.mapred import device_output
            whole = device_output.lookup(
                conf, device, FileSystem.get(split.path, conf),
                split.path, st.length, st.mtime)
            if (whole is not None and getattr(whole, "ndim", 0) == 2
                    and whole.shape[0] >= split.row_start + split.num_rows
                    and whole.shape[1] == split.cols
                    and str(whole.dtype) == str(np.dtype(split.dtype))):
                staged = whole[split.row_start:
                               split.row_start + split.num_rows]
                ids = np.arange(split.row_start,
                                split.row_start + split.num_rows,
                                dtype=np.int64)
                cache.put(key, (staged, ids, {}, None), int(staged.nbytes))
                return DenseBatch(staged, ids, {}), False, 0
            batch = in_fmt.read_batch(split, conf)
            host = batch.values
            if conf.get_map_kernel():
                from tpumr.ops import get_kernel
                host = get_kernel(conf.get_map_kernel()).staged_form(host)
            rows = None if host is batch.values else batch.num_records
            staged = jax.device_put(host, device)
            cache.put(key, (staged, batch.ids, dict(batch.meta), rows),
                      int(host.nbytes))
            return DenseBatch(staged, batch.ids, batch.meta, rows), False, \
                int(host.nbytes)
        batch = in_fmt.read_batch(split, conf)
        return batch, False, int(getattr(batch, "nbytes", 0))
    values = []
    for _k, v in reader:
        if isinstance(v, (bytes, bytearray)):
            values.append(bytes(v))
        elif isinstance(v, str):
            values.append(v.encode("utf-8"))
        else:
            values.append(serialize(v))
    batch = RecordBatch.from_values(values)
    return batch, True, int(batch.nbytes)


def _count(kernel, reporter, records: int, on_tpu: bool) -> None:
    """Add the task counters the kernel names for the split it mapped."""
    for (group, name), n in kernel.task_counters(records, on_tpu).items():
        reporter.incr_counter(group, name, n)


def _select_device(dev_id: int):
    """The one device-binding rule (≈ GPUDeviceId → cudaSetDevice), shared
    by the per-task runner and the windowed prelaunch."""
    from tpumr.parallel.jaxruntime import accelerator_device
    return accelerator_device(dev_id)


def _device_rows_of(kernel, state, conf):
    """The kernel's device output rows for chaining, or None — gated on
    the job's output format actually claiming them (DenseNpyOutputFormat)
    so other jobs can never strand HBM in the pending table."""
    if state is None:
        return None
    hook = getattr(kernel, "device_output_rows", None)
    if hook is None:
        return None
    try:
        fmt = conf.get_output_format()
    except Exception:  # noqa: BLE001 — unset/bogus output format
        return None
    if not getattr(fmt, "claims_device_rows", False):
        return None
    return hook(state)


def _offer_device_rows(kernel, state, conf) -> None:
    rows = _device_rows_of(kernel, state, conf)
    if rows is not None:
        from tpumr.mapred import device_output
        device_output.offer(str(conf.get("tpumr.task.attempt.id", "")),
                            rows)


class DevicePrefetch:
    """Fetched kernel output for one map task of a pipelined window.
    ``device_rows`` carries the still-resident output array when the job
    chains through DenseNpyOutputFormat (offered at drain time)."""

    __slots__ = ("fetched", "num_records", "staged_bytes", "device_rows")

    def __init__(self, fetched: Any, num_records: int,
                 staged_bytes: int, device_rows: Any = None) -> None:
        self.fetched = fetched
        self.num_records = num_records
        self.staged_bytes = staged_bytes
        self.device_rows = device_rows


def prelaunch_device_maps(conf, tasks: "list[Any]") -> "list[DevicePrefetch] | None":
    """Stage + dispatch a window of map tasks' kernels, then fetch EVERY
    task's device output in ONE ``jax.device_get`` — one host
    synchronization for the whole window instead of one per output array
    per task.

    Dispatch is asynchronous, so the window's kernels queue back-to-back
    on the device and the host blocks once, at the fetch. This deepens the
    north-star design (whole-split HBM staging replacing the reference's
    per-record socket loop, PipesGPUMapRunner.java:97-107) by one more
    level: per-JOB, not per-task, host synchronization. What that is worth
    against per-task fetches on a given machine is a measurement (see
    PERF.md), not a property of the mechanism.

    Returns one :class:`DevicePrefetch` per task — possibly for a PREFIX
    of ``tasks`` only: the whole window is device-resident until the
    fetch, so staging is byte-bounded (``tpumr.tpu.pipeline.window.mb``)
    and the window closes early once the budget is spent (always taking
    at least one task, so the job progresses). Returns None when the job
    is not eligible (no kernel, kernel without the launch/drain protocol,
    a custom TPU runner, or an input format that cannot hand over whole
    splits) — callers fall back to the per-task path.
    """
    import jax
    from tpumr.ops import get_kernel
    from tpumr.parallel.jaxruntime import configure_persistent_cache
    configure_persistent_cache(conf)

    name = conf.get_map_kernel()
    if not name:
        return None
    kernel = get_kernel(name)
    if not type(kernel).supports_launch():
        return None
    # a custom TPU runner (or a subclass overriding run) would ignore the
    # prefetch and redo the work — require the stock run method
    if conf.get_tpu_map_runner_class().run is not TpuMapRunner.run:
        return None
    in_fmt = new_instance(conf.get_input_format(), conf)
    if getattr(in_fmt, "read_batch", None) is None:
        return None
    if any(not getattr(t, "split", None) for t in tasks):
        return None
    # one window = one device: mirror the per-task binding (tpu_device_id)
    dev_ids = {getattr(t, "tpu_device_id", -1) for t in tasks}
    if len(dev_ids) != 1:
        return None
    device = _select_device(dev_ids.pop())

    budget = conf.get_int("tpumr.tpu.pipeline.window.mb", 2048) * 1024 * 1024
    states: list[Any] = []
    meta: list[tuple[int, int]] = []
    resident = 0
    with jax.default_device(device):
        for task in tasks:
            batch, _counted, staged_bytes = stage_batch(
                conf, None, task, device)
            state = kernel.map_batch_launch(batch, conf, task)
            if state is None:
                return None
            states.append(state)
            meta.append((int(getattr(batch, "num_records", 0)),
                         int(staged_bytes),
                         _device_rows_of(kernel, state, conf)))
            # every staged input stays device-resident until the window
            # fetch (cache hits were already resident — they don't count)
            resident += int(staged_bytes)
            if resident >= budget and len(states) < len(tasks):
                break  # close the window early; caller resumes after us
        fetched = jax.device_get(states)  # ONE fetch for the window
    return [DevicePrefetch(f, n, b, rows)
            for f, (n, b, rows) in zip(fetched, meta)]


class CpuBatchMapRunner(MapRunnable):
    """CPU-slot whole-batch runner — the vectorized host twin of
    :class:`TpuMapRunner`. The reference's hybrid premise is that CPU slots
    carry real work (3 CPU + 1 GPU slots per node,
    JobQueueTaskScheduler.java:127-178): per-record Python would make the
    CPU backend artificially slow and inflate the measured acceleration
    factor, so kernel jobs whose kernel provides ``map_batch_cpu`` (numpy)
    process the whole staged split per task here, exactly like the device
    path minus the device."""

    def configure(self, conf) -> None:
        self.conf = conf

    def run(self, reader, output, reporter, task_ctx=None) -> None:
        from tpumr.ops import get_kernel

        conf = self.conf
        kernel = get_kernel(conf.get_map_kernel())
        assert kernel.map_batch_cpu is not None  # selection checked upstream
        batch, counted_by_reader, _ = stage_batch(conf, reader, task_ctx)
        if not counted_by_reader:
            reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                  TaskCounter.MAP_INPUT_RECORDS,
                                  getattr(batch, "num_records", 0))
        reporter.incr_counter(BackendCounter.GROUP,
                              BackendCounter.CPU_BATCH_MAP_TASKS)
        t0 = time.monotonic()
        with runner_metrics().histogram("tpu_cpu_batch_seconds").time():
            for key, value in kernel.map_batch_cpu(batch, conf, task_ctx):
                output.collect(key, value)
        _count(kernel, reporter, getattr(batch, "num_records", 0), False)
        reporter.set_status(
            f"cpu-batch kernel {kernel.name}: "
            f"{getattr(batch, 'num_records', 0)} records in "
            f"{time.monotonic() - t0:.3f}s")
