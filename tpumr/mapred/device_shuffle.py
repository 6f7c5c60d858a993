"""Device-shuffled reduce — the MapReduce shuffle+sort as ICI collectives.

The reference's shuffle/sort is host machinery end to end: R reduce tasks
each run parallel HTTP fetchers against every map's spill file
(ReduceTask.java:659 ReduceCopier ↔ TaskTracker.java:4050 MapOutputServlet)
and k-way-merge on disk (:399-409). On a TPU mesh that entire exchange is
ONE ``all_to_all`` and the merge is a per-device vectorized sort — so this
mode re-plans the reduce phase as a single *gang task* that owns the host's
device mesh:

  map tasks (CPU or TPU, unchanged) → **dense map output** (fixed-width
  key/value byte arrays, no sort/spill/partition — the device does both) →
  one device-reduce task: stage all map outputs onto the mesh →
  ``device_partition_sort`` (range partition from sampled splitters, ICI
  all-to-all, per-device lexsort — tpumr.parallel.device_sort) → host
  writes the R range-ordered part files through the normal OutputFormat/
  OutputCommitter path.

Opt-in per job: ``conf.set_device_shuffle(key_bytes, value_bytes)``; keys
and values must be fixed-width ``bytes`` (the device-sortable contract,
SURVEY.md §7 — terasort's 10+90 layout is the canonical fit). The reduce
phase collapses to one task; the original reduce count becomes the number
of output ranges (``part-*`` files), preserving the job's output shape.
Capacity overflow in the exchange retries with doubled buckets and finally
falls back to a host numpy sort (the reference's disk-spill fallback role)
— never wrong output, only a slower path.

Why map outputs come back to the host before staging: map tasks and the
reduce gang task are separate slots, possibly separate processes; the
hand-off rides the same host shuffle-serving seam as the reference
(MapOutputServlet role). The *exchange and sort* — the O(N log N) part the
reference does over HTTP + disk merges — run on device.
"""

from __future__ import annotations

import bisect
import os
import struct
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable

import numpy as np

from tpumr.core import tracing
from tpumr.core.counters import BackendCounter, TaskCounter
from tpumr.mapred.api import OutputCollector, Reporter
from tpumr.mapred.output_formats import FileOutputCommitter
from tpumr.mapred.task import Task
from tpumr.utils.reflection import new_instance

#: job conf keys
DEVICE_SHUFFLE_KEY = "tpumr.shuffle.device"
KEY_BYTES_KEY = "tpumr.shuffle.device.key.bytes"
VALUE_BYTES_KEY = "tpumr.shuffle.device.value.bytes"
RANGES_KEY = "tpumr.shuffle.device.ranges"
CAPACITY_KEY = "tpumr.shuffle.device.capacity"

_MAGIC = b"TDSH"
_HEADER = struct.Struct(">4sIHH")  # magic, n, klen, vlen


def is_device_shuffle(conf: Any) -> bool:
    return bool(conf.get_boolean(DEVICE_SHUFFLE_KEY, False))


def prepare_device_shuffle_job(conf: Any) -> None:
    """Submission-side re-plan (JobClient + LocalJobRunner): the reduce
    phase becomes ONE gang task; the requested reduce count survives as the
    output range count so the job still produces R part files."""
    if not is_device_shuffle(conf):
        return
    if conf.get_int(KEY_BYTES_KEY, 0) <= 0 or \
            conf.get_int(VALUE_BYTES_KEY, 0) < 0:
        raise ValueError(
            f"device shuffle needs fixed record widths: set {KEY_BYTES_KEY}"
            f" / {VALUE_BYTES_KEY} (JobConf.set_device_shuffle)")
    r = conf.num_reduce_tasks
    if r == 0:
        raise ValueError("device shuffle requires a reduce phase "
                         "(num_reduce_tasks >= 1)")
    # the device sorts raw bytes ascending — a custom key order or a
    # grouping comparator would silently change output order/grouping
    # relative to the host path, so reject rather than diverge
    from tpumr.mapred.api import RawComparator
    cmp_cls = conf.get_class("mapred.output.key.comparator.class")
    if cmp_cls is not None and cmp_cls is not RawComparator:
        raise ValueError(
            f"device shuffle sorts raw bytes ascending; output key "
            f"comparator {cmp_cls.__name__} is not supported — use "
            f"RawComparator or the host shuffle")
    if conf.get_class("mapred.output.value.groupfn.class") is not None:
        raise ValueError("device shuffle does not support a grouping "
                         "comparator (secondary sort) — use the host "
                         "shuffle")
    name = conf.get_reduce_kernel()
    if name:
        from tpumr.ops import get_reduce_kernel
        kernel = get_reduce_kernel(name)
        if conf.get_reducer_class() is not None:
            raise ValueError(f"the job names the reduce kernel {name!r} "
                             f"and a reducer class: one reducer a job")
        if conf.get_int(VALUE_BYTES_KEY, 0) != kernel.value_bytes:
            raise ValueError(
                f"reduce kernel {name!r} takes {kernel.value_bytes}-byte "
                f"values; {VALUE_BYTES_KEY} is "
                f"{conf.get_int(VALUE_BYTES_KEY, 0)}")
    if not conf.get(RANGES_KEY):
        conf.set(RANGES_KEY, r)
    conf.set_num_reduce_tasks(1)


class DenseMapOutputBuffer:
    """Map-side collector for device-shuffled jobs: fixed-width records
    appended to flat byte buffers, written as ONE dense file — no
    partitioning, no sort, no spill (the device does all three). Replaces
    MapOutputBuffer at the same seam in ``run_map_task``."""

    def __init__(self, conf: Any, local_dir: str, reporter: Reporter) -> None:
        self.klen = conf.get_int(KEY_BYTES_KEY, 0)
        self.vlen = conf.get_int(VALUE_BYTES_KEY, 0)
        self.local_dir = local_dir
        self.reporter = reporter
        self._keys = bytearray()
        self._values = bytearray()
        self._n = 0
        os.makedirs(local_dir, exist_ok=True)

    def collect(self, key: Any, value: Any) -> None:
        if not isinstance(key, (bytes, bytearray)) or len(key) != self.klen:
            raise ValueError(
                f"device shuffle requires {self.klen}-byte keys, got "
                f"{type(key).__name__}[{len(key) if hasattr(key, '__len__') else '?'}]")
        if not isinstance(value, (bytes, bytearray)) or \
                len(value) != self.vlen:
            raise ValueError(
                f"device shuffle requires {self.vlen}-byte values, got "
                f"{type(value).__name__}")
        self._keys += key
        self._values += value
        self._n += 1
        self.reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                   TaskCounter.MAP_OUTPUT_RECORDS)
        self.reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                   TaskCounter.MAP_OUTPUT_BYTES,
                                   self.klen + self.vlen)

    def collect_fixed_batch(self, keys: np.ndarray,
                            values: np.ndarray) -> None:
        """Bulk ingest for the identity-map fast path: ``[n, klen]`` /
        ``[n, vlen]`` uint8 arrays appended in two copies, with the same
        width validation and counter accounting as n ``collect`` calls."""
        if keys.ndim != 2 or keys.shape[1] != self.klen:
            raise ValueError(f"device shuffle requires {self.klen}-byte "
                             f"keys, got array {keys.shape}")
        if values.ndim != 2 or values.shape[1] != self.vlen:
            raise ValueError(f"device shuffle requires {self.vlen}-byte "
                             f"values, got array {values.shape}")
        if keys.shape[0] != values.shape[0]:
            raise ValueError("key/value row counts differ")
        n = int(keys.shape[0])
        self._keys += keys.astype(np.uint8, copy=False).tobytes()
        self._values += values.astype(np.uint8, copy=False).tobytes()
        self._n += n
        self.reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                   TaskCounter.MAP_OUTPUT_RECORDS, n)
        self.reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                   TaskCounter.MAP_OUTPUT_BYTES,
                                   n * (self.klen + self.vlen))

    def collect_fixed_rows(self, rows: np.ndarray, klen: int) -> None:
        """``OutputCollector``'s bulk lane: ``[n, klen + vlen]`` rows of a
        map that makes its records in arrays."""
        if klen != self.klen:
            raise ValueError(f"device shuffle requires {self.klen}-byte "
                             f"keys, got rows cut at {klen}")
        self.collect_fixed_batch(rows[:, :klen], rows[:, klen:])

    def flush(self) -> tuple[str, dict]:
        path = os.path.join(self.local_dir, "file.dense")
        with open(path, "wb") as f:
            f.write(_HEADER.pack(_MAGIC, self._n, self.klen, self.vlen))
            f.write(bytes(self._keys))
            f.write(bytes(self._values))
        return path, {"dense": True, "n": self._n,
                      "klen": self.klen, "vlen": self.vlen}


def parse_dense_bytes(data: bytes) -> tuple[np.ndarray, np.ndarray]:
    """(keys [n, klen] u8, values [n, vlen] u8) from dense-output bytes —
    the serving tracker ships the file verbatim (header is self-describing)
    so there is no reserialize hop on the hot shuffle path."""
    magic, n, klen, vlen = _HEADER.unpack_from(data)
    if magic != _MAGIC:
        raise ValueError("not a dense map output (bad magic)")
    off = _HEADER.size
    keys = np.frombuffer(data, np.uint8, n * klen, off).reshape(n, klen)
    values = np.frombuffer(data, np.uint8, n * vlen,
                           off + n * klen).reshape(n, vlen)
    return keys, values


def read_dense_output(path: str) -> tuple[np.ndarray, np.ndarray]:
    """(keys, values) arrays from a dense map output file."""
    with open(path, "rb") as f:
        return parse_dense_bytes(f.read())


#: a dense fetch returns one map's (keys, values) arrays
DenseFetchFn = Callable[[int], tuple[np.ndarray, np.ndarray]]


class RowLanding:
    """Where the copy phase puts what it fetched: the job's rows
    ``[n, klen + vlen]`` (key first) and, where asked for, their key
    words ``[n, cols]`` (``key_columns``) or, for a reduce kernel, their
    key and value words ``[cols, n]`` (``reduce_words``), each map's share
    written once when that map arrives, in the order of arrival. How many
    rows the job has is known only after the last map, so the buffers are
    sized from the maps seen so far (their mean for every map to come, and
    an eighth more) and grown, by a copy of what has landed, when a map
    does not fit."""

    def __init__(self, klen: int, vlen: int, num_maps: int) -> None:
        from tpumr.parallel.device_sort import num_key_columns
        self.klen = klen
        self._num_maps = num_maps
        self._seen = 0
        self._rows = np.empty((0, klen + vlen), np.uint8)
        self._n = 0
        self._words = np.empty((0, num_key_columns(klen)), np.uint32)
        self._n_words = 0
        self._reduce_words = np.empty(
            (num_key_columns(klen) + vlen // 4, 0), np.uint32)
        self._n_reduce_words = 0

    def land_rows(self, keys: np.ndarray, values: np.ndarray) -> bool:
        """Append one map's rows; True where the buffer had to grow."""
        self._seen += 1
        end = self._n + keys.shape[0]
        grown = end > self._rows.shape[0]
        if grown:
            mean = -(-end // self._seen)
            to_come = max(0, self._num_maps - self._seen)
            self._rows = _regrown(self._rows, self._n,
                                  end + to_come * (mean + mean // 8))
        self._rows[self._n:end, :self.klen] = keys
        self._rows[self._n:end, self.klen:] = values
        self._n = end
        return grown

    def land_key_words(self, keys: np.ndarray) -> None:
        """The key words of one map's keys, after its rows have landed."""
        from tpumr.parallel.device_sort import key_columns
        end = self._n_words + keys.shape[0]
        if end > self._words.shape[0]:
            self._words = _regrown(self._words, self._n_words,
                                   max(end, self._rows.shape[0]))
        self._words[self._n_words:end] = key_columns(keys, self.klen)
        self._n_words = end

    def land_reduce_words(self) -> None:
        """What a reduce kernel's device call sends up, for the rows that
        have landed since the last call."""
        from tpumr.parallel.device_sort import reduce_words
        at, end = self._n_reduce_words, self._n
        if end > self._reduce_words.shape[1]:
            grown = np.empty((self._reduce_words.shape[0],
                              max(end, self._rows.shape[0])), np.uint32)
            grown[:, :at] = self._reduce_words[:, :at]
            self._reduce_words = grown
        self._reduce_words[:, at:end] = reduce_words(self._rows[at:end],
                                                     self.klen)
        self._n_reduce_words = end

    @property
    def reduce_words(self) -> "np.ndarray | None":
        """The reduce kernel's words that have landed; None where none
        were asked for."""
        return self._reduce_words[:, :self._n_reduce_words] \
            if self._n_reduce_words else None

    @property
    def rows(self) -> np.ndarray:
        """What has landed, a view of the buffer."""
        return self._rows[:self._n]

    @property
    def key_words(self) -> "np.ndarray | None":
        """The key words that have landed; None where none were asked
        for."""
        return self._words[:self._n_words] if self._n_words else None


def _regrown(buf: np.ndarray, used: int, capacity: int) -> np.ndarray:
    """A buffer of ``capacity`` rows that starts with ``buf[:used]``."""
    out = np.empty((capacity,) + buf.shape[1:], buf.dtype)
    out[:used] = buf[:used]
    return out


def _shuffle_mesh(conf: Any) -> Any:
    """The mesh over this process's slot devices that the exchange and
    sort run on."""
    from tpumr.parallel.jaxruntime import (accelerator_devices,
                                           configure_persistent_cache)
    from tpumr.parallel.mesh import make_mesh
    configure_persistent_cache(conf)
    return make_mesh(devices=accelerator_devices())


def _load_splitters(conf: Any, keys: np.ndarray, num_ranges: int,
                    klen: int) -> np.ndarray:
    """Range cut points [r-1, klen] u8: the job's TotalOrderPartitioner
    file when present (terasort writes one), else sampled from the staged
    keys themselves (device mode is self-contained — ≈ TeraInputFormat's
    in-job sampling)."""
    from tpumr.mapred.total_order import PARTITION_PATH_KEY
    path = conf.get(PARTITION_PATH_KEY)
    if path:
        from tpumr.fs import get_filesystem
        from tpumr.io.writable import deserialize
        cuts = deserialize(get_filesystem(path, conf).read_bytes(path))
        good = [c for c in cuts
                if isinstance(c, (bytes, bytearray)) and len(c) == klen]
        if len(good) == len(cuts) and cuts:
            return np.frombuffer(b"".join(good), np.uint8).reshape(-1, klen)
    if num_ranges <= 1 or keys.shape[0] == 0:
        return np.zeros((0, klen), np.uint8)
    n = keys.shape[0]
    sample_idx = np.linspace(0, n - 1, min(n, 64 * num_ranges)).astype(int)
    samp = keys[sample_idx]
    order = np.lexsort(tuple(samp[:, c] for c in range(klen - 1, -1, -1)))
    samp = samp[order]
    cut_idx = [min(len(samp) - 1, round(i * len(samp) / num_ranges))
               for i in range(1, num_ranges)]
    return samp[cut_idx]


def _range_boundaries(sorted_keys: np.ndarray, splitters: np.ndarray,
                      lo_range: int, hi_range: int) -> list[int]:
    """Split one device's key-sorted shard into its ranges: boundary after
    range i = #keys <= splitters[i] ('equal goes low', as compute_dest and
    the host TotalOrderPartitioner have it), found by bisection: the
    shard's key bytes are in byte order, so a cut is two dozen probes of
    one key each, whatever the shard holds. Cut lists can be SHORT
    (write_partition_file dedups duplicate samples): a missing splitter
    acts as +inf, leaving the top ranges empty — same tolerance as the
    host TotalOrderPartitioner."""
    n = sorted_keys.shape[0]

    def key_at(i: int) -> bytes:
        return sorted_keys[i].tobytes()

    return [bisect.bisect_right(range(n), splitters[i].tobytes(), key=key_at)
            if i < len(splitters) else n
            for i in range(lo_range, hi_range - 1)]


def run_device_reduce(conf: Any, task: Task, dense_fetch: DenseFetchFn,
                      reporter: Reporter | None = None) -> None:
    """Execute the reduce gang task: fetch every map's dense output, run
    the device partition+exchange+sort, apply the job's reducer over each
    range's sorted stream, write R part files, one commit.

    Traced jobs get one ``dshuffle`` span around all of it, and under it
    a span per phase, each opened where the work is done:
    ``dshuffle:locate`` / ``dshuffle:fetch`` (the fetch function), then
    for that map ``dshuffle:assemble`` (its rows landed) and, where one
    device sorts, ``dshuffle:pack`` (its key words); after the last map
    one more ``dshuffle:assemble`` (the splitters), ``dshuffle:pack`` /
    ``dshuffle:device`` (on a mesh with a child per step: ``:put``,
    ``:dest``, ``:exchange``, ``:sort``, ``:get``) / ``dshuffle:gather``
    (``device_partition_sort``) or
    ``dshuffle:host_sort`` (the fallback), then ONE ``dshuffle:write``
    around the whole write phase with a ``dshuffle:range`` under it for
    every range: each shard is cut at the job's splitters by bisection,
    and where the rows are written as they are (the identity reducer, a
    kernel's groups) every range has a writer of its own on a thread of
    its own (``TPU_SHUFFLE_WRITERS`` at a time); a user's reducer class
    is called one range after another in this thread. Where the
    job's reducer is a kernel (``tpumr.reduce.kernel``) the device call
    reduces where it sorted (``dshuffle:sort`` and ``dshuffle:reduce``
    under ``dshuffle:device``) and groups are written, not rows; rows
    sorted elsewhere are reduced by the kernel's numpy twin
    (``dshuffle:reduce`` with ``host_twin``). What the parent does not
    spend in a child is its self time."""
    with tracing.span("dshuffle") as ds:
        _device_reduce(conf, task, dense_fetch, reporter or Reporter(), ds)


def _device_reduce(conf: Any, task: Task, dense_fetch: DenseFetchFn,
                   reporter: Reporter, ds: "tracing.Span | None") -> None:
    from tpumr.mapred.map_task import localize_task_conf
    conf = localize_task_conf(conf, task)
    from tpumr.utils.fi import maybe_fail
    maybe_fail("reduce.task", conf)

    klen = conf.get_int(KEY_BYTES_KEY, 0)
    vlen = conf.get_int(VALUE_BYTES_KEY, 0)
    num_ranges = conf.get_int(RANGES_KEY, 1)

    # ---- copy phase (host, ≈ ReduceCopier.fetchOutputs): a map's rows
    # are landed when that map arrives, so that what follows the last
    # map is one map's landing and not the whole job's
    t0 = time.monotonic()
    landing = RowLanding(klen, vlen, task.num_maps)
    mesh = None
    kernel = None
    if conf.get_reduce_kernel():
        from tpumr.ops import get_reduce_kernel
        kernel = get_reduce_kernel(conf.get_reduce_kernel())
    for m in range(task.num_maps):
        k, v = dense_fetch(m)
        if k.shape[1] != klen or v.shape[1] != vlen:
            raise ValueError(f"map {m} dense output widths "
                             f"({k.shape[1]},{v.shape[1]}) != conf "
                             f"({klen},{vlen})")
        if mesh is None and k.shape[0]:
            mesh = _shuffle_mesh(conf)
        with tracing.span("dshuffle:assemble", map_index=m,
                          rows=int(k.shape[0]),
                          bytes=int(k.nbytes + v.nbytes)) as sp:
            grown = landing.land_rows(k, v)
            if sp is not None:
                sp.set(grown=grown)
        if mesh is not None and mesh.size == 1:
            # the one-device sort sends the key words, not the rows; a
            # reduce kernel's value column goes up beside them
            with tracing.span("dshuffle:pack", map_index=m,
                              rows=int(k.shape[0])):
                if kernel is not None:
                    landing.land_reduce_words()
                else:
                    landing.land_key_words(k)
    records = landing.rows
    n = records.shape[0]
    with tracing.span("dshuffle:assemble", rows=n,
                      bytes=int(records.nbytes)):
        splitters = _load_splitters(conf, records[:, :klen], num_ranges,
                                    klen)
    reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                          TaskCounter.REDUCE_INPUT_RECORDS, n)

    # ---- exchange + sort phase (device)
    shards = None
    overflow = 0
    reduced_on_device = False
    if n > 0:
        from tpumr.parallel.device_sort import device_partition_sort
        capacity = conf.get_int(CAPACITY_KEY, 0) or None
        stats: dict = {}
        shards, overflow = device_partition_sort(
            mesh, records, klen, splitters, num_ranges, capacity=capacity,
            stats=stats, key_words=landing.key_words, reduce=kernel,
            reduce_words_made=landing.reduce_words)
        reporter.incr_counter(BackendCounter.GROUP,
                              BackendCounter.TPU_SHUFFLE_RETRIES,
                              stats.get("retries", 0))
        reporter.incr_counter(BackendCounter.GROUP,
                              BackendCounter.TPU_SHUFFLE_PAD_ROWS,
                              stats.get("pad_rows", 0))
        reporter.incr_counter(BackendCounter.GROUP,
                              BackendCounter.TPU_SHUFFLE_BYTES_BACK,
                              stats.get("bytes_back", 0))
        if shards is not None:  # count only records the device actually moved
            reporter.incr_counter(BackendCounter.GROUP,
                                  BackendCounter.TPU_SHUFFLE_DEVICES,
                                  len(shards))
            reporter.incr_counter(BackendCounter.GROUP,
                                  BackendCounter.TPU_SHUFFLE_RECORDS, n)
            reporter.incr_counter(BackendCounter.GROUP,
                                  BackendCounter.TPU_SHUFFLE_BYTES,
                                  int(records.nbytes))
            on_accel = mesh.devices.flat[0].platform != "cpu"
            if on_accel:
                reporter.incr_counter(BackendCounter.GROUP,
                                      BackendCounter.DEVICE_SORT_ON_ACCEL)
            if "reduced_groups" in stats:   # the shards hold groups
                reduced_on_device = True
                reporter.incr_counter(BackendCounter.GROUP,
                                      BackendCounter.TPU_REDUCE_RECORDS, n)
                reporter.incr_counter(BackendCounter.GROUP,
                                      BackendCounter.TPU_REDUCE_GROUPS,
                                      stats["reduced_groups"])
                reporter.incr_counter(BackendCounter.GROUP,
                                      BackendCounter.TPU_REDUCE_BYTES_BACK,
                                      stats["reduce_bytes_back"])
                if on_accel:
                    reporter.incr_counter(
                        BackendCounter.GROUP,
                        BackendCounter.DEVICE_REDUCE_ON_ACCEL)
    host_fallback = shards is None
    if host_fallback:
        # host fallback: full numpy lexsort, then the same range split
        # (≈ the disk-spill fallback role; correctness never depends on
        # the device path)
        if n > 0 and overflow:
            reporter.incr_counter(BackendCounter.GROUP,
                                  BackendCounter.SHUFFLE_HOST_FALLBACKS)
        from tpumr.parallel.device_sort import key_columns
        with tracing.span("dshuffle:host_sort", rows=n):
            kcols = key_columns(records, klen) if n else None
            order = np.lexsort(tuple(
                kcols[:, c] for c in range(kcols.shape[1] - 1, -1, -1))) \
                if n else np.zeros(0, int)
            all_sorted = records[order]
        n_dev = 1
        shards = [all_sorted]
    else:
        n_dev = len(shards)
    if kernel is not None:
        # every counter of a kernel's reduce is in the rollup, 0 or not
        for c in (BackendCounter.TPU_REDUCE_RECORDS,
                  BackendCounter.TPU_REDUCE_GROUPS,
                  BackendCounter.TPU_REDUCE_BYTES_BACK,
                  BackendCounter.DEVICE_REDUCE_ON_ACCEL,
                  BackendCounter.REDUCE_HOST_TWIN):
            reporter.incr_counter(BackendCounter.GROUP, c, 0)
        if not reduced_on_device:
            # an overflow, a host fallback, a mesh: the sorted rows are
            # on the host, and the kernel's numpy twin reduces them (a
            # group never spans two shards: the exchange is by key range)
            with tracing.span("dshuffle:reduce", rows=n, kernel=kernel.name,
                              host_twin=True) as sp:
                shards = [kernel.reduce_host(s, klen) for s in shards]
                if sp is not None:
                    sp.set(groups=sum(s.shape[0] for s in shards))
            reporter.incr_counter(BackendCounter.GROUP,
                                  BackendCounter.REDUCE_HOST_TWIN)
        reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                              TaskCounter.REDUCE_INPUT_GROUPS,
                              sum(s.shape[0] for s in shards))
    if ds is not None:
        ds.set(rows=n, n_dev=n_dev, overflow=overflow,
               host_fallback=host_fallback)
    ranges_per_dev = -(-num_ranges // n_dev)
    reporter.set_status(
        f"device shuffle: {n} records over {n_dev} devices in "
        f"{time.monotonic() - t0:.3f}s (overflow retries seen: {overflow})")

    # ---- reduce + write phase (host, range-ordered part files). Three
    # ways through it: the identity reducer writes the rows; a kernel's
    # groups are rows already and are written the same way; any other
    # reducer is called group by group
    reducer_cls = conf.get_reducer_class()
    from tpumr.mapred.api import IdentityReducer
    identity = kernel is not None or reducer_cls is None \
        or reducer_cls is IdentityReducer
    committer = FileOutputCommitter(conf)
    wd = committer.setup_task(str(task.attempt_id))
    out_fmt = new_instance(conf.get_output_format(), conf)

    with tracing.span("dshuffle:write", ranges=num_ranges) as sp:
        # every range of every shard first: a shard is in key order (the
        # device sort's, the host fallback's, a kernel's groups alike), so
        # its cuts cost nothing and all ranges are known at once; a device
        # that received no row leaves its ranges empty
        t_cut = time.monotonic()
        ranges: list[np.ndarray] = []
        for lo_r in range(0, num_ranges, ranges_per_dev):
            hi_r = min(lo_r + ranges_per_dev, num_ranges)
            shard = shards[lo_r // ranges_per_dev]
            cuts = [0] + _range_boundaries(shard[:, :klen], splitters,
                                           lo_r, hi_r) + [shard.shape[0]]
            ranges += [shard[a:b] for a, b in zip(cuts, cuts[1:])]
        cut_s = time.monotonic() - t_cut
        ctx = tracing.capture()   # this span, for the workers' spans

        def write_range(range_idx: int, rows: np.ndarray) -> None:
            with tracing.activate_captured(ctx), tracing.span(
                    "dshuffle:range", range=range_idx,
                    device=range_idx // ranges_per_dev,
                    rows=int(rows.shape[0]), bytes=int(rows.nbytes)):
                writer = out_fmt.get_record_writer(conf, wd, range_idx)
                try:
                    if identity:
                        _write_rows(writer, rows, klen)
                    else:
                        _reduce_rows(conf, reducer_cls, rows, klen, writer,
                                     reporter)
                finally:
                    writer.close()

        total = sum(r.shape[0] for r in ranges)
        if identity:
            # rows as they are: a writer a range, side by side, each into
            # its own part file. Framing and writing leave the interpreter
            # free (Writer.append_fixed_rows), so the phase lasts about as
            # long as its largest range
            writers = min(num_ranges, os.cpu_count() or 1)
            with ThreadPoolExecutor(
                    writers, thread_name_prefix="dshuffle-range") as pool:
                written = [pool.submit(write_range, r, rows)
                           for r, rows in enumerate(ranges)]
            # every worker has ended and closed its stream: the first
            # error, if any, fails the task
            for w in written:
                w.result()
            reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                  TaskCounter.REDUCE_OUTPUT_RECORDS, total)
        else:
            # a user's reducer is Python a record and was never promised
            # to run beside itself: one range after another, in this thread
            writers = 1
            for r, rows in enumerate(ranges):
                write_range(r, rows)
        reporter.incr_counter(BackendCounter.GROUP,
                              BackendCounter.TPU_SHUFFLE_WRITERS, writers)
        if sp is not None:
            sp.set(writers=writers, cut_s=round(cut_s, 6), rows=total,
                   bytes=sum(int(r.nbytes) for r in ranges))
    # commit is the CALLER's job (tracker: master-gated can_commit;
    # local runner: direct commit_task) — same contract as run_reduce_task


def _write_rows(writer: Any, rows: np.ndarray, klen: int) -> None:
    bulk = getattr(writer, "write_fixed_rows", None)
    if bulk is not None:
        bulk(rows, klen)  # vectorized framing — per-record append would
        #                   dominate the whole device-shuffled job
    else:
        kb = rows[:, :klen]
        vb = rows[:, klen:]
        for i in range(rows.shape[0]):
            writer.write(kb[i].tobytes(), vb[i].tobytes())


def _reduce_rows(conf: Any, reducer_cls: type, rows: np.ndarray, klen: int,
                 writer: Any, reporter: Reporter) -> None:
    """Run the user reducer over the key-sorted rows of one range: groups
    are consecutive equal keys (device sort replaced the merge, grouping
    semantics preserved)."""
    reducer = new_instance(reducer_cls, conf)
    n = rows.shape[0]

    def emit(k: Any, v: Any) -> None:
        reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                              TaskCounter.REDUCE_OUTPUT_RECORDS)
        writer.write(k, v)

    collector = OutputCollector(emit)
    # the group boundaries once, by comparing adjacent rows' key bytes
    from tpumr.ops.segment_sum import group_starts
    starts = np.flatnonzero(group_starts(rows[:, :klen])) if n else []
    ends = list(starts[1:]) + [n]
    try:
        for i, j in zip(starts, ends):
            reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                                  TaskCounter.REDUCE_INPUT_GROUPS)
            values = (rows[t, klen:].tobytes() for t in range(i, j))
            reducer.reduce(rows[i, :klen].tobytes(), values, collector,
                           reporter)
    finally:
        reducer.close()


def local_dense_fetch(map_outputs: "list[tuple[str, dict] | None]"
                      ) -> DenseFetchFn:
    """In-process fetch over the maps' dense files (LocalJobRunner path)."""

    def fetch(map_index: int) -> tuple[np.ndarray, np.ndarray]:
        ent = map_outputs[map_index]
        assert ent is not None, f"map {map_index} output missing"
        with tracing.span("dshuffle:fetch", map_index=map_index) as sp:
            k, v = read_dense_output(ent[0])
            if sp is not None:
                sp.set(bytes=int(k.nbytes + v.nbytes))
        return k, v

    return fetch
