"""Hierarchical job counters.

≈ ``org.apache.hadoop.mapred.Counters`` (reference:
src/mapred/org/apache/hadoop/mapred/Counters.java): named groups of named
counters, incremented by tasks, serialized in every heartbeat, and summed
job-wide. The TPU build additionally makes backend placement a first-class
counter group (the reference's GPU observability was log-only — SURVEY.md §5).
"""

from __future__ import annotations

import threading
from typing import Iterator


class TaskCounter:
    """Framework counter names (≈ Task.Counter enum)."""
    MAP_INPUT_RECORDS = "MAP_INPUT_RECORDS"
    MAP_OUTPUT_RECORDS = "MAP_OUTPUT_RECORDS"
    MAP_INPUT_BYTES = "MAP_INPUT_BYTES"
    MAP_OUTPUT_BYTES = "MAP_OUTPUT_BYTES"
    COMBINE_INPUT_RECORDS = "COMBINE_INPUT_RECORDS"
    COMBINE_OUTPUT_RECORDS = "COMBINE_OUTPUT_RECORDS"
    REDUCE_INPUT_GROUPS = "REDUCE_INPUT_GROUPS"
    REDUCE_INPUT_RECORDS = "REDUCE_INPUT_RECORDS"
    REDUCE_OUTPUT_RECORDS = "REDUCE_OUTPUT_RECORDS"
    REDUCE_SHUFFLE_BYTES = "REDUCE_SHUFFLE_BYTES"
    #: bytes that actually crossed the shuffle wire (post wire-codec
    #: compression) — the ratio REDUCE_SHUFFLE_WIRE_BYTES /
    #: REDUCE_SHUFFLE_BYTES is the wire compression win per job
    REDUCE_SHUFFLE_WIRE_BYTES = "REDUCE_SHUFFLE_WIRE_BYTES"
    #: copier segment placement (ShuffleRamManager budget outcome):
    #: how many map outputs merged straight from RAM vs spilled local
    REDUCE_SHUFFLE_SEGMENTS_MEM = "REDUCE_SHUFFLE_SEGMENTS_MEM"
    REDUCE_SHUFFLE_SEGMENTS_DISK = "REDUCE_SHUFFLE_SEGMENTS_DISK"
    #: fetch failures the copier survived (local retries, penalty box,
    #: and fetch-failure reports to the master — shuffle fault tolerance)
    REDUCE_FETCH_FAILURES = "REDUCE_FETCH_FAILURES"
    SPILLED_RECORDS = "SPILLED_RECORDS"
    #: shuffle merge engine: background in-memory merges that freed
    #: ShuffleRamManager budget mid-copy (≈ InMemFSMergeThread), and the
    #: segments they consumed
    SHUFFLE_INMEM_MERGES = "SHUFFLE_INMEM_MERGES"
    SHUFFLE_INMEM_MERGE_SEGMENTS = "SHUFFLE_INMEM_MERGE_SEGMENTS"
    #: background disk-run merges during the copy phase (≈ the
    #: reference LocalFSMerger): accumulated per-segment spills folded
    #: into one sorted run while fetchers wait on the wire, keeping the
    #: final merge single-pass
    SHUFFLE_DISK_MERGES = "SHUFFLE_DISK_MERGES"
    SHUFFLE_DISK_MERGE_SEGMENTS = "SHUFFLE_DISK_MERGE_SEGMENTS"
    #: bounded-fan-in merging (≈ Merger intermediate passes honoring
    #: io.sort.factor): intermediate passes run and segments they merged
    MERGE_PASSES = "MERGE_PASSES"
    MERGE_PASS_SEGMENTS = "MERGE_PASS_SEGMENTS"
    #: maps whose whole-split read parsed every block of a SequenceFile
    #: split at once, as one strided view (sequencefile.read_batch_range's
    #: ``strided`` form); the others read no such split or parsed a block
    #: at a time
    MAP_INPUT_SPLITS_STRIDED = "MAP_INPUT_SPLITS_STRIDED"
    FRAMEWORK_GROUP = "tpumr.TaskCounter"


class BackendCounter:
    """New in the TPU build: per-backend placement/runtime counters."""
    CPU_MAP_TASKS = "CPU_MAP_TASKS"
    TPU_MAP_TASKS = "TPU_MAP_TASKS"
    CPU_MAP_MILLIS = "CPU_MAP_MILLIS"
    TPU_MAP_MILLIS = "TPU_MAP_MILLIS"
    TPU_DEVICE_BYTES_STAGED = "TPU_DEVICE_BYTES_STAGED"
    CPU_BATCH_MAP_TASKS = "CPU_BATCH_MAP_TASKS"
    TPU_SHUFFLE_RECORDS = "TPU_SHUFFLE_RECORDS"
    TPU_SHUFFLE_BYTES = "TPU_SHUFFLE_BYTES"
    #: gang reduces whose device sort ran on a REAL accelerator backend
    #: (vs the same vectorized path on the CPU backend) — lets a job
    #: artifact PROVE which backend sorted it, not just that the dense
    #: path ran
    DEVICE_SORT_ON_ACCEL = "DEVICE_SORT_ON_ACCEL"
    SHUFFLE_HOST_FALLBACKS = "SHUFFLE_HOST_FALLBACKS"
    #: what the gang reduce's device call did: the mesh size the exchange
    #: and sort ran over (1: the one-device argsort), the overflow
    #: retries of the exchange, the rows its shape bucket added, the
    #: bytes the mesh sort copied back from the devices (over
    #: TPU_SHUFFLE_BYTES: how much more than the job's rows)
    TPU_SHUFFLE_DEVICES = "TPU_SHUFFLE_DEVICES"
    TPU_SHUFFLE_RETRIES = "TPU_SHUFFLE_RETRIES"
    TPU_SHUFFLE_PAD_ROWS = "TPU_SHUFFLE_PAD_ROWS"
    TPU_SHUFFLE_BYTES_BACK = "TPU_SHUFFLE_BYTES_BACK"
    #: maps whose dense output the gang reduce read from the disk of its
    #: own tracker, not through the RPC of the tracker that serves it
    TPU_SHUFFLE_LOCAL_MAPS = "TPU_SHUFFLE_LOCAL_MAPS"
    #: the most range writers the gang reduce's write phase ran side by
    #: side: the lesser of its ranges and the host's cores where the rows
    #: are written as they are, 1 where a user's reducer is called (one
    #: range after another, in the task's thread)
    TPU_SHUFFLE_WRITERS = "TPU_SHUFFLE_WRITERS"
    #: a gang reduce whose reducer is a kernel (tpumr.reduce.kernel):
    #: the rows and groups the DEVICE reduced where it had sorted them,
    #: the bytes of groups it copied back (near the output's size, not
    #: the input's), whether that device was a real accelerator, and the
    #: gang reduces that reduced on the host with the kernel's numpy twin
    #: instead (an overflow, a host fallback, a mesh)
    TPU_REDUCE_RECORDS = "TPU_REDUCE_RECORDS"
    TPU_REDUCE_GROUPS = "TPU_REDUCE_GROUPS"
    TPU_REDUCE_BYTES_BACK = "TPU_REDUCE_BYTES_BACK"
    DEVICE_REDUCE_ON_ACCEL = "DEVICE_REDUCE_ON_ACCEL"
    REDUCE_HOST_TWIN = "REDUCE_HOST_TWIN"
    GROUP = "tpumr.BackendCounter"


class JobCounter:
    LAUNCHED_MAP_TASKS = "LAUNCHED_MAP_TASKS"
    LAUNCHED_REDUCE_TASKS = "LAUNCHED_REDUCE_TASKS"
    DATA_LOCAL_MAPS = "DATA_LOCAL_MAPS"
    RACK_LOCAL_MAPS = "RACK_LOCAL_MAPS"
    FAILED_MAP_TASKS = "FAILED_MAP_TASKS"
    FAILED_REDUCE_TASKS = "FAILED_REDUCE_TASKS"
    SPECULATIVE_MAPS = "SPECULATIVE_MAPS"
    #: accelerator fault tolerance: TIPs pinned CPU-only after repeated
    #: device/compile-classed TPU failures, and attempts the tracker
    #: reaper failed for progress silence (failure_class=timeout)
    TPU_DEMOTIONS = "TPU_DEMOTIONS"
    TASKS_REAPED_TIMEOUT = "TASKS_REAPED_TIMEOUT"
    #: the hybrid scheduler's estimate at work (mapred/map_cost.py):
    #: asks at which a free CPU slot got no map of the job because the
    #: chip ends it sooner, and running CPU maps done over on an idle
    #: chip
    CPU_MAPS_WITHHELD = "CPU_MAPS_WITHHELD"
    TPU_TWINS_OF_CPU_MAPS = "TPU_TWINS_OF_CPU_MAPS"
    GROUP = "tpumr.JobCounter"


class Counter:
    __slots__ = ("name", "display_name", "_value", "_lock")

    def __init__(self, name: str, display_name: str | None = None,
                 value: int = 0) -> None:
        self.name = name
        self.display_name = display_name or name
        self._value = int(value)
        self._lock = threading.Lock()

    @property
    def value(self) -> int:
        return self._value

    def increment(self, amount: int = 1) -> None:
        with self._lock:
            self._value += amount

    def set_value(self, value: int) -> None:
        with self._lock:
            self._value = int(value)

    def __repr__(self) -> str:  # pragma: no cover
        return f"Counter({self.name}={self._value})"


class CounterGroup:
    def __init__(self, name: str) -> None:
        self.name = name
        self._counters: dict[str, Counter] = {}
        self._lock = threading.Lock()

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def __iter__(self) -> Iterator[Counter]:
        return iter(self._counters.values())

    def __len__(self) -> int:
        return len(self._counters)

    def merge(self, other: "CounterGroup") -> None:
        for c in other:
            self.counter(c.name).increment(c.value)


class Counters:
    """Thread-safe counter set: group → name → value."""

    def __init__(self) -> None:
        self._groups: dict[str, CounterGroup] = {}
        self._lock = threading.Lock()
        #: (group, name) -> Counter fast path: incr() runs once per
        #: RECORD on the host map/reduce paths — the two-level locked
        #: lookup is profiling-visible. CPython dict reads are atomic;
        #: insertion goes through the locked path once per counter.
        self._flat: dict[tuple, Counter] = {}

    def group(self, name: str) -> CounterGroup:
        with self._lock:
            g = self._groups.get(name)
            if g is None:
                g = self._groups[name] = CounterGroup(name)
            return g

    def counter(self, group: str, name: str) -> Counter:
        key = (group, name)
        c = self._flat.get(key)
        if c is None:
            c = self.group(group).counter(name)
            self._flat[key] = c
        return c

    def incr(self, group: str, name: str, amount: int = 1) -> None:
        self.counter(group, name).increment(amount)

    def value(self, group: str, name: str) -> int:
        return self.counter(group, name).value

    def __iter__(self) -> Iterator[CounterGroup]:
        return iter(list(self._groups.values()))

    def merge(self, other: "Counters") -> None:
        """Sum another counter set into this one (≈ Counters.incrAllCounters)."""
        for g in other:
            self.group(g.name).merge(g)

    # wire format (heartbeats / history)

    def to_dict(self) -> dict[str, dict[str, int]]:
        return {g.name: {c.name: c.value for c in g} for g in self}

    @classmethod
    def from_dict(cls, d: dict[str, dict[str, int]]) -> "Counters":
        out = cls()
        for gname, cs in d.items():
            for cname, v in cs.items():
                out.counter(gname, cname).set_value(v)
        return out

    def __repr__(self) -> str:  # pragma: no cover
        total = sum(len(g) for g in self)
        return f"Counters({len(self._groups)} groups, {total} counters)"
