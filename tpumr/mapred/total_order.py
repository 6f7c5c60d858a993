"""Global-sort support: input sampling + range partitioning.

≈ the reference's ``mapred/lib/TotalOrderPartitioner.java`` +
``mapred/lib/InputSampler.java`` (used by TeraSort — the reference's
terasort ships its own sampler in ``examples/terasort/TeraInputFormat``).
The sampler draws keys from the job's input splits, picks R-1 evenly
spaced cut points, and writes them to a partition file; the partitioner
bisects each map-output key against the cut points so reduce r receives
exactly the keys in (cut[r-1], cut[r]] — per-reduce sorted output is then
globally sorted by part index.
"""

from __future__ import annotations

import bisect
from typing import Any

from tpumr.fs import get_filesystem
from tpumr.io.writable import deserialize, serialize
from tpumr.mapred.api import Partitioner
from tpumr.utils.reflection import new_instance

PARTITION_PATH_KEY = "total.order.partitioner.path"


def sample_input(conf: Any, num_samples: int = 1000,
                 max_splits: int = 10, key_of: Any = None) -> list:
    """Draw up to ``num_samples`` keys from the job's input (SplitSampler
    semantics: evenly across the first ``max_splits`` splits). Where the
    job's map makes its output key from the record, ``key_of(key,
    value)`` makes the sample's the same way."""
    input_format = new_instance(conf.get_input_format(), conf)
    splits = input_format.get_splits(conf, conf.num_map_tasks_hint)
    splits = splits[:max_splits]
    if not splits:
        return []
    per_split = max(1, num_samples // len(splits))
    samples: list = []
    for split in splits:
        reader = input_format.get_record_reader(split, conf)
        for i, (key, _value) in enumerate(reader):
            if i >= per_split:
                break
            samples.append(key if key_of is None else key_of(key, _value))
    return samples


def write_partition_file(conf: Any, path: str, samples: list,
                         num_reduces: int) -> None:
    """Pick R-1 cut points from sorted samples and persist them; also sets
    the conf key the partitioner reads (≈ TotalOrderPartitioner.setPartitionFile)."""
    cuts: list = []
    if num_reduces > 1 and samples:
        ordered = sorted(samples)
        step = len(ordered) / num_reduces
        last = None
        for r in range(1, num_reduces):
            cand = ordered[min(len(ordered) - 1, int(round(r * step)))]
            if last is None or cand > last:
                cuts.append(cand)
                last = cand
    fs = get_filesystem(path, conf)
    fs.write_bytes(path, serialize(cuts))
    conf.set(PARTITION_PATH_KEY, path)


class TotalOrderPartitioner(Partitioner):
    """Range partitioner over the persisted cut points. Keys equal to a cut
    point go right (bisect_left), matching the reference's binary-search
    convention for the last key <= cut."""

    def __init__(self) -> None:
        self._cuts: list | None = None

    def configure(self, conf: Any) -> None:
        path = conf.get(PARTITION_PATH_KEY)
        if not path:
            raise ValueError(f"{PARTITION_PATH_KEY} not set — call "
                             "write_partition_file before submitting")
        fs = get_filesystem(path, conf)
        self._cuts = deserialize(fs.read_bytes(path))

    def get_partition(self, key: Any, value: Any, num_partitions: int) -> int:
        assert self._cuts is not None, "partitioner not configured"
        return min(bisect.bisect_left(self._cuts, key), num_partitions - 1)
