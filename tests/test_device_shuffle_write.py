"""The gang reduce's write phase (PR 34): a shard is cut at the job's
splitters by bisection, and where the rows are written as they are every
range has a writer of its own, side by side. What comes out is byte for
byte what one writer after another wrote. Runs on the conftest's virtual
CPU devices."""

import os
import threading

import numpy as np
import pytest
from test_device_shuffle import FixedKeyMapper, _teragen

from tpumr.core.counters import BackendCounter, TaskCounter
from tpumr.fs import get_filesystem
from tpumr.io import sequencefile
from tpumr.mapred.device_shuffle import (KEY_BYTES_KEY, RANGES_KEY,
                                         _load_splitters, _range_boundaries)
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.local_runner import run_job
from tpumr.mapred.output_formats import (OutputFormat, RecordWriter,
                                         SequenceFileOutputFormat)

# ------------------------------------------------------------------ the cut


def _linear_boundaries(sorted_keys, splitters, lo_range, hi_range):
    """The cut as it was before PR 34, kept as the plain reference: a
    count of the keys above each splitter, over key words."""
    from tpumr.parallel.device_sort import _lex_gt, key_columns
    n, klen = sorted_keys.shape
    if n == 0:
        return [0] * (hi_range - lo_range - 1)
    kcols = key_columns(sorted_keys, klen)
    scols = key_columns(splitters, klen) if len(splitters) else None
    bounds = []
    for i in range(lo_range, hi_range - 1):
        if scols is None or i >= len(scols):
            bounds.append(n)
        else:
            bounds.append(int(n - _lex_gt(kcols, scols[i]).sum()))
    return bounds


def _sorted(keys):
    return keys[np.lexsort(tuple(keys[:, c]
                                 for c in range(keys.shape[1] - 1, -1, -1)))]


def _random(rng, klen):
    keys = _sorted(rng.integers(0, 256, size=(5000, klen), dtype=np.uint8))
    return keys, _sorted(rng.integers(0, 256, size=(7, klen),
                                      dtype=np.uint8)), 0, 8


def _equal_to_a_splitter(rng, klen):
    keys, _cuts, lo, hi = _random(rng, klen)
    return keys, keys[[0, 17, 2500, 2501, 4998, 4999, 4999]].copy(), lo, hi


def _duplicates_across_a_cut(rng, klen):
    few = rng.integers(0, 256, size=(5, klen), dtype=np.uint8)
    keys = _sorted(few[rng.integers(0, 5, size=4000)])
    return keys, _sorted(few)[[0, 2, 4]].copy(), 0, 4


def _all_ff(rng, klen):
    keys = np.full((300, klen), 0xFF, np.uint8)
    keys[:100] = _sorted(rng.integers(0, 256, size=(100, klen),
                                      dtype=np.uint8))
    cuts = np.full((3, klen), 0xFF, np.uint8)
    cuts[0, -1] = 0xFE
    cuts[1, 0] = 0x80
    return keys, _sorted(cuts), 0, 4


def _short_splitter_list(rng, klen):
    keys, cuts, _lo, _hi = _random(rng, klen)
    return keys, cuts[:2].copy(), 0, 16    # 13 missing: +inf each


def _no_splitters(rng, klen):
    keys, _cuts, _lo, _hi = _random(rng, klen)
    return keys, np.zeros((0, klen), np.uint8), 0, 4


def _empty_shard(rng, klen):
    _keys, cuts, lo, hi = _random(rng, klen)
    return np.zeros((0, klen), np.uint8), cuts, lo, hi


def _one_range_a_shard(rng, klen):
    keys, cuts, _lo, _hi = _random(rng, klen)
    return keys, cuts, 3, 4                # the mesh's layout: no cut at all


def _a_devices_share_of_the_ranges(rng, klen):
    keys, cuts, _lo, _hi = _random(rng, klen)
    lo, hi = bytes(cuts[3]), bytes(cuts[5])
    mine = np.array([lo < bytes(k) <= hi for k in keys])
    return keys[mine], cuts, 4, 6          # ranges 4 and 5 of 8


CUTS = [_random, _equal_to_a_splitter, _duplicates_across_a_cut, _all_ff,
        _short_splitter_list, _no_splitters, _empty_shard,
        _one_range_a_shard, _a_devices_share_of_the_ranges]


@pytest.mark.parametrize("klen", [10, 16])
@pytest.mark.parametrize("case", CUTS, ids=lambda c: c.__name__.strip("_"))
def test_the_bisection_cuts_where_the_linear_count_did(case, klen):
    keys, cuts, lo, hi = case(np.random.default_rng(34 + klen), klen)
    got = _range_boundaries(keys, cuts, lo, hi)
    assert got == _linear_boundaries(keys, cuts, lo, hi)
    assert len(got) == hi - lo - 1 and got == sorted(got)
    # what a boundary means: the keys at or under the splitter, none over
    for at, s in zip(got, cuts[lo:hi - 1]):
        assert all(bytes(k) <= bytes(s) for k in keys[max(0, at - 3):at])
        assert all(bytes(k) > bytes(s) for k in keys[at:at + 3])


def test_a_cut_probes_a_few_keys_and_makes_no_key_words(monkeypatch):
    """Two dozen probes a splitter whatever the shard holds, and nothing
    of the shard's size is built (``key_columns`` of 10M keys was 1.9 s of
    the write phase)."""
    from tpumr.parallel import device_sort
    rng = np.random.default_rng(5)
    keys = _sorted(rng.integers(0, 256, size=(200_000, 10), dtype=np.uint8))

    class Counting(np.ndarray):
        probes = 0

        def __getitem__(self, i):
            Counting.probes += 1
            return np.asarray(self).__getitem__(i)

    cuts = keys[[50_000, 100_000, 150_000]].copy()
    want = _linear_boundaries(keys, cuts, 0, 4)
    monkeypatch.setattr(device_sort, "key_columns", lambda *a: 1 / 0)
    assert _range_boundaries(keys.view(Counting), cuts, 0, 4) == want
    assert Counting.probes <= 3 * 19   # 2^18 > 200,000


# -------------------------------------------------------------- the writers


def _rows_of(fs, d):
    """Every record under ``d`` as [n, 100] uint8, in file order."""
    recs = []
    for st in sorted(fs.list_status(d), key=lambda s: str(s.path)):
        if st.path.name.startswith("part-"):
            with fs.open(st.path) as f:
                recs += [k + v for k, v in sequencefile.Reader(f)]
    return np.frombuffer(b"".join(recs), np.uint8).reshape(len(recs), -1)


def _one_device(monkeypatch):
    import jax

    from tpumr.parallel import jaxruntime
    monkeypatch.setattr(jaxruntime, "accelerator_devices",
                        lambda: jax.devices()[:1])


@pytest.mark.parametrize("n_dev", [1, 8])
def test_four_ranges_side_by_side_are_the_files_one_writer_wrote(
        n_dev, monkeypatch):
    """The part files of a four-range job, byte for byte, against the plain
    way: the input's rows ordered by numpy, cut by the linear count at the
    job's splitters, each range appended record by record by ONE writer
    after another (the sync marker pinned). One device holds all four
    ranges in one shard (the one-chip host: the cut decides); on eight a
    range is a device's whole shard."""
    from tpumr.examples.terasort import make_terasort_conf
    if n_dev == 1:
        _one_device(monkeypatch)
    monkeypatch.setattr(sequencefile.os, "urandom", lambda n: b"\x5a" * n)
    fs = get_filesystem("mem:///")
    base = f"mem:///dsw{n_dev}"
    _teragen(f"{base}/gen", 5000, maps=3)
    conf = make_terasort_conf(f"{base}/gen", f"{base}/out", 4,
                              device_shuffle=True)
    result = run_job(conf)
    assert result.successful

    def counted(group, name):
        return result.counters.value(group, name)

    assert counted(BackendCounter.GROUP,
                   BackendCounter.TPU_SHUFFLE_DEVICES) == n_dev
    assert counted(BackendCounter.GROUP, BackendCounter.TPU_SHUFFLE_WRITERS) \
        == min(4, os.cpu_count() or 1)
    assert counted(TaskCounter.FRAMEWORK_GROUP,
                   TaskCounter.REDUCE_OUTPUT_RECORDS) == 5000

    rows = _rows_of(fs, f"/dsw{n_dev}/gen")
    rows = rows[np.lexsort(tuple(rows[:, c] for c in range(9, -1, -1)))]
    cuts = [0] + _linear_boundaries(
        rows[:, :10], _load_splitters(conf, rows[:, :10], 4, 10), 0, 4) \
        + [5000]
    assert cuts == sorted(cuts) and 0 < cuts[1] < cuts[3] < 5000
    for r in range(4):
        import io
        plain = io.BytesIO()
        w = sequencefile.Writer(plain)
        for row in rows[cuts[r]:cuts[r + 1]]:
            w.append(bytes(row[:10]), bytes(row[10:]))
        w.close()
        assert fs.read_bytes(f"/dsw{n_dev}/out/part-{r:05d}") \
            == plain.getvalue(), f"part {r}"


class _Watched(RecordWriter):
    """A writer that notes what happens to it; range 2's raises."""

    log: "list[tuple]" = []

    def __init__(self, partition):
        self.partition = partition
        _Watched.log.append(("open", partition, threading.get_ident()))

    def write_fixed_rows(self, rows, klen):
        if self.partition == 2:
            raise IOError("disk full under range 2")
        _Watched.log.append(("rows", self.partition, int(rows.shape[0])))

    def close(self):
        _Watched.log.append(("close", self.partition))


class _WatchedFormat(OutputFormat):
    def get_record_writer(self, conf, work_dir, partition, prefix="part"):
        return _Watched(partition)


def test_a_writer_that_raises_fails_the_task_with_every_stream_closed():
    """The error of one range's worker is the task's, after every worker
    has ended: the other ranges were written and every writer closed; the
    output counter, which is added after the workers, was not."""
    from tpumr.examples.terasort import make_terasort_conf
    _teragen("mem:///dsx/gen", 2000, maps=2)
    conf = make_terasort_conf("mem:///dsx/gen", "mem:///dsx/out", 4,
                              device_shuffle=True)
    conf.set_output_format(_WatchedFormat)
    _Watched.log = []
    with pytest.raises(IOError, match="disk full under range 2"):
        run_job(conf)
    log = _Watched.log
    assert sorted(e[1] for e in log if e[0] == "open") == [0, 1, 2, 3]
    assert sorted(e[1] for e in log if e[0] == "close") == [0, 1, 2, 3]
    assert sorted(e[1] for e in log if e[0] == "rows") == [0, 1, 3]
    # nothing was committed: that is the caller's, after a task that ended
    fs = get_filesystem("mem:///")
    assert not [st for st in fs.list_status("/dsx/out")
                if st.path.name.startswith("part-")]


class _WatchingReducer:
    """A user's reducer that notes, for every group, the thread it was
    called in and which instance (a range has an instance of its own)."""

    calls: "list[tuple]" = []

    def configure(self, conf):
        pass

    def reduce(self, key, values, output, reporter):
        _WatchingReducer.calls.append(
            (id(self), threading.get_ident(), bytes(key)))
        output.collect(key, sum(int.from_bytes(v, "big")
                                for v in values).to_bytes(4, "big"))

    def close(self):
        _WatchingReducer.calls.append((id(self), threading.get_ident(), None))


def test_a_users_reducer_sees_its_ranges_one_after_another_in_one_thread():
    """User code was never promised to run beside itself: a reducer class
    keeps the phase serial, in the task's own thread, each range's
    instance closed before the next is made."""
    fs = get_filesystem("mem:///")
    fs.write_bytes("/dsu/in.txt",
                   b"\n".join(b"key%04d" % (i % 40) for i in range(400)))
    conf = JobConf()
    conf.set_input_paths("mem:///dsu/in.txt")
    conf.set_output_path("mem:///dsu/out")
    conf.set_mapper_class(FixedKeyMapper)
    conf.set_reducer_class(_WatchingReducer)
    conf.set_output_format(SequenceFileOutputFormat)
    conf.set_num_reduce_tasks(4)
    conf.set_device_shuffle(7, 4)
    _WatchingReducer.calls = []
    result = run_job(conf)
    assert result.successful
    assert conf.get_int(RANGES_KEY, 0) == 4 and conf.get_int(KEY_BYTES_KEY, 0)
    calls = _WatchingReducer.calls
    assert {c[1] for c in calls} == {threading.get_ident()}
    # an instance's calls are consecutive and end with its close
    instances = [c[0] for c in calls]
    firsts = [i for i, who in enumerate(instances)
              if i == 0 or instances[i - 1] != who]
    assert len(firsts) == len(set(instances)) == 4
    assert [calls[i - 1][2] for i in firsts[1:]] + [calls[-1][2]] \
        == [None] * 4
    keys = [c[2] for c in calls if c[2] is not None]
    assert keys == sorted(keys) and len(keys) == 40
    assert result.counters.value(
        BackendCounter.GROUP, BackendCounter.TPU_SHUFFLE_WRITERS) == 1
    assert result.counters.value(
        TaskCounter.FRAMEWORK_GROUP, TaskCounter.REDUCE_OUTPUT_RECORDS) == 40


def test_range_writers_on_more_threads_than_cores_lose_nothing(monkeypatch):
    """Sixteen ranges on a host that says it has sixteen cores, the
    interpreter switching threads every few microseconds: every range's
    rows arrive in its own part file, in order, and the counters are the
    sums they were."""
    import sys

    from tpumr.examples.terasort import make_terasort_conf
    from tpumr.mapred import device_shuffle
    _one_device(monkeypatch)
    monkeypatch.setattr(device_shuffle.os, "cpu_count", lambda: 16)
    fs = get_filesystem("mem:///")
    _teragen("mem:///dsm/gen", 8000, maps=2)
    conf = make_terasort_conf("mem:///dsm/gen", "mem:///dsm/out", 16,
                              device_shuffle=True)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        result = run_job(conf)
    finally:
        sys.setswitchinterval(was)
    assert result.successful
    assert result.counters.value(
        BackendCounter.GROUP, BackendCounter.TPU_SHUFFLE_WRITERS) == 16
    assert result.counters.value(
        TaskCounter.FRAMEWORK_GROUP, TaskCounter.REDUCE_OUTPUT_RECORDS) \
        == 8000
    out = _rows_of(fs, "/dsm/out")
    gen = _rows_of(fs, "/dsm/gen")
    assert len([st for st in fs.list_status("/dsm/out")
                if st.path.name.startswith("part-")]) == 16
    assert out.tobytes() == gen[np.lexsort(tuple(
        gen[:, c] for c in range(9, -1, -1)))].tobytes()
