"""A reduce on the device, other than the sort's: the segment-sum kernel
(tpumr.ops.segment_sum) behind the device shuffle, and the job that names
it, ``tpumr examples uservisits-agg --device-shuffle``.

The reference is the benchmark family's own (bench/families/
uservisits_agg.py: the table made again from the seed, the distinct keys
in byte order, sums in float64), at a size a test can hold. Keys and their
order are compared exactly; float32 sums within ``GAP``: a group of up to
a few dozen float32 values in [1, 1000) added pairwise is off float64 by
a few parts in 10^7."""

import os
import struct
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.families import uservisits_agg as uv  # noqa: E402
from tpumr.core.counters import BackendCounter, TaskCounter  # noqa: E402
from tpumr.mapred.api import OutputCollector, Reporter  # noqa: E402
from tpumr.mapred.job_client import JobClient  # noqa: E402
from tpumr.mapred.local_runner import run_job  # noqa: E402
from tpumr.mapred.mini_cluster import MiniMRCluster  # noqa: E402

SEED = 2_400_000_011
GAP = 1e-6
KLEN = 16


# ------------------------------------------------------ the kernel alone


def _sorted_rows(case: str, klen: int = KLEN) -> np.ndarray:
    """Key-sorted ``[n, klen + 4]`` rows of one named input, from a
    seed."""
    rng = np.random.default_rng([SEED, len(case)])
    if case == "runs_of_every_length":
        runs = np.concatenate([np.arange(1, 40), [1, 1, 257, 2, 64, 1]])
    elif case == "one_group":
        runs = np.array([3001])
    elif case == "all_distinct":
        runs = np.ones(2500, int)
    elif case == "one_row":
        runs = np.array([1])
    else:
        assert case == "a_key_of_all_ff"
        runs = np.array([5, 1, 9, 4])
    keys = rng.integers(0, 255, size=(len(runs), klen), dtype=np.uint8)
    if case == "a_key_of_all_ff":
        keys[-1] = 0xFF         # sorts last, where the padding's keys are
    keys = keys[np.lexsort(tuple(keys[:, c]
                                 for c in range(klen - 1, -1, -1)))]
    assert len({bytes(k) for k in keys}) == len(runs)
    values = rng.uniform(1, 1000, size=int(runs.sum())).astype("<f4")
    return np.concatenate([np.repeat(keys, runs, axis=0),
                           values.view(np.uint8).reshape(-1, 4)], axis=1)


def _float64_sums(rows: np.ndarray, klen: int = KLEN):
    keys = rows[:, :klen]
    first = np.ones(len(rows), bool)
    first[1:] = (keys[1:] != keys[:-1]).any(axis=1)
    values = np.ascontiguousarray(rows[:, klen:]).view("<f4")[:, 0]
    return keys[first], np.add.reduceat(values.astype(np.float64),
                                        np.flatnonzero(first))


def _sums_of(group_rows: np.ndarray, klen: int = KLEN) -> np.ndarray:
    return np.ascontiguousarray(group_rows[:, klen:]).view("<f4")[:, 0]


CASES = ["runs_of_every_length", "one_group", "all_distinct", "one_row",
         "a_key_of_all_ff"]


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_agrees_with_its_twin_add_for_add(case):
    """The device program (here on the CPU backend) and the numpy twin
    make the same float32 additions in the same order, so their sums are
    equal bit for bit; both are within ``GAP`` of float64."""
    from tpumr.ops import get_reduce_kernel
    from tpumr.parallel.device_sort import (bucket_rows, num_key_columns,
                                            reduce_words, rows_of_words)
    kernel = get_reduce_kernel("segment-sum-f32")
    rows = _sorted_rows(case)
    n, kc = rows.shape[0], num_key_columns(KLEN)
    n_pad = bucket_rows(n, 1)
    words = np.zeros((kc + 1, n_pad), np.uint32)
    words[:, :n] = reduce_words(rows, KLEN)
    words[:kc, n:] = 0xFFFFFFFF
    table, groups = kernel.device_program(kc)(words, np.int32(n))
    got = rows_of_words(np.asarray(table)[:, :int(groups)], KLEN)
    twin = kernel.reduce_host(rows, KLEN)
    assert got.tobytes() == twin.tobytes()
    keys, sums = _float64_sums(rows)
    assert (twin[:, :KLEN] == keys).all() and len(twin) == len(keys)
    assert (np.abs(_sums_of(twin) - sums) / sums).max() < GAP


@pytest.mark.parametrize("klen", [16, 10])
@pytest.mark.parametrize("case", ["runs_of_every_length", "one_group",
                                  "a_key_of_all_ff"])
def test_one_device_sorts_and_reduces_and_only_groups_come_back(case, klen):
    """``device_partition_sort`` with a reduce kernel on one device: rows
    in any order go up, the groups come back key-sorted, and what came
    back is counted in bytes near 20 a group, not 20 a row."""
    from tpumr.ops import get_reduce_kernel
    from tpumr.parallel.device_sort import device_partition_sort
    from tpumr.parallel.mesh import make_mesh
    rows = _sorted_rows(case, klen)
    shuffled = rows[np.random.default_rng(3).permutation(len(rows))]
    stats: dict = {}
    shards, overflow = device_partition_sort(
        make_mesh(1), shuffled, klen, np.zeros((0, klen), np.uint8), 1,
        stats=stats, reduce=get_reduce_kernel("segment-sum-f32"))
    keys, sums = _float64_sums(rows, klen)
    assert overflow == 0 and len(shards) == 1
    assert (shards[0][:, :klen] == keys).all()
    assert (np.abs(_sums_of(shards[0], klen) - sums) / sums).max() < GAP
    assert stats["reduced_groups"] == len(keys)
    row_bytes = 4 * (-(-klen // 4) + 1)
    assert stats["reduce_bytes_back"] <= 4 + row_bytes * (len(keys) + 64)


def test_a_mesh_returns_its_sorted_rows_for_the_twin():
    """The mesh branch has no kernel program: it keeps the contract by
    saying nothing was reduced and returning the rows, whose groups never
    span two devices."""
    from tpumr.ops import get_reduce_kernel
    from tpumr.parallel.device_sort import device_partition_sort
    from tpumr.parallel.mesh import make_mesh
    kernel = get_reduce_kernel("segment-sum-f32")
    rows = _sorted_rows("runs_of_every_length")
    cuts = rows[[len(rows) // 4, len(rows) // 2, 3 * len(rows) // 4], :KLEN]
    stats: dict = {}
    shards, _ = device_partition_sort(
        make_mesh(4), rows[::-1].copy(), KLEN, cuts, 4, stats=stats,
        reduce=kernel)
    assert "reduced_groups" not in stats
    assert sum(len(s) for s in shards) == len(rows)
    groups = np.concatenate([kernel.reduce_host(s, KLEN) for s in shards])
    keys, sums = _float64_sums(rows)    # a group's rows came in another
    assert (groups[:, :KLEN] == keys).all()     # order: not bit for bit
    assert (np.abs(_sums_of(groups) - sums) / sums).max() < GAP


# ------------------------------------------------------------- the job


def _table(tmp_path, rows: int, groups: int, files: int = 3) -> dict:
    """The family's table at a small size, written here (no worker
    processes)."""
    sizes = {"rows": rows, "groups": groups, "files": files}
    table = tmp_path / "uservisits"
    table.mkdir()
    keys, key_len = uv.group_keys(SEED, groups)
    for i in range(files):
        n = uv._file_rows(rows, files, i)[1]
        with open(table / f"part-{i:05d}.txt", "wb") as f:
            for c, chunk in enumerate(uv._chunks(n)):
                f.write(uv.chunk_text(SEED, i, c, chunk, groups, keys,
                                      key_len).tobytes())
    return {"sizes": sizes, "in": f"file://{table}",
            "out": str(tmp_path / "out")}


def _one_device(monkeypatch, n_dev: int) -> None:
    import jax

    from tpumr.parallel import jaxruntime
    monkeypatch.setattr(jaxruntime, "accelerator_devices",
                        lambda: jax.devices()[:n_dev])


def _counted(result, name, group=BackendCounter.GROUP):
    return result.counters.value(group, name)


def _holds_the_reference(t: dict) -> None:
    wrong, gap = uv.compare(uv.read_output(t["out"]),
                            uv.reference(t["sizes"], SEED))
    assert wrong == 0 and gap < GAP, (wrong, gap)


@pytest.mark.parametrize("n_dev,ranges", [(1, 4), (1, 1), (4, 4), (4, 1)])
def test_the_job_through_the_local_runner(tmp_path, monkeypatch, n_dev,
                                          ranges):
    """One device reduces where it sorted; on a mesh the kernel's twin
    reduces the rows on the host and a counter says so. The answer and
    the record counters are the same."""
    from tpumr.examples.uservisits import make_uservisits_agg_conf
    _one_device(monkeypatch, n_dev)
    t = _table(tmp_path, 20_000, 3_000)
    result = run_job(make_uservisits_agg_conf(t["in"], "file://" + t["out"],
                                              ranges, device_shuffle=True))
    assert result.successful
    _holds_the_reference(t)
    groups = len(uv.reference(t["sizes"], SEED)[0])
    for name in (TaskCounter.REDUCE_INPUT_GROUPS,
                 TaskCounter.REDUCE_OUTPUT_RECORDS):
        assert _counted(result, name, TaskCounter.FRAMEWORK_GROUP) == groups
    assert _counted(result, TaskCounter.REDUCE_INPUT_RECORDS,
                    TaskCounter.FRAMEWORK_GROUP) == 20_000
    assert _counted(result, BackendCounter.TPU_SHUFFLE_RECORDS) == 20_000
    on_device = n_dev == 1
    assert _counted(result, BackendCounter.REDUCE_HOST_TWIN) == (
        0 if on_device else 1)
    assert _counted(result, BackendCounter.TPU_REDUCE_RECORDS) == (
        20_000 if on_device else 0)
    assert _counted(result, BackendCounter.TPU_REDUCE_GROUPS) == (
        groups if on_device else 0)
    assert _counted(result, BackendCounter.DEVICE_REDUCE_ON_ACCEL) == 0
    back = _counted(result, BackendCounter.TPU_REDUCE_BYTES_BACK)
    if on_device:   # near 20 bytes a group, far from 20 bytes a row
        assert 20 * groups <= back <= 20 * (groups + 400) + 4
    else:
        assert back == 0


def test_the_job_through_the_in_process_cluster(tmp_path, monkeypatch):
    """Client, master, tracker slots, dense map output, the gang reduce
    and the committer, with the reduce on the one device."""
    from tpumr.examples.uservisits import make_uservisits_agg_conf
    _one_device(monkeypatch, 1)
    t = _table(tmp_path, 12_000, 2_000)
    with MiniMRCluster(num_trackers=1, cpu_slots=2, tpu_slots=0) as c:
        conf = make_uservisits_agg_conf(t["in"], "file://" + t["out"], 4,
                                        device_shuffle=True)
        for k, v in c.create_job_conf():
            conf.set_if_unset(k, v)
        result = JobClient(conf).run_job(conf)
    assert result.successful and result.num_reduces == 1
    _holds_the_reference(t)
    assert sorted(p for p in os.listdir(t["out"]) if p.startswith("part-")
                  ) == [f"part-{r:05d}" for r in range(4)]
    assert _counted(result, BackendCounter.TPU_REDUCE_RECORDS) == 12_000
    assert _counted(result, BackendCounter.REDUCE_HOST_TWIN) == 0


def test_the_host_shuffle_gives_the_same_groups(tmp_path):
    """Without ``--device-shuffle`` a reducer class makes the sums behind
    the host shuffle."""
    from tpumr.examples.uservisits import make_uservisits_agg_conf
    t = _table(tmp_path, 6_000, 1_500)
    result = run_job(make_uservisits_agg_conf(t["in"], "file://" + t["out"],
                                              3))
    assert result.successful
    _holds_the_reference(t)
    assert _counted(result, BackendCounter.TPU_SHUFFLE_RECORDS) == 0


def test_a_range_without_a_key_gets_an_empty_part(tmp_path, monkeypatch):
    from tpumr.examples.uservisits import make_uservisits_agg_conf
    from tpumr.fs import get_filesystem
    from tpumr.io.writable import serialize
    from tpumr.mapred.total_order import PARTITION_PATH_KEY
    _one_device(monkeypatch, 1)
    t = _table(tmp_path, 8_000, 1_000)
    conf = make_uservisits_agg_conf(t["in"], "file://" + t["out"], 4,
                                    device_shuffle=True)
    keys = uv.reference(t["sizes"], SEED)[0]
    cut = bytes(keys[300])
    path = conf.get(PARTITION_PATH_KEY)
    # no key lies above ``cut`` and at or below ``cut`` with a last byte
    # of 1: the second range is empty
    get_filesystem(path, conf).write_bytes(path, serialize(
        [cut, cut[:-1] + b"\x01", bytes(keys[700])]))
    assert run_job(conf).successful
    _holds_the_reference(t)
    sizes = [len(uv.parse_container(open(os.path.join(t["out"], p),
                                         "rb").read()))
             for p in sorted(os.listdir(t["out"])) if p.startswith("part-")]
    assert sizes == [301, 0, 400, len(keys) - 701]


def test_an_overflow_ends_in_the_twin_not_the_row_loop(tmp_path,
                                                       monkeypatch):
    """Every retry of the exchange overflows: the rows are sorted on the
    host and reduced by the kernel's numpy twin."""
    from tpumr.examples.uservisits import make_uservisits_agg_conf
    from tpumr.mapred import device_shuffle
    _one_device(monkeypatch, 4)
    monkeypatch.setattr(
        device_shuffle, "_reduce_rows",
        lambda *a, **k: pytest.fail("the row loop reduced a kernel's job"))
    t = _table(tmp_path, 8_000, 1_000)
    conf = make_uservisits_agg_conf(t["in"], "file://" + t["out"], 4,
                                    device_shuffle=True)
    conf.set(device_shuffle.CAPACITY_KEY, 2)
    result = run_job(conf)
    assert result.successful
    _holds_the_reference(t)
    assert _counted(result, BackendCounter.SHUFFLE_HOST_FALLBACKS) == 1
    assert _counted(result, BackendCounter.REDUCE_HOST_TWIN) == 1
    assert _counted(result, BackendCounter.TPU_REDUCE_RECORDS) == 0
    assert _counted(result, BackendCounter.TPU_SHUFFLE_RECORDS) == 0


@pytest.mark.parametrize("what", ["a_reducer_class_too", "a_wrong_width",
                                  "an_unknown_kernel"])
def test_a_job_that_misnames_its_reduce_kernel_is_refused(what):
    from tpumr.mapred.api import IdentityReducer
    from tpumr.mapred.device_shuffle import prepare_device_shuffle_job
    from tpumr.mapred.jobconf import JobConf
    conf = JobConf()
    conf.set_num_reduce_tasks(2)
    conf.set_device_shuffle(16, 8 if what == "a_wrong_width" else 4)
    conf.set_reduce_kernel("no-such" if what == "an_unknown_kernel"
                           else "segment-sum-f32")
    if what == "a_reducer_class_too":
        conf.set_reducer_class(IdentityReducer)
    with pytest.raises((ValueError, KeyError)):
        prepare_device_shuffle_job(conf)


# ------------------------------------------------ the map's batch parse


def test_the_batch_parse_gives_what_the_row_parse_gives():
    """``parse_rows`` against ``record_of`` on the family's rows and on
    rows it must hand to the slow path (an exponent, more digits than a
    float64 holds exactly, no fraction)."""
    from tpumr.examples.uservisits import parse_rows, record_of
    keys, key_len = uv.group_keys(SEED, 500)
    lines = uv.chunk_text(SEED, 0, 0, 2_000, 500, keys,
                          key_len).tobytes().split(b"\n")[:-1]
    odd = [b"1.2.3.4|u|d|%s|a|c|l|w|7" % r for r in
           (b"1e2", b"12", b"0.1234567890123456789", b"-3.5", b"7.")]
    lines += odd
    data = np.frombuffer(b"".join(lines), np.uint8)
    offsets = np.concatenate([[0], np.cumsum([len(x) for x in lines])])
    got = parse_rows(data, offsets)
    for i, line in enumerate(lines):
        k, v = record_of(line)
        assert bytes(got[i, :16]) == k and bytes(got[i, 16:]) == v, line
    g, cents = uv.query_columns(SEED, 0, 0, 2_000, 500)
    assert struct.unpack("<f", bytes(got[5, 16:]))[0] == np.float32(
        cents[5] / 100.0)
    with pytest.raises(ValueError):
        parse_rows(np.frombuffer(b"1.2.3.4|only|three", np.uint8),
                   np.array([0, 18]))


# ------------------------------------- reducers that are not kernels


class _Collecting:
    def __init__(self):
        self.records = []

    def write(self, k, v):
        self.records.append((k, v))


class _CountAndConcat:
    """Emits (key, count + the values' first bytes), and the key again
    for groups of an odd size: a reducer whose output shows its input."""

    def configure(self, conf):
        pass

    def reduce(self, key, values, output, reporter):
        vs = list(values)
        output.collect(key, bytes([len(vs) % 256]) + b"".join(
            v[:1] for v in vs))
        if len(vs) % 2:
            output.collect(key, b"odd")

    def close(self):
        pass


def _reduce_rows_row_by_row(reducer_cls, rows, klen, writer, reporter):
    """``_reduce_rows`` as it was before its boundaries were found with
    numpy: one ``tobytes()`` comparison a row. Kept here as the
    reference."""
    reducer = reducer_cls()

    def emit(k, v):
        reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                              TaskCounter.REDUCE_OUTPUT_RECORDS)
        writer.write(k, v)

    collector, n, i = OutputCollector(emit), rows.shape[0], 0
    while i < n:
        key = rows[i, :klen].tobytes()
        j = i
        while j < n and rows[j, :klen].tobytes() == key:
            j += 1
        reporter.incr_counter(TaskCounter.FRAMEWORK_GROUP,
                              TaskCounter.REDUCE_INPUT_GROUPS)
        reducer.reduce(key, (rows[t, klen:].tobytes()
                             for t in range(i, j)), collector, reporter)
        i = j


@pytest.mark.parametrize("case", CASES + ["no_rows"])
def test_a_reducer_that_is_no_kernel_gets_the_groups_it_got_before(case):
    """Same records written, same counters, whatever the runs."""
    from tpumr.mapred.device_shuffle import _reduce_rows
    from tpumr.mapred.jobconf import JobConf
    rows = _sorted_rows("one_row")[:0] if case == "no_rows" \
        else _sorted_rows(case)
    new, old = _Collecting(), _Collecting()
    new_rep, old_rep = Reporter(), Reporter()
    _reduce_rows(JobConf(), _CountAndConcat, rows, KLEN, new, new_rep)
    _reduce_rows_row_by_row(_CountAndConcat, rows, KLEN, old, old_rep)
    assert new.records == old.records
    for name in (TaskCounter.REDUCE_INPUT_GROUPS,
                 TaskCounter.REDUCE_OUTPUT_RECORDS):
        assert new_rep.counters.value(TaskCounter.FRAMEWORK_GROUP, name) \
            == old_rep.counters.value(TaskCounter.FRAMEWORK_GROUP, name)
    if case != "no_rows":
        assert len(new.records) >= len(_float64_sums(rows)[0])
