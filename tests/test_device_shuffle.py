"""Device-shuffled reduce (tpumr.mapred.device_shuffle + parallel.device_sort):
the MapReduce shuffle+sort as an ICI all_to_all + per-device sort, wired
into the REAL job paths (LocalJobRunner and the mini-cluster through
JobClient) — ≈ the role of ReduceTask.java:659 ReduceCopier ↔
TaskTracker.java:4050 MapOutputServlet, re-planned as mesh collectives.
Runs on the conftest's virtual 8-device CPU mesh."""

import numpy as np
import pytest

from tpumr.core.counters import BackendCounter
from tpumr.fs import get_filesystem
from tpumr.io import sequencefile
from tpumr.mapred.job_client import JobClient
from tpumr.mapred.jobconf import JobConf
from tpumr.mapred.local_runner import run_job
from tpumr.mapred.mini_cluster import MiniMRCluster


def _teragen(path: str, rows: int, maps: int = 3) -> None:
    from tpumr.cli import main as cli_main
    assert cli_main(["examples", "teragen", str(rows), path,
                     "-m", str(maps)]) == 0


def _read_parts(fs, d):
    recs = []
    parts = []
    for st in sorted(fs.list_status(d), key=lambda s: str(s.path)):
        if not st.path.name.startswith("part-"):
            continue
        parts.append(st.path.name)
        with fs.open(st.path) as f:
            recs.extend(sequencefile.Reader(f))
    return recs, parts


class TestDeviceSortPrimitives:
    def test_key_columns_order_preserving(self):
        from tpumr.parallel.device_sort import key_columns
        rng = np.random.default_rng(3)
        keys = rng.integers(0, 256, size=(500, 10), dtype=np.uint8)
        cols = key_columns(keys, 10)
        by_bytes = sorted(range(500), key=lambda i: bytes(keys[i]))
        by_cols = np.lexsort(tuple(cols[:, c] for c in range(2, -1, -1)))
        assert by_bytes == list(by_cols)

    def test_compute_dest_matches_host_partitioner(self):
        """Device dest must agree with TotalOrderPartitioner's bisect
        convention (equal key → lower range)."""
        import bisect
        from tpumr.parallel.device_sort import compute_dest, key_columns
        rng = np.random.default_rng(4)
        keys = rng.integers(32, 127, size=(300, 10), dtype=np.uint8)
        cuts = sorted(bytes(keys[i]) for i in [10, 50, 99])
        cuts_np = np.frombuffer(b"".join(cuts), np.uint8).reshape(-1, 10)
        dest = compute_dest(key_columns(keys, 10),
                            key_columns(cuts_np, 10))
        for i in range(300):
            expect = bisect.bisect_left(cuts, bytes(keys[i]))
            assert int(dest[i]) == expect, (i, bytes(keys[i]))

    def test_partition_sort_full_roundtrip(self):
        from tpumr.parallel.device_sort import device_partition_sort
        from tpumr.parallel.mesh import make_mesh
        rng = np.random.default_rng(7)
        n, klen = 1003, 10
        records = rng.integers(0, 256, size=(n, klen + 6), dtype=np.uint8)
        samp = np.sort(records[rng.choice(n, 50, replace=False), :klen]
                       .view("u1").reshape(-1, klen), axis=0)
        order = np.lexsort(tuple(samp[:, c] for c in range(klen - 1, -1, -1)))
        cuts = samp[order][[6, 12, 18, 24, 30, 36, 43]]
        mesh = make_mesh(8)
        shards, _ = device_partition_sort(mesh, records, klen, cuts, 8)
        assert shards is not None
        merged = np.concatenate(shards)
        assert merged.shape[0] == n
        kb = [bytes(r[:klen]) for r in merged]
        assert kb == sorted(kb)
        assert sorted(bytes(r) for r in merged) == \
            sorted(bytes(r) for r in records)

    def test_overflow_signals_fallback(self):
        from tpumr.parallel.device_sort import device_partition_sort
        from tpumr.parallel.mesh import make_mesh
        rng = np.random.default_rng(9)
        records = rng.integers(0, 256, size=(512, 12), dtype=np.uint8)
        # every record to range 0 (no splitters) with capacity 1: the
        # per-bucket load is 64 — retries 1→2→4 all overflow
        shards, overflow = device_partition_sort(
            make_mesh(8), records, 10, np.zeros((0, 10), np.uint8), 1,
            capacity=1)
        assert shards is None and overflow > 0


def _seeded_case(case: str, n_dev: int):
    """(records, splitters, num_ranges) of one named input, from a seed."""
    rng = np.random.default_rng([20261001, n_dev])
    klen, vlen, num_ranges = 10, 14, n_dev
    n = 3000
    if case == "not_divisible":
        n = 3000 + n_dev - 1
    if case == "empty":
        n = 0
    keys = rng.integers(0x20, 0x7F, size=(n, klen), dtype=np.uint8)
    if case == "duplicate_heavy":
        keys = keys[rng.integers(0, 5, size=n)]     # five distinct keys
    if case == "fewer_ranges":
        num_ranges = n_dev - 2
    samp = rng.integers(0x20, 0x7F, size=(64, klen), dtype=np.uint8) \
        if n == 0 else keys[rng.integers(0, n, size=64)]
    samp = samp[np.lexsort(tuple(samp[:, c]
                                 for c in range(klen - 1, -1, -1)))]
    splitters = samp[[round(i * 64 / num_ranges)
                      for i in range(1, num_ranges)]]
    if case == "equal_to_a_splitter":
        keys[::7] = splitters[rng.integers(0, len(splitters),
                                           size=len(keys[::7]))]
    values = rng.integers(0, 256, size=(n, vlen), dtype=np.uint8)
    return np.concatenate([keys, values], axis=1), splitters, num_ranges


MESH_CASES = ["uniform", "duplicate_heavy", "equal_to_a_splitter",
              "fewer_ranges", "not_divisible", "empty"]


@pytest.mark.parametrize("case", MESH_CASES)
@pytest.mark.parametrize("n_dev", [4, 8])
def test_mesh_partition_sort_agrees_with_numpy_lexsort(n_dev, case):
    """The mesh branch against a plain ``numpy.lexsort`` of the same
    seeded rows: the shards in device order are the sorted input, each
    device holds exactly its ranges (a key equal to a cut stays in the
    lower one), and the bucket's padding is gone."""
    from tpumr.parallel.device_sort import (bucket_rows, compute_dest,
                                            device_partition_sort,
                                            key_columns, piece_rows)
    from tpumr.parallel.mesh import make_mesh
    records, splitters, num_ranges = _seeded_case(case, n_dev)
    klen, n = 10, records.shape[0]
    stats = {}
    shards, overflow = device_partition_sort(
        make_mesh(n_dev), records, klen, splitters, num_ranges, stats=stats)
    assert shards is not None and len(shards) == n_dev
    # five distinct keys may fill a bucket: then a retry, never a loss
    assert overflow == 0 or case == "duplicate_heavy"
    back = stats.pop("bytes_back")
    assert stats == {"pad_rows": bucket_rows(n, n_dev) - n,
                     "retries": 1 if overflow else 0}
    local = bucket_rows(n, n_dev) // n_dev
    active = max(1, -(-num_ranges // -(-num_ranges // n_dev)))
    capacity = max(16, 2 * local // active) * (2 if overflow else 1)
    piece = piece_rows(local, n_dev * capacity)
    assert records.nbytes <= back <= _fetch_bound(shards, piece)
    if case == "uniform":       # the rows, not the slots reserved for them
        slots = n_dev * n_dev * capacity * (records.shape[1] + 1)
        assert back < 0.6 * slots
    kcols = key_columns(records[:, :klen], klen)
    order = np.lexsort(tuple(kcols[:, c] for c in range(2, -1, -1)))
    want = records[order]
    got = np.concatenate(shards)
    assert got.shape == want.shape and got.dtype == np.uint8
    # row for row: the exchange keeps a source's order, the devices'
    # shares are dealt in input order and both sorts are stable, so
    # equal keys come out in the order they went in, as numpy's do
    assert (got == want).all()
    ranges_per_dev = -(-num_ranges // n_dev)
    for d, shard in enumerate(shards):
        if shard.shape[0]:
            dest = compute_dest(key_columns(shard[:, :klen], klen),
                                key_columns(splitters, klen))
            assert set(np.unique(dest // ranges_per_dev)) == {d}
    active = -(-num_ranges // ranges_per_dev)
    assert all(s.shape[0] == 0 for s in shards[active:])


def _fetch_bound(shards, piece: int) -> int:
    """The most the mesh sort may copy back for these shards: whole
    pieces of whole 32-bit words, and a count a device."""
    w = shards[0].shape[1]
    return sum(-(-s.shape[0] // piece) * piece * -(-w // 4) * 4
               for s in shards) + 4 * len(shards)


#: rows per device of a four-device sort of 1793 to 2048 rows: a device's
#: padded share is 512 rows, a piece 64, and a device has 1024 slots
#: (2048 where ``capacity`` says so)
FETCH_CASES = {
    "no_row_on_a_device": ([700, 0, 650, 600], None),
    "exactly_one_piece": ([64, 700, 600, 600], None),
    "a_multiple_of_the_piece": ([192, 640, 576, 512], None),
    "one_row_more": ([193, 641, 577, 513], None),
    "every_slot_of_a_device": ([2048, 0, 0, 0], 512),
}


@pytest.mark.parametrize("width", [12, 27, 100])
@pytest.mark.parametrize("case", list(FETCH_CASES))
def test_mesh_fetch_brings_back_each_devices_rows_whatever_their_count(
        case, width):
    """Device d is sent exactly ``counts[d]`` rows (the first key byte
    says which range): the shards are ``numpy.lexsort``'s rows byte for
    byte, cut where the counts say, at a width that is and is not whole
    32-bit words; and no more left the devices than whole pieces."""
    from tpumr.parallel.device_sort import (device_partition_sort,
                                            key_columns, piece_rows)
    from tpumr.parallel.mesh import make_mesh
    counts, capacity = FETCH_CASES[case]
    n_dev, klen, n = 4, 10, sum(FETCH_CASES[case][0])
    rng = np.random.default_rng([29, width, n])
    records = rng.integers(0, 256, size=(n, width), dtype=np.uint8)
    records[:, 0] = np.repeat(np.arange(n_dev) * 0x40, counts) \
        + rng.integers(1, 0x40, size=n)
    records = records[rng.permutation(n)]
    splitters = np.zeros((n_dev - 1, klen), np.uint8)
    splitters[:, 0] = [0x40, 0x80, 0xC0]
    stats = {}
    shards, overflow = device_partition_sort(
        make_mesh(n_dev), records, klen, splitters, n_dev,
        capacity=capacity, stats=stats)
    assert overflow == 0 and stats["retries"] == 0
    assert [s.shape for s in shards] == [(c, width) for c in counts]
    assert all(s.dtype == np.uint8 and s.flags.c_contiguous for s in shards)
    kcols = key_columns(records, klen)
    order = np.lexsort(tuple(kcols[:, c] for c in range(2, -1, -1)))
    assert (np.concatenate(shards) == records[order]).all()
    per_dev = n_dev * (capacity or 256)
    piece = piece_rows(512, per_dev)
    assert piece == 64 and max(counts) <= per_dev
    assert records.nbytes <= stats["bytes_back"] <= _fetch_bound(shards,
                                                                  piece)


@pytest.mark.parametrize("counts", [[0, 0, 0, 0], [150, 1, 64, 0],
                                    [149, 150, 128, 65]],
                         ids=["none", "last_piece_cut", "to_the_last_slot"])
def test_fetch_of_a_shard_that_is_no_whole_number_of_pieces(counts):
    """150 slots a device in pieces of 64: the third piece cannot start
    at row 128, so it is cut from the shard's end and the host skips
    what it has; a full device comes back to its last slot."""
    from tpumr.parallel.device_sort import fetch_live_rows
    from tpumr.parallel.mesh import make_mesh, shard_over
    n_dev, per_dev, w, piece = 4, 150, 27, 64
    mesh = make_mesh(n_dev)
    rng = np.random.default_rng(sum(counts))
    slots = rng.integers(0, 256, size=(n_dev * per_dev, w + 1),
                         dtype=np.uint8)
    live = (np.arange(n_dev * per_dev) % per_dev
            < np.repeat(counts, per_dev))
    shards, back, pieces = fetch_live_rows(
        mesh, shard_over(mesh, slots), shard_over(mesh, live), w, piece)
    for d, c in enumerate(counts):
        assert (shards[d] == slots[d * per_dev:d * per_dev + c, :w]).all()
    assert pieces == sum(-(-c // piece) for c in counts)
    assert back == pieces * piece * 28 + 4 * n_dev


def test_mesh_programs_compile_once_per_bucket_not_per_input():
    """Three inputs in a row on one mesh: other splitters and another row
    count inside the same bucket, so other counts per device, add nothing
    to the jitted functions' caches; a row count in the next bucket adds
    one entry to each."""
    from tpumr.parallel.device_sort import (bucket_rows,
                                            device_partition_sort,
                                            make_count_fn, make_dest_fn,
                                            make_piece_fn, make_sort_fn,
                                            piece_rows)
    from tpumr.parallel.mesh import make_mesh
    from tpumr.parallel.shuffle import make_shuffle
    n_dev, klen, num_ranges = 4, 10, 4
    width = 27      # no other test's rows: the caches below are shared
    mesh = make_mesh(n_dev)
    first, second, third = 5000, 4700, 5300
    assert bucket_rows(first, n_dev) == bucket_rows(second, n_dev) == 5120
    assert bucket_rows(third, n_dev) == 6144
    rng = np.random.default_rng(11)

    def one(n):
        records = rng.integers(0x20, 0x7F, size=(n, width), dtype=np.uint8)
        samp = records[rng.integers(0, n, size=3), :klen]
        splitters = samp[np.lexsort(tuple(
            samp[:, c] for c in range(klen - 1, -1, -1)))]
        shards, _ = device_partition_sort(mesh, records, klen, splitters,
                                          num_ranges)
        got = np.concatenate(shards)
        assert got.shape[0] == n
        keys = [bytes(k) for k in got[:, :klen]]
        assert keys == sorted(keys)
        return splitters, [s.shape[0] for s in shards]

    def compiled():
        """Executables held by the jitted functions; the exchange's and
        the piece's at the sizes of each of the two buckets."""
        per_bucket = [(rows // n_dev, 2 * (rows // n_dev) // 4)
                      for rows in (5120, 6144)]
        return [make_dest_fn(mesh, klen, 1, 4, "data")._cache_size(),
                make_sort_fn(mesh, klen, "data")._cache_size()] + [
            make_shuffle(mesh, capacity, "data",
                         with_keys=False)._cache_size()
            for _, capacity in per_bucket] + [
            make_piece_fn(mesh, width, piece_rows(local, n_dev * capacity),
                          "data")._cache_size()
            for local, capacity in per_bucket]

    def counted():
        # the count's shape has no row width in it: other tests share it
        return make_count_fn(mesh, "data")._cache_size()

    before = compiled()
    a, a_counts = one(first)
    after_first, counted_first = compiled(), counted()
    assert [x - y for x, y in zip(after_first, before)] \
        == [1, 1, 1, 0, 1, 0]
    b, b_counts = one(second)
    assert not (a == b).all()               # other splitters, other rows
    # other counts a device, and another number of pieces for one of them
    assert a_counts != b_counts
    assert [-(-c // 160) for c in a_counts] != [-(-c // 160)
                                                for c in b_counts]
    assert compiled() == after_first        # and nothing compiled
    assert counted() == counted_first
    one(third)
    assert [x - y for x, y in zip(compiled(), after_first)] \
        == [1, 1, 0, 1, 0, 1]
    assert counted() <= counted_first + 1


def test_skewed_input_retries_then_gives_up_with_the_retries_counted():
    """Every row for one range with a capacity far too small: the
    exchange overflows, is retried twice with doubled capacity, and the
    caller is handed None (it sorts on the host)."""
    from tpumr.parallel.device_sort import device_partition_sort
    from tpumr.parallel.mesh import make_mesh
    rng = np.random.default_rng(9)
    records = rng.integers(0, 256, size=(2048, 12), dtype=np.uint8)
    stats = {}
    shards, overflow = device_partition_sort(
        make_mesh(4), records, 10, np.zeros((0, 10), np.uint8), 1,
        capacity=16, stats=stats)
    assert shards is None and overflow > 0
    assert stats["retries"] == 2 and stats["pad_rows"] == 0
    # one doubling is enough here: a retry, then a result
    stats = {}
    shards, overflow = device_partition_sort(
        make_mesh(4), records, 10, np.zeros((0, 10), np.uint8), 1,
        capacity=256, stats=stats)
    assert overflow > 0 and stats["retries"] == 1
    assert sum(s.shape[0] for s in shards) == 2048


class TestDeviceShuffleLocalJob:
    def test_an_overflowing_job_counts_its_retries_and_sorts_on_the_host(
            self):
        from tpumr.examples.terasort import make_terasort_conf
        from tpumr.mapred.device_shuffle import CAPACITY_KEY
        fs = get_filesystem("mem:///")
        _teragen("mem:///dso/gen", 4000, maps=2)
        conf = make_terasort_conf("mem:///dso/gen", "mem:///dso/out", 4,
                                  device_shuffle=True)
        conf.set(CAPACITY_KEY, 2)
        result = run_job(conf)
        assert result.successful

        def counted(name):
            return result.counters.value(BackendCounter.GROUP, name)

        assert counted(BackendCounter.TPU_SHUFFLE_RETRIES) == 2
        assert counted(BackendCounter.SHUFFLE_HOST_FALLBACKS) == 1
        assert counted(BackendCounter.TPU_SHUFFLE_DEVICES) == 0
        assert counted(BackendCounter.TPU_SHUFFLE_RECORDS) == 0
        keys = [k for k, _ in _read_parts(fs, "/dso/out")[0]]
        assert len(keys) == 4000 and keys == sorted(keys)


    def test_terasort_device_shuffle_local(self):
        """Terasort through LocalJobRunner with the device reduce: output
        part files globally sorted, same multiset, R parts kept."""
        from tpumr.examples.terasort import make_terasort_conf
        fs = get_filesystem("mem:///")
        _teragen("mem:///dsl/gen", 900, maps=3)
        conf = make_terasort_conf("mem:///dsl/gen", "mem:///dsl/out", 5,
                                  device_shuffle=True)
        result = run_job(conf)
        assert result.successful
        out, parts = _read_parts(fs, "/dsl/out")
        assert parts == [f"part-{r:05d}" for r in range(5)]
        assert len(out) == 900
        keys = [k for k, _ in out]
        assert keys == sorted(keys), "concatenated parts must be sorted"
        gen, _ = _read_parts(fs, "/dsl/gen")
        assert sorted(k + v for k, v in out) == sorted(k + v for k, v in gen)
        shuffled = result.counters.value(BackendCounter.GROUP,
                                       BackendCounter.TPU_SHUFFLE_RECORDS)
        assert shuffled == 900
        assert result.counters.value(
            BackendCounter.GROUP, BackendCounter.TPU_SHUFFLE_DEVICES) == 8
        assert result.counters.value(
            BackendCounter.GROUP, BackendCounter.TPU_SHUFFLE_RETRIES) == 0
        assert result.counters.value(
            BackendCounter.GROUP,
            BackendCounter.TPU_SHUFFLE_PAD_ROWS) == 8 * 128 - 900

    def test_device_shuffle_with_real_reducer(self):
        """A non-identity reducer still runs (grouped over the device-sorted
        stream): fixed-width count aggregation."""
        from tpumr.mapred.api import Mapper, Reducer
        fs = get_filesystem("mem:///")
        fs.write_bytes("/dsr/in.txt",
                       b"\n".join(b"key%04d" % (i % 7) for i in range(210)))

        conf = JobConf()
        conf.set_job_name("dense-count")
        conf.set_input_paths("mem:///dsr/in.txt")
        conf.set_output_path("mem:///dsr/out")
        from tpumr.mapred.output_formats import SequenceFileOutputFormat
        conf.set_mapper_class(FixedKeyMapper)
        conf.set_reducer_class(FixedCountReducer)
        conf.set_output_format(SequenceFileOutputFormat)
        conf.set_num_reduce_tasks(3)
        conf.set_device_shuffle(7, 4)
        result = run_job(conf)
        assert result.successful
        out, parts = _read_parts(fs, "/dsr/out")
        assert len(parts) == 3
        counts = {bytes(k): int.from_bytes(v, "big") for k, v in out}
        assert counts == {b"key%04d" % i: 30 for i in range(7)}

    def test_identity_subclass_overriding_map_is_not_bypassed(self):
        """A subclass of an identity mapper that overrides map() (but
        inherits identity_map) must have its map() honored — the bulk
        fast path only applies to classes declaring the flag themselves."""
        fs = get_filesystem("mem:///")
        fs.write_bytes("/dsi/in.txt",
                       b"\n".join(b"key%04d" % i for i in range(20)))
        conf = JobConf()
        conf.set_input_paths("mem:///dsi/in.txt")
        conf.set_output_path("mem:///dsi/out")
        from tpumr.mapred.output_formats import SequenceFileOutputFormat
        conf.set_mapper_class(DroppingIdentitySubclass)
        conf.set_output_format(SequenceFileOutputFormat)
        conf.set_num_reduce_tasks(1)
        conf.set_device_shuffle(7, 0)
        result = run_job(conf)
        assert result.successful
        out, _ = _read_parts(fs, "/dsi/out")
        assert len(out) == 10  # the override's filter ran

    def test_duplicate_heavy_input_short_cut_list(self):
        """write_partition_file dedups duplicate samples, so the cut list
        can be shorter than R-1 — top ranges must come back empty, not
        crash (host TotalOrderPartitioner tolerance preserved)."""
        from tpumr.mapred.output_formats import SequenceFileOutputFormat
        fs = get_filesystem("mem:///")
        fs.write_bytes("/dsd/in.txt",
                       b"\n".join(b"dup%04d" % (i % 2) for i in range(100)))
        conf = JobConf()
        conf.set_input_paths("mem:///dsd/in.txt")
        conf.set_output_path("mem:///dsd/out")
        conf.set_mapper_class(FixedKeyMapper)
        conf.set_output_format(SequenceFileOutputFormat)
        conf.set_num_reduce_tasks(16)   # >> distinct keys: short cut list
        conf.set_device_shuffle(7, 4)
        assert run_job(conf).successful
        out, parts = _read_parts(fs, "/dsd/out")
        assert len(parts) == 16
        assert len(out) == 100
        keys = [k for k, _ in out]
        assert keys == sorted(keys)

    def test_custom_comparator_rejected(self):
        from tpumr.mapred.api import DeserializingComparator
        conf = JobConf()
        conf.set_input_paths("mem:///x/in.txt")
        conf.set_output_path("mem:///x/out")
        conf.set_num_reduce_tasks(2)
        conf.set_device_shuffle(10, 4)
        conf.set_output_key_comparator_class(DeserializingComparator)
        from tpumr.mapred.device_shuffle import prepare_device_shuffle_job
        with pytest.raises(ValueError, match="comparator"):
            prepare_device_shuffle_job(conf)

    def test_wrong_width_fails_with_clear_error(self):
        fs = get_filesystem("mem:///")
        fs.write_bytes("/dsw/in.txt", b"hello world\n")
        conf = JobConf()
        conf.set_input_paths("mem:///dsw/in.txt")
        conf.set_output_path("mem:///dsw/out")
        conf.set_mapper_class(FixedKeyMapper)   # emits 7-byte keys
        conf.set_num_reduce_tasks(1)
        conf.set_device_shuffle(10, 4)          # conf says 10 — mismatch
        with pytest.raises(Exception, match="10-byte keys"):
            run_job(conf)


from tpumr.mapred.api import IdentityMapper


class DroppingIdentitySubclass(IdentityMapper):
    """Inherits identity_map=True but overrides map() to keep only even
    rows — the override must run (7-byte key, empty value)."""

    def map(self, key, value, output, reporter):
        line = value if isinstance(value, (bytes, bytearray)) else \
            str(value).encode()
        if int(line[-1:] or b"0", 10) % 2 == 0:
            output.collect(bytes(line.strip()[:7]), b"")


class FixedKeyMapper:
    """Emits (7-byte key, 4-byte big-endian 1) per input line."""

    def configure(self, conf):
        pass

    def map(self, key, value, output, reporter):
        line = value if isinstance(value, (bytes, bytearray)) else \
            str(value).encode()
        if line.strip():
            output.collect(bytes(line.strip()[:7]), (1).to_bytes(4, "big"))

    def close(self):
        pass


class FixedCountReducer:
    """Sums 4-byte big-endian counts into a 4-byte value."""

    def configure(self, conf):
        pass

    def reduce(self, key, values, output, reporter):
        total = sum(int.from_bytes(v, "big") for v in values)
        output.collect(key, total.to_bytes(4, "big"))

    def close(self):
        pass


class TestDeviceShuffleMiniCluster:
    def test_terasort_device_shuffle_through_jobclient(self):
        """The full distributed path: teragen + device-shuffled terasort
        submitted through JobClient to a mini-cluster (maps on trackers,
        dense outputs served over tracker RPC, ONE reduce gang task runs
        the mesh exchange), then validated globally sorted."""
        from tpumr.examples.terasort import make_terasort_conf
        fs = get_filesystem("mem:///")
        _teragen("mem:///dsc/gen", 600, maps=3)
        with MiniMRCluster(num_trackers=2, cpu_slots=2, tpu_slots=0) as c:
            conf = make_terasort_conf("mem:///dsc/gen", "mem:///dsc/out", 4,
                                      device_shuffle=True)
            for k, v in c.create_job_conf():
                conf.set_if_unset(k, v)
            result = JobClient(conf).run_job(conf)
            assert result.successful
            # collapsed to one gang reduce task
            assert result.num_reduces == 1
        out, parts = _read_parts(fs, "/dsc/out")
        assert parts == [f"part-{r:05d}" for r in range(4)]
        assert len(out) == 600
        keys = [k for k, _ in out]
        assert keys == sorted(keys)
        shuffled = result.counters.value(BackendCounter.GROUP,
                                       BackendCounter.TPU_SHUFFLE_RECORDS)
        assert shuffled == 600


def test_device_partition_sort_single_device_mesh():
    """The n_dev==1 short-circuit (the real single-chip bench path): no
    exchange, no padding — straight device sort, full row fidelity."""
    import numpy as np

    from tpumr.parallel.device_sort import device_partition_sort
    from tpumr.parallel.mesh import make_mesh

    rng = np.random.default_rng(7)
    n, klen, vlen = 5000, 10, 22
    records = rng.integers(0, 256, size=(n, klen + vlen), dtype=np.uint8)
    splitters = np.sort(
        rng.integers(0, 256, size=(3, klen), dtype=np.uint8), axis=0)
    mesh = make_mesh(1)
    shards, overflow = device_partition_sort(mesh, records, klen,
                                             splitters, 4)
    assert overflow == 0 and len(shards) == 1
    out = shards[0]
    assert out.shape == (n, klen + vlen)
    keys = [bytes(r) for r in out[:, :klen]]
    assert keys == sorted(keys)
    # permutation fidelity: exact multiset of rows survives
    assert sorted(map(bytes, out)) == sorted(map(bytes, records))


# ------------------------------------------------- the copy phase (PR 27)


def _rows_of(records):
    return np.frombuffer(b"".join(k + v for k, v in records),
                         np.uint8).reshape(len(records), -1)


def _numpy_sorted(rows: np.ndarray, klen: int = 10) -> np.ndarray:
    """The reference: rows in ascending key order, by numpy alone."""
    return rows[np.lexsort(tuple(rows[:, c]
                                 for c in range(klen - 1, -1, -1)))]


@pytest.mark.parametrize("trackers", [1, 2])
def test_gang_reduce_reads_its_own_trackers_maps_and_calls_for_the_rest(
        trackers, monkeypatch):
    """A map that the gang reduce's tracker serves is read from its file;
    a map of another tracker comes through that tracker's RPC. One slot a
    tracker and eight maps, so with two trackers each runs some."""
    from tpumr.examples.terasort import make_terasort_conf
    rows, maps = 1600, 8
    gen, out = f"mem:///dsl{trackers}/gen", f"mem:///dsl{trackers}/out"
    fs = get_filesystem("mem:///")
    _teragen(gen, rows, maps=maps)
    served: list = []       # (serving tracker, map) of every dense RPC
    reduced: list = []      # the tracker that ran the gang reduce
    own: set = set()        # the maps that tracker held when asked for
    with MiniMRCluster(num_trackers=trackers, cpu_slots=1,
                       tpu_slots=0) as c:
        for t in c.trackers:
            def serve(job_id, m, t=t, real=t.get_map_output_dense):
                served.append((t.name, m))
                return real(job_id, m)

            def factory(job_id, task, reporter, t=t,
                        real=t._remote_dense_fetch_factory):
                reduced.append(t)
                fetch = real(job_id, task, reporter)

                def watched(m):
                    k, v = fetch(m)
                    if t._map_output_entry(job_id, m) is not None:
                        own.add(m)
                    return k, v

                return watched

            monkeypatch.setattr(t, "get_map_output_dense", serve)
            monkeypatch.setattr(t, "_remote_dense_fetch_factory", factory)
        conf = make_terasort_conf(gen, out, 4, device_shuffle=True)
        for k, v in c.create_job_conf():
            conf.set_if_unset(k, v)
        result = JobClient(conf).run_job(conf)
        assert result.successful
        reducer, = reduced
    assert _rows_of(_read_parts(fs, out)[0]).tobytes() == _numpy_sorted(
        _rows_of(_read_parts(fs, gen)[0])).tobytes()
    counted = result.counters.value(BackendCounter.GROUP,
                                    BackendCounter.TPU_SHUFFLE_LOCAL_MAPS)
    assert counted == len(own) == maps - len(served)
    # what was called for is what the reduce's tracker does not hold,
    # once each and from the other tracker
    assert sorted(m for _t, m in served) == sorted(set(range(maps)) - own)
    assert all(name != reducer.name for name, _m in served)
    if trackers == 1:
        assert counted == maps and not served
    else:
        assert 0 < counted < maps
    assert result.counters.value(BackendCounter.GROUP,
                                 BackendCounter.TPU_SHUFFLE_RECORDS) == rows


LANDINGS = {
    "grows-as-maps-grow": [3, 0, 50, 7, 400, 1],
    "equal-maps": [64, 64, 64, 64],
    "empty-maps-first": [0, 0, 9, 30],
    "one-map": [25],
    "nothing": [0, 0],
}


@pytest.mark.parametrize("arrival", ["in-order", "reversed"])
@pytest.mark.parametrize("case", LANDINGS)
def test_rows_and_key_words_land_map_by_map_as_the_whole_would(case,
                                                               arrival):
    from tpumr.mapred.device_shuffle import RowLanding
    from tpumr.parallel.device_sort import key_columns
    klen, vlen = 10, 7
    rng = np.random.default_rng(len(case))
    sizes = LANDINGS[case][::-1 if arrival == "reversed" else 1]
    parts = [(rng.integers(0, 256, (n, klen), dtype=np.uint8),
              rng.integers(0, 256, (n, vlen), dtype=np.uint8))
             for n in sizes]
    landing = RowLanding(klen, vlen, len(parts))
    grown = 0
    for k, v in parts:
        grown += landing.land_rows(k, v)
        landing.land_key_words(k)
    whole = np.concatenate([np.concatenate([k, v], axis=1)
                            for k, v in parts])
    assert landing.rows.dtype == np.uint8
    assert np.array_equal(landing.rows, whole)
    if sum(sizes) == 0:
        assert landing.key_words is None and grown == 0
        return
    assert landing.key_words.dtype == np.uint32
    assert np.array_equal(landing.key_words, key_columns(whole, klen))
    # unknown in advance, so at least the first rows grow it; a run of
    # equal maps fits what the first one predicted
    assert grown >= 1
    assert grown == 1 or case != "equal-maps"
    assert grown >= 2 or case != "grows-as-maps-grow"


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("n", [16, 1000, 5000])
def test_handed_over_key_words_change_nothing_in_the_shards(n, n_dev):
    """One device: the sort sends the words it is handed and the shards
    are those it makes from its own; a mesh has no use for them."""
    from tpumr.parallel.device_sort import (device_partition_sort,
                                            key_columns)
    from tpumr.parallel.mesh import make_mesh
    rng = np.random.default_rng(n)
    klen = 10
    records = rng.integers(0, 256, size=(n, klen + 6), dtype=np.uint8)
    records[:, 2:klen] = 7      # two bytes decide: some keys come twice
    splitters = np.zeros((3, klen), np.uint8)
    splitters[:, 0] = [64, 128, 192]
    mesh = make_mesh(n_dev)
    plain, lost = device_partition_sort(mesh, records, klen, splitters, 4)
    handed, lost_too = device_partition_sort(
        mesh, records, klen, splitters, 4,
        key_words=key_columns(records, klen))
    assert lost == lost_too == 0 and len(plain) == len(handed) == n_dev
    for a, b in zip(plain, handed):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert np.array_equal(np.concatenate(handed),
                          _numpy_sorted(records, klen))


def test_key_words_of_other_rows_are_refused_on_one_device():
    from tpumr.parallel.device_sort import (device_partition_sort,
                                            key_columns)
    from tpumr.parallel.mesh import make_mesh
    records = np.zeros((40, 12), np.uint8)
    with pytest.raises(ValueError, match="key words of 39 rows"):
        device_partition_sort(make_mesh(1), records, 10,
                              np.zeros((0, 10), np.uint8), 1,
                              key_words=key_columns(records[1:], 10))


def test_a_one_device_reduce_hands_over_the_key_words_it_made_per_map(
        monkeypatch):
    """The one-chip host's path (one device stands in): a span of
    assemble and of pack a map, and ``device_partition_sort`` is handed
    the key words of exactly the rows it is handed."""
    import jax

    from tpumr.core import tracing
    from tpumr.examples.terasort import make_terasort_conf
    from tpumr.parallel import device_sort, jaxruntime
    monkeypatch.setattr(jaxruntime, "accelerator_devices",
                        lambda: jax.devices()[:1])
    seen = {}
    real = device_sort.device_partition_sort

    def spy(mesh, records, klen, *args, key_words=None, **kwargs):
        seen.update(n_dev=mesh.size, records=records, key_words=key_words)
        return real(mesh, records, klen, *args, key_words=key_words,
                    **kwargs)

    monkeypatch.setattr(device_sort, "device_partition_sort", spy)
    fs = get_filesystem("mem:///")
    _teragen("mem:///ds1/gen", 700, maps=3)
    conf = make_terasort_conf("mem:///ds1/gen", "mem:///ds1/out", 4,
                              device_shuffle=True)
    tracer = tracing.Tracer("task")
    with tracing.activate(tracer, tracer.start_span("task:run", "job_ds1")):
        result = run_job(conf)
    assert result.successful
    assert seen["n_dev"] == 1 and seen["records"].shape == (700, 100)
    assert np.array_equal(seen["key_words"],
                          device_sort.key_columns(seen["records"], 10))
    assert result.counters.value(BackendCounter.GROUP,
                                 BackendCounter.TPU_SHUFFLE_DEVICES) == 1
    gen = _rows_of(_read_parts(fs, "/ds1/gen")[0])
    assert _rows_of(_read_parts(fs, "/ds1/out")[0]).tobytes() \
        == _numpy_sorted(gen).tobytes()
    names = [s.name for s in tracer.pending()
             if s.name.startswith("dshuffle:")]
    assert names[:10] == ["dshuffle:fetch", "dshuffle:assemble",
                          "dshuffle:pack"] * 3 + ["dshuffle:assemble"]
    assert names[10:13] == ["dshuffle:pack", "dshuffle:device",
                            "dshuffle:gather"]
    packs = [s.attributes for s in tracer.pending()
             if s.name == "dshuffle:pack"]
    assert [p.get("map_index") for p in packs] == [0, 1, 2, None]
    assert sum(p.get("rows", 0) for p in packs) == 700
    assert packs[-1]["n_pad"] == 1024


class _Here:
    """A located map whose serving address is the given one."""

    def __init__(self, addr):
        self.addr = addr

    def call(self, *a):
        raise AssertionError("a map of this tracker went through RPC")


@pytest.mark.parametrize("fault, error", [
    ("no-entry", KeyError), ("not-dense", ValueError),
    ("file-gone", FileNotFoundError)])
def test_a_local_entry_withdrawn_before_the_read_fails_as_the_rpc_does(
        fault, error, tmp_path, monkeypatch):
    """The gang reduce's own read and the RPC handler raise the same for
    the same fault, so the attempt fails and is retried as before."""
    from tpumr.mapred.api import Reporter
    from tpumr.mapred.device_shuffle import DenseMapOutputBuffer
    conf = JobConf()
    conf.set_device_shuffle(4, 2)
    buf = DenseMapOutputBuffer(conf, str(tmp_path), Reporter())
    buf.collect(b"abcd", b"xy")
    path, index = buf.flush()
    with MiniMRCluster(num_trackers=1, cpu_slots=1, tpu_slots=0) as c:
        t, = c.trackers
        monkeypatch.setattr(t, "_map_locator",
                            lambda job_id: lambda m: _Here(t.shuffle_addr))
        reporter = Reporter()
        fetch = t._remote_dense_fetch_factory("job_x", None, reporter)
        t.map_outputs[("job_x", 0)] = (path, index)
        k, v = fetch(0)
        assert k.tobytes() == b"abcd" and v.tobytes() == b"xy"
        if fault == "no-entry":
            del t.map_outputs[("job_x", 0)]
        elif fault == "not-dense":
            t.map_outputs[("job_x", 0)] = (path, {"partitions": []})
        else:
            import os
            os.unlink(path)
        with pytest.raises(error) as local:
            fetch(0)
        with pytest.raises(error) as remote:
            t.get_map_output_dense("job_x", 0)
        assert str(local.value) == str(remote.value)
        assert reporter.counters.value(
            BackendCounter.GROUP, BackendCounter.TPU_SHUFFLE_LOCAL_MAPS) == 1
