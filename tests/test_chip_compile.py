"""Ask the chip's compiler first: every kernel and device-shuffle program
of the smoke's path (chip_smoke.py), compiled ahead of time for a DESCRIBED
v5e:2x2 topology — no chip attached, nothing executed.

What this catches that interpret mode and the CPU backend cannot: a Pallas
kernel Mosaic refuses (tiling, fast-memory budget), a program that does not
fit a chip's 16 GB, a layout the compiler pads (the [n, 16] K-Means split
must NOT become [n, 128]), a collective that is not in the program. It says
nothing about results or speed.

Rules of this file (the on-chip-measurement guide, section 2): the topology
is described only inside a module-scoped fixture, which skips when it cannot
be described; nothing touches JAX at import or in a parametrize argument;
the persistent compile cache is off around these compiles (a TPU executable
written from here cannot be read back without a chip); all cases live in
this ONE file, because only one worker process may load the TPU library.

Sizes are the smoke's own, except the sort. Its compile time is set by
the number of operands and by n up to about 2^20, then flat (one-device
argsort, three keys + index: 0.8 s at n=2^12, 2.6 s at 2^13, 14 s at 2^14,
70 s at 2^17, 97 s at 2^20, 101 s at 2^24; the mesh sort, four keys + index
+ row gather: 1.5 s at 2^12, 5.1 s at 2^13, 92 s at 2^15, 204 s at 20M rows
per device — this sandbox's CPU, PERF.md has the table). Tier-1 compiles
the sorts at 2^13 rows per device, the smallest bucket that shows the growth
(twice the rows, more than three times the seconds), and records the seconds.
"""

import os
import time

import numpy as np
import pytest

KM_ROWS, D, K = 4_000_000, 16, 16     # one K-Means split of the smoke
SORT_ROWS = 1 << 13                   # per device; see the docstring
ROW_W, KLEN = 100, 10                 # the Sort Benchmark's rows


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def meshes(topo):
    from jax.sharding import Mesh
    return {n: Mesh(np.array(topo.devices[:n]), ("data",)) for n in (1, 4)}


def _shape(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


def _compile(lowerable, *args, **static):
    """(compiled, memory_analysis, seconds) of one AOT compile."""
    t0 = time.monotonic()
    compiled = lowerable.lower(*args, **static).compile()
    return compiled, compiled.memory_analysis(), time.monotonic() - t0


def _record(record_property, mem, seconds):
    record_property("compile_seconds", round(seconds, 2))
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes"):
        record_property(field, int(getattr(mem, field)))


def test_kmeans_xla_step_keeps_the_narrow_layout(one_chip, record_property):
    """The XLA assign+partials program at the smoke's split size: its
    arguments are the 256 MB the split holds, so the [n, 16] layout is not
    padded to 128 lanes (which would be 2 GB and 8x the HBM traffic)."""
    from tpumr.ops.kmeans import _assign_and_partials_jax
    _c, mem, secs = _compile(
        _assign_and_partials_jax,
        _shape((KM_ROWS, D), np.float32, one_chip),
        _shape((K, D), np.float32, one_chip))
    _record(record_property, mem, secs)
    unpadded = KM_ROWS * D * 4 + K * D * 4
    assert unpadded <= mem.argument_size_in_bytes <= 1.05 * unpadded


def test_kmeans_xla_step_at_sift_widths_keeps_no_rows_by_k_temporary(
        one_chip, record_property):
    """The same program at one split of the SIFT cell, (500,000, 128) x
    (1024, 128): its arguments are the split's 256 MB, and the distance
    matrix, the argmin and the one-hot are fused into the two dots, so
    nothing rows-by-k (2 GB in float32) is kept."""
    from tpumr.ops.kmeans import _assign_and_partials_jax
    n, d, k = 500_000, 128, 1024
    _c, mem, secs = _compile(
        _assign_and_partials_jax,
        _shape((n, d), np.float32, one_chip),
        _shape((k, d), np.float32, one_chip))
    _record(record_property, mem, secs)
    unpadded = n * d * 4 + k * d * 4
    assert unpadded <= mem.argument_size_in_bytes <= 1.05 * unpadded
    assert mem.temp_size_in_bytes < n * k * 4


@pytest.mark.parametrize("n,d,k", [(KM_ROWS, 16, 16), (1 << 18, 128, 1024)],
                         ids=["d16-k16", "d128-k1024"])
def test_pallas_assign_lowers_to_a_mosaic_kernel(one_chip, record_property,
                                                 n, d, k):
    """The one Pallas kernel, default block_n=2048: at the smoke's widths,
    and at the SIFT widths of ROADMAP reach item 1, where one grid step
    holds a 2048 x 1024 f32 distance block beside its inputs."""
    import jax

    from tpumr.ops.kmeans import pallas_assign
    compiled, mem, secs = _compile(
        jax.jit(pallas_assign),
        _shape((n, d), np.float32, one_chip),
        _shape((k, d), np.float32, one_chip))
    _record(record_property, mem, secs)
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel pads features to 128 lanes: the padded copy of the
    # points is a temporary of the program, at least n x 128 f32
    assert mem.temp_size_in_bytes >= n * max(d, 128) * 4


def test_pi_sampler_step(one_chip, record_property):
    from tpumr.ops.pi import _count_inside_many
    _c, mem, secs = _compile(
        _count_inside_many, _shape((1,), np.uint32, one_chip), n=50_000_000)
    _record(record_property, mem, secs)
    assert mem.output_size_in_bytes <= 1024   # one counter per seed


def test_matmul_bf16_block(one_chip, record_property):
    from tpumr.ops.matmul import _matmul_bf16
    _c, mem, secs = _compile(
        _matmul_bf16, _shape((4096, 4096), np.float32, one_chip),
        _shape((4096, 4096), np.float32, one_chip))
    _record(record_property, mem, secs)
    assert mem.output_size_in_bytes == 4096 * 4096 * 4


def test_one_device_argsort(one_chip, record_property):
    """What a one-chip tracker's device shuffle really runs: on a one-
    device mesh ``device_partition_sort`` uploads only the packed key
    columns and argsorts them (three keys plus the index)."""
    from tpumr.parallel.device_sort import _argsort_keys, num_key_columns
    cols = num_key_columns(KLEN)
    _c, mem, secs = _compile(
        _argsort_keys(cols), _shape((SORT_ROWS, cols), np.uint32, one_chip))
    _record(record_property, mem, secs)
    assert mem.output_size_in_bytes >= SORT_ROWS * 4


@pytest.mark.parametrize("program", ["sort", "segment_sum", "piece"])
def test_one_device_reduce_program(one_chip, record_property, program):
    """What a one-chip tracker runs for a job whose reducer is a kernel
    (the aggregation's 16-byte key: four key columns and the value's):
    the sort (a loop of stable single-key passes that carry the
    permutation, then the gather of the columns), the segment-sum kernel,
    and a piece of its table as flat words. Columns are rows of the
    array, so a column's words are not padded; the array is, from 5 to 8
    rows."""
    from tpumr.ops.segment_sum import segment_sum_program
    from tpumr.parallel import device_sort
    n, cols = SORT_ROWS, 5
    words = _shape((cols, n), np.uint32, one_chip)
    scalar = _shape((), np.int32, one_chip)
    if program == "sort":
        compiled, mem, secs = _compile(device_sort.sort_columns(cols - 1),
                                       words)
        assert n * cols * 4 <= mem.output_size_in_bytes <= n * 8 * 4
        # the passes are a loop over one sort, not four sorts
        assert compiled.as_text().count(" sort(") == 1
    elif program == "segment_sum":
        _c, mem, secs = _compile(segment_sum_program(cols - 1), words,
                                 scalar)
        assert n * cols * 4 <= mem.output_size_in_bytes <= n * 8 * 4 + 4096
    else:
        piece = max(64, n // 64)
        _c, mem, secs = _compile(device_sort._piece_of_columns(piece),
                                 words, scalar)
        assert 0 <= mem.output_size_in_bytes - piece * cols * 4 < 4096
    _record(record_property, mem, secs)


@pytest.mark.parametrize("program", ["map", "last_arcs"])
def test_pagerank_programs_at_graph500_23(one_chip, record_property,
                                          program):
    """The PageRank map over graph500-23 as ONE split (258.7M arcs of
    (destination, source) int32, 4.61M vertices, padded to their shape
    buckets): the [n, 2] arcs are not
    padded to 128 lanes, the program fits one chip's 16 GB beside the
    resident arcs, and it holds no scatter and no loop (a gather, the
    segmented scan's passes, a binary search unrolled)."""
    from tpumr.ops.pagerank import _last_arcs, _pagerank_map
    from tpumr.parallel.device_sort import bucket_rows
    # the shapes a seed's graph is padded to (268,435,456 and 5,242,880)
    n, v = bucket_rows(258_657_378, 1), bucket_rows(4_609_823, 1)
    arcs = _shape((n, 2), np.int32, one_chip)
    if program == "map":
        compiled, mem, secs = _compile(
            _pagerank_map, arcs, _shape((v,), np.float32, one_chip),
            _shape((v,), np.int32, one_chip))
    else:
        compiled, mem, secs = _compile(
            _last_arcs, arcs, _shape((), np.int32, one_chip), v)
    text = compiled.as_text()
    assert " while(" not in text and "scatter(" not in text
    assert n * 8 <= mem.argument_size_in_bytes <= n * 8 + 2 * v * 4 + 4096
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 10e9
    _record(record_property, mem, secs)


@pytest.mark.parametrize("program", ["dest", "exchange", "sort"])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_device_shuffle_program(meshes, record_property, n_dev, program):
    """The device shuffle's three programs over a mesh of described
    chips: destination from the sampled splitters (a runtime argument),
    the all_to_all exchange, the per-device lexsort + row gather; every
    shape from ``bucket_rows``, as ``device_partition_sort`` runs them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpumr.parallel.device_sort import (bucket_rows, make_dest_fn,
                                            make_sort_fn, num_key_columns)
    from tpumr.parallel.shuffle import make_shuffle
    mesh = meshes[n_dev]
    rows = NamedSharding(mesh, P("data"))
    w = ROW_W + 1                       # rows carry a validity byte
    # device_partition_sort's own sizing, all from the shape bucket: a
    # job of a few rows under SORT_ROWS // 2 a device is padded up to it,
    # and 2x headroom per (src, dst) bucket of the exchange makes each
    # device sort twice the rows it sent
    n = bucket_rows(SORT_ROWS // 2 * n_dev - 37, n_dev)
    assert n == SORT_ROWS // 2 * n_dev
    capacity = max(16, 2 * (n // n_dev) // n_dev)
    if program == "dest":
        # the splitters are an argument: [r-1, cols] uint32, replicated
        compiled, mem, secs = _compile(
            make_dest_fn(mesh, KLEN, 1, n_dev),
            _shape((n, w), np.uint8, rows),
            _shape((3, num_key_columns(KLEN)), np.uint32,
                   NamedSharding(mesh, P())))
    elif program == "exchange":
        compiled, mem, secs = _compile(
            make_shuffle(mesh, capacity),
            _shape((n, w), np.uint8, rows), _shape((n,), np.int32, rows))
        if n_dev > 1:
            assert "all-to-all" in compiled.as_text()
    else:
        m = n_dev * n_dev * capacity    # what the exchange hands the sort
        assert m == SORT_ROWS * n_dev
        compiled, mem, secs = _compile(
            make_sort_fn(mesh, KLEN),
            _shape((m, w), np.uint8, rows), _shape((m,), np.bool_, rows))
        assert mem.output_size_in_bytes >= m * w // n_dev
    _record(record_property, mem, secs)


@pytest.mark.parametrize("program", ["count", "piece"])
def test_mesh_fetch_program(meshes, record_property, program):
    """What brings the mesh sort's rows back, over four described chips:
    the count of each device's live rows, and the piece of its shard from
    a start given at run time, as flat 32-bit words."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpumr.parallel.device_sort import (make_count_fn, make_piece_fn,
                                            piece_rows)
    n_dev = 4
    mesh = meshes[n_dev]
    rows = NamedSharding(mesh, P("data"))
    local = SORT_ROWS // 2              # as test_device_shuffle_program
    per_dev = n_dev * max(16, 2 * local // n_dev)
    m = n_dev * per_dev
    if program == "count":
        _c, mem, secs = _compile(make_count_fn(mesh),
                                 _shape((m,), np.bool_, rows))
        assert mem.output_size_in_bytes >= 4
    else:
        piece = piece_rows(local, per_dev)
        assert piece == local // 8
        _c, mem, secs = _compile(
            make_piece_fn(mesh, ROW_W, piece),
            _shape((m, ROW_W + 1), np.uint8, rows),
            _shape((), np.int32, NamedSharding(mesh, P())))
        # the rows' bytes a device and no padding a row: flat words are
        # laid out in tiles of 1024, [p, 25] words would take 32 a row
        assert 0 <= mem.output_size_in_bytes - piece * ROW_W < 4096
    _record(record_property, mem, secs)


def test_kmeans_distributed_step_has_the_psum(meshes, record_property):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpumr.ops.kmeans import make_distributed_step
    mesh = meshes[4]
    compiled, mem, secs = _compile(
        make_distributed_step(mesh),
        _shape((4 * KM_ROWS, D), np.float32, NamedSharding(mesh, P("data"))),
        _shape((K, D), np.float32, NamedSharding(mesh, P())))
    _record(record_property, mem, secs)
    assert "all-reduce" in compiled.as_text()
    # per device: its quarter of the points, unpadded
    assert mem.argument_size_in_bytes <= 1.05 * (KM_ROWS * D * 4 + K * D * 4)
